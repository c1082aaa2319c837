"""The trace reduction's interval arithmetic on hand-made events."""
import pytest

import tiny  # noqa: F401

import xplane
from xplane import Event, Trace


def make():
    ops = [Event("%a = f32[] fusion(%x)", 100, 200),
           Event("b", 150, 260), Event("c", 400, 450), Event("d", 900, 1000)]
    ops.append(Event("%while.1 = (s32[]) while(%t)", 100, 260))
    ops.sort(key=lambda e: e.start)
    mods = [Event("jit__serve_step_paged_program", 100, 460),
            Event("jit__serve_step_paged_program", 880, 1000),
            Event("jit_concatenate", 1005, 1010)]
    host = [Event("replay", 50, 1050), Event("decode_chunk", 60, 120),
            Event("admit", 500, 880), Event("dispatch", 860, 870)]
    return Trace([ops], [mods], host)


def test_union_busy_and_window():
    t = make()
    assert xplane.window(t) == (50, 1050)
    assert xplane.union(t.ops[0], 50, 1050) == [(100, 260), (400, 450), (900, 1000)]
    assert xplane.busy_ns(t) == 160 + 50 + 100


def test_program_runs_and_ops_within():
    t = make()
    runs = xplane.program_runs(t, "_serve_step_paged_program")
    assert [r.start for r in runs] == [100, 880]
    assert [xplane.op_name(e.name) for e in xplane.ops_within(t, runs)] == [
        "%a", "%while.1", "b", "c", "d"]
    assert [e.name for e in xplane.ops_within(t, runs[1:])] == ["d"]


def test_top_ops_and_idle_gaps():
    t = make()
    top = xplane.top_ops(t)
    assert top[0][0] == "b" and abs(top[0][1] - 110e-9) < 1e-15
    # the loop is counted through the ops inside it, not again itself
    assert dict(top)["%a"] == pytest.approx(100e-9) and "%while.1" not in dict(top)
    gaps = xplane.idle_gaps(t)
    # gaps 450-900, 260-400, 50-100, 1000-1050; each named by the innermost
    # host span open at its middle
    assert [g[0] for g in gaps] == ["admit", "replay", "decode_chunk", "replay"]
    assert [g[1] for g in gaps] == pytest.approx([450e-9, 140e-9, 50e-9, 50e-9])


def test_idle_gaps_are_named_by_the_serve_loops_spans():
    ops = [Event("a", 0, 100), Event("b", 200, 300), Event("c", 400, 500),
           Event("d", 600, 700), Event("e", 800, 1000)]
    spans = [Event("replay", 0, 1000), Event("serve_setup", 0, 160),
             Event("prepare", 320, 390), Event("retire", 540, 560),
             Event("finish", 700, 900),
             # a host event of no kept span, inside the gap under prepare
             Event("PjitFunction(_serve_step_paged_program)", 340, 360)]
    # kept as ``xplane.load`` keeps them
    host = [e for e in spans if e.name in xplane.HOST_SPANS]
    gaps = xplane.idle_gaps(Trace([ops], [[]], host))
    assert [g[0] for g in gaps] == ["serve_setup", "prepare", "retire", "finish"]
    assert [g[1] for g in gaps] == pytest.approx([100e-9] * 4)
