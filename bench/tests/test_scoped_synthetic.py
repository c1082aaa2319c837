"""Device time by named scope (``bench/scoped.py``) and the four readers
of this layer, on hand-made events."""
import types

import pytest

import tiny  # noqa: F401

import run as R
from record import Dispatch
from xplane import Event, Trace

PROGRAM = "jit__serve_step_paged_program"
#: chunk size -> instruction -> scope, as a traced ``ServeReport`` gives it;
#: the same instruction names mean different things in the two programs
SCOPES = {
    2: {"%fusion.1": "coded_head/mix", "%fusion.2": "coded_head/solve",
        "%fusion.3": "model/attention", "%copy.4": "model/layers"},
    0: {"%fusion.1": "prefill", "%fusion.3": "model/attention"},
}


def op(name, start, end):
    return Event(f"{name} = f32[4]{{0}} fusion(%p)", start, end)


def make(scopes=SCOPES):
    mods = [Event(PROGRAM, 100, 200), Event(PROGRAM, 300, 400),
            Event(PROGRAM, 500, 600), Event("jit_concatenate", 610, 620)]
    ops = [
        # run 1: a decode-only dispatch of 2 steps
        Event("%while.9 = (f32[4]) while(%t)", 100, 190),
        op("%fusion.1", 100, 110), op("%fusion.2", 110, 150),
        op("%fusion.3", 150, 170), op("%copy.4", 170, 180),
        op("%fusion.7", 180, 190),  # in no scope
        # run 2: a prefill-only dispatch (steps 0)
        op("%fusion.1", 300, 350), op("%fusion.3", 350, 390),
        # run 3: a second decode-only dispatch of 2 steps
        op("%fusion.2", 500, 560), op("%fusion.3", 560, 580),
        # outside every run
        op("%fusion.2", 610, 620),
    ]
    host = [Event("replay", 50, 700)]
    dispatches = [Dispatch(False, 2, [], [[3, 4], [4, 5]]),
                  Dispatch(True, 0, [1, 2, 3], []),
                  Dispatch(False, 2, [], [[5, 6], [6, 7]])]
    report = types.SimpleNamespace(scopes=scopes)
    return types.SimpleNamespace(
        trace=Trace([sorted(ops, key=lambda e: e.start)], [mods], host),
        reports=[report], dispatches=dispatches, program="_serve_step_paged_program",
        spans=[])


def read(metric, run):
    return R._load_metric(metric).read(run)


def test_scope_times_by_program_and_dispatch():
    import scoped

    t = scoped.scope_times(make())
    # decode-only dispatches: runs 1 and 3; the loop is counted through
    # the ops inside it, and the op outside every run is left out
    assert t.decode_steps == 4
    assert t.by_scope == {"coded_head/mix": 10, "coded_head/solve": 100,
                          "model/attention": 40, "model/layers": 10}
    assert t.leaf_ns == 170
    every = scoped.scope_times(make(), decode_only=False)
    # the prefill program maps the same names to other scopes
    assert every.by_scope["prefill"] == 50
    assert every.by_scope["model/attention"] == 80
    assert every.leaf_ns == 260 and every.decode_steps == 4


@pytest.mark.parametrize("lost", [0, 1, 2])
def test_a_run_whose_event_the_profile_lost_is_rebuilt_from_its_ops(lost):
    import scoped

    run = make()
    mods = run.trace.modules[0]
    run.trace.modules[0] = [m for i, m in enumerate(mods) if i != lost]
    for decode_only in (True, False):
        want = scoped.scope_times(make(), decode_only=decode_only)
        assert scoped.scope_times(run, decode_only=decode_only) == want


def test_a_lost_run_without_its_ops_gives_nothing():
    import scoped

    run = make()
    run.trace.modules[0] = run.trace.modules[0][1:]
    run.trace.ops[0] = [e for e in run.trace.ops[0] if e.start >= 300]
    assert scoped.scope_times(run) is None
    assert read("coded_head_ms", run) is None


def test_device_readers():
    run = make()
    assert read("coded_head_ms", run) == pytest.approx(110e-6 / 4)
    assert read("erasure_solve_ms", run) == pytest.approx(100e-6 / 4)
    assert read("attention_ms", run) == pytest.approx(40e-6 / 4)
    assert 0 < read("erasure_solve_ms", run) <= read("coded_head_ms", run)


@pytest.mark.parametrize("broken", ["no_maps", "no_chip", "misaligned",
                                    "unknown_size"])
def test_device_readers_give_nothing_they_cannot_read(broken):
    run = make()
    if broken == "no_maps":  # a program without named scopes
        run.reports = [types.SimpleNamespace()]
    elif broken == "no_chip":
        run.trace = Trace([], [], run.trace.host)
    elif broken == "misaligned":
        run.dispatches = run.dispatches[:2]
    else:
        run = make({2: SCOPES[2]})
    for metric in ("coded_head_ms", "erasure_solve_ms", "attention_ms"):
        assert read(metric, run) is None


def test_prepare_ms_per_dispatch():
    run = types.SimpleNamespace(spans=[
        ("serve_setup", 0.0, 0.5, {}), ("admit", 0.5, 0.501, {}),
        ("prepare", 0.501, 0.503, {}), ("dispatch", 0.503, 0.504, {}),
        ("prefill_chunk", 0.503, 0.505, {}), ("retire", 0.505, 0.506, {}),
        ("admit", 0.506, 0.507, {}), ("prepare", 0.507, 0.511, {}),
        ("dispatch", 0.511, 0.512, {}), ("decode_chunk", 0.511, 0.512, {}),
    ])
    assert read("prepare_ms_per_dispatch", run) == pytest.approx(3.0)
    # a program that opens no prepare span
    old = types.SimpleNamespace(spans=[
        s for s in run.spans if s[0] != "prepare"])
    assert read("prepare_ms_per_dispatch", old) is None
