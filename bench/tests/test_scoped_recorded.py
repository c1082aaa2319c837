"""Device time by named scope on a recorded TPU trace: the tiny cell's
traced slice on one TPU v5 lite, with the optimized HLO text of each
serve program it ran and its dispatches in order (both recorded by
``record_scoped.py``). The maps are built from the recorded text by the
program's own ``scope_map``; the sweep here puts ops into runs by array
arithmetic, apart from ``bench/scoped.py``'s walk."""
import json
import lzma
import os
import types

import numpy as np
import pytest

import tiny

import run as R
import scoped
import xplane
from record import Dispatch
from repro.obs.trace import SCOPES, scope_map

DATA = os.path.join(tiny.BENCH, "tests", "data")


@pytest.fixture(scope="module")
def record():
    with lzma.open(os.path.join(DATA, "tiny_scoped.json.xz"), "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def run(record, tmp_path_factory):
    out = tmp_path_factory.mktemp("trace") / "tiny_scoped.xplane.pb"
    with lzma.open(os.path.join(DATA, "tiny_scoped.xplane.pb.xz")) as f:
        out.write_bytes(f.read())
    maps = {int(k): scope_map(v) for k, v in record["hlo"].items()}
    return types.SimpleNamespace(
        trace=xplane.load(str(out)),
        reports=[types.SimpleNamespace(scopes=maps)],
        dispatches=[Dispatch(p, s, [], []) for p, s in record["dispatches"]],
        program=R.PROGRAM, spans=[])


def sweep(run, decode_only):
    """Per-scope device ns by array arithmetic: each op is put in the run
    whose interval holds its start, and read with that run's map."""
    ops = run.trace.ops[0]
    start = np.array([e.start for e in ops])
    dur = np.array([e.end - e.start for e in ops])
    leaf = np.array([not any(c in e.name for c in xplane.CONTAINERS) for e in ops])
    runs = xplane.program_runs(run.trace, run.program)
    lo = np.array([r.start for r in runs])
    hi = np.array([r.end for r in runs])
    k = np.searchsorted(lo, start, side="right") - 1
    inside = (k >= 0) & (start < hi[np.clip(k, 0, None)]) & leaf
    maps = run.reports[0].scopes
    tot, steps = {}, 0
    for i, d in enumerate(run.dispatches):
        if decode_only and (d.prefill or not d.steps):
            continue
        steps += d.steps
        for j in np.flatnonzero(inside & (k == i)):
            s = maps[d.steps].get(xplane.op_name(ops[j].name))
            if s is not None:
                tot[s] = tot.get(s, 0.0) + float(dur[j])
    return tot, float(dur[inside].sum()) if not decode_only else None, steps


def test_recorded_programs_name_every_scope(run):
    maps = run.reports[0].scopes
    assert set().union(*(m.values() for m in maps.values())) == set(SCOPES)
    runs = xplane.program_runs(run.trace, run.program)
    assert len(runs) == len(run.dispatches) > 0


@pytest.mark.parametrize("decode_only", [False, True])
def test_scope_totals_match_an_independent_sweep(run, decode_only):
    t = scoped.scope_times(run, decode_only=decode_only)
    tot, leaf, steps = sweep(run, decode_only)
    assert t.decode_steps == steps
    assert t.by_scope.keys() == tot.keys()
    for s, ns in tot.items():
        assert t.by_scope[s] == pytest.approx(ns)
    if leaf is not None:
        assert t.leaf_ns == pytest.approx(leaf)


@pytest.mark.parametrize("lost", [0, 3, 7])
def test_a_lost_run_event_is_rebuilt_from_its_ops(run, lost):
    runs = xplane.program_runs(run.trace, run.program)
    mods = [m for m in run.trace.modules[0] if m is not runs[lost]]
    cut = types.SimpleNamespace(**vars(run))
    cut.trace = xplane.Trace(run.trace.ops, [mods], run.trace.host)
    assert len(xplane.program_runs(cut.trace, run.program)) == len(runs) - 1
    for decode_only in (False, True):
        assert (scoped.scope_times(cut, decode_only=decode_only)
                == scoped.scope_times(run, decode_only=decode_only))


def test_most_device_time_resolves_to_a_scope(run):
    t = scoped.scope_times(run, decode_only=False)
    assert sum(t.by_scope.values()) / t.leaf_ns >= 0.95


def test_readers_agree_with_the_sweep(run):
    tot, _, steps = sweep(run, decode_only=True)
    got = {m: R._load_metric(m).read(run)
           for m in ("coded_head_ms", "erasure_solve_ms", "attention_ms")}
    coded = sum(v for k, v in tot.items() if k.startswith("coded_head/"))
    assert got["coded_head_ms"] == pytest.approx(coded * 1e-6 / steps)
    assert got["erasure_solve_ms"] == pytest.approx(
        tot["coded_head/solve"] * 1e-6 / steps)
    assert got["attention_ms"] == pytest.approx(
        tot["model/attention"] * 1e-6 / steps)
    assert 0 < got["erasure_solve_ms"] <= got["coded_head_ms"]
