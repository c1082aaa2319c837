"""The trace reduction on a recorded TPU trace: the tiny cell's traced
replay on one TPU v5 lite (6 requests, 4 layers, d_model 128), whose run
reported busy_s 0.003735575 and window_s 0.038238726."""
import lzma
import os

import numpy as np
import pytest

import tiny

import xplane

DATA = os.path.join(tiny.BENCH, "tests", "data", "tiny.xplane.pb.xz")


@pytest.fixture(scope="module")
def path(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with lzma.open(DATA) as f:
        out.write_bytes(f.read())
    return str(out)


@pytest.fixture(scope="module")
def trace(path):
    return xplane.load(path)


def test_planes_and_slice(trace):
    assert len(trace.ops) == len(trace.modules) == 1  # one chip
    assert len(trace.ops[0]) == 8210
    lo, hi = xplane.window(trace)
    assert (hi - lo) * 1e-9 == pytest.approx(0.038238726, abs=1e-12)


def test_busy_matches_an_independent_sweep(trace):
    lo, hi = xplane.window(trace)
    s = np.array([e.start for e in trace.ops[0]])
    t = np.array([e.end for e in trace.ops[0]])
    s, t = np.clip(s, lo, hi), np.clip(t, lo, hi)
    order = np.argsort(s, kind="stable")
    s, t = s[order], t[order]
    reach = np.maximum.accumulate(t)
    new = np.r_[True, s[1:] > reach[:-1]]  # a start past all earlier ends
    starts = s[new]
    ends = np.r_[reach[np.flatnonzero(new)[1:] - 1], reach[-1]]
    assert xplane.busy_ns(trace) == pytest.approx(float(np.sum(ends - starts)))
    assert xplane.busy_ns(trace) * 1e-9 == pytest.approx(0.003735575, abs=1e-12)


def test_one_program_run_per_dispatch(trace):
    lo, hi = xplane.window(trace)
    dispatches = [e for e in trace.host if e.name in ("prefill_chunk", "decode_chunk")
                  and lo <= e.start <= hi]
    runs = xplane.program_runs(trace, "_serve_step_paged_program")
    assert len(runs) == len(dispatches) > 0
    # each run starts after its dispatch was issued
    assert all(r.start >= d.start for r, d in zip(runs, dispatches))


def test_breakdown(trace):
    busy = xplane.busy_ns(trace) * 1e-9
    top = xplane.top_ops(trace)
    assert 0 < len(top) <= 10 and all(n.startswith("%") for n, _ in top)
    assert sum(v for _, v in top) <= busy * 1.0001
    gaps = xplane.idle_gaps(trace)
    lo, hi = xplane.window(trace)
    assert 0 < len(gaps) <= 10
    assert all(g[0] in xplane.HOST_SPANS for g in gaps)
    assert sum(g for _, g in gaps) <= (hi - lo) * 1e-9 - busy + 1e-12
