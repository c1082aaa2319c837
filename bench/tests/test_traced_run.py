"""A traced run drives the window to its end with the profile stopped
after a slice of whole rounds, and reads the host-span metrics."""
import pytest

import tiny

import peaks
import run as R


@pytest.fixture
def short_slice(monkeypatch):
    # the CPU has no published peaks; the device readers find no chip
    # plane there and leave their metrics out
    monkeypatch.setattr(peaks, "peaks", lambda kind: peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(R, "TRACE_SECONDS", 0.05)


def test_traced_run_reads_the_host_metrics(short_slice):
    out = tiny.run(2 ** 31 + 3, trace=1, seconds=0.1)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert m["host_ms_per_dispatch"]["value"] > 0
    assert 0 < m["slot_occupancy"]["value"] <= 100
    assert m["compile_s"]["value"] > 0
    assert "tokens_per_s" not in m
    assert out["device"]["window_s"] > 0


def test_host_ms_leaves_out_the_calls_and_the_profile_stop():
    import types

    metric = R._load_metric("host_ms_per_dispatch")
    spans = [("admit", 0.0, 0.001, {}), ("dispatch", 0.001, 0.010, {}),
             ("decode_chunk", 0.001, 0.011, {}), ("profile_stop", 0.011, 5.0, {}),
             ("admit", 5.0, 5.001, {}), ("dispatch", 5.001, 5.002, {}),
             ("decode_chunk", 5.001, 5.003, {}), ("profile_stop", 6.0, 7.0, {})]
    # loop 0 .. 5.003 s, less 0.009 + 0.001 s of calls and 4.989 s of stop
    got = metric.read(types.SimpleNamespace(spans=spans))
    assert abs(got - 1e3 * (5.003 - 0.010 - 4.989) / 2) < 1e-9
