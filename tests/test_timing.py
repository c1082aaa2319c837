"""Measured-reality control loop: RoundClock + timing-path bugfixes (§12).

The clock tests time REAL jitted dispatches, but every assertion is on
structure the decomposition guarantees deterministically (calibration
identity, per-round common scale, pad attribution, skip bookkeeping) —
never on absolute wall-clock values, so nothing here is load-sensitive.
The two acceptance replays (stationary fleet holds, sleep-padded group
replans) mirror ``test_adaptive.py``'s simulated closed-loop tests on
the measured path; their dispatches carry a duration floor (see
``_dispatch``) so co-tenant scheduling jitter stays a small relative
wobble, as it is for real model-step dispatches.
"""
import dataclasses
import json
import time

import jax
import numpy as np
import pytest

from repro.core.runtime_model import ClusterSpec
from repro.core.schemes import make_scheme
from repro.runtime.control import AdaptConfig, AdaptiveController
from repro.runtime.executor import CodedRoundExecutor
from repro.runtime.fault_tolerance import StragglerTracker
from repro.runtime.telemetry import Telemetry
from repro.runtime.timing import RoundClock, RoundTiming

KEY = jax.random.PRNGKey(23)
BASE = ClusterSpec.make([8, 16, 8], [4.0, 1.0, 0.25], 1.0, [16.0, 8.0, 4.0])
K = 1_000


def _dispatch(exe, key, floor_s=0.0):
    """A real jitted dispatch; ``floor_s`` pads it to a realistic round
    duration. The sampler alone runs in ~100us, so under co-tenant load
    (parallel pytest shards, CI neighbors) scheduling jitter would
    dominate ``dispatch_s`` and the per-round scale would be mostly
    noise — real dispatches are model steps, many ms long, where the
    same absolute jitter is a small relative wobble. The closed-loop
    acceptance tests use the floor; the structural tests don't care."""

    def dispatch():
        if floor_s:
            time.sleep(floor_s)
        return exe.round_times_jit(key)

    return dispatch


# ------------------------------------------------------------ RoundClock
def test_clock_warmup_then_calibration_identity():
    """The first fed round pins unit_s and decomposes to EXACTLY the
    virtual draw (scale 1.0): measured and simulated observation streams
    coincide on the calibration round by construction."""
    exe = CodedRoundExecutor(BASE, K, "optimal")
    clock = RoundClock(exe, warmup=1)
    k0, k1 = jax.random.fold_in(KEY, 0), jax.random.fold_in(KEY, 1)

    t0 = clock.measure(_dispatch(exe, k0), key=k0)
    assert t0.skipped == "warmup" and t0.times is None
    assert clock.unit_s is None and clock.fed == 0
    assert t0.dispatch_s > 0 and t0.wall_s >= t0.dispatch_s

    t1 = clock.measure(_dispatch(exe, k1), key=k1)
    v, _ = exe.round_observation(k1)
    assert t1.skipped is None and clock.fed == 1
    assert t1.scale == pytest.approx(1.0)
    np.testing.assert_allclose(t1.times, v, rtol=1e-12)
    assert clock.unit_s is not None and clock.unit_s > 0


def test_clock_later_rounds_share_one_common_scale():
    """Every post-calibration round is the virtual draw times ONE scalar
    (the round's wall-clock factor) — per-group ratios are exact, which
    is why stationary fleets can never replan spuriously."""
    exe = CodedRoundExecutor(BASE, K, "optimal")
    clock = RoundClock(exe, warmup=1)
    for i in range(2):  # warmup + calibration
        k = jax.random.fold_in(KEY, i)
        clock.measure(_dispatch(exe, k), key=k)
    k = jax.random.fold_in(KEY, 2)
    t = clock.measure(_dispatch(exe, k), key=k)
    v, _ = exe.round_observation(k)
    assert np.isfinite(t.scale) and t.scale > 0
    np.testing.assert_allclose(t.times, v * t.scale, rtol=1e-12)


def test_clock_discard_next_and_outlier_guard():
    exe = CodedRoundExecutor(BASE, K, "optimal")
    clock = RoundClock(exe, warmup=1, outlier_factor=5.0)
    for i in range(3):
        k = jax.random.fold_in(KEY, i)
        clock.measure(_dispatch(exe, k), key=k)
    unit_before, fed_before = clock.unit_s, clock.fed

    # a consumer-flagged recompile round is measured but not fed
    clock.discard_next("recompile")
    k = jax.random.fold_in(KEY, 3)
    t = clock.measure(_dispatch(exe, k), key=k)
    assert t.skipped == "recompile" and t.times is None
    assert clock.fed == fed_before

    # a dispatch way past the smoothed EMA is dropped automatically
    # (sleep INSIDE the dispatch window = a GC-pause stand-in)
    stall = max(clock.outlier_factor * clock._smoothed * 3, 0.02)

    def stalled():
        time.sleep(stall)
        return exe.round_times_jit(k)

    t = clock.measure(stalled, key=k)
    assert t.skipped == "outlier" and t.times is None
    assert clock.unit_s == unit_before  # neither skip recalibrates

    # and the next normal round feeds again
    t = clock.measure(_dispatch(exe, k), key=k)
    assert t.skipped is None


def test_clock_pad_is_slept_and_attributed_per_worker():
    """pad_s really sleeps (measured in wall_s) and each padded worker
    is attributed its proportional share of the MEASURED sleep, in
    calibrated units on top of its decomposed time."""
    exe = CodedRoundExecutor(BASE, K, "optimal")
    clock = RoundClock(exe, warmup=1)
    for i in range(2):
        k = jax.random.fold_in(KEY, i)
        clock.measure(_dispatch(exe, k), key=k)
    w = BASE.total_workers
    pad = np.zeros(w)
    pad[-8:] = 0.02  # slow the last group only
    clock.pad_s = pad
    k = jax.random.fold_in(KEY, 5)
    t = clock.measure(_dispatch(exe, k), key=k)
    assert t.pad_wall_s >= 0.02
    assert t.wall_s >= t.dispatch_s + t.pad_wall_s - 1e-6
    v, _ = exe.round_observation(k)
    expected = v * t.scale + (pad / pad.max()) * t.pad_wall_s / clock.unit_s
    np.testing.assert_allclose(t.times, expected, rtol=1e-9)
    # unpadded workers: pure decomposition; padded: strictly slower
    np.testing.assert_allclose(t.times[:-8], (v * t.scale)[:-8], rtol=1e-12)
    assert (t.times[-8:] > (v * t.scale)[-8:]).all()


def test_clock_true_cluster_leavers_decompose_to_inf():
    exe = CodedRoundExecutor(BASE, K, "optimal")
    clock = RoundClock(exe, warmup=0)
    groups = list(BASE.groups)
    groups[1] = dataclasses.replace(groups[1], num_workers=14)
    shrunk = ClusterSpec(tuple(groups))
    k = jax.random.fold_in(KEY, 9)
    t = clock.measure(_dispatch(exe, k), key=k, true_cluster=shrunk)
    assert int(np.isinf(t.times).sum()) == 2  # 16 -> 14 in group 1
    assert t.membership == (8, 14, 8)


def test_clock_emits_round_timing_events():
    exe = CodedRoundExecutor(BASE, K, "optimal")
    with Telemetry(None) as tel:
        clock = RoundClock(exe, telemetry=tel, warmup=1)
        for i in range(3):
            k = jax.random.fold_in(KEY, i)
            clock.measure(_dispatch(exe, k), key=k)
    recs = [e for e in tel.events if e["event"] == "round_timing"]
    assert len(recs) == 3
    assert [r["fed"] for r in recs] == [False, True, True]
    assert recs[0]["skipped"] == "warmup" and recs[0]["t_max"] is None
    for r in recs[1:]:
        assert r["skipped"] is None
        assert r["unit_s"] > 0 and r["t_max"] >= r["t_mean"] > 0
        assert r["workers"] == BASE.total_workers
    # JSONL-serializable as-is (the sink json.dumps's every record)
    for r in recs:
        json.dumps(r)


def test_clock_validates_knobs():
    exe = CodedRoundExecutor(BASE, K, "optimal")
    with pytest.raises(ValueError, match="warmup"):
        RoundClock(exe, warmup=-1)
    with pytest.raises(ValueError, match="outlier_factor"):
        RoundClock(exe, outlier_factor=1.0)
    with pytest.raises(ValueError, match="smooth"):
        RoundClock(exe, smooth=1.0)


# -------------------------------------------- controller ingest bugfixes
def test_observe_round_clamps_nonpositive_times_without_transfer():
    """Satellite regression: the >=1e-9 clamp used to live INSIDE the
    transfer_times branch, so measured wall-clock jitter going
    non-positive on the plain path reached the MLE raw (negative alpha
    estimates, garbage mu)."""
    exe = CodedRoundExecutor(BASE, K, "optimal")
    ctl = AdaptiveController(exe, AdaptConfig(every=1))
    times = np.array(exe.sample_round_times(KEY))
    times[:8] = -0.5  # clock jitter gone negative
    times[8] = 0.0
    d = ctl.observe_round(times)
    assert d is not None
    assert (ctl.tracker.alpha_estimates >= 0).all()
    assert np.isfinite(ctl.tracker.mu_estimates).all()
    assert (ctl.tracker.mu_estimates > 0).all()


def test_observe_round_clamps_comm_overshoot():
    """Overshooting bandwidth estimates: transfer + download subtraction
    exceeds the observed round time — the single ingest-point clamp
    keeps the compute-time residual positive."""
    sch = make_scheme("comm_aware", upload=2.0, download=1.0)
    exe = CodedRoundExecutor(BASE, K, sch)
    ctl = AdaptiveController(exe, AdaptConfig(every=1))
    times, shifts = exe.round_observation(jax.random.fold_in(KEY, 3))
    overshoot = np.where(np.isfinite(shifts), shifts + 2.0 * times, shifts)
    d = ctl.observe_round(times, transfer_times=overshoot, payload=2.0)
    assert d is not None
    assert (ctl.tracker.alpha_estimates >= 0).all()
    assert (ctl.tracker.mu_estimates > 0).all()
    assert np.isfinite(ctl.coverage_latency())


def test_tracker_defends_direct_nonpositive_times():
    tracker = StragglerTracker(BASE)
    loads = CodedRoundExecutor(BASE, K, "optimal").plan.loads_per_worker
    tracker.observe_round(
        np.full(BASE.total_workers, -1.0), np.asarray(loads), K
    )
    assert (tracker.alpha_estimates >= 0).all()
    assert (tracker.mu_estimates > 0).all()


def test_observe_timing_skipped_rounds_are_noops():
    exe = CodedRoundExecutor(BASE, K, "optimal")
    ctl = AdaptiveController(exe, AdaptConfig(every=1))
    skipped = RoundTiming(
        round=1, result=None, wall_s=0.1, dispatch_s=0.1, pad_wall_s=0.0,
        scale=float("nan"), times=None, transfer_times=None, payload=1.0,
        membership=None, skipped="warmup",
    )
    assert ctl.observe_timing(skipped) is None
    assert ctl.observe_timing(None) is None
    assert ctl.round == 0 and ctl.decisions == []


# ------------------------------------------------- Telemetry.log bugfix
def test_telemetry_log_uses_explicit_none_checks():
    """Satellite regression: truthiness dropped tokens_per_s when
    tokens_per_step == 0 (a real rate of 0.0) and divided-by-zero risk
    hid behind `if self.step_time` (0.0 falsy)."""
    with Telemetry(None) as tel:
        rec = tel.log(1, {}, tokens_per_step=128)
        assert "tokens_per_s" not in rec  # genuinely no timing yet
        tel.step_time = 0.5
        rec = tel.log(2, {}, tokens_per_step=0)
        assert rec["tokens_per_s"] == 0.0
        tel.step_time = 0.0
        rec = tel.log(3, {}, tokens_per_step=64)
        assert rec["tokens_per_s"] == float("inf")
        rec = tel.log(4, {"loss": 1.0})
        assert "tokens_per_s" not in rec  # no tokens_per_step given


# ------------------------------------- measured-vs-simulated acceptance
def test_measured_stationary_fleet_zero_spurious_replans():
    """ISSUE acceptance: wall-clock observations on a stationary fleet
    never replan — per-round decomposition applies one common factor to
    every worker, and the decision rule is scale-invariant."""
    exe = CodedRoundExecutor(BASE, K, "optimal")
    ctl = AdaptiveController(exe, AdaptConfig(every=5, threshold=0.05))
    clock = RoundClock(exe, warmup=1)
    for t in range(41):
        k = jax.random.fold_in(KEY, 100 + t)
        ctl.observe_timing(
            clock.measure(_dispatch(exe, k, floor_s=0.02), key=k)
        )
    assert clock.fed == 40 and ctl.round == 40
    assert ctl.replans == 0, [d for d in ctl.decisions if d.replanned]
    assert len(ctl.decisions) == 8
    assert all(d.reason == "hold" for d in ctl.decisions)


def test_measured_sleep_padded_group_replans_within_two_cadences():
    """ISSUE acceptance: a sleep-padded worker group — a REAL wall-clock
    slowdown, invisible to the simulated path — triggers a replan within
    two cadences of the injection, and the new plan sheds load off the
    padded group."""
    exe = CodedRoundExecutor(BASE, K, "optimal")
    old_loads = np.asarray(exe.plan.allocation.loads).copy()
    ctl = AdaptiveController(exe, AdaptConfig(every=5, threshold=0.05))
    clock = RoundClock(exe, warmup=1)
    inject_at = 10  # fed-round index of the injection
    for t in range(31):
        if clock.fed == inject_at and clock.pad_s is None:
            # group 0 (the fast one) starts stalling: pad it by several
            # calibrated units, far beyond the planned round latency
            pad = np.zeros(BASE.total_workers)
            pad[:8] = 4.0 * clock.unit_s * float(exe.deadline)
            clock.pad_s = pad
        k = jax.random.fold_in(KEY, 500 + t)
        ctl.observe_timing(
            clock.measure(_dispatch(exe, k, floor_s=0.02), key=k)
        )
    replans = [d for d in ctl.decisions if d.replanned]
    assert replans, "sleep-padded group never triggered a replan"
    # injection lands at fed round 10; cadence 5 => rounds 15/20 are the
    # first two post-injection decisions
    assert inject_at < replans[0].round <= inject_at + 2 * 5
    new_loads = np.asarray(ctl.plan.allocation.loads)
    assert new_loads[0] < old_loads[0]


def test_trainer_measured_times_static_fleet_holds():
    """End to end: Trainer --measure-times on a stationary fleet — every
    round is timed and fed, zero replans, zero extra retraces, and the
    round_timing stream lands in telemetry."""
    from repro.configs import ARCHS
    from repro.configs.base import ShapeConfig
    from repro.data import SyntheticLMData
    from repro.models.model import Model
    from repro.optim import AdamWConfig
    from repro.runtime.train_loop import TrainConfig, Trainer

    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    data = SyntheticLMData(c, ShapeConfig("t", 16, 4, "train"), seed=1)
    cluster = ClusterSpec.make([8, 8], [4.0, 0.5])
    cfg = TrainConfig(
        steps=10, log_every=5, cluster=cluster, scheme="grad_coding",
        adapt_every=2, adapt_threshold=0.1, measure_times=True,
    )
    t = Trainer(m, data, AdamWConfig(lr=1e-3, warmup_steps=0,
                                     total_steps=10), cfg)
    assert t.clock is not None
    _, _, history = t.run()
    assert all(np.isfinite(h["loss"]) for h in history)
    assert t.clock.rounds == 10 and t.clock.fed == 9  # 1 warmup
    assert t.controller.round == 9
    assert t.controller.replans == 0
    assert all(d.reason == "hold" for d in t.controller.decisions)
    assert t.traces == 1  # stationary: the step never recompiled
    recs = [e for e in t.telemetry.events if e["event"] == "round_timing"]
    assert len(recs) == 10 and sum(r["fed"] for r in recs) == 9


def test_trainer_measure_times_requires_cluster():
    from repro.configs import ARCHS
    from repro.configs.base import ShapeConfig
    from repro.data import SyntheticLMData
    from repro.models.model import Model
    from repro.optim import AdamWConfig
    from repro.runtime.train_loop import TrainConfig, Trainer

    c = ARCHS["qwen3-0.6b"].reduced()
    data = SyntheticLMData(c, ShapeConfig("t", 16, 4, "train"), seed=1)
    with pytest.raises(ValueError, match="measure_times"):
        Trainer(Model(c), data,
                AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=5),
                TrainConfig(steps=5, measure_times=True))


# ------------------------------------------------------------ CLI smokes
@pytest.mark.slow
def test_train_cli_measure_times_smoke(capsys):
    from repro.launch import train as train_cli

    train_cli.main([
        "--arch", "qwen3-0.6b", "--reduced", "--steps", "6",
        "--batch", "4", "--seq-len", "16",
        "--hetero-groups", "2:2.0,2:0.5", "--scheme", "grad_coding",
        "--adapt-every", "2", "--measure-times",
    ])
    out = capsys.readouterr().out
    assert "measured:" in out and "rounds fed" in out


@pytest.mark.slow
def test_serve_cli_measure_times_smoke(tmp_path, capsys):
    from repro.launch import serve as serve_cli

    tel_path = str(tmp_path / "serve_tel.jsonl")
    serve_cli.main([
        "--arch", "qwen3-0.6b", "--reduced", "--coded", "--batch", "2",
        "--prompt-len", "8", "--max-new", "4", "--scenario", "mu_step",
        "--adapt-every", "2", "--rounds", "6", "--measure-times",
        "--telemetry", tel_path,
    ])
    out = capsys.readouterr().out
    assert "measured:" in out
    events = [json.loads(line) for line in open(tel_path)]
    names = {e.get("event") for e in events}
    assert "round_timing" in names and "adapt_decision" in names


def test_cli_measure_times_flag_validation():
    from repro.launch import serve as serve_cli
    from repro.launch import train as train_cli

    with pytest.raises(SystemExit, match="--measure-times"):
        train_cli.main(["--arch", "qwen3-0.6b", "--reduced",
                        "--measure-times"])
    with pytest.raises(SystemExit, match="--measure-times"):
        serve_cli.main(["--arch", "qwen3-0.6b", "--reduced",
                        "--measure-times"])
