"""Continuous-batching serve front-end: scheduler policy units, the
batched-prefill model path, and the no-retrace guarantee of slot swaps
in ``Server.serve``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import ClusterSpec
from repro.models.model import Model
from repro.runtime.serve_loop import ServeConfig, Server
from repro.serve import (
    CLASS_PRIORITY,
    Request,
    SlotScheduler,
    make_workload,
    workload_names,
)

KEY = jax.random.PRNGKey(0)


def _req(rid, arrival=0.0, out_len=4, cls="standard", plen=3):
    return Request(rid=rid, arrival=arrival, prompt=tuple(range(1, plen + 1)),
                   out_len=out_len, deadline_class=cls)


class _Sink:
    """Telemetry stand-in capturing (name, fields) event records."""

    def __init__(self):
        self.events = []

    def event(self, name, **fields):
        self.events.append((name, fields))


# ------------------------------------------------------- scheduler units
def test_full_queue_sheds_queue_full():
    sched = SlotScheduler(1, queue_cap=2)
    sched.fill_slots(0.0)
    assert [sched.offer(_req(i), 0.0) for i in range(3)] == [True, True, False]
    assert sched.shed == 1
    shed = [f for f in sched.finished if f.outcome == "shed"]
    assert [f.reason for f in shed] == ["queue_full"]
    assert shed[0].latency == float("inf")


def test_all_slots_busy_places_nothing():
    sched = SlotScheduler(2)
    for i in range(3):
        sched.offer(_req(i), 0.0)
    assert [si for si, _ in sched.fill_slots(0.0)] == [0, 1]
    assert sched.fill_slots(1.0) == []  # both busy, third must wait
    assert sched.busy_slots == 2 and len(sched.queue) == 1
    sched.advance(4)  # out_len reached on both
    sched.retire_done(4.0)
    assert [r.rid for _, r in sched.fill_slots(4.0)] == [2]


def test_deadline_class_priority_fifo_within_class():
    sched = SlotScheduler(4, queue_cap=8)
    order = [("batch", 0), ("standard", 1), ("strict", 2), ("standard", 3)]
    for cls, rid in order:
        sched.offer(_req(rid, cls=cls), 0.0)
    placed = sched.fill_slots(0.0)
    # strict first, then the standards in arrival order, batch last
    assert [r.rid for _, r in placed] == [2, 1, 3, 0]
    prios = [CLASS_PRIORITY[r.deadline_class] for _, r in placed]
    assert prios == sorted(prios)


def test_deadline_risk_sheds_strict_but_never_batch():
    sched = SlotScheduler(1, queue_cap=64)
    for i in range(20):  # deep backlog of long requests
        assert sched.offer(_req(i, out_len=30, cls="batch"), 0.0)
    assert not sched.offer(_req(99, out_len=4, cls="strict"), 0.0)
    assert [f.reason for f in sched.finished if f.outcome == "shed"] == [
        "deadline_risk"
    ]
    # identical pressure: batch class is only ever shed by a full queue
    assert sched.offer(_req(100, out_len=4, cls="batch"), 0.0)


def test_slow_fleet_latency_factor_triggers_shedding():
    slow = SlotScheduler(4, round_latency=lambda: 50.0,
                         reference_latency=1.0)
    assert not slow.offer(_req(0, out_len=8, cls="standard"), 0.0)
    # the same offer sails through at reference speed
    ok = SlotScheduler(4, round_latency=lambda: 1.0, reference_latency=1.0)
    assert ok.offer(_req(0, out_len=8, cls="standard"), 0.0)
    # a fleet that cannot cover k (inf latency) sheds every non-batch
    dead = SlotScheduler(4, round_latency=lambda: float("inf"),
                         reference_latency=1.0)
    assert not dead.offer(_req(1, out_len=8, cls="strict"), 0.0)
    assert dead.offer(_req(2, out_len=8, cls="batch"), 0.0)


def test_scheduler_replay_is_deterministic():
    def drive(seed):
        trace = make_workload("poisson", num_requests=12).trace(seed)
        sched = SlotScheduler(2, queue_cap=3)
        now, i = 0.0, 0
        log = []
        while i < len(trace) or not sched.idle:
            while i < len(trace) and trace[i].arrival <= now:
                sched.offer(trace[i], now)
                i += 1
            for si, r in sched.fill_slots(now):
                log.append(("admit", si, r.rid, now))
            sched.advance(1)
            now += 1.0
            for si, f in sched.retire_done(now):
                log.append(("done", si, f.request.rid, now))
        return log, sched.shed

    assert drive(7) == drive(7)
    assert drive(7) != drive(8)


def test_telemetry_events_schema():
    sink = _Sink()
    sched = SlotScheduler(1, queue_cap=1, telemetry=sink)
    sched.offer(_req(0, out_len=2), 0.0)
    sched.offer(_req(1), 0.0)  # queue full -> evicted
    sched.fill_slots(1.0)
    sched.advance(2)
    sched.retire_done(3.0)
    names = [n for n, _ in sink.events]
    assert names == ["request_evicted", "request_admitted", "request_done"]
    by = dict(sink.events)
    assert by["request_evicted"]["reason"] == "queue_full"
    assert by["request_evicted"]["request_id"] == 1
    assert by["request_admitted"]["queue_wait"] == 1.0
    assert by["request_done"]["tokens"] == 2
    assert by["request_done"]["latency"] == 3.0
    # field coverage is the schema registry's job (repro.obs.schema):
    # every emitted record must satisfy its declared contract
    from repro.obs.schema import validate_event

    for name, fields in sink.events:
        validate_event({"event": name, **fields})


# -------------------------------------------------------------- workload
def test_workload_traces_are_seeded_and_validated():
    wl = make_workload("chat", num_requests=10)
    t1, t2 = wl.trace(seed=3), wl.trace(seed=3)
    assert t1 == t2
    assert t1 != wl.trace(seed=4)
    assert all(a.arrival <= b.arrival for a, b in zip(t1, t1[1:]))
    assert {"poisson", "trickle", "overload", "chat"} <= set(workload_names())
    with pytest.raises(ValueError, match="unknown workload"):
        make_workload("nope")
    with pytest.raises(ValueError, match="does not accept"):
        make_workload("poisson", slots=4)
    with pytest.raises(ValueError, match="out_len"):
        _req(0, out_len=0)


# --------------------------------------------- batched prefill model path
def test_prefill_matches_full_forward_last_position():
    """One batched prefill pass == the full forward at each row's end."""
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    s0 = 10
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, s0), 0,
                                c.vocab_size).astype(jnp.int32)
    lengths = jnp.asarray([s0, 6, 3], jnp.int32)
    plog, ks, vs = m.prefill(params, tokens, lengths)
    assert ks.shape == (c.num_layers, 3, s0, c.num_kv_heads,
                        c.resolved_head_dim)
    for b, ln in enumerate([s0, 6, 3]):
        full = m.lm_logits(params, tokens[b: b + 1, :ln])
        np.testing.assert_allclose(
            np.asarray(plog[b]), np.asarray(full[0, -1]), rtol=2e-4,
            atol=2e-4,
        )


def test_slot_decode_continues_prefilled_stream():
    """Prefill spliced into the paged pool + per-slot paged decode ==
    teacher-forced full-forward logits."""
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    s0, steps, slots, bl = 6, 3, 2, 4
    tokens = jax.random.randint(jax.random.PRNGKey(2), (slots, s0), 0,
                                c.vocab_size).astype(jnp.int32)
    lengths = jnp.full((slots,), s0, jnp.int32)
    plog, ks, vs = m.prefill(params, tokens, lengths)
    # each slot's blocks in logical order, slot 1's after slot 0's
    mb = -(-(s0 + steps + 1) // bl)
    cache = m.init_paged_cache(slots * mb, bl)
    table = np.arange(slots * mb, dtype=np.int32).reshape(slots, mb)
    pk = np.array(cache["kv"]["k"])
    pv = np.array(cache["kv"]["v"])
    # a pool row holds a token's KV heads side by side
    ks = np.asarray(ks).reshape(*ks.shape[:3], -1)
    vs = np.asarray(vs).reshape(*vs.shape[:3], -1)
    for s in range(slots):
        for t in range(s0):
            pk[:, table[s, t // bl], t % bl] = ks[:, s, t]
            pv[:, table[s, t // bl], t % bl] = vs[:, s, t]
    cache = {"kv": {"k": jnp.asarray(pk), "v": jnp.asarray(pv)}}
    active = jnp.ones((slots,), bool)
    pos = jnp.full((slots,), s0, jnp.int32)
    logits, ctx = plog, tokens
    for _ in range(steps):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        ctx = jnp.concatenate([ctx, tok[:, None]], axis=1)
        logits, cache = m.decode_step_paged(params, cache, tok, pos,
                                            jnp.asarray(table), active)
        full = m.lm_logits(params, ctx)[:, -1]
        np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                                   rtol=2e-4, atol=2e-4)
        pos = pos + 1


# ------------------------------------------------- serve(): no retraces
def test_serve_slot_swaps_never_retrace_and_replay_is_deterministic():
    """Admits/evicts across a whole trace reuse the fused compiled
    program (at most one trace per chunk size); an identical replay
    compiles nothing and reproduces the schedule exactly."""
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    server = Server(m, params, ClusterSpec.make([2, 2], [4.0, 0.8]),
                    ServeConfig(block_rows=64))
    wl = make_workload("poisson", num_requests=8, prompt_len=(4, 8),
                       out_len=(2, 6), vocab=c.vocab_size)
    trace = wl.trace(seed=5)
    decode_block = 2
    rep1 = server.serve(trace, slots=2, decode_block=decode_block)
    traces_after_first = server.serve_traces
    assert 1 <= traces_after_first <= decode_block
    rep2 = server.serve(trace, slots=2, decode_block=decode_block)
    assert server.serve_traces == traces_after_first, (
        "slot admits/evicts must be pure buffer updates, not retraces"
    )
    done1 = {f.request.rid: f for f in rep1.finished if f.outcome == "done"}
    done2 = {f.request.rid: f for f in rep2.finished if f.outcome == "done"}
    assert len(done1) == 8 and rep1.shed == 0
    for rid, f in done1.items():
        assert f.tokens == f.request.out_len
        assert done2[rid].finish_round == f.finish_round
    assert rep1.rounds == rep2.rounds and rep1.tokens == rep2.tokens
    assert rep1.latency_percentile(99) == rep2.latency_percentile(99)


@pytest.mark.parametrize("dead_fleet", [False, True])
def test_serve_counts_undecodable_rounds(dead_fleet):
    """``fallback_rounds`` counts decode rounds whose coded head had too
    few survivors: none for the planned fleet, every one when no worker
    can meet the deadline (the plain logits stand in, tokens still flow)."""
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    cluster = ClusterSpec.make([2, 2], [4.0, 0.8])
    server = Server(m, params, cluster, ServeConfig(block_rows=64))
    if dead_fleet:
        server.set_true_cluster(ClusterSpec.make([2, 2], [1e-9, 1e-9]))
    wl = make_workload("poisson", num_requests=4, prompt_len=(4, 8),
                       out_len=(2, 6), vocab=c.vocab_size)
    rep = server.serve(wl.trace(seed=5), slots=2, decode_block=2)
    assert rep.tokens > 0 and rep.decode_rounds > 0
    assert rep.fallback_rounds == (rep.decode_rounds if dead_fleet else 0)


def test_serve_overload_sheds_and_reports():
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    server = Server(m, params, None, ServeConfig())  # uncoded head is fine
    sink = _Sink()
    wl = make_workload("overload", num_requests=10, prompt_len=(4, 6),
                       out_len=(4, 8), vocab=c.vocab_size)
    rep = server.serve(wl.trace(seed=1), slots=2, decode_block=2,
                       queue_cap=2, telemetry=sink)
    assert rep.shed > 0 and rep.admitted + rep.shed == 10
    assert rep.tokens == sum(
        f.request.out_len for f in rep.finished if f.outcome == "done"
    )
    names = {n for n, _ in sink.events}
    assert {"request_admitted", "request_evicted", "request_done"} <= names
