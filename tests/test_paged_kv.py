"""Paged KV serving (DESIGN.md §13): kernel-family parity, pool-size
invariance, block-pool policy, memory admission control, chunked
prefill, and the no-retrace guarantee across prompt lengths."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_routed_window import tiny_model

from repro.configs import ARCHS
from repro.core import ClusterSpec
from repro.kernels import paged_attention as pa
from repro.models.model import Model
from repro.runtime.control import AdaptiveController
from repro.runtime.executor import CodedRoundExecutor
from repro.runtime.serve_loop import ServeConfig, Server
from repro.serve import BlockPool, Request, SlotScheduler, make_workload

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


def _rand_paged(seed, *, s=3, nb=6, bl=4, kv=2, g=2, hd=8):
    """Random pool + a scattered (non-contiguous) block layout."""
    rng = np.random.default_rng(seed)
    k_pool = rng.standard_normal((nb + 1, bl, kv, hd)).astype(np.float32)
    v_pool = rng.standard_normal((nb + 1, bl, kv, hd)).astype(np.float32)
    q = rng.standard_normal((s, kv, g, hd)).astype(np.float32)
    table = np.full((s, nb), -1, np.int32)
    table[0, :2] = [3, 0]
    table[1, :3] = [1, 4, 2]
    table[2, :1] = [5]
    pos = np.array([5, 9, 2], np.int32)
    return q, k_pool, v_pool, table, pos


def _rows(pool):
    """A (NBp, BL, KV, hd) oracle pool as the ops' rows of KV * hd."""
    return jnp.asarray(pool).reshape(*pool.shape[:2], -1)


# --------------------------------------------- kernel family: ref/ops/pallas
def test_paged_decode_attend_family_parity():
    q, k_pool, v_pool, table, pos = _rand_paged(1)
    want = pa.paged_decode_attend_ref(q, k_pool, v_pool, table, pos)
    got_ops = np.asarray(pa.paged_decode_attend(
        jnp.asarray(q), _rows(k_pool), _rows(v_pool),
        jnp.asarray(table), jnp.asarray(pos),
    ))
    np.testing.assert_allclose(got_ops, want, rtol=1e-5, atol=1e-5)
    got_kernel = np.asarray(pa.paged_decode_attend_kernel(
        jnp.asarray(q), _rows(k_pool), _rows(v_pool),
        jnp.asarray(table), jnp.asarray(pos), interpret=True,
    ))
    np.testing.assert_allclose(got_kernel, want, rtol=1e-5, atol=1e-5)


def test_paged_chunk_attend_matches_ref():
    rng = np.random.default_rng(2)
    _, k_pool, v_pool, table, _ = _rand_paged(2)
    s, c = table.shape[0], 3
    q = rng.standard_normal((s, c, 2, 2, 8)).astype(np.float32)
    start = np.array([2, 6, 0], np.int32)
    q_pos = start[:, None] + np.arange(c, dtype=np.int32)[None, :]
    want = pa.paged_chunk_attend_ref(q, k_pool, v_pool, table, q_pos)
    got = np.asarray(pa.paged_chunk_attend(
        jnp.asarray(q), _rows(k_pool), _rows(v_pool),
        jnp.asarray(table), jnp.asarray(q_pos),
    ))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [None, 6])
def test_narrow_table_attends_like_the_pool_wide_table(window):
    """A table as wide as the largest reservation attends like the same
    table padded with unmapped (-1) entries to the whole pool: the
    entries it drops were masked to zero weight."""
    rng = np.random.default_rng(5)
    q, k_pool, v_pool, narrow, pos = _rand_paged(5, nb=12)
    narrow = narrow[:, :3]
    wide = np.full((narrow.shape[0], 12), -1, np.int32)
    wide[:, :3] = narrow
    args = lambda t: (_rows(k_pool), _rows(v_pool), jnp.asarray(t))
    got = pa.paged_decode_attend(jnp.asarray(q), *args(narrow),
                                 jnp.asarray(pos), window)
    want = pa.paged_decode_attend(jnp.asarray(q), *args(wide),
                                  jnp.asarray(pos), window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    c = 3
    qc = rng.standard_normal((narrow.shape[0], c, 2, 2, 8)).astype(np.float32)
    q_pos = jnp.asarray(np.array([2, 6, 0], np.int32)[:, None]
                        + np.arange(c, dtype=np.int32)[None, :])
    got = pa.paged_chunk_attend(jnp.asarray(qc), *args(narrow), q_pos, window)
    want = pa.paged_chunk_attend(jnp.asarray(qc), *args(wide), q_pos, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_scatter_routes_inactive_rows_to_sink():
    """Frozen/padded rows must write ONLY the sink block — a freed block
    reassigned to another stream can never be corrupted by them."""
    nb, bl, kv, hd = 4, 2, 1, 3
    k_pool = jnp.zeros((nb + 1, bl, kv * hd))
    v_pool = jnp.zeros((nb + 1, bl, kv * hd))
    table = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    k_new = jnp.ones((2, kv, hd))
    pos = jnp.asarray([1, 3], jnp.int32)
    active = jnp.asarray([True, False])
    k2, _ = pa.scatter_decode(k_pool, v_pool, k_new, k_new, table, pos, active)
    k2 = np.asarray(k2)
    assert np.all(k2[0, 1] == 1.0)  # active slot 0: block 0, offset 1
    assert np.all(k2[1:nb] == 0.0)  # inactive slot 1 touched no real block
    assert np.all(k2[nb, 1] == 1.0)  # its write landed in the sink


# ------------------------------------------ the stacked pool, by layer index
def _stacked(seed, layers=3):
    """A random stacked pool of rows, the table of ``_rand_paged`` and a
    decode batch whose second row is inactive (it writes the sink)."""
    rng = np.random.default_rng(seed)
    q, _, _, table, pos = _rand_paged(seed)
    nbp, bl, kv, hd = 7, 4, 2, 8
    k, v = (jnp.asarray(rng.standard_normal((layers, nbp, bl, kv * hd)),
                        jnp.float32) for _ in range(2))
    new = [jnp.asarray(rng.standard_normal((3, kv, hd)), jnp.float32)
           for _ in range(2)]
    active = jnp.asarray([True, False, True])
    return q, k, v, new, jnp.asarray(table), jnp.asarray(pos), active


@pytest.mark.parametrize("kind", ["decode", "chunk"])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_layer_indexed_ops_equal_the_per_layer_ops(layer, kind):
    """Each op on layer ``layer`` of the stacked pool equals the same op
    on that layer's own pool: the scatter writes only that layer (an
    inactive or padded row lands in its sink block), and the gather and
    the attend read only that layer."""
    q, k, v, (k_new, v_new), table, pos, active = _stacked(7 + layer)
    lk, lv = k[layer], v[layer]
    if kind == "decode":
        want = pa.scatter_decode(lk, lv, k_new, v_new, table, pos, active)
        got = pa.scatter_decode(k, v, k_new, v_new, table, pos, active,
                                layer=layer)
    else:
        start = jnp.asarray([2, 6, 0], jnp.int32)
        chunk_len = jnp.asarray([3, 0, 2], jnp.int32)  # slot 1 not prefilling
        kc, vc = (jnp.stack([t, t[::-1]], axis=1) for t in (k_new, v_new))
        want = pa.scatter_chunk(lk, lv, kc, vc, table, start, chunk_len)
        got = pa.scatter_chunk(k, v, kc, vc, table, start, chunk_len,
                               layer=layer)
    sink = k.shape[1] - 1
    for full, one, before in zip(got, want, (k, v)):
        np.testing.assert_array_equal(np.asarray(full[layer]), np.asarray(one))
        others = np.arange(k.shape[0]) != layer
        np.testing.assert_array_equal(np.asarray(full)[others],
                                      np.asarray(before)[others])
        assert not np.array_equal(np.asarray(one[sink]),
                                  np.asarray(before[layer, sink]))
    k2, v2 = got
    np.testing.assert_array_equal(np.asarray(pa.gather_kv(k2, table, layer)),
                                  np.asarray(pa.gather_kv(k2[layer], table)))
    for window in (None, 6):
        if kind == "decode":
            attend = lambda *a, **kw: pa.paged_decode_attend(
                jnp.asarray(q), *a, table, pos, window, **kw)
        else:
            qc = jnp.stack([jnp.asarray(q)] * 2, axis=1)
            q_pos = start[:, None] + jnp.arange(2, dtype=jnp.int32)[None, :]
            attend = lambda *a, **kw: pa.paged_chunk_attend(
                qc, *a, table, q_pos, window, **kw)
        np.testing.assert_array_equal(
            np.asarray(attend(k2, v2, layer=layer)),
            np.asarray(attend(k2[layer], v2[layer])))


def _paged_model(arch):
    """A float32 model of each scan form: reduced qwen3 (one scan over
    layers) or the tiny window/expert model (a scan over periods)."""
    if arch == "qwen3":
        return Model(ARCHS["qwen3-0.6b"].reduced())
    return tiny_model()


def _paged_batch(model, seed=0):
    """Weights, a pool with history and one decode step's arguments."""
    c = model.config
    params = model.init_params(jax.random.PRNGKey(seed))
    slots, bl, mb = 2, 8, 3
    cache = model.init_paged_cache(slots * mb, bl)
    table = jnp.asarray(np.arange(slots * mb, dtype=np.int32).reshape(slots, mb))
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (slots, 8), 0,
                                c.vocab_size).astype(jnp.int32)
    return params, cache, table, tokens


@pytest.mark.parametrize("arch", ["qwen3", "mellum"])
def test_paged_steps_equal_under_unrolled_layers(arch):
    """``prefill_paged`` then ``decode_step_paged`` give the same logits
    and the same pool whether the layers run as a scan (over layers or
    over periods) or unrolled (``scan_layers=False``): the layer index
    that selects the pool's layer is a scan input in one and a constant
    in the other."""
    model = _paged_model(arch)
    unrolled = Model(dataclasses.replace(model.config, scan_layers=False))
    params, cache, table, tokens = _paged_batch(model)
    start = jnp.asarray([0, 0], jnp.int32)
    chunk_len = jnp.asarray([8, 5], jnp.int32)
    active = jnp.asarray([True, True])
    outs = []
    for m in (model, unrolled):
        plog, pool = m.prefill_paged(params, cache, tokens, start, chunk_len,
                                     table)
        dlog, pool = m.decode_step_paged(params, pool, tokens[:, 0],
                                         chunk_len, table, active)
        outs.append((plog, dlog, pool))
    for a, b in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


#: HLO ops that move a whole operand: a layer of the pool sliced out of
#: the stack, written back into it, or the stack copied
_POOL_MOVES = re.compile(
    r"= \w+\[([\d,]*)\](?:\{[^}]*\})? (copy|dynamic-slice|dynamic-update-slice)\(")


@pytest.mark.parametrize("arch", ["qwen3", "mellum"])
def test_paged_programs_move_no_pool_sized_buffer(arch):
    """Compiled with the pool donated, a two-step decode scan and a
    prefill chunk write the pool only by scatter: no copy, dynamic-slice
    or dynamic-update-slice, fused or not, has the stacked pool's shape
    or one layer's (the pool rides in the layer scan's carry, indexed by
    layer, and is not the scan's input and output)."""
    model = _paged_model(arch)
    params, cache, table, tokens = _paged_batch(model)
    pool = cache["kv"]["k"].shape
    shapes = {pool, pool[1:], (1, *pool[1:])}
    active = jnp.asarray([True, True])

    def decode(params, cache, tok, pos):
        def step(carry, _):
            cache, tok, pos = carry
            logits, cache = model.decode_step_paged(params, cache, tok, pos,
                                                    table, active)
            return (cache, jnp.argmax(logits, -1).astype(jnp.int32), pos + 1), None

        (cache, tok, _), _ = jax.lax.scan(step, (cache, tok, pos), None, length=2)
        return cache, tok

    def prefill(params, cache):
        return model.prefill_paged(params, cache, tokens,
                                   jnp.asarray([0, 0], jnp.int32),
                                   jnp.asarray([8, 5], jnp.int32), table)

    programs = {
        "decode": jax.jit(decode, donate_argnums=1).lower(
            params, cache, tokens[:, 0], jnp.asarray([8, 5], jnp.int32)),
        "prefill": jax.jit(prefill, donate_argnums=1).lower(params, cache),
    }
    for name, lowered in programs.items():
        hlo = lowered.compile().as_text()
        assert "scatter" in hlo, name
        moves = [(op, shp) for shp, op in _POOL_MOVES.findall(hlo)
                 if tuple(int(d) for d in shp.split(",") if d) in shapes]
        assert not moves, (name, moves)


def test_chunked_prefill_paged_matches_full_prefill_logits():
    """Prefilling in chunks across rounds reproduces the one-shot
    batched prefill's pending logits (the serve loop's admission path
    for prompts longer than the chunk)."""
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    slots, chunk, bl = 2, 4, 4
    plens = [7, 5]
    s0 = max(plens)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (slots, s0), 0,
                                c.vocab_size).astype(jnp.int32)
    want, _, _ = m.prefill(params, tokens,
                           jnp.asarray(plens, jnp.int32))

    mb = -(-(s0 + 1) // bl)
    nb = slots * mb
    cache = m.init_paged_cache(nb, bl)
    table_np = np.full((slots, nb), -1, np.int32)
    for s in range(slots):
        table_np[s, :mb] = np.arange(s * mb, (s + 1) * mb)
    table = jnp.asarray(table_np)

    prefilled = [0] * slots
    final = {}
    while any(prefilled[s] < plens[s] for s in range(slots)):
        takes = [min(chunk, plens[s] - prefilled[s]) for s in range(slots)]
        chunk_tok = np.zeros((slots, chunk), np.int32)
        for s in range(slots):
            if takes[s]:
                chunk_tok[s, :takes[s]] = np.asarray(
                    tokens[s, prefilled[s]:prefilled[s] + takes[s]]
                )
        logits, cache = m.prefill_paged(
            params, cache, jnp.asarray(chunk_tok),
            jnp.asarray(prefilled, jnp.int32),
            jnp.asarray(takes, jnp.int32), table,
        )
        for s in range(slots):
            prefilled[s] += takes[s]
            if takes[s] and prefilled[s] >= plens[s]:
                final[s] = np.asarray(logits[s])
    for s in range(slots):
        np.testing.assert_allclose(final[s], np.asarray(want[s]),
                                   rtol=2e-4, atol=2e-4)


# ------------------------------------------- serve(): pool-size invariance
@pytest.mark.parametrize("safety,seed", [(1.2, 0), (3.0, 1)])
def test_paged_serve_is_pool_size_invariant_across_erasure_grid(safety,
                                                                seed):
    """Same trace, same key, same deadline => same erasure masks: a pool
    three times the default must reproduce the default pool's run exactly
    (token counts, finish rounds, round accounting). The tables stay as
    wide as the largest reservation, so any logits divergence from where
    the blocks sit would change an argmax somewhere and break this."""
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    server = Server(m, params, ClusterSpec.make([2, 2], [4.0, 0.8]),
                    ServeConfig(block_rows=64, deadline_safety=safety))
    wl = make_workload("poisson", num_requests=6, prompt_len=(4, 8),
                       out_len=(2, 5), vocab=c.vocab_size)
    trace = wl.trace(seed=seed)
    key = jax.random.PRNGKey(seed)
    rep_p = server.serve(trace, slots=2, decode_block=2, key=key)
    rep_w = server.serve(trace, slots=2, decode_block=2, key=key,
                         num_blocks=3 * rep_p.pool_blocks)
    assert rep_w.pool_blocks == 3 * rep_p.pool_blocks
    assert rep_w.table_blocks == rep_p.table_blocks
    done_p = {f.request.rid: f for f in rep_p.finished if f.outcome == "done"}
    assert done_p
    assert rep_w.tokens == rep_p.tokens
    assert rep_w.rounds == rep_p.rounds
    assert rep_w.decode_rounds == rep_p.decode_rounds
    assert rep_w.admitted == rep_p.admitted and rep_w.shed == rep_p.shed
    done = {f.request.rid: f for f in rep_w.finished if f.outcome == "done"}
    assert done_p.keys() == done.keys()
    for rid, f in done_p.items():
        assert done[rid].finish_round == f.finish_round
        assert done[rid].tokens == f.tokens


# ------------------------------------------------ serve(): retrace pinning
def test_paged_serve_one_trace_across_8x_prompt_spread():
    """Prompt lengths spread 8x within and across traces: ONE compiled
    program total (decode_block=1 => a single steps variant). Both
    traces' largest reservation is 9 blocks, so both calls' tables are
    9 wide: shapes depend only on (num_blocks, block_len, S) and that
    width — never a prompt length as such."""
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    server = Server(m, params, ClusterSpec.make([2, 2], [4.0, 0.8]),
                    ServeConfig(block_rows=64))
    plens = [4, 32, 8, 16, 32, 4]
    trace = [_req(i, arrival=2.0 * i, out_len=3, plen=p)
             for i, p in enumerate(plens)]
    bl, nb = 4, 2 * -(-(32 + 3 + 1) // 4)
    kw = dict(slots=2, decode_block=1, block_len=bl, num_blocks=nb)
    rep = server.serve(trace, **kw)
    assert server.serve_traces == 1
    assert sum(1 for f in rep.finished if f.outcome == "done") == len(plens)
    # a second trace with a different prompt-length mix compiles nothing
    trace2 = [_req(i, arrival=1.5 * i, out_len=3, plen=p)
              for i, p in enumerate([32, 4, 24, 6])]
    server.serve(trace2, prompt_cap=32, **kw)
    assert server.serve_traces == 1


def test_paged_serve_compiles_once_per_table_width():
    """Two calls with the same largest reservation share one program; a
    call with a longer one compiles exactly one more, for its width."""
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    server = Server(m, params, ClusterSpec.make([2, 2], [4.0, 0.8]),
                    ServeConfig(block_rows=64))
    bl = 4
    kw = dict(slots=2, decode_block=1, block_len=bl,
              num_blocks=2 * -(-(48 + 3 + 1) // bl), prompt_cap=48)
    widths = []
    for plens in ([4, 32, 8], [32, 6, 16, 4], [4, 48, 8]):
        trace = [_req(i, arrival=2.0 * i, out_len=3, plen=p)
                 for i, p in enumerate(plens)]
        rep = server.serve(trace, **kw)
        assert sum(f.outcome == "done" for f in rep.finished) == len(plens)
        widths.append((rep.table_blocks, server.serve_traces))
    assert widths == [(9, 1), (9, 1), (13, 2)]


# --------------------------------------- serve(): block-table width report
@pytest.mark.parametrize("nb,width", [(2 * 9, 9), (9 + 3, 9), (7, 7)])
def test_paged_serve_reports_table_width(nb, width):
    """The tables are as wide as the call's largest reservation (9
    blocks here), capped at the pool: a pool of slots x largest, one
    smaller than that (the width is still the largest reservation, not
    nb / slots), and one smaller than the largest (which is then shed)."""
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    server = Server(m, params, ClusterSpec.make([2, 2], [4.0, 0.8]),
                    ServeConfig(block_rows=64))
    trace = [_req(i, out_len=3, plen=p, cls="batch")
             for i, p in enumerate([5, 32, 10])]
    rep = server.serve(trace, slots=2, decode_block=2, block_len=4,
                       num_blocks=nb)
    assert (rep.table_blocks, rep.pool_blocks) == (width, nb)
    assert rep.shed == (1 if nb < 9 else 0)


def test_long_prompt_admits_via_chunked_prefill():
    """A prompt 4.6x ``prompt_cap`` is admitted and prefilled chunk by
    chunk across admit rounds."""
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    server = Server(m, params, ClusterSpec.make([2, 2], [4.0, 0.8]),
                    ServeConfig(block_rows=64))
    long_req = _req(0, out_len=3, plen=37)
    trace = [long_req, _req(1, arrival=1.0, out_len=3, plen=5)]
    rep = server.serve(trace, slots=2, prompt_cap=8, decode_block=2)
    done = {f.request.rid: f for f in rep.finished if f.outcome == "done"}
    assert set(done) == {0, 1}
    assert done[0].tokens == 3
    assert rep.prefill_rounds >= -(-37 // 8)  # one round per chunk


def test_serve_config_accepts_only_the_paged_path():
    """``paged`` is True only: the dense slot-cache path is gone, and
    asking for it fails at construction rather than serving paged."""
    assert ServeConfig().paged is True
    with pytest.raises(ValueError, match="dense slot-cache"):
        ServeConfig(paged=False)


# ----------------------------------------------------- BlockPool + policy
def test_block_pool_lifo_reuse():
    with pytest.raises(ValueError, match="num_blocks"):
        BlockPool(0, 4)
    with pytest.raises(ValueError, match="block_len"):
        BlockPool(4, 0)
    pool = BlockPool(6, 4)
    assert pool.blocks_for(1) == 1 and pool.blocks_for(9) == 3
    a = pool.alloc(2)
    b = pool.alloc(2)
    assert a == [0, 1] and b == [2, 3]
    assert pool.alloc(3) is None  # only 2 free; pool state untouched
    assert pool.free_blocks == 2
    pool.free(a)
    assert pool.alloc(3) == [1, 0, 4]  # most recently freed reused first
    assert pool.blocks_freed == 2


def test_scheduler_reuses_freed_blocks_after_retirement():
    pool = BlockPool(2, 4)
    sched = SlotScheduler(1, pool=pool)
    assert sched.offer(_req(0, plen=3, out_len=4, cls="batch"), 0.0)
    assert sched.offer(_req(1, plen=3, out_len=4, cls="batch"), 0.0)
    (si, _), = sched.fill_slots(0.0)
    first = sched.slots[si].blocks
    assert first == (0, 1)
    assert sched.fill_slots(0.0) == []  # pool empty: head waits, no shed
    sched.advance(4)
    sched.retire_done(4.0)
    assert pool.free_blocks == 2 and pool.blocks_freed == 2
    (si2, r2), = sched.fill_slots(4.0)
    assert r2.rid == 1
    assert set(sched.slots[si2].blocks) == {0, 1}  # LIFO reuse of the frees


def test_pool_exhaustion_sheds_only_never_fitting_requests():
    sink = _Sink()
    pool = BlockPool(2, 4, telemetry=sink)
    sched = SlotScheduler(2, pool=pool, telemetry=sink)
    big = _req(0, plen=20, out_len=20, cls="batch")  # 11 blocks > 2: never
    assert not sched.offer(big, 0.0)
    shed = [f for f in sched.finished if f.outcome == "shed"]
    assert [f.reason for f in shed] == ["pool_exhausted"]
    evicted = [f for n, f in sink.events if n == "request_evicted"]
    assert evicted[0]["reason"] == "pool_exhausted"
    # a request that fits an EMPTY pool is never shed on memory, even
    # when the pool is currently full — it waits at the queue head
    assert sched.offer(_req(1, plen=3, out_len=4, cls="batch"), 0.0)
    sched.fill_slots(0.0)
    assert sched.offer(_req(2, plen=3, out_len=4, cls="batch"), 0.0)
    assert sched.fill_slots(0.0) == []
    assert all(f.reason != "pool_exhausted"
               for f in sched.finished[len(shed):])


def test_block_pool_telemetry_schema():
    sink = _Sink()
    pool = BlockPool(4, 2, bytes_per_block=128, telemetry=sink)
    got = pool.alloc(3, rid=7, now=2.0)
    assert [n for n, _ in sink.events] == ["blocks_in_use", "kv_bytes"]
    use = sink.events[0][1]
    assert use == {"in_use": 3, "free": 1, "capacity": 4,
                   "request_id": 7, "round": 2.0}
    kvb = sink.events[1][1]
    assert kvb == {"bytes_in_use": 384, "bytes_total": 512,
                   "utilization": 0.75, "request_id": 7, "round": 2.0}
    pool.free(got[:2], rid=7, now=3.0)
    assert [n for n, _ in sink.events[2:]] == [
        "blocks_freed", "blocks_in_use", "kv_bytes"
    ]
    freed = sink.events[2][1]
    assert freed == {"blocks": 2, "total_freed": 2,
                     "request_id": 7, "round": 3.0}
    # declared-contract coverage (repro.obs.schema) on every record
    from repro.obs.schema import validate_event

    for name, fields in sink.events:
        validate_event({"event": name, **fields})


# ------------------------------------------------- controller-chosen slots
def test_recommend_slots_scales_with_measured_latency():
    exe = CodedRoundExecutor(
        ClusterSpec.make([8, 16, 8], [4.0, 1.0, 0.25]), 1_000, "optimal"
    )
    ctl = AdaptiveController(exe)
    cur = ctl.coverage_latency()
    assert np.isfinite(cur) and cur > 0
    assert ctl.recommend_slots(base=4) == 4  # no drift: estimates == plan
    assert ctl.recommend_slots(base=4, reference=2 * cur) == 8
    assert ctl.recommend_slots(base=4, reference=cur / 2) == 2
    assert ctl.recommend_slots(base=4, reference=100 * cur) == 16  # hi=4*base
    assert ctl.recommend_slots(base=4, reference=cur / 100) == 1  # lo
    assert ctl.recommend_slots(base=4, reference=float("inf")) == 4  # fallback
    with pytest.raises(ValueError, match="base"):
        ctl.recommend_slots(base=0)
