"""Jit-native decode pipeline: oracle parity, fused master step, and the
single-compiled-program guarantee of the serving loop."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from repro.configs import ARCHS
from repro.core import ClusterSpec, plan_deployment
from repro.core.coded_matvec import (
    DecodePipeline,
    end_to_end_coded_matvec,
    masked_decode,
    pack_coded_matrix,
)
from repro.core.coding import (
    decode_systematic,
    decode_systematic_jit,
    encode,
    make_generator,
    systematic_passthrough,
)
from repro.models.model import Model
from repro.runtime.serve_loop import CodedLMHead, ServeConfig, Server
from repro.serve import make_workload

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------- decode_systematic_jit
@pytest.mark.parametrize("erasures", [0, 3, 8, 16])  # 16 = exactly threshold
@pytest.mark.parametrize("cols", [None, 5])
def test_decode_jit_matches_numpy_oracle(erasures, cols):
    """Fixed-shape jit decode == numpy oracle across the erasure grid."""
    k, n = 32, 48
    g = make_generator(n, k, KEY)
    shape = (k,) if cols is None else (k, cols)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(2), shape))
    y = np.asarray(encode(g, jnp.asarray(x)))
    rng = np.random.default_rng(erasures)
    mask = np.ones(n, bool)
    mask[rng.choice(n, size=erasures, replace=False)] = False
    z_jit, ok_jit = decode_systematic_jit(g, jnp.asarray(y), jnp.asarray(mask))
    z_np, ok_np = decode_systematic(g, y, mask, k)
    assert bool(ok_jit) and ok_np
    np.testing.assert_allclose(np.asarray(z_jit), z_np, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(z_jit), x, rtol=1e-4, atol=1e-4)


def test_decode_jit_insufficient_survivors():
    """< k survivors: ok=False and a zeroed (not garbage) output."""
    k, n = 16, 24
    g = make_generator(n, k, KEY)
    y = np.asarray(encode(g, np.ones((k,), np.float32)))
    mask = np.zeros(n, bool)
    mask[: k - 1] = True
    z, ok = decode_systematic_jit(g, jnp.asarray(y), jnp.asarray(mask))
    assert not bool(ok)
    np.testing.assert_array_equal(np.asarray(z), np.zeros(k, np.float32))
    _, ok_np = decode_systematic(g, y, mask, k)
    assert not ok_np


def test_decode_jit_is_traceable_fixed_shape():
    """The decode survives jit with mask as a traced argument."""
    k, n = 8, 12
    g = make_generator(n, k, KEY)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (k,)))
    y = encode(g, jnp.asarray(x))
    f = jax.jit(lambda m: decode_systematic_jit(g, y, m))
    mask = np.ones(n, bool)
    mask[[0, 5]] = False
    z, ok = f(jnp.asarray(mask))
    assert bool(ok)
    np.testing.assert_allclose(np.asarray(z), x, rtol=1e-4, atol=1e-4)


def _dense_decode(g, y, mask):
    """The dense solve alone: first k survivors in index order, LU, two
    solves and a refinement, zeros when fewer than k survive."""
    g, y, mask = map(np.asarray, (g, y, mask))
    k = g.shape[1]
    idx = np.flatnonzero(mask)[:k]
    if idx.size < k:
        return np.zeros((k,) + y.shape[1:], y.dtype), False
    return np.linalg.solve(g[idx].astype(np.float64), y[idx]), True


def _erase(n, rows):
    mask = np.ones(n, bool)
    mask[list(rows)] = False
    return mask


@pytest.mark.parametrize("parity_erased", [0, 3, 16])
@pytest.mark.parametrize("cols", [None, 5])
def test_decode_jit_passes_systematic_rows_through(parity_erased, cols):
    """No systematic row erased: the decode is y[:k], bit for bit, and the
    numpy oracle gives the same."""
    k, n = 32, 48
    g = make_generator(n, k, KEY)
    shape = (k,) if cols is None else (k, cols)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(2), shape))
    y = np.asarray(encode(g, jnp.asarray(x)))
    rng = np.random.default_rng(parity_erased)
    mask = _erase(n, k + rng.choice(n - k, size=parity_erased, replace=False))
    assert bool(systematic_passthrough(g, mask))
    z, ok = decode_systematic_jit(g, jnp.asarray(y), jnp.asarray(mask))
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(z), y[:k])
    z_np, ok_np = decode_systematic(g, y, mask, k)
    assert ok_np
    np.testing.assert_array_equal(np.asarray(z), z_np)


@pytest.mark.parametrize("erased", [[0], [1, 7, 30], list(range(16)),
                                    list(range(17))])  # 17: < k survive
@pytest.mark.parametrize("cols", [None, 5])
def test_decode_jit_systematic_erasure_runs_the_dense_solve(erased, cols):
    """A systematic row erased: the dense solve's answer, and zeros with
    ok False when fewer than k rows survive."""
    k, n = 32, 48
    g = make_generator(n, k, KEY)
    shape = (k,) if cols is None else (k, cols)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(2), shape))
    y = np.asarray(encode(g, jnp.asarray(x)))
    mask = _erase(n, erased)
    assert not bool(systematic_passthrough(g, mask))
    z, ok = decode_systematic_jit(g, jnp.asarray(y), jnp.asarray(mask))
    z_dense, ok_dense = _dense_decode(g, y, mask)
    assert bool(ok) == ok_dense == (len(erased) <= n - k)
    if ok_dense:
        np.testing.assert_allclose(np.asarray(z), z_dense, rtol=1e-4,
                                   atol=1e-4)
    else:
        np.testing.assert_array_equal(np.asarray(z), z_dense)


def test_decode_jit_non_systematic_generator_always_solves():
    """A generator whose first k rows are not the identity never passes
    through, even with no erasure, and still decodes."""
    k, n = 8, 12
    g = make_generator(n, k, kind="chebyshev_vandermonde")
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (k,)))
    y = encode(g, jnp.asarray(x))
    mask = np.ones(n, bool)
    assert not bool(systematic_passthrough(g, mask))
    z, ok = decode_systematic_jit(g, y, jnp.asarray(mask))
    assert bool(ok)
    np.testing.assert_allclose(np.asarray(z), x, rtol=1e-3, atol=1e-3)


# ------------------------------------------------------ fused master step
def _one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1), ("workers",))


def test_fused_pipeline_matches_host_decode():
    """DecodePipeline (device decode) == legacy host numpy decode."""
    mesh = _one_device_mesh()
    cluster = ClusterSpec.make([4, 4], [4.0, 1.0], 1.0)
    plan = plan_deployment(cluster, k=64)
    a = jax.random.normal(KEY, (64, 32))
    x = jax.random.normal(jax.random.PRNGKey(1), (32,))
    fin = np.ones(plan.num_workers, bool)
    fin[[plan.num_workers - 1]] = False
    z_jit, ok_jit = end_to_end_coded_matvec(mesh, a, x, plan,
                                            finished_workers=fin)
    z_host, ok_host = end_to_end_coded_matvec(mesh, a, x, plan,
                                              finished_workers=fin,
                                              jit_decode=False)
    assert bool(ok_jit) and ok_host
    np.testing.assert_allclose(np.asarray(z_jit), z_host, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(z_jit), np.asarray(a @ x),
                               rtol=2e-2, atol=2e-3)


def test_fused_pipeline_insufficient_flag():
    mesh = _one_device_mesh()
    cluster = ClusterSpec.make([4], [2.0], 1.0)
    plan = plan_deployment(cluster, k=64)
    a = jax.random.normal(KEY, (64, 16))
    x = jax.random.normal(jax.random.PRNGKey(1), (16,))
    fin = np.zeros(plan.num_workers, bool)
    _, ok = end_to_end_coded_matvec(mesh, a, x, plan, finished_workers=fin)
    assert not bool(ok)


def test_decode_pipeline_kernel_route():
    """use_kernel=True (Pallas, interpreted on CPU) matches the einsum route."""
    mesh = _one_device_mesh()
    cluster = ClusterSpec.make([3, 3], [4.0, 1.0], 1.0)
    plan = plan_deployment(cluster, k=48)
    gen = make_generator(plan.n, plan.k, KEY)
    a = jax.random.normal(KEY, (48, 16))
    x = jax.random.normal(jax.random.PRNGKey(1), (16,))
    packed, row_of = pack_coded_matrix(gen, a, plan)
    fin = jnp.ones(plan.num_workers, bool)
    ref = DecodePipeline(mesh, gen, row_of)
    ker = DecodePipeline(mesh, gen, row_of, use_kernel=True)
    z_ref, ok_ref = ref(jnp.asarray(packed), x, fin)
    with pltpu.force_tpu_interpret_mode():
        z_ker, ok_ker = ker(jnp.asarray(packed), x, fin)
    assert bool(ok_ref) and bool(ok_ker)
    np.testing.assert_allclose(np.asarray(z_ker), np.asarray(z_ref),
                               rtol=1e-4, atol=1e-4)


def test_masked_decode_drops_pad_and_dead_slots():
    """Pad slots (-1) and straggler rows never reach the solve."""
    k, n = 8, 12
    g = make_generator(n, k, KEY)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (k,)))
    y = np.asarray(encode(g, jnp.asarray(x)))
    # 3 workers x 5 slots, ragged loads (4, 4, 4) + pads
    row_of = np.full((3, 5), -1, np.int32)
    partials = np.full((3, 5), 1e9, np.float32)  # garbage in pad slots
    for w in range(3):
        rows = np.arange(4 * w, 4 * w + 4)
        row_of[w, :4] = rows
        partials[w, :4] = y[rows]
    fin = np.array([True, False, True])  # worker 1 straggles: rows 4..7 dead
    z, ok = masked_decode(g, row_of, jnp.asarray(partials), jnp.asarray(fin))
    assert bool(ok)  # 8 surviving rows == k
    np.testing.assert_allclose(np.asarray(z), x, rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------- coded head
def _head(block_rows=64, groups=((4, 2.0), (4, 0.5))):
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    cluster = ClusterSpec.make([n for n, _ in groups], [mu for _, mu in groups])
    head = CodedLMHead(params["embed"]["table"], cluster, block_rows=block_rows)
    return c, m, params, cluster, head


def test_head_decode_jit_matches_numpy_oracle():
    c, m, params, cluster, head = _head()
    h = jax.random.normal(KEY, (3, c.d_model))
    products = head.worker_products(h)
    # kill one worker (stays above threshold for the optimal plan's slack)
    mask = np.ones(head.plan.num_workers, bool)
    w_kill = int(np.argmin(head.plan.loads_per_worker))
    if head.nb - int(head.plan.loads_per_worker[w_kill]) >= head.kb:
        mask[w_kill] = False
    logits_jit, ok_jit = head.decode_logits_jit(products, jnp.asarray(mask))
    logits_np, ok_np = head.decode_logits(products, mask)
    assert bool(ok_jit) and ok_np
    np.testing.assert_allclose(np.asarray(logits_jit), logits_np,
                               rtol=1e-3, atol=1e-3)
    expected = np.asarray(h @ head.table.T)
    np.testing.assert_allclose(
        np.asarray(logits_jit)[:, : head.table.shape[0]], expected,
        rtol=1e-3, atol=1e-3,
    )


def test_head_encode_logits_kernel_parity():
    c, m, params, cluster, head = _head()
    logits = jax.random.normal(KEY, (2, head.kb * head.block_rows))
    ref = head.encode_logits(logits)
    with pltpu.force_tpu_interpret_mode():
        ker = head.encode_logits(logits, use_kernel=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_head_worker_products_kernel_parity():
    c, m, params, cluster, head = _head()
    h = jax.random.normal(KEY, (2, c.d_model))
    ref = head.worker_products(h)
    with pltpu.force_tpu_interpret_mode():
        ker = head.worker_products(h, use_kernel=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# -------------------------------------------------------- serving loop
def test_server_generate_coded_matches_uncoded_regression():
    """Full generate with coded head == uncoded argmax, no stragglers."""
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    prompts = jax.random.randint(KEY, (2, 4), 0, c.vocab_size).astype(jnp.int32)

    plain = Server(m, params, None, ServeConfig(max_decode_steps=8))
    out_plain = plain.generate(prompts, 8)

    cluster = ClusterSpec.make([8], [5.0])
    coded = Server(m, params, cluster, ServeConfig(max_decode_steps=8))
    coded.coded_head.deadline = 1e9  # nobody misses
    out_coded = coded.generate(prompts, 8)
    np.testing.assert_array_equal(np.asarray(out_plain), np.asarray(out_coded))


def test_generate_is_single_compiled_program():
    """No retrace across calls; the program is scan-driven and callback-free."""
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    cluster = ClusterSpec.make([8], [5.0])
    server = Server(m, params, cluster, ServeConfig(max_decode_steps=5))
    prompts = jax.random.randint(KEY, (2, 4), 0, c.vocab_size).astype(jnp.int32)

    server.generate(prompts, 5)
    assert server.traces == 1
    server.generate(prompts, 5, key=jax.random.PRNGKey(9))
    assert server.traces == 1  # same shapes: zero Python work between tokens

    # jaxpr-level: the token loop is lax.scan, with no host callbacks
    cache = m.init_cache(2, 9, None)
    closed = jax.make_jaxpr(functools.partial(server._gen_program, max_new=5))(
        server.params, cache, prompts, KEY, jnp.float32(1e9)
    )
    ClosedJaxpr = type(closed)
    Jaxpr = type(closed.jaxpr)

    def prims(jaxpr, acc):
        for eqn in jaxpr.eqns:
            acc.add(eqn.primitive.name)
            for v in eqn.params.values():
                for sub in jax.tree_util.tree_leaves(
                    v, is_leaf=lambda x: isinstance(x, (Jaxpr, ClosedJaxpr))
                ):
                    if isinstance(sub, ClosedJaxpr):
                        prims(sub.jaxpr, acc)
                    elif isinstance(sub, Jaxpr):
                        prims(sub, acc)
        return acc

    top = {eqn.primitive.name for eqn in closed.jaxpr.eqns}
    assert "scan" in top  # prefill scan + token-loop scan
    everything = prims(closed.jaxpr, set())
    assert not everything & {"pure_callback", "io_callback", "debug_callback"}


def test_generate_first_token_is_coded():
    """Trace-time spy: the coded select runs for token 0 and the scan body."""
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    cluster = ClusterSpec.make([8], [5.0])
    server = Server(m, params, cluster, ServeConfig(max_decode_steps=4))
    calls = []
    orig = server._coded_select

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    server._coded_select = spy
    prompts = jax.random.randint(KEY, (1, 3), 0, c.vocab_size).astype(jnp.int32)
    server.generate(prompts, 4)
    assert len(calls) == 2  # token 0 + once inside the (traced-once) scan body


# ------------------------------------------ pass-through count in serve
def _count_server(deadline_safety=3.0):
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    cluster = ClusterSpec.make([2, 2], [4.0, 0.8])
    server = Server(m, params, cluster,
                    ServeConfig(block_rows=64, deadline_safety=deadline_safety))
    return c, server


def _serve_counting(server, vocab):
    """Serve a small trace; also count on the host, from each dispatch's
    own keys and straggler parameters, the rounds that erase no
    systematic block."""
    head = server.coded_head
    step = server._serve_step_paged_fn
    expected = []

    def count(*args, steps):
        skey, deadline, (mus, alphas, shifts) = args[10:13]
        for t in range(steps):
            fin = head.finish_mask_jit(jax.random.fold_in(skey, t), deadline,
                                       mus=mus, alphas=alphas, shifts=shifts)
            alive = np.asarray(fin)[head.block_owner]
            expected.append(bool(alive[: head.kb].all()))
        return step(*args, steps=steps)

    server._serve_step_paged_fn = count
    wl = make_workload("poisson", num_requests=6, prompt_len=(4, 8),
                       out_len=(4, 12), vocab=vocab)
    rep = server.serve(wl.trace(seed=3), slots=2, decode_block=4)
    assert len(expected) == rep.decode_rounds > 0
    return rep, sum(expected)


@pytest.mark.parametrize("case", ["planned", "no_miss", "systematic_out"])
def test_serve_counts_passthrough_rounds(case):
    """``passthrough_rounds`` is the number of decode rounds that erased
    no systematic block; the rest ran the dense solve. A deadline no
    worker misses passes every round through; a fleet whose systematic
    blocks never arrive passes none."""
    c, server = _count_server(deadline_safety=1.0)
    head = server.coded_head
    if case == "no_miss":
        head.deadline = 1e9
    if case == "systematic_out":
        # workers 0-1 hold block 0, a systematic block, and never finish
        assert head.block_owner[0] == 0
        server.set_true_cluster(ClusterSpec.make([2, 2], [1e-9, 0.8]))
    rep, passed = _serve_counting(server, c.vocab_size)
    dense = rep.decode_rounds - passed
    assert rep.passthrough_rounds == passed
    assert rep.passthrough_rounds + dense == rep.decode_rounds
    if case == "planned":
        assert 0 < rep.passthrough_rounds < rep.decode_rounds
    if case == "no_miss":
        assert rep.passthrough_rounds == rep.decode_rounds
    if case == "systematic_out":
        assert rep.passthrough_rounds == 0


def test_serve_non_systematic_head_never_passes_through():
    """A coded head whose generator is not systematic takes the dense
    solve on every round, though no block is erased."""
    c, server = _count_server()
    head = server.coded_head
    head.generator = np.asarray(make_generator(
        head.nb, head.kb, kind="chebyshev_vandermonde"))
    head.generator_j = jnp.asarray(head.generator)
    head.deadline = 1e9
    wl = make_workload("poisson", num_requests=4, prompt_len=(4, 8),
                       out_len=(2, 6), vocab=c.vocab_size)
    rep = server.serve(wl.trace(seed=5), slots=2, decode_block=2)
    assert rep.decode_rounds > 0 and rep.fallback_rounds == 0
    assert rep.passthrough_rounds == 0
