"""MDS coding: encode/decode correctness, erasure tolerance, planner."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from repro.core import ClusterSpec, plan_deployment
from repro.core.coding import (
    decode_from_rows,
    decode_systematic,
    encode,
    make_generator,
    split_loads,
)
from repro.core.planner import estimate_mu_online, replan_on_membership_change

KEY = jax.random.PRNGKey(0)


def test_any_k_rows_decode_gaussian():
    k, d, n = 32, 8, 48
    g = make_generator(n, k, KEY)
    a = jax.random.normal(jax.random.PRNGKey(1), (k, d))
    y = encode(g, a @ jnp.ones((d,)))
    rng = np.random.default_rng(0)
    for _ in range(10):
        rows = rng.choice(n, size=k, replace=False)
        z = decode_from_rows(g[rows], y[rows])
        np.testing.assert_allclose(
            np.asarray(z), np.asarray(a @ jnp.ones((d,))), rtol=1e-3, atol=1e-4
        )


def test_vandermonde_decode():
    k, n = 16, 24
    g = make_generator(n, k, kind="chebyshev_vandermonde")
    x = jax.random.normal(KEY, (k,))
    y = encode(g, x)
    rows = np.arange(n)[-k:]  # all-parity worst case
    z = decode_from_rows(g[rows], y[rows])
    np.testing.assert_allclose(np.asarray(z), np.asarray(x), rtol=1e-3, atol=1e-4)


def test_systematic_fast_decode():
    k, n = 64, 96
    g = make_generator(n, k, KEY)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (k,)))
    y = np.asarray(encode(g, jnp.asarray(x)))
    # erase 10 systematic rows and 5 parity rows
    mask = np.ones((n,), dtype=bool)
    mask[[3, 7, 11, 20, 31, 40, 41, 50, 60, 63]] = False
    mask[[70, 80, 90, 94, 95]] = False
    z, ok = decode_systematic(g, y, mask, k)
    assert ok
    np.testing.assert_allclose(z, x, rtol=1e-4, atol=1e-5)


def test_systematic_decode_insufficient():
    k, n = 8, 10
    g = make_generator(n, k, KEY)
    y = np.zeros((n,), dtype=np.float32)
    mask = np.zeros((n,), dtype=bool)
    mask[:5] = True
    _, ok = decode_systematic(g, y, mask, k)
    assert not ok


@settings(max_examples=25, deadline=None)
@given(
    st.integers(min_value=4, max_value=40),
    st.integers(min_value=0, max_value=16),
    st.integers(min_value=0, max_value=999),
)
def test_property_mds_recovery(k, extra, seed):
    """Any k surviving coded rows recover the product (MDS property)."""
    n = k + extra
    g = make_generator(n, k, jax.random.PRNGKey(seed))
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(seed + 1), (k,)))
    y = np.asarray(encode(g, jnp.asarray(x)))
    rng = np.random.default_rng(seed)
    alive = rng.choice(n, size=k, replace=False)
    mask = np.zeros((n,), dtype=bool)
    mask[alive] = True
    z, ok = decode_systematic(g, y, mask, k)
    assert ok
    np.testing.assert_allclose(z, x, rtol=5e-2, atol=5e-3)


def test_split_loads():
    assert split_loads([3, 2, 4]) == [(0, 3), (3, 5), (5, 9)]


def test_planner_deployment_and_replan():
    c = ClusterSpec.make([4, 8], [4.0, 1.0], 1.0)
    plan = plan_deployment(c, k=256, scheme="optimal")
    assert plan.num_workers == 12
    assert plan.n == plan.loads_per_worker.sum() >= 256
    assert len(plan.row_ranges) == 12
    # elastic: group 2 loses half its workers -> replan keeps invariants
    c2 = ClusterSpec.make([4, 4], [4.0, 1.0], 1.0)
    plan2 = replan_on_membership_change(plan, c2)
    assert plan2.num_workers == 8
    assert plan2.n >= 256
    assert plan2.t_star > plan.t_star  # fewer workers -> higher latency


def test_estimate_mu_online():
    rng = np.random.default_rng(0)
    k, load = 1000, 50.0
    mu_true, alpha_true = 3.0, 1.0
    t = alpha_true * load / k + (load / (k * mu_true)) * rng.exponential(
        size=(20000,)
    )
    mus, alphas = estimate_mu_online([t], k, [load])
    assert mus[0] == pytest.approx(mu_true, rel=0.05)
    assert alphas[0] == pytest.approx(alpha_true, rel=0.05)


DISTRIBUTED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import ClusterSpec, plan_deployment
from repro.core.coded_matvec import end_to_end_coded_matvec

c = ClusterSpec.make([4, 4], [4.0, 1.0], 1.0)
plan = plan_deployment(c, k=128, scheme="optimal")
assert plan.num_workers == 8
mesh = Mesh(np.array(jax.devices()).reshape(8), ("workers",))
key = jax.random.PRNGKey(0)
a = jax.random.normal(key, (128, 64))
x = jax.random.normal(jax.random.PRNGKey(1), (64,))
# all workers finish
z, ok = end_to_end_coded_matvec(mesh, a, x, plan)
assert ok
np.testing.assert_allclose(z, np.asarray(a @ x), rtol=2e-2, atol=2e-3)
# stragglers: two slow-group workers miss the deadline (34 of the 40
# redundant rows -- within the plan's straggler tolerance)
fin = np.ones(8, bool); fin[[6, 7]] = False
z2, ok2 = end_to_end_coded_matvec(mesh, a, x, plan, finished_workers=fin)
assert ok2
np.testing.assert_allclose(z2, np.asarray(a @ x), rtol=2e-2, atol=2e-3)
print("DISTRIBUTED_OK")
"""


def test_distributed_coded_matvec_8_devices():
    """shard_map coded matvec on 8 placeholder devices (own process so the
    device-count flag never leaks into this test session)."""
    r = subprocess.run(
        [sys.executable, "-c", DISTRIBUTED_SCRIPT],
        capture_output=True,
        text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             "JAX_PLATFORMS": "cpu"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "DISTRIBUTED_OK" in r.stdout
