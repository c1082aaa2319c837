"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.kernels.coded_matvec.ops import blocked_matvec, blocked_matvec_batch
from repro.kernels.coded_matvec.ref import matvec_batch_ref, matvec_ref
from repro.kernels.mds_encode.ops import mds_encode
from repro.kernels.mds_encode.ref import encode_ref

KEY = jax.random.PRNGKey(7)


def _tol(dt):
    return (2e-2, 2e-1) if dt == jnp.bfloat16 else (1e-5, 1e-4)


@pytest.mark.parametrize("r,d", [(8, 128), (256, 1024), (100, 333),
                                 (513, 2050), (1, 1), (7, 4096)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matvec_kernel_matches_ref(r, d, dtype):
    a = jax.random.normal(KEY, (r, d), dtype)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (d,), dtype)
    rtol, atol = _tol(dtype)
    np.testing.assert_allclose(
        np.asarray(blocked_matvec(a, x, interpret=True), np.float32),
        np.asarray(matvec_ref(a, x), np.float32),
        rtol=rtol, atol=atol,
    )


@pytest.mark.parametrize("n,r,d", [(1, 8, 128), (4, 300, 1030),
                                   (300, 24, 37)])
def test_matvec_kernel_vector_batch_matches_ref(n, r, d):
    """Vectors as the rows of x: (N, D) -> (N, R), every A tile shared."""
    a = jax.random.normal(KEY, (r, d), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (n, d), jnp.float32)
    got = np.asarray(blocked_matvec(a, x, interpret=True))
    assert got.shape == (n, r)
    np.testing.assert_allclose(got, np.asarray(matvec_ref(a, x)),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("w,l,d", [(3, 16, 64), (5, 100, 257)])
def test_matvec_batch_matches_ref(w, l, d):
    a = jax.random.normal(KEY, (w, l, d))
    x = jax.random.normal(KEY, (d,))
    np.testing.assert_allclose(
        np.asarray(blocked_matvec_batch(a, x, interpret=True)),
        np.asarray(matvec_batch_ref(a, x)),
        rtol=1e-5, atol=1e-4,
    )


@pytest.mark.parametrize("n,k,d", [(256, 128, 256), (300, 200, 77),
                                   (17, 9, 5), (512, 512, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_encode_kernel_matches_ref(n, k, d, dtype):
    g = jax.random.normal(KEY, (n, k), dtype)
    a = jax.random.normal(jax.random.fold_in(KEY, 2), (k, d), dtype)
    rtol, atol = _tol(dtype)
    np.testing.assert_allclose(
        np.asarray(mds_encode(g, a, interpret=True), np.float32),
        np.asarray(encode_ref(g, a), np.float32),
        rtol=rtol, atol=atol * 10,
    )


@settings(max_examples=20, deadline=None)
@given(
    r=st.integers(1, 300), d=st.integers(1, 600),
    seed=st.integers(0, 2**31 - 1),
)
def test_matvec_property_random_shapes(r, d, seed):
    k = jax.random.PRNGKey(seed)
    a = jax.random.normal(k, (r, d))
    x = jax.random.normal(jax.random.fold_in(k, 1), (d,))
    np.testing.assert_allclose(
        np.asarray(blocked_matvec(a, x, interpret=True)),
        np.asarray(matvec_ref(a, x)),
        rtol=1e-5, atol=1e-4,
    )


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(1, 150), k=st.integers(1, 120), d=st.integers(1, 150),
    seed=st.integers(0, 2**31 - 1),
)
def test_encode_property_random_shapes(n, k, d, seed):
    key = jax.random.PRNGKey(seed)
    g = jax.random.normal(key, (n, k))
    a = jax.random.normal(jax.random.fold_in(key, 1), (k, d))
    np.testing.assert_allclose(
        np.asarray(mds_encode(g, a, interpret=True)),
        np.asarray(encode_ref(g, a)),
        rtol=1e-5, atol=1e-4,
    )


def test_kernel_linearity_invariant():
    """Coded matvec must be linear: kernel(G A, x) == G kernel-rows(A, x)."""
    g = jax.random.normal(KEY, (24, 16))
    a = jax.random.normal(jax.random.fold_in(KEY, 3), (16, 80))
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (80,))
    coded = mds_encode(g, a, interpret=True)
    lhs = blocked_matvec(coded, x, interpret=True)
    rhs = g @ blocked_matvec(a, x, interpret=True)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), rtol=1e-4, atol=1e-4)
