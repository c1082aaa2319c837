"""Fused linear-cross-entropy kernel vs oracle: value + gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_ce.ops import fused_linear_ce
from repro.kernels.fused_ce.ref import linear_ce_ref

KEY = jax.random.PRNGKey(11)


@pytest.mark.parametrize("t,v,d", [(256, 512, 128), (100, 300, 64),
                                   (8, 1000, 32)])
def test_fused_ce_value_matches_ref(t, v, d):
    h = jax.random.normal(KEY, (t, d)) * 0.5
    e = jax.random.normal(jax.random.fold_in(KEY, 1), (v, d)) * 0.5
    labels = jax.random.randint(jax.random.fold_in(KEY, 2), (t,), 0, v)
    got = fused_linear_ce(h, e, labels, interpret=True)
    want = linear_ce_ref(h, e, labels)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_fused_ce_masked_labels():
    t, v, d = 64, 256, 32
    h = jax.random.normal(KEY, (t, d))
    e = jax.random.normal(jax.random.fold_in(KEY, 1), (v, d))
    labels = jax.random.randint(jax.random.fold_in(KEY, 2), (t,), 0, v)
    masked = labels.at[: t // 2].set(-1)
    got = fused_linear_ce(h, e, masked, interpret=True)
    want = linear_ce_ref(h[t // 2:], e, labels[t // 2:])
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_fused_ce_gradients_match_ref():
    t, v, d = 64, 384, 48
    h = jax.random.normal(KEY, (t, d)) * 0.3
    e = jax.random.normal(jax.random.fold_in(KEY, 1), (v, d)) * 0.3
    labels = jax.random.randint(jax.random.fold_in(KEY, 2), (t,), 0, v)
    gh, ge = jax.grad(
        lambda hh, ee: fused_linear_ce(hh, ee, labels, interpret=True),
        argnums=(0, 1),
    )(h, e)
    gh_r, ge_r = jax.grad(
        lambda hh, ee: linear_ce_ref(hh, ee, labels), argnums=(0, 1)
    )(h, e)
    np.testing.assert_allclose(np.asarray(gh), np.asarray(gh_r),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ge), np.asarray(ge_r),
                               rtol=1e-4, atol=1e-6)
