"""Real-valued MDS coding for distributed matrix-vector multiplication.

The paper applies an (n, k) MDS code to the ROWS of the data matrix
``A in R^{k x d}``: ``A~ = G A`` with a generator ``G in R^{n x k}`` whose
every k-row submatrix is invertible. The master recovers ``A x`` from any
k coded inner products by solving ``G_S z = y~_S``.

Generators provided:

* ``systematic_gaussian`` — ``G = [I_k; P]`` with i.i.d. Gaussian parity
  ``P`` (MDS with probability 1). When no systematic row is erased the
  decode is the first k coded rows as they are, with no solve; the numpy
  oracle also solves only for the missing systematic rows when some are
  erased.
* ``chebyshev_vandermonde`` — Vandermonde on Chebyshev nodes (determinis-
  tic, every minor nonsingular; conditioning degrades with k, fine for
  k <= a few hundred as used in tests/examples).

Encoding is a matmul (performed once, offline, like the paper's setup
phase); the Pallas kernel in ``repro/kernels/mds_encode`` provides the
TPU-tiled version of the same contraction.

Decoding comes in two flavours: ``decode_systematic_jit`` — the
fixed-shape, device-resident decode used by the serving pipeline (one
compiled program per round that runs the gather and solve only when the
round erased a systematic row, composable under ``jax.lax.scan``) —
and the numpy ``decode_systematic`` / ``decode_from_rows`` pair kept as
reference oracles for tests and the legacy host-loop path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def make_generator(n: int, k: int, key=None, kind: str = "systematic_gaussian"):
    """Build an (n, k) real MDS generator matrix."""
    assert n >= k >= 1
    if kind == "systematic_gaussian":
        if key is None:
            key = jax.random.PRNGKey(0)
        p = jax.random.normal(key, (n - k, k), dtype=jnp.float32) / np.sqrt(k)
        return jnp.concatenate([jnp.eye(k, dtype=jnp.float32), p], axis=0)
    if kind == "chebyshev_vandermonde":
        i = np.arange(n)
        nodes = np.cos((2 * i + 1) * np.pi / (2 * n))  # distinct in (-1, 1)
        powers = np.arange(k)
        g = nodes[:, None] ** powers[None, :]
        return jnp.asarray(g, dtype=jnp.float32)
    raise ValueError(f"unknown generator kind: {kind}")


def encode(generator, a):
    """A~ = G A  (rows of A are coded; columns untouched).

    ``a`` may carry trailing dims (k, ...): a row is then a whole block.
    Full float32 precision: on a TPU the default would round the coded
    rows to bfloat16, and the erasure solve amplifies that error.
    """
    return jnp.tensordot(
        generator, a, axes=1, precision=jax.lax.Precision.HIGHEST
    )


def split_loads(loads_int_per_worker):
    """Row ranges [(start, stop)) of A~ for each worker, from integer loads."""
    starts = np.concatenate([[0], np.cumsum(loads_int_per_worker)[:-1]])
    return [
        (int(s), int(s + l)) for s, l in zip(starts, loads_int_per_worker)
    ]


@functools.partial(jax.jit, static_argnames=())
def decode_from_rows(generator_rows, coded_values):
    """Recover A x from >= k coded inner products.

    Args:
      generator_rows: (m, k) the generator rows of the surviving coded
        inner products, m >= k.
      coded_values: (m,) or (m, c) the corresponding values of A~ x.

    Returns the least-squares solution z (= A x when G_S has rank k).
    """
    sol = jnp.linalg.lstsq(generator_rows, coded_values)[0]
    return sol


def systematic_passthrough(generator, finished_mask):
    """Traced bool: the decode is the first k coded rows as they are.

    True when the generator's first k rows are the identity (a
    systematic code) and every one of those rows survived, so that the
    first k survivors in index order give ``G_S = I``.
    """
    g = jnp.asarray(generator)
    k = g.shape[1]
    mask = jnp.asarray(finished_mask, dtype=bool)
    return jnp.all(g[:k] == jnp.eye(k, dtype=g.dtype)) & jnp.all(mask[:k])


@jax.jit
def decode_systematic_jit(generator, coded_values, finished_mask):
    """Fixed-shape, device-resident erasure decode (the serving hot path).

    Unlike ``decode_systematic`` (the numpy reference oracle below) this
    never leaves the device and has one branch, a ``lax.cond`` on a
    scalar (``systematic_passthrough``):

    * pass-through: the generator is systematic and no systematic row is
      erased, so ``y[:k]`` is the answer; no factorisation or solve runs;
    * dense solve, on every other step (any non-systematic generator,
      or a systematic row erased): the surviving coded rows are selected
      with a stable argsort on the erasure mask — survivors first, in
      index order — and the first k of them are gathered into a static
      ``(k, k)`` system, LU-factored and solved on-device. For a
      systematic generator with few erasures that system is mostly
      identity rows, so it stays well-conditioned; one step of iterative
      refinement recovers oracle-level accuracy at float32.

    Under ``vmap`` the ``cond`` becomes a select that runs both sides.

    Args:
      generator: (n, k) MDS generator used at encode time.
      coded_values: (n,) or (n, c) coded products; garbage where
        ``finished_mask`` is False (garbage rows are never gathered
        while >= k rows survive).
      finished_mask: (n,) bool — which coded rows arrived by the deadline.

    Returns (z, ok): the decoded (k,) or (k, c) product and a traced
    bool that is False when fewer than k rows survived (z is zeroed; the
    caller selects a fallback with ``jnp.where`` — see DESIGN.md §4).
    """
    g = jnp.asarray(generator)
    n, k = g.shape
    y = jnp.asarray(coded_values)
    mask = jnp.asarray(finished_mask, dtype=bool)

    def passthrough():
        return y[:k], jnp.bool_(True)

    def solve():
        # Survivors first, original order preserved -> static (k,) gather.
        order = jnp.argsort(~mask, stable=True)
        idx = order[:k]
        g_s = g[idx]
        y_s = y[idx].astype(g.dtype)
        rhs = y_s if y_s.ndim == 2 else y_s[:, None]
        lu, piv = jax.scipy.linalg.lu_factor(g_s)
        z = jax.scipy.linalg.lu_solve((lu, piv), rhs)
        resid = rhs - jnp.matmul(g_s, z, precision=jax.lax.Precision.HIGHEST)
        z = z + jax.scipy.linalg.lu_solve((lu, piv), resid)  # refine
        z = z if y_s.ndim == 2 else z[:, 0]
        ok = jnp.sum(mask) >= k
        zero = jnp.zeros_like(z, dtype=y.dtype)
        return jnp.where(ok, z.astype(y.dtype), zero), ok

    return jax.lax.cond(systematic_passthrough(g, mask), passthrough, solve)


def decode_systematic(generator, coded_values, finished_mask, k: int):
    """Fast decode for systematic generators.

    Uses surviving systematic rows directly and solves only for the
    missing ones using parity rows — an O(e^3) solve for e erasures
    instead of O(k^3). Falls back to a dense solve when not systematic.

    Args:
      generator: (n, k) systematic generator [I; P].
      coded_values: (n,) or (n, c) coded products, garbage where
        ``finished_mask`` is False.
      finished_mask: (n,) bool — which coded rows arrived in time.
      k: number of uncoded rows.

    Returns (z, ok): the decoded A x and whether enough rows survived.
    This path is numpy (decode happens on the master, tiny cost compared
    to the distributed matvec itself).
    """
    g = np.asarray(generator)
    y = np.asarray(coded_values)
    mask = np.asarray(finished_mask)
    n = g.shape[0]
    assert mask.shape == (n,)
    if mask.sum() < k:
        return np.zeros((k,) + y.shape[1:], dtype=y.dtype), False
    sys_alive = mask[:k]
    missing = np.flatnonzero(~sys_alive)
    out_shape = (k,) + y.shape[1:]
    z = np.zeros(out_shape, dtype=y.dtype)
    z[np.flatnonzero(sys_alive)] = y[:k][sys_alive]
    if missing.size == 0:
        return z, True
    parity_alive = np.flatnonzero(mask[k:]) + k
    if parity_alive.size < missing.size:
        return z, False
    # Choose the first e surviving parity rows; G_par @ z_full = y_par.
    use = parity_alive[: max(missing.size, min(parity_alive.size, 2 * missing.size))]
    g_par = g[use]  # (p, k)
    rhs = y[use] - g_par[:, np.flatnonzero(sys_alive)] @ z[np.flatnonzero(sys_alive)]
    sub = g_par[:, missing]  # (p, e)
    sol, *_ = np.linalg.lstsq(sub, rhs, rcond=None)
    z[missing] = sol
    return z, True
