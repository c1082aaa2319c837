"""Jit'd wrappers for the coded-matvec kernel (padding + batching)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.coded_matvec.kernel import BD, BN, BR, matvec_kernel


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


@functools.partial(jax.jit, static_argnames=("interpret",))
def blocked_matvec(a, x, *, interpret: bool = False):
    """A x for arbitrary (R, D), padded to tile multiples and sliced back.

    ``x`` is one vector (D,) -> (R,), or a batch of vectors as rows
    (N, D) -> (N, R), i.e. ``x A^T``: every A tile is streamed once for
    the whole batch.
    """
    r, d = a.shape
    xs = x[None] if x.ndim == 1 else x
    n = xs.shape[0]
    bn = min(BN, _pad_to(n, 8))
    br = min(BR, _pad_to(r, 128))
    bd = min(BD, _pad_to(d, 128))
    np_, rp, dp = _pad_to(n, bn), _pad_to(r, br), _pad_to(d, bd)
    if (rp, dp) != (r, d):
        a = jnp.pad(a, ((0, rp - r), (0, dp - d)))
    if (np_, dp) != (n, d):
        xs = jnp.pad(xs, ((0, np_ - n), (0, dp - d)))
    y = matvec_kernel(a, xs, bn=bn, br=br, bd=bd, interpret=interpret)
    y = y[:n, :r]
    return y[0] if x.ndim == 1 else y


@functools.partial(jax.jit, static_argnames=("interpret",))
def blocked_matvec_batch(a, x, *, interpret: bool = False):
    """a: (W, L, ..., D), x: (D,) -> (W, L, ...): one kernel call over
    the flattened rows of every worker's block."""
    y = blocked_matvec(a.reshape(-1, a.shape[-1]), x, interpret=interpret)
    return y.reshape(a.shape[:-1])
