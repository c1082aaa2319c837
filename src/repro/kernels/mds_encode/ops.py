"""Jit'd wrapper for the MDS-encode kernel (padding to tile multiples)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.mds_encode.kernel import BD, BK, BN, encode_kernel


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


@functools.partial(jax.jit, static_argnames=("bn", "bd", "bk", "interpret"))
def mds_encode(g, a, *, bn: int = BN, bd: int = BD, bk: int = BK,
               interpret: bool = False):
    """A~ = G A for arbitrary shapes: pad, run kernel, slice."""
    n, k = g.shape
    _, d = a.shape
    bn = min(bn, _pad_to(n, 8))
    bk = min(bk, _pad_to(k, 128))
    bd = min(bd, _pad_to(d, 128))
    np_, kp, dp = _pad_to(n, bn), _pad_to(k, bk), _pad_to(d, bd)
    if (np_, kp) != (n, k):
        g = jnp.pad(g, ((0, np_ - n), (0, kp - k)))
    if (kp, dp) != (k, d):
        a = jnp.pad(a, ((0, kp - k), (0, dp - d)))
    out = encode_kernel(g, a, bn=bn, bd=bd, bk=bk, interpret=interpret)
    return out[:n, :d]
