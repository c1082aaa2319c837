"""Seeded weights, drawn leaf by leaf so that two independent callers get
the same bits.

The benchmark makes the weights, not the program: ``program_params``
fills the program's parameter tree in one jitted call on the device, and
the plain reference draws each layer's leaves again with ``leaf`` when it
needs them. Every value is exact in float32 before the one rounding to the
served dtype: a 16-bit integer from the seed's random bits times a power
of two. So the two calls agree bit for bit, whatever XLA fuses.

A leaf is named by its path in the tree (``blocks/attn/wq``); a leaf of
the stacked layers (under a prefix the reference names in ``STACKED``)
is drawn per layer from ``fold_in(key, layer)``.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

#: leaves drawn as 1 +- 1/8 (norm scales); every other leaf is a weight
NORM_LEAVES = ("scale", "q_norm", "k_norm")
#: the embedding's half-width (uniform on [-1/32, 1/32): std 0.018)
EMBED_HALF_WIDTH = 2.0 ** -5


def seed_key(seed: int):
    """A key from any non-negative seed up to 64 bits (no x64 needed)."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def _path_id(path: str) -> np.uint32:
    return np.uint32(zlib.crc32(path.encode()))


def half_width(path: str, shape) -> float:
    """Power-of-two half-width of a weight leaf's uniform distribution,
    nearest to sqrt(3 / fan_in) (a unit-variance input keeps unit
    variance), so scaling is exact. The fan-in is the contracting axis,
    the second to last: ``d_in`` of a ``(d_in, d_out)`` matrix and of a
    layer's ``(experts, d_in, d_out)`` stack."""
    if path == "embed/table":
        return EMBED_HALF_WIDTH
    fan_in = shape[-2] if len(shape) >= 2 else shape[0]
    return 2.0 ** round(math.log2(math.sqrt(3.0 / fan_in)))


def leaf(key, path: str, shape, layer=None):
    """Float32 values of one leaf (one layer's slice for stacked leaves).

    ``layer`` may be traced (``vmap`` over layers gives the same bits as
    one call per layer).
    """
    k = jax.random.fold_in(key, _path_id(path))
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    bits = jax.random.bits(k, tuple(shape), jnp.uint32)
    u = ((bits >> 16).astype(jnp.int32) - 32768).astype(jnp.float32)
    u = u * (2.0 ** -15)  # exact: [-1, 1) on a 2**-15 grid
    if path.rsplit("/", 1)[-1] in NORM_LEAVES:
        return 1.0 + u * 0.125
    return u * half_width(path, shape)


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def program_params(shapes, seed: int, stacked=("blocks/",)):
    """Fill the tree of ``ShapeDtypeStruct``s ``shapes`` (the program's
    ``init_params`` layout) in one jitted call. Leaves under any prefix of
    ``stacked`` are stacked over layers on axis 0."""

    def fill(key):
        def one(path, sds):
            name = _path_str(path)
            if name.startswith(tuple(stacked)):
                layers = jnp.arange(sds.shape[0], dtype=jnp.uint32)
                vals = jax.vmap(
                    lambda i: leaf(key, name, sds.shape[1:], i)
                )(layers)
            else:
                vals = leaf(key, name, sds.shape)
            return vals.astype(sds.dtype)

        return jax.tree_util.tree_map_with_path(one, shapes)

    return jax.jit(fill)(seed_key(seed))
