"""Jax gather/scatter paged-attention path (the production serve path).

The pool is ``(num_blocks + 1, block_len, KV * hd)`` per layer: a row
holds one token's K (or V) heads side by side, and physical block
``num_blocks`` is the WRITE SINK — inactive / frozen / padded writes are
routed there so no predicate is needed around the scatter and a frozen
slot can never corrupt a block that was freed and reassigned to another
stream. The sink is never referenced by any block table, so the
gather+mask path never reads it as valid history.

Every op takes ``layer``: None for one layer's pool (the form the kernel
parity tests use), or a layer index into the stacked ``(L, num_blocks +
1, block_len, KV * hd)`` pool that the model's layer scan carries. The
stacked form scatters ``pool[layer, blk, off]`` and gathers ``pool[layer,
phys]`` straight from the stack, so a layer never slices its pool out of
the stack nor writes it back (DESIGN.md §13). Rows of ``KV * hd`` keep
the pool's last two dimensions ``(block_len, KV * hd)`` whole tiles for
any head count: with ``(KV, hd)`` last and 4 KV heads, the compiler
gives the chunk's gather a different layout of the whole stacked pool
than the scatter's, and copies the pool between them every layer.

The served logits these attends produce are checked against the plain
float32 references in ``bench/reference/`` (``tests/test_routed_window.py``
compares every pending-logits row of a served trace), and each attend
against the numpy oracle in ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def _phys(table, sink):
    """Physical block per table entry; unallocated -> sink."""
    return jnp.where(table >= 0, table, sink)


def _at(layer, *idx):
    """The pool index of ``idx`` in one layer's pool (``layer`` None) or
    in layer ``layer`` of the stacked pool."""
    return idx if layer is None else (layer, *idx)


def gather_kv(pool, table, layer=None):
    """(NBp, BL, R) or (L, NBp, BL, R) with ``layer``, (S, MB) -> (S,
    MB*BL, R) logical rows."""
    sink = pool.shape[-3] - 1
    s, mb = table.shape
    bl = pool.shape[-2]
    rows = pool[_at(layer, _phys(table, sink))]
    return rows.reshape(s, mb * bl, pool.shape[-1])


def _gather_heads(pool, table, layer, q):
    """``gather_kv`` split into q's heads: (S, MB*BL, KV, hd)."""
    rows = gather_kv(pool, table, layer)
    return rows.reshape(*rows.shape[:2], q.shape[-3], q.shape[-1])


def valid_mask(table, block_len, q_pos, window=None):
    """(S, MB), BL, (S, ...) -> (S, ..., MB*BL) attendable-entry mask:
    allocated, at or before the query's position, and with a ``window``
    fewer than ``window`` positions behind it. Logical entry j of a
    slot's table holds its token at position j."""
    alloc = jnp.repeat(table >= 0, block_len, axis=1)
    j = jnp.arange(alloc.shape[1])
    q = q_pos[..., None]
    alloc = alloc.reshape(alloc.shape[:1] + (1,) * (q.ndim - 2) + alloc.shape[1:])
    valid = alloc & (j <= q)
    if window is not None:
        valid &= q - j < window
    return valid


def scatter_decode(k_pool, v_pool, k_new, v_new, table, pos, active,
                   layer=None):
    """Write one token per slot into the pool at logical position ``pos``.

    k_new/v_new: (S, KV, hd); pos: (S,) int32; active: (S,) bool — rows
    that are not actively decoding write to the sink block. With
    ``layer``, the rows land in that layer of the stacked pool.
    """
    sink = jnp.int32(k_pool.shape[-3] - 1)
    bl = k_pool.shape[-2]
    mb = table.shape[1]
    bidx = jnp.clip(pos // bl, 0, mb - 1)
    blk = jnp.take_along_axis(table, bidx[:, None], axis=1)[:, 0]
    blk = jnp.where(active & (blk >= 0), blk, sink).astype(jnp.int32)
    off = jnp.mod(pos, bl).astype(jnp.int32)
    at = _at(layer, blk, off)
    row = lambda t: t.reshape(t.shape[0], -1)
    return k_pool.at[at].set(row(k_new)), v_pool.at[at].set(row(v_new))


def scatter_chunk(k_pool, v_pool, k_new, v_new, table, start, chunk_len,
                  layer=None):
    """Write a prefill chunk per slot into the pool.

    k_new/v_new: (S, C, KV, hd); chunk row ``i`` of slot ``s`` lands at
    logical position ``start[s] + i`` when ``i < chunk_len[s]``; padded
    rows (and rows of slots not prefilling this round) go to the sink.
    With ``layer``, the rows land in that layer of the stacked pool.
    """
    s, c = k_new.shape[:2]
    sink = jnp.int32(k_pool.shape[-3] - 1)
    bl = k_pool.shape[-2]
    mb = table.shape[1]
    p = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]  # (S, C)
    writing = jnp.arange(c)[None, :] < chunk_len[:, None]
    bidx = jnp.clip(p // bl, 0, mb - 1)
    blk = jnp.take_along_axis(table, bidx, axis=1)
    blk = jnp.where(writing & (blk >= 0), blk, sink).astype(jnp.int32)
    off = jnp.mod(p, bl).astype(jnp.int32)
    row = lambda t: t.reshape(s * c, -1)
    at = _at(layer, blk.reshape(-1), off.reshape(-1))
    return k_pool.at[at].set(row(k_new)), v_pool.at[at].set(row(v_new))


def paged_decode_attend(q, k_pool, v_pool, table, pos, window=None,
                        layer=None):
    """Single-query paged attention over the gathered pool.

    q: (S, KV, G, hd) post-rope; pos: (S,) write positions (already
    scattered); ``layer``: as in ``gather_kv``. Returns (S, KV, G, hd)
    in v's dtype; checked against ``ref.paged_decode_attend_ref`` and,
    served, against the float32 references.
    """
    bl = k_pool.shape[-2]
    # python-float scale (f64 sqrt), the same constant as the chunk
    # attend's — a traced f32 rsqrt can differ in the last ulp
    scale = 1.0 / np.sqrt(q.shape[-1])
    k = _gather_heads(k_pool, table, layer, q)
    v = _gather_heads(v_pool, table, layer, q)
    sc = jnp.einsum("bkgh,bskh->bkgs", q, k).astype(jnp.float32) * scale
    valid = valid_mask(table, bl, pos, window)
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    w = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgs,bskh->bkgh", w, v)


def paged_chunk_attend(q, k_pool, v_pool, table, q_pos, window=None,
                       layer=None):
    """Chunked-prefill paged attention: C queries per slot.

    q: (S, C, KV, G, hd) post-rope; q_pos: (S, C) absolute positions;
    ``layer``: as in ``gather_kv``. One mask covers cross-chunk history
    (earlier admit rounds' blocks) and in-chunk causality. Returns
    (S, C, KV, G, hd).
    """
    bl = k_pool.shape[-2]
    scale = 1.0 / np.sqrt(q.shape[-1])
    k = _gather_heads(k_pool, table, layer, q)
    v = _gather_heads(v_pool, table, layer, q)
    sc = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32) * scale
    valid = valid_mask(table, bl, q_pos, window)  # (S, C, L)
    sc = jnp.where(valid[:, None, None, :, :], sc, NEG_INF)
    w = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bkgqh", w, v)
    return out.transpose(0, 3, 1, 2, 4)  # (S, C, KV, G, hd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attend_kernel(q, k_pool, v_pool, table, pos, *,
                               interpret: bool = False):
    """Pallas-kernel route for the decode attend (ops-compatible API: one
    layer's pool of rows, split into q's heads for the kernel's blocks)."""
    from repro.kernels.paged_attention.kernel import paged_decode_kernel

    heads = lambda t: t.reshape(*t.shape[:2], q.shape[1], q.shape[-1])
    return paged_decode_kernel(q, heads(k_pool), heads(v_pool), table, pos,
                               interpret=interpret)
