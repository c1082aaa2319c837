"""Jax gather/scatter paged-attention path (the production serve path).

The pool is ``(num_blocks + 1, block_len, KV, hd)`` per layer: physical
block ``num_blocks`` is the WRITE SINK — inactive / frozen / padded
writes are routed there so no predicate is needed around the scatter and
a frozen slot can never corrupt a block that was freed and reassigned to
another stream. The sink is never referenced by any block table, so the
gather+mask path never reads it as valid history.

The decode attend mirrors ``models.attention.decode_attention_slots``
operation-for-operation (same einsums, same f32 promotion points, same
softmax) so that with an equivalent layout (blocks in logical order) the
paged decode logits BIT-MATCH the dense slot-cache oracle.
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


def gather_kv(pool, table):
    """(NBp, BL, KV, hd), (S, MB) -> (S, MB*BL, KV, hd) logical view."""
    sink = pool.shape[0] - 1
    s, mb = table.shape
    bl = pool.shape[1]
    return pool[_phys(table, sink)].reshape(s, mb * bl, *pool.shape[2:])


def valid_mask(table, block_len, q_pos):
    """(S, MB), BL, (S,) -> (S, MB*BL) attendable-entry mask."""
    alloc = jnp.repeat(table >= 0, block_len, axis=1)
    j = jnp.arange(alloc.shape[1])
    return alloc & (j[None, :] <= q_pos[:, None])


def scatter_decode(k_pool, v_pool, k_new, v_new, table, pos, active):
    """Write one token per slot into the pool at logical position ``pos``.

    k_new/v_new: (S, KV, hd); pos: (S,) int32; active: (S,) bool — rows
    that are not actively decoding write to the sink block.
    """
    sink = jnp.int32(k_pool.shape[0] - 1)
    bl = k_pool.shape[1]
    mb = table.shape[1]
    bidx = jnp.clip(pos // bl, 0, mb - 1)
    blk = jnp.take_along_axis(table, bidx[:, None], axis=1)[:, 0]
    blk = jnp.where(active & (blk >= 0), blk, sink).astype(jnp.int32)
    off = jnp.mod(pos, bl).astype(jnp.int32)
    return k_pool.at[blk, off].set(k_new), v_pool.at[blk, off].set(v_new)


def scatter_chunk(k_pool, v_pool, k_new, v_new, table, start, chunk_len):
    """Write a prefill chunk per slot into the pool.

    k_new/v_new: (S, C, KV, hd); chunk row ``i`` of slot ``s`` lands at
    logical position ``start[s] + i`` when ``i < chunk_len[s]``; padded
    rows (and rows of slots not prefilling this round) go to the sink.
    """
    s, c = k_new.shape[:2]
    sink = jnp.int32(k_pool.shape[0] - 1)
    bl = k_pool.shape[1]
    mb = table.shape[1]
    p = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]  # (S, C)
    writing = jnp.arange(c)[None, :] < chunk_len[:, None]
    bidx = jnp.clip(p // bl, 0, mb - 1)
    blk = jnp.take_along_axis(table, bidx, axis=1)
    blk = jnp.where(writing & (blk >= 0), blk, sink).astype(jnp.int32)
    off = jnp.mod(p, bl).astype(jnp.int32)
    flat = lambda t: t.reshape(s * c, *t.shape[2:])
    return (
        k_pool.at[flat(blk), flat(off)].set(flat(k_new)),
        v_pool.at[flat(blk), flat(off)].set(flat(v_new)),
    )


def paged_decode_attend(q, k_pool, v_pool, table, pos):
    """Single-query paged attention over the gathered pool.

    q: (S, KV, G, hd) post-rope; pos: (S,) write positions (already
    scattered). Mirrors ``decode_attention_slots``'s attend math exactly
    (bit-parity with the dense oracle under an order-preserving layout).
    Returns (S, KV, G, hd) in v's dtype.
    """
    bl = k_pool.shape[1]
    # python-float scale (f64 sqrt), matching decode_attention_slots
    # bit-for-bit — a traced f32 rsqrt can differ in the last ulp
    scale = 1.0 / np.sqrt(q.shape[-1])
    k = gather_kv(k_pool, table)
    v = gather_kv(v_pool, table)
    sc = jnp.einsum("bkgh,bskh->bkgs", q, k).astype(jnp.float32) * scale
    valid = valid_mask(table, bl, pos)
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    w = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgs,bskh->bkgh", w, v)


def paged_chunk_attend(q, k_pool, v_pool, table, q_pos):
    """Chunked-prefill paged attention: C queries per slot.

    q: (S, C, KV, G, hd) post-rope; q_pos: (S, C) absolute positions.
    One mask covers cross-chunk history (earlier admit rounds' blocks)
    and in-chunk causality. Returns (S, C, KV, G, hd).
    """
    bl = k_pool.shape[1]
    scale = 1.0 / np.sqrt(q.shape[-1])
    k = gather_kv(k_pool, table)
    v = gather_kv(v_pool, table)
    sc = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32) * scale
    alloc = jnp.repeat(table >= 0, bl, axis=1)  # (S, L)
    j = jnp.arange(alloc.shape[1])
    valid = alloc[:, None, :] & (j[None, None, :] <= q_pos[:, :, None])
    sc = jnp.where(valid[:, None, None, :, :], sc, NEG_INF)
    w = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bkgqh", w, v)
    return out.transpose(0, 3, 1, 2, 4)  # (S, C, KV, G, hd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attend_kernel(q, k_pool, v_pool, table, pos, *,
                               interpret: bool = False):
    """Pallas-kernel route for the decode attend (ops-compatible API)."""
    from repro.kernels.paged_attention.kernel import paged_decode_kernel

    return paged_decode_kernel(q, k_pool, v_pool, table, pos,
                               interpret=interpret)
