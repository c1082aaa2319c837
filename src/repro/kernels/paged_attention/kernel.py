"""Pallas TPU kernel: paged single-query attention over a KV block pool.

Grid = (S, MB): program (s, j) processes logical block j of slot s. The
block table and per-slot positions ride in as SCALAR-PREFETCH operands
(``pltpu.PrefetchScalarGridSpec``), so the K/V BlockSpec index maps can
resolve ``table[s, j]`` to a physical pool block *before* the body runs
— the DMA engine fetches exactly the (1, BL, KV, hd) block the table
points at (unallocated entries fetch the sink block and are masked).

Accumulation across the MB grid dimension is the standard online
softmax: running max ``m``, normalizer ``l`` and weighted-value ``acc``
live in VMEM scratch, initialized at j == 0 and stored at j == MB-1
(same revisiting-output pattern as ``kernels/coded_matvec``). ``m`` is
initialized to the finite ``NEG_INF`` sentinel (not −inf) so fully
masked blocks contribute exp(0) terms that the next valid block's
correction factor underflows to exactly zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref):
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (KV, G, hd)
    k = k_ref[0].astype(jnp.float32)  # (BL, KV, hd)
    v = v_ref[0].astype(jnp.float32)
    bl = k.shape[0]
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    sc = jnp.einsum("kgh,bkh->kgb", q, k) * scale  # (KV, G, BL)
    logical = j * bl + jnp.arange(bl, dtype=jnp.int32)
    ok = (table_ref[s, j] >= 0) & (logical <= pos_ref[s])
    sc = jnp.where(ok[None, None, :], sc, NEG_INF)
    m_new = jnp.maximum(m_ref[...], jnp.max(sc, axis=-1))
    p = jnp.exp(sc - m_new[..., None])
    corr = jnp.exp(m_ref[...] - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[..., None] + jnp.einsum(
        "kgb,bkh->kgh", p, v
    )
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _store():
        out = acc_ref[...] / jnp.maximum(l_ref[...][..., None], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_kernel(q, k_pool, v_pool, table, pos, *,
                        interpret: bool = False):
    """Paged decode attend. q: (S, KV, G, hd); pools: (NBp, BL, KV, hd);
    table: (S, MB) int32; pos: (S,) int32. Returns (S, KV, G, hd)."""
    s, kv, g, hd = q.shape
    nbp, bl = k_pool.shape[:2]
    mb = table.shape[1]
    sink = nbp - 1

    def kv_index(si, j, table_ref, pos_ref):
        t = table_ref[si, j]
        return (jnp.where(t >= 0, t, sink), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # table, pos
        grid=(s, mb),
        in_specs=[
            pl.BlockSpec((1, kv, g, hd), lambda si, j, t, p: (si, 0, 0, 0)),
            pl.BlockSpec((1, bl, kv, hd), kv_index),
            pl.BlockSpec((1, bl, kv, hd), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, kv, g, hd), lambda si, j, t, p: (si, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((kv, g), jnp.float32),  # running max
            pltpu.VMEM((kv, g), jnp.float32),  # normalizer
            pltpu.VMEM((kv, g, hd), jnp.float32),  # weighted values
        ],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, kv, g, hd), v_pool.dtype),
        interpret=interpret,
    )(table.astype(jnp.int32), pos.astype(jnp.int32), q, k_pool, v_pool)
