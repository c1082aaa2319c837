"""Pallas TPU kernel: fused linear cross-entropy (Liger-style).

The dominant memory term of every train_4k cell is the (B*S, V) logits
round-trip (EXPERIMENTS.md §Roofline): materializing f32 logits at 1M
tokens x 100k+ vocab costs hundreds of GB of HBM traffic per device.
This kernel never writes logits to HBM: it tiles the unembedding matmul
``logits = H @ E^T`` over (token-tile, vocab-tile) grid cells, keeps
each (BT, BV) logit tile in VMEM, and folds it directly into an online
logsumexp (running max + rescaled sumexp, the flash-attention trick
applied to the softmax denominator) plus the label logit.

Grid: (T/BT, V/BV) with the vocab dimension innermost; per token tile
the accumulators (m, s, ll) are (BT,) VMEM scratch, carried across
vocab tiles via the revisiting-output pattern.

HBM traffic: H read V/BV... no — H tile is re-read per vocab tile
(nv * T * D * 2 bytes) and E read once (V * D * 2): both orders of
magnitude below the T*V*4 logit write it replaces whenever
nv * D << V (e.g. nv=26, D=4096, V=131k).

out: per-token (lse, label_logit) pairs -> loss = mean(lse - ll).
Backward (dH, dE) recomputes the tile softmax — provided as a
custom-vjp in ops.py using the same tiling in pure jnp (the recompute
is again logit-materialization-free per tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BT = 256  # tokens per tile
BV = 512  # vocab rows per tile (BT*BV f32 tile = 512 KiB VMEM)

NEG = -1e30


def _kernel(h_ref, e_ref, lab_ref, lse_ref, ll_ref, m_ref, s_ref, ll_acc):
    vj = pl.program_id(1)

    @pl.when(vj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        ll_acc[...] = jnp.zeros_like(ll_acc)

    h = h_ref[...].astype(jnp.float32)  # (BT, D)
    e = e_ref[...].astype(jnp.float32)  # (BV, D)
    logits = jax.lax.dot_general(
        h, e, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (BT, BV) — lives only in VMEM
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(logits, axis=-1))
    corr = jnp.exp(m_old - m_new)
    s_ref[...] = s_ref[...] * corr + jnp.sum(
        jnp.exp(logits - m_new[:, None]), axis=-1
    )
    m_ref[...] = m_new
    # label logit if it falls inside this vocab tile
    bv = logits.shape[1]
    local = lab_ref[...] - vj * bv  # (BT,)
    hit = (local >= 0) & (local < bv)
    idx = jnp.clip(local, 0, bv - 1)
    picked = jnp.take_along_axis(logits, idx[:, None], axis=1)[:, 0]
    ll_acc[...] += jnp.where(hit, picked, 0.0)

    @pl.when(vj == pl.num_programs(1) - 1)
    def _store():
        lse_ref[...] = m_ref[...] + jnp.log(s_ref[...])
        ll_ref[...] = ll_acc[...]


@functools.partial(jax.jit, static_argnames=("bt", "bv", "interpret"))
def fused_ce_kernel(h, table, labels, *, bt: int = BT, bv: int = BV,
                    interpret: bool = False):
    """Per-token (lse, label_logit). Shapes must divide (bt, bv)."""
    t, d = h.shape
    v, _ = table.shape
    assert t % bt == 0 and v % bv == 0, (t, bt, v, bv)
    grid = (t // bt, v // bv)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bv, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bt,), lambda i, j: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((bt,), lambda i, j: (i,)),
            pl.BlockSpec((bt,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t,), jnp.float32),
            jax.ShapeDtypeStruct((t,), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bt,), jnp.float32),
            pltpu.VMEM((bt,), jnp.float32),
            pltpu.VMEM((bt,), jnp.float32),
        ],
        interpret=interpret,
    )(h, table, labels)
