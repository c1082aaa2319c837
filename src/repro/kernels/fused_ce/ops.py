"""Jit'd wrapper + custom VJP for the fused linear-cross-entropy kernel.

Forward: the Pallas kernel (logits never touch HBM).
Backward: d_logits = (softmax - onehot) / T, folded tile-by-tile into
dH = d_logits @ E and dE = d_logits^T @ H with the lse from the forward
— again without materializing the full (T, V) tensor (a lax.scan over
vocab tiles; each tile's logits are recomputed in registers/VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fused_ce.kernel import BT, BV, fused_ce_kernel


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_linear_ce(h, table, labels, bt: int = BT, bv: int = BV,
                    interpret: bool = False):
    """Mean cross entropy of ``h @ table^T`` vs labels, fused.

    h: (T, D); table: (V, D); labels: (T,) int32 (negatives = masked).
    """
    loss, _ = _forward(h, table, labels, bt, bv, interpret)
    return loss


def _forward(h, table, labels, bt, bv, interpret):
    t, d = h.shape
    v, _ = table.shape
    bt = min(bt, _pad_to(t, 8))
    bv = min(bv, _pad_to(v, 128))
    tp, vp = _pad_to(t, bt), _pad_to(v, bv)
    mask = labels >= 0
    safe_labels = jnp.where(mask, labels, 0).astype(jnp.int32)
    hp = jnp.pad(h, ((0, tp - t), (0, 0))) if tp != t else h
    # pad table with -inf-producing rows? zero rows give logit 0 which
    # perturbs the lse; instead pad and mask via a huge negative bias on
    # padded labels never being hit, and subtract their contribution is
    # messy — pad with a large-negative constant row instead:
    if vp != v:
        pad_rows = jnp.full((vp - v, d), 0.0, table.dtype)
        tablep = jnp.concatenate([table, pad_rows], axis=0)
    else:
        tablep = table
    labp = jnp.pad(safe_labels, (0, tp - t)) if tp != t else safe_labels
    lse, ll = fused_ce_kernel(hp, tablep, labp, bt=bt, bv=bv,
                              interpret=interpret)
    lse, ll = lse[:t], ll[:t]
    if vp != v:
        # remove the padded rows' exp(h . 0) = 1 contributions exactly:
        # lse' = log(exp(lse) - n_pad) computed stably.
        n_pad = float(vp - v)
        lse = lse + jnp.log1p(-n_pad * jnp.exp(-lse))
    nll = (lse - ll) * mask
    denom = jnp.maximum(jnp.sum(mask), 1)
    loss = jnp.sum(nll) / denom
    return loss, (h, table, safe_labels, mask, lse, denom)


def _fwd(h, table, labels, bt, bv, interpret):
    loss, res = _forward(h, table, labels, bt, bv, interpret)
    return loss, res


def _bwd(bt, bv, interpret, res, g):
    h, table, labels, mask, lse, denom = res
    t, d = h.shape
    v, _ = table.shape
    bvp = min(bv, _pad_to(v, 128))
    scale = (g * mask / denom).astype(jnp.float32)  # (T,)
    h32 = h.astype(jnp.float32)
    nv = -(-v // bvp)
    vp = nv * bvp
    tablep = jnp.pad(table, ((0, vp - v), (0, 0))) if vp != v else table

    def tile(carry, j):
        dh = carry
        start = j * bvp
        e_tile = jax.lax.dynamic_slice(
            tablep, (start, 0), (bvp, d)
        ).astype(jnp.float32)  # (BV, D) — padded table: no start clamping
        logits = h32 @ e_tile.T  # (T, BV) one tile at a time
        # mask rows beyond the true vocab
        ids = start + jnp.arange(bvp)
        p = jnp.exp(logits - lse[:, None])
        p = jnp.where((ids < v)[None, :], p, 0.0)
        onehot = (labels[:, None] == ids[None, :]).astype(jnp.float32)
        dl = (p - onehot) * scale[:, None]  # (T, BV)
        de_tile = dl.T @ h32  # (BV, D)
        dh = dh + dl @ e_tile
        return dh, (de_tile, j)

    dh0 = jnp.zeros((t, d), jnp.float32)
    dh, (de_tiles, _) = jax.lax.scan(tile, dh0, jnp.arange(nv))
    de = de_tiles.reshape(nv * bvp, d)[:v]
    return dh.astype(h.dtype), de.astype(table.dtype), None


fused_linear_ce.defvjp(_fwd, _bwd)
