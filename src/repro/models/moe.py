"""Mixture-of-Experts FFN: a router over every expert, the held experts'
part of the result, no token ever dropped.

Expert parallelism divides a layer's experts over chips; this layer is
one chip's part. It holds experts ``0 .. E_held - 1`` of the router's
``R`` (``E_held == R`` when every expert is held):

* routing: float32 softmax over all ``R`` router logits, top-k, and the
  k chosen probabilities renormalised to sum to 1;
* the output is the sum, over a token's chosen experts that are held
  here, of gate x SwiGLU_e(x). What the other chips' experts add is left
  out, as it would arrive from them.

Dispatch is by sort, not capacity: the T*k token-choice pairs are sorted
by expert (pairs for experts held elsewhere last), and each held expert
multiplies exactly its own rows in one grouped matmul per projection
(``jax.lax.ragged_dot``). Every pair gets its row, so a token's output
never depends on what other tokens route, and FLOPs scale with the
pairs that land here, not with T*E.

Expert weights are stacked (E_held, ...) so the expert dimension shards
on the ``model`` mesh axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import _dense_init


def init_moe(key, d_model, d_ff, num_experts, dtype, router_width=None):
    """``num_experts`` held experts behind a router of ``router_width``
    outputs (default: every expert held)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "w_router": _dense_init(k1, (d_model, router_width or num_experts), dtype),
        "w_gate": _dense_init(k2, (num_experts, d_model, d_ff), dtype),
        "w_up": _dense_init(k3, (num_experts, d_model, d_ff), dtype),
        "w_down": _dense_init(k4, (num_experts, d_ff, d_model), dtype),
    }


def route(params, xf, top_k):
    """(T, D) -> gates (T, k) float32, experts (T, k) int32."""
    logits = jnp.dot(xf, params["w_router"].astype(xf.dtype),
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)
    return gates / jnp.sum(gates, axis=-1, keepdims=True), experts


def moe_ffn(params, x, *, top_k, valid=None):
    """x: (B, S, D) -> ((B, S, D), counts).

    ``counts`` (int32 (2,)): the token-choice pairs that landed on held
    experts, and the held experts that received at least one, over the
    rows where ``valid`` (B, S) is true (every row by default)."""
    b, s, d = x.shape
    t = b * s
    held = params["w_gate"].shape[0]
    xf = x.reshape(t, d)
    gates, experts = route(params, xf, top_k)

    e_flat = experts.reshape(-1)  # (T*k,), token-major
    mine = e_flat < held
    group = jnp.where(mine, e_flat, held)  # held elsewhere: sorted last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
    tok = order // top_k
    rows = xf[tok]  # (T*k, D), grouped by expert

    def grouped(lhs, w):
        return jax.lax.ragged_dot(lhs, w.astype(lhs.dtype), sizes)

    h = jax.nn.silu(grouped(rows, params["w_gate"])) * grouped(rows, params["w_up"])
    y = grouped(h, params["w_down"])  # (T*k, D)
    w = jnp.where(mine[order], gates.reshape(-1)[order], 0.0)
    # rows of pairs held elsewhere lie past the groups: masked, never read
    contrib = jnp.where(mine[order][:, None], y.astype(jnp.float32) * w[:, None], 0.0)
    out = jnp.zeros((t, d), jnp.float32).at[tok].add(contrib)

    counted = mine
    if valid is not None:
        counted &= jnp.repeat(jnp.asarray(valid, bool).reshape(t), top_k)
    hits = jnp.zeros((held + 1,), jnp.int32).at[
        jnp.where(counted, e_flat, held)].add(1)[:held]
    counts = jnp.stack([jnp.sum(counted, dtype=jnp.int32),
                        jnp.sum(hits > 0, dtype=jnp.int32)])
    return out.astype(x.dtype).reshape(b, s, d), counts
