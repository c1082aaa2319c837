"""Shared neural-net layers (pure JAX, explicit param pytrees).

Every layer is a pair of functions: ``init_*(key, ...) -> params`` and
``apply`` (the function itself). Params are plain dicts so the sharding
layer can pattern-match on path names.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _dense_init(key, shape, dtype, scale=None):
    fan_in = shape[0]
    if scale is None:
        scale = 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def init_rmsnorm(d, dtype):
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def init_layernorm(d, dtype):
    return {"scale": jnp.ones((d,), dtype=dtype), "bias": jnp.zeros((d,), dtype=dtype)}


def layernorm(params, x, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(dt)


def init_linear(key, d_in, d_out, dtype, bias=False):
    p = {"w": _dense_init(key, (d_in, d_out), dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype=dtype)
    return p


def linear(params, x):
    y = x @ params["w"].astype(x.dtype)
    if "b" in params:
        y = y + params["b"].astype(x.dtype)
    return y


def init_mlp(key, d_model, d_ff, dtype, activation="silu"):
    k1, k2, k3 = jax.random.split(key, 3)
    if activation == "silu":  # SwiGLU: gate + up + down
        return {
            "w_gate": _dense_init(k1, (d_model, d_ff), dtype),
            "w_up": _dense_init(k2, (d_model, d_ff), dtype),
            "w_down": _dense_init(k3, (d_ff, d_model), dtype),
        }
    return {  # plain GELU MLP (gemma/whisper style)
        "w_up": _dense_init(k1, (d_model, d_ff), dtype),
        "w_down": _dense_init(k2, (d_ff, d_model), dtype),
    }


def mlp(params, x):
    if "w_gate" in params:
        g = jax.nn.silu(x @ params["w_gate"].astype(x.dtype))
        u = x @ params["w_up"].astype(x.dtype)
        return (g * u) @ params["w_down"].astype(x.dtype)
    h = jax.nn.gelu(x @ params["w_up"].astype(x.dtype))
    return h @ params["w_down"].astype(x.dtype)


def init_embedding(key, vocab, d_model, dtype):
    return {"table": (jax.random.normal(key, (vocab, d_model)) * 0.02).astype(dtype)}


def embed(params, tokens, compute_dtype):
    return params["table"][tokens].astype(compute_dtype)


def unembed(params, x, logit_dtype=jnp.float32):
    """LM head: x @ table^T for the tied embedding (``{"table": (V, D)}``),
    x @ w for an untied output head (``{"w": (D, V)}``). logit_dtype bf16
    halves the dominant (B, S, V) activation bytes; the contraction still
    accumulates f32."""
    if "w" in params:
        spec, w = "...d,dv->...v", params["w"]
    else:
        spec, w = "...d,vd->...v", params["table"]
    return jnp.einsum(
        spec, x, w.astype(x.dtype), preferred_element_type=jnp.float32,
    ).astype(logit_dtype)


def yarn_frequencies(head_dim: int, theta: float, yarn) -> tuple[np.ndarray, float]:
    """(inverse frequencies (head_dim / 2,), cos/sin factor) of YaRN, after
    ``transformers``' ``_compute_yarn_parameters`` (truncated correction
    range), in float64 on the host."""
    half = head_dim // 2
    base = theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    extrapolated, interpolated = 1.0 / base, 1.0 / (yarn.factor * base)

    def correction_dim(rotations):
        return (head_dim * np.log(yarn.original_max_position
                                  / (rotations * 2 * np.pi))
                / (2 * np.log(theta)))

    low = max(np.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(np.ceil(correction_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp  # share of the extrapolated (unscaled) frequency
    inv = interpolated * (1 - keep) + extrapolated * keep
    factor = yarn.attention_factor
    if factor is None:
        factor = 0.1 * np.log(yarn.factor) + 1.0 if yarn.factor > 1 else 1.0
    return inv, float(factor)


def rope(x, positions, theta: float = 10_000.0, yarn=None):
    """Rotary embeddings. x: (..., S, H, hd); positions: (..., S).
    ``yarn`` (a ``configs.base.Yarn``) rescales the frequencies and
    multiplies cos and sin (``yarn_frequencies``)."""
    hd = x.shape[-1]
    half = hd // 2
    if yarn is None:
        freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        inv, mult = yarn_frequencies(hd, theta, yarn)
        freq = jnp.asarray(inv, jnp.float32)
    angles = positions[..., :, None].astype(jnp.float32) * freq  # (..., S, half)
    cos = jnp.cos(angles)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., :, None, :]
    if yarn is not None:
        cos, sin = cos * mult, sin * mult
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1.astype(x.dtype), y2.astype(x.dtype)], axis=-1)


def cross_entropy_loss(logits, labels, mask=None, z_loss: float = 1e-4):
    """Token-mean cross entropy with optional z-loss.

    Works on bf16 or f32 logits WITHOUT materializing an upcast copy:
    the max/exp/sum chain is elementwise-into-reduction (XLA fuses it, so
    the only HBM traffic over the (B, S, V) tensor is reading the logits
    once per reduction), with f32 accumulation for stability.
    """
    m = jnp.max(logits, axis=-1).astype(jnp.float32)  # fused reduce
    sumexp = jnp.sum(
        jnp.exp(logits.astype(jnp.float32) - m[..., None]), axis=-1
    )  # elementwise+reduce: fuses, no f32 copy materialized
    lse = m + jnp.log(sumexp)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll.astype(jnp.float32)
    if z_loss:
        nll = nll + z_loss * lse**2
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
