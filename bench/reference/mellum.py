"""Plain reference of Mellum2's decoder (``MellumForCausalLM``): routed
experts, window and full attention mixed, an untied head. Float32 at
``Precision.HIGHEST``, one layer at a time.

It follows the published configuration's own keys:

- embedding lookup; per layer: RMSNorm -> GQA attention with half-split
  rotary embedding, RMSNorm on each query and key head first (assumed, as
  in Qwen3-MoE: the file's ``use_qk_norm``, with its reason under
  ``assumed``), softmax scale
  1/sqrt(head_dim); residual; RMSNorm -> routed experts; residual;
- ``layer_types``: a ``sliding_attention`` layer's query at position i
  attends keys j with i - j < ``sliding_window``, a ``full_attention``
  layer's every j <= i; ``rope_parameters`` per layer type: plain RoPE,
  or YaRN (``transformers``' ``_compute_yarn_parameters``, truncated
  correction range: frequencies ramped between ``beta_fast`` and
  ``beta_slow`` rotations over ``original_max_position_embeddings``,
  interpolated ones divided by ``factor``, cos and sin both times
  ``attention_factor``);
- experts: router logits over every routed expert, softmax in float32,
  top ``num_experts_per_tok``, the chosen probabilities renormalised
  (``norm_topk_prob``); the output is the sum over the chosen experts
  that this chip holds (ids below the file's ``num_experts``) of gate x
  SwiGLU_e(x). The experts held on the deployment's other chips add
  nothing here, as in the program;
- final RMSNorm, logits = h W_head (``lm_head``, untied).

It imports nothing of the program. Its weights are drawn again from the
seed, layer by layer, by ``bench/weights.py``, in the served dtype and
then widened to float32. ``precision="fp8"`` is the control: every matmul
operand rounded through float8 e4m3 with a per-tensor scale.

Attention is computed in blocks of queries, so that a row of 2,112
positions at 32 heads fits beside the drawn layer.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import weights as W  # noqa: E402

ARCHITECTURES = ("MellumForCausalLM",)
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
BYTES = {"bfloat16": 2, "float32": 4}
E4M3_MAX = 448.0
#: the program's parameter-tree prefixes whose leaves are stacked over
#: layers on axis 0
STACKED = ("blocks/",)
#: queries per attention block
Q_BLOCK = 512
HIGHEST = jax.lax.Precision.HIGHEST


def _pad_vocab(v: int) -> int:
    return -(-v // 256) * 256


def _period(types: list) -> int:
    """The period of ``layer_types``: window layers, then one full layer."""
    if "full_attention" not in types:
        raise ValueError("reference needs full_attention layers in layer_types")
    p = types.index("full_attention") + 1
    one = ["sliding_attention"] * (p - 1) + ["full_attention"]
    if len(types) % p or types != one * (len(types) // p):
        raise ValueError(f"layer_types is not a repeat of {one}")
    return p


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference needs, from the configuration's keys."""

    d: int
    f: int
    layers: int
    heads: int
    kv: int
    hd: int
    vocab: int
    eps: float
    window: int
    period: int
    theta_window: float
    theta_full: float
    #: (factor, original_max_position_embeddings, beta_fast, beta_slow,
    #: attention_factor) of the full layers' YaRN
    yarn: tuple
    held: int
    routed: int
    top_k: int
    qk_norm: bool
    dtype: str

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        arch = cfg["architectures"][0]
        if arch not in ARCHITECTURES:
            raise ValueError(f"reference knows {ARCHITECTURES}, not {arch}")
        if cfg.get("tie_word_embeddings", False):
            raise ValueError("reference covers an untied head only")
        if cfg.get("attention_bias", False) or cfg.get("hidden_act") != "silu":
            raise ValueError("reference covers bias-free SwiGLU blocks only")
        if not cfg.get("norm_topk_prob", False):
            raise ValueError("reference covers renormalised top-k gates only")
        if set(cfg["mlp_layer_types"]) != {"sparse"}:
            raise ValueError("reference covers experts in every layer only")
        if cfg.get("shared_expert_intermediate_size") or cfg.get("mtp"):
            raise ValueError("reference has no shared expert and no MTP head")
        types = cfg["layer_types"]
        if len(types) != cfg["num_hidden_layers"]:
            raise ValueError("layer_types does not give every layer")
        rp = cfg["rope_parameters"]
        full, slid = rp["full_attention"], rp["sliding_attention"]
        if slid["rope_type"] != "default" or full["rope_type"] != "yarn":
            raise ValueError("reference covers plain RoPE on window layers "
                             "and YaRN on full layers only")
        if not full.get("truncate", True):
            raise ValueError("reference covers YaRN's truncated range only")
        routed = cfg.get("deployment", {}).get("num_experts", cfg["num_experts"])
        held = cfg["num_experts"]
        if not 0 < held <= routed:
            raise ValueError(f"{held} held experts of {routed}")
        d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
        factor = float(full["factor"])
        attn = full.get("attention_factor")
        if attn is None:
            attn = 0.1 * math.log(factor) + 1.0
        return cls(
            d=d, f=cfg["moe_intermediate_size"], layers=cfg["num_hidden_layers"],
            heads=heads, kv=cfg["num_key_value_heads"],
            hd=cfg.get("head_dim") or d // heads, vocab=cfg["vocab_size"],
            eps=float(cfg["rms_norm_eps"]), window=int(cfg["sliding_window"]),
            period=_period(types), theta_window=float(slid["rope_theta"]),
            theta_full=float(full["rope_theta"]),
            yarn=(factor, int(full["original_max_position_embeddings"]),
                  float(full.get("beta_fast", 32)), float(full.get("beta_slow", 1)),
                  float(attn)),
            held=held, routed=routed, top_k=cfg["num_experts_per_tok"],
            qk_norm=bool(cfg["use_qk_norm"]),
            dtype=cfg["torch_dtype"],
        )

    def layer_shapes(self) -> dict:
        d, hd, e, f = self.d, self.hd, self.held, self.f
        out = {
            "blocks/ln1/scale": (d,), "blocks/ln2/scale": (d,),
            "blocks/attn/wq": (d, self.heads * hd),
            "blocks/attn/wk": (d, self.kv * hd),
            "blocks/attn/wv": (d, self.kv * hd),
            "blocks/attn/wo": (self.heads * hd, d),
            "blocks/moe/w_router": (d, self.routed),
            "blocks/moe/w_gate": (e, d, f), "blocks/moe/w_up": (e, d, f),
            "blocks/moe/w_down": (e, f, d),
        }
        if self.qk_norm:
            out["blocks/attn/q_norm"] = (hd,)
            out["blocks/attn/k_norm"] = (hd,)
        return out

    def program_sizes(self) -> dict:
        """``{ModelConfig attribute: value}`` the program's registry entry
        must hold to run this file."""
        return {
            "d_model": self.d, "num_layers": self.layers, "num_heads": self.heads,
            "num_kv_heads": self.kv, "d_ff": self.f, "vocab_size": self.vocab,
            "resolved_head_dim": self.hd, "qk_norm": self.qk_norm,
            "tie_embeddings": False, "family": "moe", "activation": "silu",
            "sliding_window": self.window, "full_attn_every": self.period,
            "rope_theta": self.theta_full, "yarn": self.yarn,
            "num_experts": self.held, "router_width": self.routed,
            "top_k": self.top_k,
        }

    def constants_off(self) -> dict:
        """``{what: (the program's, the file's)}`` for each constant the
        program fixes and this file states otherwise."""
        off = {}
        if self.eps != 1e-6:
            off["rms_norm_eps"] = (1e-6, self.eps)
        # the program has one RoPE theta for both layer types
        if self.theta_window != self.theta_full:
            off["rope_theta"] = (self.theta_full, self.theta_window)
        return off

    # Bytes and operations of a step, from the shapes: the least work,
    # whatever implements it. A decode step reads every weight once at the
    # served dtype except the embedding (a row per token) and the held
    # experts that no token reached; each active slot's KV at a window
    # layer counts min(context, window) positions. The coded head's mix
    # and solve count as nothing.

    def expert_params(self) -> int:
        """One expert of one layer."""
        return 3 * self.d * self.f

    def attn_params(self) -> int:
        return 2 * self.d * self.hd * (self.heads + self.kv)

    def params(self) -> int:
        norms = 2 * self.d + (2 * self.hd if self.qk_norm else 0)
        layer = (self.attn_params() + self.d * self.routed
                 + self.held * self.expert_params() + norms)
        return self.layers * layer + 2 * self.vocab * self.d + self.d

    def weight_bytes(self) -> int:
        return self.params() * BYTES[self.dtype]

    def router_bytes(self) -> int:
        """The routers of every layer."""
        return self.layers * self.d * self.routed * BYTES[self.dtype]

    def expert_bytes(self) -> int:
        """One expert of one layer."""
        return self.expert_params() * BYTES[self.dtype]

    def kv_bytes(self, context: int, kv_dtype: str = "bfloat16") -> int:
        """KV a token at ``context`` attends, over the layers."""
        full = self.layers // self.period
        seen = full * context + (self.layers - full) * min(context, self.window)
        return seen * 2 * self.kv * self.hd * BYTES[kv_dtype]

    def decode_step_bytes(self, contexts, experts_hit=None) -> int:
        """Least bytes of one decode step over the active slots'
        contexts; ``experts_hit``: held experts reached, summed over the
        layers (the program's counter). Without it, every held expert."""
        if experts_hit is None:
            experts_hit = self.layers * self.held
        dense = self.weight_bytes() - self.vocab * self.d * BYTES[self.dtype]
        dense -= self.layers * self.held * self.expert_bytes()
        return (dense + experts_hit * self.expert_bytes()
                + sum(self.kv_bytes(c) for c in contexts))

    def token_flops(self, context: int) -> int:
        """Operations of one token that attends ``context`` positions,
        without its experts (``expert_flops`` of the pairs the program
        counts on held experts)."""
        full = self.layers // self.period
        seen = full * context + (self.layers - full) * min(context, self.window)
        matmul = self.layers * (self.attn_params() + self.d * self.routed)
        return 2 * (matmul + self.vocab * self.d) + 4 * seen * self.heads * self.hd

    def expert_flops(self, pairs: int) -> int:
        return 2 * self.expert_params() * pairs


def _served(x, dtype: str):
    return x.astype(DTYPES[dtype]).astype(jnp.float32)


def _fp8(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = E4M3_MAX / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(spec, a, b, fp8: bool):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn_inv_freq(hd: int, theta: float, yarn: tuple) -> tuple[np.ndarray, float]:
    """YaRN's inverse frequencies (hd / 2,) and cos/sin factor, float64."""
    factor, orig, beta_fast, beta_slow, attn = yarn
    pos = theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)

    def dim(rot):
        return hd * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(dim(beta_fast)), 0), min(math.ceil(dim(beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    extra = 1.0 - ramp
    return (1.0 / (factor * pos)) * (1 - extra) + (1.0 / pos) * extra, attn


def _rope(x, inv_freq, mult):
    # x: (B, T, H, hd); half-split rotation, position = index in the row
    t, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    cos = jnp.cos(ang)[None, :, None, :] * mult
    sin = jnp.sin(ang)[None, :, None, :] * mult
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, window):
    """Causal (and windowed) attention of (B, T, KV, G, hd) queries in
    blocks of ``Q_BLOCK``."""
    t, hd = q.shape[1], q.shape[-1]
    j = jnp.arange(t)
    outs = []
    for a in range(0, t, Q_BLOCK):
        qb = q[:, a:a + Q_BLOCK]
        i = jnp.arange(a, a + qb.shape[1])
        s = jnp.einsum("btkgh,bskh->bkgts", qb, k, precision=HIGHEST) / math.sqrt(hd)
        mask = j[None, :] <= i[:, None]
        if window is not None:
            mask &= i[:, None] - j[None, :] < window
        w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bkgts,bskh->btkgh", w, v, precision=HIGHEST))
    return jnp.concatenate(outs, axis=1)


def experts(x, p, dims: Dims, fp8: bool):
    """The held experts' part of the routed layer, and each token's chosen
    experts. x: (B, T, D) -> ((B, T, D), (B, T, top_k))."""
    logits = _mm("btd,de->bte", x, p["blocks/moe/w_router"], fp8)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, dims.top_k)
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    # (B, T, held): each held expert's gate, 0 where it was not chosen
    onehot = jax.nn.one_hot(idx, dims.held, dtype=jnp.float32)  # ids >= held: 0
    weight = jnp.einsum("btk,btke->bte", gates, onehot, precision=HIGHEST)
    gate = jax.nn.silu(_mm("btd,edf->btef", x, p["blocks/moe/w_gate"], fp8))
    up = _mm("btd,edf->btef", x, p["blocks/moe/w_up"], fp8)
    return _mm("btef,efd->btd", weight[..., None] * gate * up,
               p["blocks/moe/w_down"], fp8), idx


@functools.partial(jax.jit, static_argnames=("dims", "full", "fp8"))
def _layer(h, key, layer, *, dims: Dims, full: bool, fp8: bool):
    p = {name: _served(W.leaf(key, name, shape, layer), dims.dtype)
         for name, shape in dims.layer_shapes().items()}
    b, t, _ = h.shape
    x = _rms(h, p["blocks/ln1/scale"], dims.eps)
    q = _mm("btd,dx->btx", x, p["blocks/attn/wq"], fp8).reshape(b, t, dims.heads, dims.hd)
    k = _mm("btd,dx->btx", x, p["blocks/attn/wk"], fp8).reshape(b, t, dims.kv, dims.hd)
    v = _mm("btd,dx->btx", x, p["blocks/attn/wv"], fp8).reshape(b, t, dims.kv, dims.hd)
    if dims.qk_norm:
        q = _rms(q, p["blocks/attn/q_norm"], dims.eps)
        k = _rms(k, p["blocks/attn/k_norm"], dims.eps)
    if full:
        inv, mult = yarn_inv_freq(dims.hd, dims.theta_full, dims.yarn)
    else:
        inv = 1.0 / dims.theta_window ** (np.arange(0, dims.hd, 2) / dims.hd)
        mult = 1.0
    q, k = _rope(q, inv, mult), _rope(k, inv, mult)
    q = q.reshape(b, t, dims.kv, dims.heads // dims.kv, dims.hd)
    o = _attend(q, k, v, None if full else dims.window)
    h = h + _mm("btx,xd->btd", o.reshape(b, t, -1), p["blocks/attn/wo"], fp8)
    x = _rms(h, p["blocks/ln2/scale"], dims.eps)
    y, idx = experts(x, p, dims, fp8)
    return h + y, idx


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _logits(h, key, *, dims: Dims, fp8: bool):
    final = _served(W.leaf(key, "final_norm/scale", (dims.d,)), dims.dtype)
    head = _served(W.leaf(key, "lm_head/w", (dims.d, _pad_vocab(dims.vocab))),
                   dims.dtype)[:, : dims.vocab]
    return _mm("btd,dv->btv", _rms(h, final, dims.eps), head, fp8)


def forward(cfg: dict, seed: int, tokens, *, precision: str = "float32",
            choices: bool = False):
    """Logits (B, T, vocab) of right-padded token rows (B, T); with
    ``choices``, also each layer's chosen experts (L, B, T, top_k).

    Each row is one sequence from position 0; padding after a row's end
    never reaches its earlier positions (causal mask).
    """
    dims = Dims.of(cfg)
    fp8 = {"float32": False, "fp8": True}[precision]
    key = W.seed_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        table = _served(W.leaf(key, "embed/table", (_pad_vocab(dims.vocab), dims.d)),
                        dims.dtype)
        h = table[tokens]
        del table
        picked = []
        for layer in range(dims.layers):
            full = (layer + 1) % dims.period == 0
            h, idx = _layer(h, key, np.uint32(layer), dims=dims, full=full, fp8=fp8)
            picked.append(idx)
        logits = _logits(h, key, dims=dims, fp8=fp8)
        return (logits, jnp.stack(picked)) if choices else logits
