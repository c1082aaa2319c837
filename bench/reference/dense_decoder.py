"""Plain reference of a dense pre-norm decoder (Qwen3, Granite), in
float32 at ``Precision.HIGHEST``, one layer at a time.

It follows the published description of the two families, read from the
configuration file's own keys:

- tied embedding, scaled by ``embedding_multiplier`` (Granite; 1 else);
- per layer: RMSNorm -> GQA attention with half-split rotary embedding
  (``rope_theta``), RMSNorm on each query and key head first where the
  architecture has it (Qwen3), softmax scale ``attention_multiplier``
  (Granite) or 1/sqrt(head_dim); residual branch scaled by
  ``residual_multiplier`` (Granite; 1 else); RMSNorm -> SwiGLU MLP;
- final RMSNorm, logits = h E^T / ``logits_scaling`` (Granite; 1 else).

It imports nothing of the program. Its weights are drawn again from the
seed, layer by layer, by ``bench/weights.py``, in the served dtype and
then widened to float32. ``precision="fp8"`` is the control: every matmul
operand (weights and activations) rounded through float8 e4m3 with a
per-tensor scale, the rest as above.

As every module of ``bench/reference/``, it is the harness's one source
of what the architecture is: ``Dims.of(cfg)`` (the sizes, the check of
the program's registry entry, the byte and operation counts), ``STACKED``
(the program's parameter-tree prefixes stacked over layers) and
``forward``.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import weights as W  # noqa: E402

#: architectures this reference knows, and whether each has query/key norms
ARCHITECTURES = {"Qwen3ForCausalLM": True, "GraniteForCausalLM": False}
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
BYTES = {"bfloat16": 2, "float32": 4}
E4M3_MAX = 448.0
#: the program's parameter-tree prefixes whose leaves are stacked over
#: layers on axis 0
STACKED = ("blocks/",)


def _pad_vocab(v: int) -> int:
    # rows the served embedding table holds (a multiple of 256); rows past
    # the vocabulary are drawn but never read here
    return -(-v // 256) * 256


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference needs, from the configuration's keys."""

    qk_norm: bool
    d: int
    f: int
    layers: int
    heads: int
    kv: int
    hd: int
    vocab: int
    theta: float
    eps: float
    emb_mult: float
    res_mult: float
    logit_div: float
    attn_scale: float
    dtype: str

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        arch = cfg["architectures"][0]
        if arch not in ARCHITECTURES:
            raise ValueError(f"reference knows {sorted(ARCHITECTURES)}, not {arch}")
        if not cfg.get("tie_word_embeddings", False):
            raise ValueError("reference covers tied embeddings only")
        d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
        hd = cfg.get("head_dim") or d // heads
        return cls(
            qk_norm=ARCHITECTURES[arch], d=d, f=cfg["intermediate_size"],
            layers=cfg["num_hidden_layers"], heads=heads,
            kv=cfg["num_key_value_heads"], hd=hd, vocab=cfg["vocab_size"],
            theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
            emb_mult=float(cfg.get("embedding_multiplier", 1.0)),
            res_mult=float(cfg.get("residual_multiplier", 1.0)),
            logit_div=float(cfg.get("logits_scaling", 1.0)),
            attn_scale=float(cfg.get("attention_multiplier", 1.0 / np.sqrt(hd))),
            dtype=cfg["torch_dtype"],
        )

    def layer_shapes(self) -> dict:
        d, hd = self.d, self.hd
        out = {
            "blocks/ln1/scale": (d,), "blocks/ln2/scale": (d,),
            "blocks/attn/wq": (d, self.heads * hd),
            "blocks/attn/wk": (d, self.kv * hd),
            "blocks/attn/wv": (d, self.kv * hd),
            "blocks/attn/wo": (self.heads * hd, d),
            "blocks/mlp/w_gate": (d, self.f), "blocks/mlp/w_up": (d, self.f),
            "blocks/mlp/w_down": (self.f, d),
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
            "resolved_head_dim": self.hd, "rope_theta": self.theta,
            "qk_norm": self.qk_norm, "tie_embeddings": True, "family": "dense",
            "activation": "silu", "sliding_window": None,
        }

    def constants_off(self) -> dict:
        """``{what: (the program's, the file's)}`` for each constant the
        program fixes and this file states otherwise."""
        off = {}
        # the program's RMSNorm eps and softmax scale are fixed: 1e-6, 1/sqrt(hd)
        if self.eps != 1e-6 or abs(self.attn_scale * self.hd ** 0.5 - 1) > 1e-12:
            off["eps/attention_multiplier"] = ((1e-6, "1/sqrt(hd)"),
                                               (self.eps, self.attn_scale))
        if (self.emb_mult, self.res_mult, self.logit_div) != (1.0, 1.0, 1.0):
            off["multipliers"] = ((1.0, 1.0, 1.0),
                                  (self.emb_mult, self.res_mult, self.logit_div))
        return off

    # Bytes and operations a step needs, from the shapes. These are the
    # least work, whatever implements it: every weight read once at the
    # served dtype and each active slot's KV context at the KV dtype for
    # bytes; 2 operations per weight per token (the LM head included) plus
    # 4 x context x heads x head_dim per layer per token for operations.
    # The coded head's block mix and erasure solve count as nothing: they
    # are redundancy, not model work.

    def matmul_params(self) -> int:
        """Weights that multiply a token's activations, the LM head included
        (tied: the embedding table counts once, as the head)."""
        attn = self.d * self.heads * self.hd * 2 + self.d * self.kv * self.hd * 2
        mlp = 3 * self.d * self.f
        return self.layers * (attn + mlp) + self.vocab * self.d

    def params(self) -> int:
        """Every parameter: matmul weights plus the norm scales."""
        norms = 2 * self.d + (2 * self.hd if self.qk_norm else 0)
        return self.matmul_params() + self.layers * norms + self.d

    def weight_bytes(self) -> int:
        return self.params() * BYTES[self.dtype]

    def kv_bytes_per_token(self, kv_dtype: str = "bfloat16") -> int:
        return self.layers * 2 * self.kv * self.hd * BYTES[kv_dtype]

    def token_flops(self, context: int) -> int:
        """Operations of one token that attends ``context`` positions."""
        return 2 * self.matmul_params() + 4 * context * self.heads * self.hd * self.layers

    def decode_step_bytes(self, contexts) -> int:
        """Least bytes of one decode step over the active slots' contexts."""
        return self.weight_bytes() + sum(contexts) * self.kv_bytes_per_token()


def _served(x, dtype: str):
    return x.astype(DTYPES[dtype]).astype(jnp.float32)


def _fp8(x):
    """Round through float8 e4m3 with a per-tensor scale."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = E4M3_MAX / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(a, b, fp8: bool):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    # x: (B, T, H, hd); half-split rotation, position = index in the row
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _layer(h, key, layer, *, dims: Dims, fp8: bool):
    p = {name: _served(W.leaf(key, name, shape, layer), dims.dtype)
         for name, shape in dims.layer_shapes().items()}
    b, t, _ = h.shape
    x = _rms(h, p["blocks/ln1/scale"], dims.eps)
    q = _mm(x, p["blocks/attn/wq"], fp8).reshape(b, t, dims.heads, dims.hd)
    k = _mm(x, p["blocks/attn/wk"], fp8).reshape(b, t, dims.kv, dims.hd)
    v = _mm(x, p["blocks/attn/wv"], fp8).reshape(b, t, dims.kv, dims.hd)
    if dims.qk_norm:
        q = _rms(q, p["blocks/attn/q_norm"], dims.eps)
        k = _rms(k, p["blocks/attn/k_norm"], dims.eps)
    q, k = _rope(q, dims.theta), _rope(k, dims.theta)
    g = dims.heads // dims.kv
    q = q.reshape(b, t, dims.kv, g, dims.hd)
    s = jnp.einsum("btkgh,bskh->bkgts", q, k,
                   precision=jax.lax.Precision.HIGHEST) * dims.attn_scale
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskh->btkgh", w, v,
                   precision=jax.lax.Precision.HIGHEST)
    o = o.reshape(b, t, dims.heads * dims.hd)
    h = h + dims.res_mult * _mm(o, p["blocks/attn/wo"], fp8)
    x = _rms(h, p["blocks/ln2/scale"], dims.eps)
    gate = jax.nn.silu(_mm(x, p["blocks/mlp/w_gate"], fp8))
    up = _mm(x, p["blocks/mlp/w_up"], fp8)
    return h + dims.res_mult * _mm(gate * up, p["blocks/mlp/w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("dims",))
def _table(key, *, dims: Dims):
    e = W.leaf(key, "embed/table", (_pad_vocab(dims.vocab), dims.d))
    return _served(e, dims.dtype)[: dims.vocab]


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _logits(h, table, final_scale, *, dims: Dims, fp8: bool):
    x = _rms(h, final_scale, dims.eps)
    return _mm(x, table.T, fp8) / dims.logit_div


def forward(cfg: dict, seed: int, tokens, *, precision: str = "float32"):
    """Logits (B, T, vocab) of right-padded token rows (B, T).

    Each row is one sequence from position 0; padding after a row's end
    never reaches its earlier positions (causal mask).
    """
    dims = Dims.of(cfg)
    fp8 = {"float32": False, "fp8": True}[precision]
    key = W.seed_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        table = _table(key, dims=dims)
        h = table[tokens] * dims.emb_mult
        for layer in range(dims.layers):
            h = _layer(h, key, np.uint32(layer), dims=dims, fp8=fp8)
        final = _served(W.leaf(key, "final_norm/scale", (dims.d,)), dims.dtype)
        return _logits(h, table, final, dims=dims, fp8=fp8)
