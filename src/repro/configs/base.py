"""Config system: ModelConfig (architecture) + ShapeConfig (workload)."""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax.numpy as jnp

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


class Yarn(NamedTuple):
    """YaRN scaling of rotary embeddings (arXiv:2309.00071), as
    ``transformers``' ``_compute_yarn_parameters`` reads a config's
    ``rope_scaling``: frequencies interpolated by ``factor`` below the
    ``beta_slow`` rotation count and kept above ``beta_fast`` (a linear
    ramp between, over ``original_max_position`` positions), and cos and
    sin both multiplied by ``attention_factor``."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    #: None: YaRN's default, 0.1 ln(factor) + 1
    attention_factor: float | None = None


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int | None = None
    qk_norm: bool = False
    sliding_window: int | None = None
    #: window and full layers mixed: with a ``sliding_window``, every k-th
    #: layer (the last of each period of k) attends in full, the others
    #: through the window; 0 = every layer windowed
    full_attn_every: int = 0
    rope_theta: float = 10_000.0
    #: YaRN on the full-attention layers' RoPE (None: plain RoPE)
    yarn: Yarn | None = None
    activation: str = "silu"
    # --- MoE ---
    #: experts held here: ids 0 .. num_experts - 1 of the router's
    num_experts: int = 0
    #: the router's outputs (0: num_experts, every expert held)
    routed_experts: int = 0
    top_k: int = 0
    # --- SSM / hybrid ---
    ssm_state: int = 0
    mamba_head_dim: int = 64
    mamba_expand: int = 2
    attn_every: int = 0  # hybrid: shared attn+mlp block every k mamba layers
    # --- xLSTM ---
    slstm_every: int = 0  # every k-th layer is sLSTM (others mLSTM)
    proj_factor: float = 2.0
    # --- enc-dec (audio) ---
    num_encoder_layers: int = 0
    encoder_seq: int = 0  # precomputed frame embeddings (conv frontend stub)
    # --- vlm ---
    num_image_tokens: int = 0  # precomputed patch embeddings (SigLIP stub)
    # --- numerics / execution ---
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    tie_embeddings: bool = True
    remat: bool = True
    # scan over stacked layers (small HLO, fast compile). The dry-run
    # unrolls instead: XLA cost analysis counts a while-loop body ONCE,
    # so roofline terms from a scanned module undercount by ~num_layers.
    scan_layers: bool = True
    # --- §Perf hillclimb knobs (EXPERIMENTS.md) ---
    # logits dtype: "float32" (baseline) or "bfloat16" (halves the
    # dominant (B,S,V) memory term; CE reductions still accumulate f32)
    logits_dtype: str = "float32"
    # skip fully-masked causal attention blocks (lower-triangular kv
    # iteration instead of the full grid): ~2x attention-FLOP cut
    causal_block_skip: bool = False
    # int8 KV cache (per-token-per-head symmetric scales): halves the
    # cache-read term that dominates decode
    kv_quant: bool = False
    attn_q_block: int = 512
    attn_kv_block: int = 1024
    mamba_chunk: int = 256

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def pdtype(self):
        return DTYPES[self.param_dtype]

    @property
    def cdtype(self):
        return DTYPES[self.compute_dtype]

    @property
    def router_width(self) -> int:
        return self.routed_experts or self.num_experts

    @property
    def layer_period(self) -> int:
        """Layers in one period of the attention pattern."""
        return self.full_attn_every or 1

    def layer_window(self, i: int) -> int | None:
        """Attention window of layer ``i`` (None: full attention)."""
        if self.full_attn_every and (i + 1) % self.full_attn_every == 0:
            return None
        return self.sliding_window

    def layer_yarn(self, i: int) -> Yarn | None:
        """YaRN of layer ``i``'s RoPE: the full-attention layers'."""
        return self.yarn if self.layer_window(i) is None else None

    @property
    def rolling_window(self) -> int | None:
        """The window when every layer has it, so a decode cache can roll."""
        return None if self.full_attn_every else self.sliding_window

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for the 500k-context decode shape (O(1)/O(window) state)."""
        return self.family in ("ssm", "hybrid") or self.rolling_window is not None

    @property
    def has_decode(self) -> bool:
        return True  # all assigned archs are decoder-bearing

    def reduced(self) -> "ModelConfig":
        """Tiny same-family variant for CPU smoke tests."""
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            num_layers=min(self.num_layers, 4 if self.attn_every == 0 else self.attn_every + 1),
            d_model=128,
            num_heads=max(4, min(self.num_heads, 4)),
            num_kv_heads=min(self.num_kv_heads, 2) if self.num_kv_heads > 1 else 1,
            d_ff=256 if self.d_ff else 0,
            vocab_size=512,
            head_dim=32,
            num_experts=min(self.num_experts, 8),
            routed_experts=min(self.routed_experts, 16),
            top_k=min(self.top_k, 2),
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            mamba_head_dim=32,
            sliding_window=64 if self.sliding_window else None,
            num_encoder_layers=min(self.num_encoder_layers, 2),
            encoder_seq=32 if self.encoder_seq else 0,
            num_image_tokens=16 if self.num_image_tokens else 0,
            param_dtype="float32",
            compute_dtype="float32",
            attn_q_block=32,
            attn_kv_block=32,
            mamba_chunk=16,
        )


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


TRAIN_4K = ShapeConfig("train_4k", 4_096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524_288, 1, "decode")

ALL_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPES_BY_NAME = {s.name: s for s in ALL_SHAPES}


def shapes_for(config: ModelConfig) -> tuple[ShapeConfig, ...]:
    """The shape cells defined for an architecture.

    ``long_500k`` needs sub-quadratic attention: run for SSM/hybrid/SWA
    archs, skip for pure full-attention archs (noted in DESIGN.md).
    """
    out = [TRAIN_4K, PREFILL_32K, DECODE_32K]
    if config.sub_quadratic:
        out.append(LONG_500K)
    return tuple(out)
