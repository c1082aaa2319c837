"""Unified model builder for every assigned architecture family.

One ``Model`` object wraps a ``ModelConfig`` and exposes the same five
entry points regardless of family, so the launcher/dry-run treats every
arch uniformly:

* ``init_params(key)``                      -> param pytree
* ``loss_fn(params, batch)``                -> (scalar loss, metrics)
* ``lm_logits(params, tokens, extras)``     -> (B, S, V) (prefill path)
* ``init_cache(batch, cache_len)``          -> decode-state pytree
* ``decode_step(params, cache, tok, pos)``  -> ((B, V) logits, cache')

Families
--------
dense   llama-style pre-norm GQA + SwiGLU, scan over stacked layers.
moe     same attention; FFN replaced by top-k routed experts.
ssm     xLSTM: mLSTM layers with periodic sLSTM layers (python loop —
        layers are heterogeneous and L is small).
hybrid  Zamba2: Mamba2 backbone (scan) + one SHARED attention+MLP block
        applied every ``attn_every`` layers (weights reused; each
        invocation has its own KV cache slot).
vlm     PaliGemma: precomputed SigLIP patch embeddings (frontend stub)
        prepended to token embeddings; Gemma-style decoder.
audio   Whisper: encoder (non-causal, sinusoidal positions) over
        precomputed conv-frontend frame embeddings (stub) + decoder with
        self- and cross-attention.

Homogeneous stacks use ``jax.lax.scan`` over stacked params (keeps the
HLO one-layer-sized: critical for 512-device dry-run compile times);
``jax.checkpoint`` per layer when ``config.remat``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.obs.trace import (
    SCOPE_ATTENTION,
    SCOPE_LAYERS,
    SCOPE_MLP,
    SCOPE_MOE,
    SCOPE_UNEMBED,
    SCOPE_WINDOW_ATTENTION,
)

PyTree = Any

DTYPES_LOGITS = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _stack_init(fn, key, n, *args, **kwargs):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: fn(k, *args, **kwargs))(keys)


def padded_vocab(v: int, multiple: int = 256) -> int:
    """Vocab padded so embedding/logit dims shard evenly on the mesh."""
    return int(-(-v // multiple) * multiple)


def _sinusoidal(seq: int, d: int):
    pos = np.arange(seq)[:, None]
    i = np.arange(d // 2)[None, :]
    ang = pos / (10_000 ** (2 * i / d))
    return jnp.asarray(
        np.concatenate([np.sin(ang), np.cos(ang)], axis=-1), dtype=jnp.float32
    )


@dataclasses.dataclass(frozen=True)
class Model:
    config: ModelConfig

    # ------------------------------------------------------------ params
    def init_params(self, key) -> PyTree:
        c = self.config
        dt = c.pdtype
        kemb, kblocks, kfinal, kextra = jax.random.split(key, 4)
        pv = padded_vocab(c.vocab_size)
        params: dict = {
            "embed": L.init_embedding(kemb, pv, c.d_model, dt),
            "final_norm": (
                L.init_layernorm(c.d_model, dt)
                if c.family == "audio"
                else L.init_rmsnorm(c.d_model, dt)
            ),
        }
        hd = c.resolved_head_dim

        def dense_block(k):
            k1, k2 = jax.random.split(k)
            return {
                "ln1": L.init_rmsnorm(c.d_model, dt),
                "attn": attn_mod.init_attention(
                    k1, c.d_model, c.num_heads, c.num_kv_heads, hd, dt,
                    qk_norm=c.qk_norm,
                ),
                "ln2": L.init_rmsnorm(c.d_model, dt),
                "mlp": L.init_mlp(k2, c.d_model, c.d_ff, dt, c.activation),
            }

        if c.family in ("dense", "vlm"):
            params["blocks"] = _stack_init(dense_block, kblocks, c.num_layers)
        elif c.family == "moe":
            def moe_block(k):
                k1, k2 = jax.random.split(k)
                return {
                    "ln1": L.init_rmsnorm(c.d_model, dt),
                    "attn": attn_mod.init_attention(
                        k1, c.d_model, c.num_heads, c.num_kv_heads, hd, dt,
                        qk_norm=c.qk_norm,
                    ),
                    "ln2": L.init_rmsnorm(c.d_model, dt),
                    "moe": moe_mod.init_moe(
                        k2, c.d_model, c.d_ff, c.num_experts, dt,
                        router_width=c.router_width,
                    ),
                }

            params["blocks"] = _stack_init(moe_block, kblocks, c.num_layers)
        elif c.family == "hybrid":
            def mamba_block(k):
                return {
                    "ln": L.init_rmsnorm(c.d_model, dt),
                    "mamba": ssm_mod.init_mamba2(
                        k, c.d_model, c.ssm_state, dt,
                        expand=c.mamba_expand, head_dim=c.mamba_head_dim,
                    ),
                }

            params["blocks"] = _stack_init(mamba_block, kblocks, c.num_layers)
            params["shared_attn"] = dense_block(kextra)  # ONE shared block
        elif c.family == "ssm":  # xLSTM
            blocks = []
            keys = jax.random.split(kblocks, c.num_layers)
            for i in range(c.num_layers):
                if self._is_slstm(i):
                    blocks.append(
                        {
                            "ln": L.init_rmsnorm(c.d_model, dt),
                            "cell": xlstm_mod.init_slstm(keys[i], c.d_model, c.num_heads, dt),
                        }
                    )
                else:
                    blocks.append(
                        {
                            "ln": L.init_rmsnorm(c.d_model, dt),
                            "cell": xlstm_mod.init_mlstm(
                                keys[i], c.d_model, c.num_heads, dt, c.proj_factor
                            ),
                        }
                    )
            params["blocks"] = blocks
        elif c.family == "audio":  # whisper enc-dec
            kenc, kdec = jax.random.split(kblocks)

            def enc_block(k):
                k1, k2 = jax.random.split(k)
                return {
                    "ln1": L.init_layernorm(c.d_model, dt),
                    "attn": attn_mod.init_attention(
                        k1, c.d_model, c.num_heads, c.num_kv_heads, hd, dt
                    ),
                    "ln2": L.init_layernorm(c.d_model, dt),
                    "mlp": L.init_mlp(k2, c.d_model, c.d_ff, dt, "gelu"),
                }

            def dec_block(k):
                k1, k2, k3 = jax.random.split(k, 3)
                return {
                    "ln1": L.init_layernorm(c.d_model, dt),
                    "self_attn": attn_mod.init_attention(
                        k1, c.d_model, c.num_heads, c.num_kv_heads, hd, dt
                    ),
                    "ln_x": L.init_layernorm(c.d_model, dt),
                    "cross_attn": attn_mod.init_attention(
                        k2, c.d_model, c.num_heads, c.num_kv_heads, hd, dt
                    ),
                    "ln2": L.init_layernorm(c.d_model, dt),
                    "mlp": L.init_mlp(k3, c.d_model, c.d_ff, dt, "gelu"),
                }

            params["encoder"] = _stack_init(enc_block, kenc, c.num_encoder_layers)
            params["blocks"] = _stack_init(dec_block, kdec, c.num_layers)
            params["enc_norm"] = L.init_layernorm(c.d_model, dt)
        else:
            raise ValueError(f"unknown family {c.family}")
        if not c.tie_embeddings:
            # untied output head, (D, V) like any linear layer
            params["lm_head"] = {"w": L._dense_init(kfinal, (c.d_model, pv), dt)}
        return params

    def head_params(self, params):
        """The LM head's parameters: the tied embedding or ``lm_head``."""
        return params["embed"] if self.config.tie_embeddings else params["lm_head"]

    def head_table(self, params):
        """The LM head as a (V_padded, D) table (logits = h @ table^T)."""
        head = self.head_params(params)
        return head["table"] if "table" in head else head["w"].T

    def _unembed(self, params, x):
        return L.unembed(self.head_params(params), x,
                         DTYPES_LOGITS[self.config.logits_dtype])

    def _is_slstm(self, layer_idx: int) -> bool:
        c = self.config
        return bool(c.slstm_every) and (layer_idx + 1) % c.slstm_every == 0

    def _mask_pad_logits(self, logits):
        """Padded vocab slots never win argmax / contribute to softmax."""
        v = self.config.vocab_size
        if logits.shape[-1] == v:
            return logits
        ids = jnp.arange(logits.shape[-1])
        return jnp.where(ids < v, logits, -1e30)

    # -------------------------------------------------------- primitives
    def _attn_kw(self, j: int) -> dict:
        """Static attention settings of layer position ``j`` in the
        period: its window and its RoPE scaling."""
        c = self.config
        return {"window": c.layer_window(j), "yarn": c.layer_yarn(j)}

    def _block_apply(self, p, x, positions, *, causal=True, j=0):
        """One pre-norm block (attention, then MLP or experts), as layer
        position ``j`` of the period (train/prefill)."""
        c = self.config
        h = x + attn_mod.attention(
            p["attn"], L.rmsnorm(p["ln1"], x), positions,
            num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
            head_dim=c.resolved_head_dim, causal=causal,
            rope_theta=c.rope_theta, q_block=c.attn_q_block,
            kv_block=c.attn_kv_block, causal_skip=c.causal_block_skip,
            **self._attn_kw(j),
        )
        return h + self._ffn(p, h)[0]

    def _ffn(self, p, h, valid=None):
        """The block's second half (without its residual): the MLP, or
        the expert layer with its counts (``moe.moe_ffn``; None else)."""
        c = self.config
        x = L.rmsnorm(p["ln2"], h)
        if c.family == "moe":
            return moe_mod.moe_ffn(p["moe"], x, top_k=c.top_k, valid=valid)
        return L.mlp(p["mlp"], x), None

    def _mamba_apply(self, p, x):
        c = self.config
        return x + ssm_mod.mamba2(
            p["mamba"], L.rmsnorm(p["ln"], x),
            d_state=c.ssm_state, expand=c.mamba_expand,
            head_dim=c.mamba_head_dim, chunk=c.mamba_chunk,
        )

    # ----------------------------------------------------------- forward
    def _scan_layers(self, body, carry, xs):
        """``body(j, carry, layer_xs) -> (carry, y)`` over the stacked
        layers, ``j`` being the layer's static position in the attention
        pattern's period; returns (carry, ys stacked over layers).

        scan_layers=True: a ``lax.scan`` over layers (one-layer HLO, fast
        compile), or over periods with the period's layers unrolled in
        the body, so that each layer type has its own static window and
        RoPE. scan_layers=False: an unrolled python loop — used by the
        dry-run so XLA cost analysis sees every layer (a while body is
        counted once).
        """
        c = self.config
        n, per = c.num_layers, c.layer_period
        stack = lambda ys: jax.tree.map(lambda *t: jnp.stack(t), *ys)
        if not c.scan_layers:
            ys = []
            for i in range(n):
                carry, y = body(i % per, carry, jax.tree.map(lambda t: t[i], xs))
                ys.append(y)
            return carry, stack(ys)
        if per == 1:
            return jax.lax.scan(functools.partial(body, 0), carry, xs)
        if n % per:
            raise ValueError(f"{n} layers are not whole periods of {per}")

        def period(carry, xp):
            ys = []
            for j in range(per):
                carry, y = body(j, carry, jax.tree.map(lambda t: t[j], xp))
                ys.append(y)
            return carry, stack(ys)

        fold = lambda t: t.reshape(n // per, per, *t.shape[1:])
        carry, ys = jax.lax.scan(period, carry, jax.tree.map(fold, xs))
        return carry, jax.tree.map(lambda t: t.reshape(n, *t.shape[2:]), ys)

    def _backbone(self, params, x, positions):
        """(B, S, D) -> (B, S, D) through all blocks (train/prefill)."""
        c = self.config

        if c.family in ("dense", "vlm", "moe"):
            fns = [functools.partial(self._block_apply, positions=positions, j=j)
                   for j in range(c.layer_period)]
            if c.remat:
                fns = [jax.checkpoint(fn) for fn in fns]
            x, _ = self._scan_layers(
                lambda j, h, p: (fns[j](p, h), None), x, params["blocks"]
            )
        elif c.family == "hybrid":
            shared = params["shared_attn"]
            every = max(c.attn_every, 1)

            def layer(p, h, i):
                h = jax.lax.cond(
                    i % every == 0,
                    lambda hh: self._block_apply(shared, hh, positions),
                    lambda hh: hh,
                    h,
                )
                return self._mamba_apply(p, h)

            fn = jax.checkpoint(layer) if c.remat else layer

            if c.scan_layers:
                def body(h, inp):
                    p, i = inp
                    return fn(p, h, i), None

                x, _ = jax.lax.scan(
                    body, x, (params["blocks"], jnp.arange(c.num_layers))
                )
            else:
                for i in range(c.num_layers):
                    p = jax.tree.map(lambda t: t[i], params["blocks"])
                    x = fn(p, x, jnp.int32(i))
        elif c.family == "ssm":
            for i, p in enumerate(params["blocks"]):
                h = L.rmsnorm(p["ln"], x)
                if self._is_slstm(i):
                    y = xlstm_mod.slstm(p["cell"], h, num_heads=c.num_heads)
                else:
                    y = xlstm_mod.mlstm(
                        p["cell"], h, num_heads=c.num_heads, proj_factor=c.proj_factor
                    )
                x = x + y
        elif c.family == "audio":
            raise RuntimeError("audio uses _encdec_forward")
        return x

    def _encode_audio(self, params, frames):
        """Whisper encoder over precomputed frame embeddings (stub frontend)."""
        c = self.config
        s = frames.shape[1]
        x = frames.astype(c.cdtype) + _sinusoidal(s, c.d_model).astype(c.cdtype)
        positions = jnp.arange(s, dtype=jnp.int32)

        def enc_apply(p, h):
            h = h + attn_mod.attention(
                p["attn"], L.layernorm(p["ln1"], h), positions,
                num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                head_dim=c.resolved_head_dim, causal=False, use_rope=False,
                q_block=c.attn_q_block, kv_block=c.attn_kv_block,
            )
            h = h + L.mlp(p["mlp"], L.layernorm(p["ln2"], h))
            return h

        fn = jax.checkpoint(enc_apply) if c.remat else enc_apply
        if c.scan_layers:
            x, _ = jax.lax.scan(lambda h, p: (fn(p, h), None), x, params["encoder"])
        else:
            for i in range(c.num_encoder_layers):
                x = fn(jax.tree.map(lambda t: t[i], params["encoder"]), x)
        return L.layernorm(params["enc_norm"], x)

    def _decoder_audio(self, params, x, positions, enc_out, enc_positions):
        c = self.config

        def dec_apply(p, h):
            h = h + attn_mod.attention(
                p["self_attn"], L.layernorm(p["ln1"], h), positions,
                num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                head_dim=c.resolved_head_dim, causal=True, use_rope=False,
                q_block=c.attn_q_block, kv_block=c.attn_kv_block,
            )
            h = h + attn_mod.attention(
                p["cross_attn"], L.layernorm(p["ln_x"], h), positions,
                num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                head_dim=c.resolved_head_dim, causal=False, use_rope=False,
                xkv=enc_out, kv_positions=enc_positions,
                q_block=c.attn_q_block, kv_block=c.attn_kv_block,
            )
            h = h + L.mlp(p["mlp"], L.layernorm(p["ln2"], h))
            return h

        fn = jax.checkpoint(dec_apply) if c.remat else dec_apply
        x, _ = self._scan_layers(lambda _j, h, p: (fn(p, h), None), x,
                                 params["blocks"])
        return x

    # ------------------------------------------------------------ logits
    def lm_logits(self, params, tokens, extras: dict | None = None):
        """Full-sequence logits. tokens: (B, S) int32.

        extras:
          vlm   -> {"image_embeds": (B, T_img, D)} prepended to the text.
          audio -> {"frames": (B, enc_S, D)} run through the encoder.
        """
        c = self.config
        extras = extras or {}
        x = L.embed(params["embed"], tokens, c.cdtype)
        b, s = tokens.shape

        if c.family == "vlm":
            img = extras["image_embeds"].astype(c.cdtype)
            x = jnp.concatenate([img, x], axis=1)
            positions = jnp.arange(x.shape[1], dtype=jnp.int32)
            x = self._backbone(params, x, positions)
            x = x[:, img.shape[1]:]
        elif c.family == "audio":
            enc_out = self._encode_audio(params, extras["frames"])
            positions = jnp.arange(s, dtype=jnp.int32)
            enc_pos = jnp.arange(enc_out.shape[1], dtype=jnp.int32)
            x = self._decoder_audio(params, x, positions, enc_out, enc_pos)
        else:
            positions = jnp.arange(s, dtype=jnp.int32)
            x = self._backbone(params, x, positions)

        norm = L.layernorm if c.family == "audio" else L.rmsnorm
        x = norm(params["final_norm"], x)
        logits = self._unembed(params, x)
        return self._mask_pad_logits(logits)

    # -------------------------------------------------------------- loss
    def loss_fn(self, params, batch):
        """batch: {"tokens": (B,S), "labels": (B,S)} (+ family extras).

        labels < 0 are masked. Logits over the PADDED vocab; pad ids are
        never produced as labels so the softmax treats them as negatives.
        """
        tokens = batch["tokens"]
        labels = batch["labels"]
        logits = self.lm_logits(params, tokens, batch.get("extras"))
        mask = labels >= 0
        loss = L.cross_entropy_loss(logits, jnp.maximum(labels, 0), mask)
        acc = jnp.sum(
            (jnp.argmax(logits, -1) == labels) & mask
        ) / jnp.maximum(jnp.sum(mask), 1)
        return loss, {"loss": loss, "accuracy": acc}

    # ------------------------------------------------------------- cache
    def n_shared_attn_calls(self) -> int:
        c = self.config
        every = max(c.attn_every, 1)
        return -(-c.num_layers // every)

    def init_cache(self, batch: int, cache_len: int, extras: dict | None = None):
        """Decode state.

        cache_len: KV capacity. A model whose every layer is windowed gets
        the rolling cache of min(cache_len, window).
        """
        c = self.config
        dt = c.cdtype
        hd = c.resolved_head_dim
        if c.rolling_window is not None:
            cache_len = min(cache_len, c.rolling_window)

        def kv(n_layers, length):
            if c.kv_quant:  # int8 + per-(token, head) f16 scales (§Perf)
                return {
                    "k": jnp.zeros((n_layers, batch, length, c.num_kv_heads, hd),
                                   jnp.int8),
                    "v": jnp.zeros((n_layers, batch, length, c.num_kv_heads, hd),
                                   jnp.int8),
                    "k_scale": jnp.zeros((n_layers, batch, length, c.num_kv_heads),
                                         jnp.float16),
                    "v_scale": jnp.zeros((n_layers, batch, length, c.num_kv_heads),
                                         jnp.float16),
                    "pos": jnp.full((n_layers, length), -1, jnp.int32),
                }
            return {
                "k": jnp.zeros((n_layers, batch, length, c.num_kv_heads, hd), dt),
                "v": jnp.zeros((n_layers, batch, length, c.num_kv_heads, hd), dt),
                "pos": jnp.full((n_layers, length), -1, jnp.int32),
            }

        if c.family in ("dense", "vlm", "moe"):
            return {"kv": kv(c.num_layers, cache_len)}
        if c.family == "hybrid":
            n_inv = self.n_shared_attn_calls()
            d_inner = c.mamba_expand * c.d_model
            n_heads = d_inner // c.mamba_head_dim
            conv_dim = d_inner + 2 * c.ssm_state
            return {
                "kv": kv(n_inv, cache_len),
                "ssm": jnp.zeros(
                    (c.num_layers, batch, n_heads, c.ssm_state, c.mamba_head_dim),
                    jnp.float32,
                ),
                "conv": jnp.zeros(
                    (c.num_layers, batch, ssm_mod.CONV_K - 1, conv_dim), dt
                ),
            }
        if c.family == "ssm":
            states = []
            for i in range(c.num_layers):
                if c.slstm_every and (i + 1) % c.slstm_every == 0:
                    states.append(xlstm_mod.init_slstm_state(batch, c.d_model, c.num_heads))
                else:
                    states.append(
                        xlstm_mod.init_mlstm_state(
                            batch, c.d_model, c.num_heads, c.proj_factor
                        )
                    )
            return {"xlstm": states}
        if c.family == "audio":
            assert extras is not None and "enc_out" in extras, (
                "whisper decode cache needs the encoder output "
                "(run model.encode(params, frames) once per request batch)"
            )
            return {
                "kv": kv(c.num_layers, cache_len),
                "enc_out": extras["enc_out"],
            }
        raise ValueError(c.family)

    def encode(self, params, frames):
        """Audio only: one-time encoder pass for a request batch."""
        return self._encode_audio(params, frames)

    # ------------------------------------------------------------ decode
    def decode_step(self, params, cache, tokens, pos):
        """One new token for every sequence in the batch.

        tokens: (B,) int32; pos: scalar int32 (uniform decode position).
        Returns (logits (B, V_padded), new_cache).
        """
        c = self.config
        hd = c.resolved_head_dim
        x = L.embed(params["embed"], tokens[:, None], c.cdtype)  # (B, 1, D)

        def body(j, h, inp):
            p, kv_slice = inp
            y, new = attn_mod.decode_attention(
                p["attn"], L.rmsnorm(p["ln1"], h), kv_slice, pos,
                num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                head_dim=hd, rope_theta=c.rope_theta, **self._attn_kw(j),
            )
            h = h + y
            return h + self._ffn(p, h)[0], new

        if c.family in ("dense", "vlm", "moe"):
            x, new_kv = self._scan_layers(body, x, (params["blocks"], cache["kv"]))
            cache = {**cache, "kv": new_kv}
        elif c.family == "hybrid":
            shared = params["shared_attn"]
            every = max(c.attn_every, 1)
            n_inv = self.n_shared_attn_calls()

            def body(carry, inp):
                h, kv_all = carry
                p, ssm_s, conv_s, i = inp
                inv = i // every

                def with_attn(operand):
                    h, kv_all = operand
                    kv_slice = jax.tree.map(lambda t: t[inv], kv_all)
                    y, new = attn_mod.decode_attention(
                        shared["attn"], L.rmsnorm(shared["ln1"], h), kv_slice, pos,
                        num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                        head_dim=hd, rope_theta=c.rope_theta,
                    )
                    h = h + y
                    h = h + L.mlp(shared["mlp"], L.rmsnorm(shared["ln2"], h))
                    kv_all = jax.tree.map(
                        lambda all_, n: jax.lax.dynamic_update_index_in_dim(
                            all_, n, inv, 0
                        ),
                        kv_all, new,
                    )
                    return h, kv_all

                h, kv_all = jax.lax.cond(
                    i % every == 0, with_attn, lambda o: o, (h, kv_all)
                )
                y, new_state = ssm_mod.mamba2(
                    p["mamba"], L.rmsnorm(p["ln"], h),
                    d_state=c.ssm_state, expand=c.mamba_expand,
                    head_dim=c.mamba_head_dim, chunk=c.mamba_chunk,
                    state={"ssm": ssm_s, "conv": conv_s},
                )
                h = h + y
                return (h, kv_all), (new_state["ssm"], new_state["conv"])

            if c.scan_layers:
                (x, new_kv), (new_ssm, new_conv) = jax.lax.scan(
                    body,
                    (x, cache["kv"]),
                    (params["blocks"], cache["ssm"], cache["conv"],
                     jnp.arange(c.num_layers)),
                )
            else:
                carry = (x, cache["kv"])
                ssm_list, conv_list = [], []
                for i in range(c.num_layers):
                    inp = jax.tree.map(
                        lambda t: t[i],
                        (params["blocks"], cache["ssm"], cache["conv"]),
                    ) + (jnp.int32(i),)
                    carry, (s_i, c_i) = body(carry, inp)
                    ssm_list.append(s_i)
                    conv_list.append(c_i)
                x, new_kv = carry
                new_ssm = jnp.stack(ssm_list)
                new_conv = jnp.stack(conv_list)
            cache = {"kv": new_kv, "ssm": new_ssm, "conv": new_conv}
        elif c.family == "ssm":
            new_states = []
            for i, (p, st) in enumerate(zip(params["blocks"], cache["xlstm"])):
                h = L.rmsnorm(p["ln"], x)
                if self._is_slstm(i):
                    y, new = xlstm_mod.slstm(
                        p["cell"], h, num_heads=c.num_heads, state=st
                    )
                else:
                    y, new = xlstm_mod.mlstm(
                        p["cell"], h, num_heads=c.num_heads,
                        proj_factor=c.proj_factor, state=st,
                    )
                x = x + y
                new_states.append(new)
            cache = {"xlstm": new_states}
        elif c.family == "audio":
            enc_out = cache["enc_out"]
            enc_pos = jnp.arange(enc_out.shape[1], dtype=jnp.int32)

            def body(_j, h, inp):
                p, kv_slice = inp
                y, new = attn_mod.decode_attention(
                    p["self_attn"], L.layernorm(p["ln1"], h), kv_slice, pos,
                    num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                    head_dim=hd, use_rope=False,
                )
                h = h + y
                h = h + attn_mod.attention(
                    p["cross_attn"], L.layernorm(p["ln_x"], h),
                    jnp.full((1,), pos, jnp.int32),
                    num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                    head_dim=hd, causal=False, use_rope=False,
                    xkv=enc_out, kv_positions=enc_pos,
                    q_block=1, kv_block=min(c.attn_kv_block, enc_out.shape[1]),
                )
                h = h + L.mlp(p["mlp"], L.layernorm(p["ln2"], h))
                return h, new

            x, new_kv = self._scan_layers(body, x, (params["blocks"], cache["kv"]))
            cache = {**cache, "kv": new_kv}
        else:
            raise ValueError(c.family)

        norm = L.layernorm if c.family == "audio" else L.rmsnorm
        x = norm(params["final_norm"], x)
        logits = self._unembed(params, x)
        logits = self._mask_pad_logits(logits[:, 0])
        return logits, cache

    # ------------------------------------------------- batched prefill
    # ``prefill`` fills a batch of prompts' per-layer KV from ONE batched
    # forward pass (the cache-returning path §4 called for) instead of a
    # per-position decode scan; ``Server.generate`` splices it into its
    # decode cache. ``_check_slot_support`` is the support envelope it
    # shares with the paged serve path below (DESIGN.md §10, §13): the
    # attention-cache families, without int8 KV.

    def _check_slot_support(self) -> None:
        c = self.config
        if c.family not in ("dense", "vlm", "moe"):
            raise NotImplementedError(
                f"prefill and paged decode support the attention-cache "
                f"families (dense/vlm/moe), not {c.family!r}"
            )
        if c.kv_quant:
            raise NotImplementedError(
                "prefill and paged decode do not support int8 KV caches yet"
            )

    def prefill(self, params, tokens, length):
        """Batched prefill: one pass -> (last logits, per-layer K/V).

        tokens: (B, S0) int32, right-padded to a fixed prompt capacity;
        length: (B,) actual prompt lengths. Runs the full-sequence
        chunked-attention forward ONCE, capturing each layer's post-rope
        K/V (``attention(return_kv=True)``) — the tensors ``decode_step``
        would have written into its cache over S0 sequential steps — and
        returns the logits at each row's last real position (predicting
        token ``length``). Padded tail positions produce garbage K/V but
        sit causally AFTER every real query and are masked out of the
        decode cache by the splice's ``pos = -1`` rows.

        Returns ``(logits (B, V_padded), k (L, B, S0, KV, hd), v ...)``.
        """
        c = self.config
        self._check_slot_support()
        hd = c.resolved_head_dim
        b, s = tokens.shape
        x = L.embed(params["embed"], tokens, c.cdtype)
        positions = jnp.arange(s, dtype=jnp.int32)

        def body(j, h, p):
            y, k, v = attn_mod.attention(
                p["attn"], L.rmsnorm(p["ln1"], h), positions,
                num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                head_dim=hd, causal=True, rope_theta=c.rope_theta,
                q_block=c.attn_q_block, kv_block=c.attn_kv_block,
                causal_skip=c.causal_block_skip, return_kv=True,
                **self._attn_kw(j),
            )
            h = h + y
            return h + self._ffn(p, h)[0], (k, v)

        x, (ks, vs) = self._scan_layers(body, x, params["blocks"])

        last = jnp.clip(length - 1, 0, s - 1).astype(jnp.int32)
        x_last = x[jnp.arange(b), last][:, None]  # (B, 1, D)
        x_last = L.rmsnorm(params["final_norm"], x_last)
        logits = self._unembed(params, x_last)[:, 0]
        return self._mask_pad_logits(logits), ks, vs

    # --------------------------------------------------- paged KV decode
    # Paged serving (DESIGN.md §13): physical KV memory is a fixed pool
    # of (block_len,)-token blocks shared across slots, and each slot
    # maps logical positions to pool blocks through a block table. The
    # program's shapes depend only on (num_blocks, block_len, S) and the
    # table's width — never on a prompt's length — so admitting a long
    # prompt (prefilled chunk-by-chunk across admit rounds) within the
    # width retraces nothing.

    def init_paged_cache(self, num_blocks: int, block_len: int):
        """KV block pool for ``decode_step_paged``/``prefill_paged``.

        Allocates ``num_blocks + 1`` physical blocks per layer, stacked
        over layers, each of ``block_len`` rows of ``KV * hd`` (a token's
        heads side by side; ``kernels/paged_attention/ops.py`` says why):
        the last block is the write SINK — inactive/frozen/padded rows
        scatter there, so a frozen slot can never corrupt a block that was
        freed and reassigned. No position array: validity is derived from
        the per-dispatch block tables and positions (runtime arguments).

        An expert model's state also carries ``"moe"``: int32 (2,), the
        decode steps' token-choice pairs that landed on held experts and
        the held experts they reached, summed over steps and layers
        (``moe.moe_ffn``'s counts of the active slots).
        """
        c = self.config
        self._check_slot_support()
        hd = c.resolved_head_dim
        shape = (c.num_layers, num_blocks + 1, block_len, c.num_kv_heads * hd)
        cache = {
            "kv": {
                "k": jnp.zeros(shape, c.cdtype),
                "v": jnp.zeros(shape, c.cdtype),
            }
        }
        if c.family == "moe":
            cache["moe"] = jnp.zeros((2,), jnp.int32)
        return cache

    def _paged_layers(self, attn_fn, x, blocks, cache, valid=None):
        """The layers over the pool: ``attn_fn(j, p, h, kv, l) -> (h,
        kv)`` then the FFN, each half in its named scope (DESIGN.md §14),
        a window layer's attention in its own. Returns (x, new cache);
        ``valid`` rows' expert counts are added to the cache's ``"moe"``,
        which is otherwise passed on.

        The stacked pool rides in the layer scan's carry beside ``h``,
        and the layer's index ``l`` is a scan input: ``attn_fn`` writes
        layer ``l``'s new rows into the stacked pool in place and reads
        that layer's blocks straight from it. Passed as the scan's input
        and output instead, the pool would be sliced out per layer,
        written back into a stacked output, and copied back after the
        loop: three whole-pool passes a step for a token's few rows.

        An expert layer's weights stay stacked over layers too, and are
        sliced inside ``model/moe`` by the same index: the scan's own
        slicing of its inputs would copy every held expert of the layer
        (of the period, and again of the layer, under a period scan) in
        ``model/layers``, away from the layer's time."""
        c = self.config
        moe = c.family == "moe"
        experts = blocks.get("moe")
        xs = ({k: v for k, v in blocks.items() if k != "moe"},
              jnp.arange(c.num_layers, dtype=jnp.int32))

        def body(j, carry, inp):
            h, kv = carry
            p, layer = inp
            window = self._attn_kw(j)["window"]
            scope = SCOPE_ATTENTION if window is None else SCOPE_WINDOW_ATTENTION
            with jax.named_scope(scope):
                h, kv = attn_fn(j, p, h, kv, layer)
            with jax.named_scope(SCOPE_MOE if moe else SCOPE_MLP):
                if moe:
                    p = {**p, "moe": jax.tree.map(
                        lambda t: jax.lax.dynamic_index_in_dim(
                            t, layer, keepdims=False), experts)}
                y, counts = self._ffn(p, h, valid)
                h = h + y
            return (h, kv), counts

        with jax.named_scope(SCOPE_LAYERS):
            (x, kv), counts = self._scan_layers(body, (x, cache["kv"]), xs)
        if not moe:
            return x, {"kv": kv}
        total = cache["moe"]
        if valid is not None:
            total = total + jnp.sum(counts, axis=0, dtype=jnp.int32)
        return x, {"kv": kv, "moe": total}

    def decode_step_paged(self, params, cache, tokens, pos, table, active,
                          *, use_kernel: bool = False):
        """One token per slot against the shared block pool.

        tokens: (S,) int32; pos: (S,) write positions; table: (S, MB)
        block table; active: (S,) bool (inactive rows write to the
        sink). Returns (logits (S, V_padded), new_cache). Served logits
        are checked against the plain float32 references in
        ``bench/reference/`` (``tests/test_routed_window.py``).
        """
        c = self.config
        self._check_slot_support()
        hd = c.resolved_head_dim
        x = L.embed(params["embed"], tokens[:, None], c.cdtype)
        pos = jnp.asarray(pos, jnp.int32)
        table = jnp.asarray(table, jnp.int32)
        active = jnp.asarray(active, bool)

        def attn_fn(j, p, h, kv, layer):
            y, kv = attn_mod.decode_attention_paged(
                p["attn"], L.rmsnorm(p["ln1"], h), kv, table, pos,
                active, num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                head_dim=hd, rope_theta=c.rope_theta, use_kernel=use_kernel,
                layer=layer, **self._attn_kw(j),
            )
            return h + y, kv

        x, new_cache = self._paged_layers(attn_fn, x, params["blocks"], cache,
                                          valid=active[:, None])
        with jax.named_scope(SCOPE_UNEMBED):
            x = L.rmsnorm(params["final_norm"], x)
            logits = self._unembed(params, x)
            return self._mask_pad_logits(logits[:, 0]), new_cache

    def prefill_paged(self, params, cache, tokens, start, chunk_len, table):
        """One chunked-prefill admit round: C prompt tokens per slot.

        tokens: (S, C) int32 — row s holds prompt positions
        ``[start[s], start[s] + chunk_len[s])`` of slot s's request
        (right-padded; rows with ``chunk_len == 0`` are slots not
        prefilling this round). KV for the chunk is scattered into the
        slot's pool blocks, every query attends the slot's full gathered
        history (earlier chunks included; a window layer's, the last
        ``window`` positions of it), and the returned logits are
        taken at each row's last real chunk position — for the chunk
        that COMPLETES a prompt these are the request's pending first-
        decode logits, exactly like the dense splice. Returns
        ``(logits (S, V_padded), new_cache)``.
        """
        c = self.config
        self._check_slot_support()
        hd = c.resolved_head_dim
        b, cc = tokens.shape
        x = L.embed(params["embed"], tokens, c.cdtype)
        start = jnp.asarray(start, jnp.int32)
        chunk_len = jnp.asarray(chunk_len, jnp.int32)
        table = jnp.asarray(table, jnp.int32)

        def attn_fn(j, p, h, kv, layer):
            y, kv = attn_mod.prefill_attention_paged(
                p["attn"], L.rmsnorm(p["ln1"], h), kv, table, start,
                chunk_len, num_heads=c.num_heads,
                num_kv_heads=c.num_kv_heads, head_dim=hd,
                rope_theta=c.rope_theta, layer=layer, **self._attn_kw(j),
            )
            return h + y, kv

        x, new_cache = self._paged_layers(attn_fn, x, params["blocks"], cache)
        with jax.named_scope(SCOPE_UNEMBED):
            last = jnp.clip(chunk_len - 1, 0, cc - 1)
            x_last = x[jnp.arange(b), last][:, None]  # (S, 1, D)
            x_last = L.rmsnorm(params["final_norm"], x_last)
            logits = self._unembed(params, x_last)[:, 0]
            return self._mask_pad_logits(logits), new_cache

    # --------------------------------------------------------- analytics
    def param_count(self) -> int:
        shapes = jax.eval_shape(
            lambda k: self.init_params(k), jax.random.PRNGKey(0)
        )
        return sum(int(np.prod(t.shape)) for t in jax.tree.leaves(shapes))

    def active_param_count(self) -> int:
        """Params touched per token (MoE: on average top_k of the router's
        experts, of which these held ones are a share)."""
        total = self.param_count()
        c = self.config
        if c.family != "moe" or not c.num_experts:
            return total
        expert_p = 3 * c.d_model * c.d_ff * c.num_experts * c.num_layers
        active = expert_p * c.top_k / c.router_width
        return int(total - expert_p + active)


# Public functional aliases -------------------------------------------------
def init_params(config: ModelConfig, key):
    return Model(config).init_params(key)


def loss_fn(config: ModelConfig, params, batch):
    return Model(config).loss_fn(params, batch)


def lm_logits(config: ModelConfig, params, tokens, extras=None):
    return Model(config).lm_logits(params, tokens, extras)


def init_cache(config: ModelConfig, batch, cache_len, extras=None):
    return Model(config).init_cache(batch, cache_len, extras)


def decode_step(config: ModelConfig, params, cache, tokens, pos):
    return Model(config).decode_step(params, cache, tokens, pos)
