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
    SCOPE_UNEMBED,
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
                    "moe": moe_mod.init_moe(k2, c.d_model, c.d_ff, c.num_experts, dt),
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
        return params

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
    def _dense_apply(self, p, x, positions, *, causal=True):
        c = self.config
        h = x + attn_mod.attention(
            p["attn"], L.rmsnorm(p["ln1"], x), positions,
            num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
            head_dim=c.resolved_head_dim, causal=causal,
            window=c.sliding_window, rope_theta=c.rope_theta,
            q_block=c.attn_q_block, kv_block=c.attn_kv_block,
            causal_skip=c.causal_block_skip,
        )
        h = h + L.mlp(p["mlp"], L.rmsnorm(p["ln2"], h))
        return h

    def _moe_apply(self, p, x, positions):
        c = self.config
        h = x + attn_mod.attention(
            p["attn"], L.rmsnorm(p["ln1"], x), positions,
            num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
            head_dim=c.resolved_head_dim, causal=True,
            window=c.sliding_window, rope_theta=c.rope_theta,
            q_block=c.attn_q_block, kv_block=c.attn_kv_block,
            causal_skip=c.causal_block_skip,
        )
        h = h + moe_mod.moe_ffn(
            p["moe"], L.rmsnorm(p["ln2"], h),
            num_experts=c.num_experts, top_k=c.top_k,
            capacity_factor=c.capacity_factor,
        )
        return h

    def _mamba_apply(self, p, x):
        c = self.config
        return x + ssm_mod.mamba2(
            p["mamba"], L.rmsnorm(p["ln"], x),
            d_state=c.ssm_state, expand=c.mamba_expand,
            head_dim=c.mamba_head_dim, chunk=c.mamba_chunk,
        )

    # ----------------------------------------------------------- forward
    def _stack_apply(self, fn, x, stacked):
        """Apply fn(layer_params, h) over stacked layers.

        scan_layers=True: lax.scan (one-layer HLO, fast compile).
        scan_layers=False: unrolled python loop — used by the dry-run so
        XLA cost analysis sees every layer (a while body is counted once).
        """
        if self.config.scan_layers:
            x, _ = jax.lax.scan(lambda h, p: (fn(p, h), None), x, stacked)
            return x
        for i in range(self.config.num_layers):
            p = jax.tree.map(lambda t: t[i], stacked)
            x = fn(p, x)
        return x

    def _backbone(self, params, x, positions):
        """(B, S, D) -> (B, S, D) through all blocks (train/prefill)."""
        c = self.config

        if c.family in ("dense", "vlm"):
            fn = lambda p, h: self._dense_apply(p, h, positions)
            fn = jax.checkpoint(fn) if c.remat else fn
            x = self._stack_apply(fn, x, params["blocks"])
        elif c.family == "moe":
            fn = lambda p, h: self._moe_apply(p, h, positions)
            fn = jax.checkpoint(fn) if c.remat else fn
            x = self._stack_apply(fn, x, params["blocks"])
        elif c.family == "hybrid":
            shared = params["shared_attn"]
            every = max(c.attn_every, 1)

            def layer(p, h, i):
                h = jax.lax.cond(
                    i % every == 0,
                    lambda hh: self._dense_apply(shared, hh, positions),
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
        return self._stack_apply(fn, x, params["blocks"])

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
        logits = L.unembed(params["embed"], x, DTYPES_LOGITS[c.logits_dtype])
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

        cache_len: KV capacity. Sliding-window models may pass
        min(cache_len, window) to get the rolling cache.
        """
        c = self.config
        dt = c.cdtype
        hd = c.resolved_head_dim
        if c.sliding_window is not None:
            cache_len = min(cache_len, c.sliding_window)

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

        def attn_decode(p, h, kv_slice):
            y, new = attn_mod.decode_attention(
                p["attn"], L.rmsnorm(p["ln1"], h), kv_slice, pos,
                num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                head_dim=hd, window=c.sliding_window, rope_theta=c.rope_theta,
            )
            h = h + y
            return h, new

        def _kv_stack_apply(body, h, blocks, kv):
            """Scan-or-unroll a decode body carrying per-layer KV slices."""
            if c.scan_layers:
                return jax.lax.scan(body, h, (blocks, kv))
            news = []
            for i in range(c.num_layers):
                inp = jax.tree.map(lambda t: t[i], (blocks, kv))
                h, new = body(h, inp)
                news.append(new)
            stacked = jax.tree.map(lambda *ts: jnp.stack(ts), *news)
            return h, stacked

        if c.family in ("dense", "vlm"):
            def body(h, inp):
                p, kv_slice = inp
                h, new = attn_decode(p, h, kv_slice)
                h = h + L.mlp(p["mlp"], L.rmsnorm(p["ln2"], h))
                return h, new

            x, new_kv = _kv_stack_apply(body, x, params["blocks"], cache["kv"])
            cache = {**cache, "kv": new_kv}
        elif c.family == "moe":
            def body(h, inp):
                p, kv_slice = inp
                h, new = attn_decode(p, h, kv_slice)
                h = h + moe_mod.moe_ffn(
                    p["moe"], L.rmsnorm(p["ln2"], h),
                    num_experts=c.num_experts, top_k=c.top_k,
                    capacity_factor=c.capacity_factor,
                )
                return h, new

            x, new_kv = _kv_stack_apply(body, x, params["blocks"], cache["kv"])
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

            def body(h, inp):
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

            x, new_kv = _kv_stack_apply(body, x, params["blocks"], cache["kv"])
            cache = {**cache, "kv": new_kv}
        else:
            raise ValueError(c.family)

        norm = L.layernorm if c.family == "audio" else L.rmsnorm
        x = norm(params["final_norm"], x)
        logits = L.unembed(params["embed"], x, DTYPES_LOGITS[c.logits_dtype])
        logits = self._mask_pad_logits(logits[:, 0])
        return logits, cache

    # ------------------------------------------------- slot-resident decode
    # The continuous-batching serve front-end (runtime/serve_loop.py
    # ``Server.serve``, DESIGN.md §10) keeps one independent request per
    # batch slot: each slot has its own sequence length, so the cache
    # carries per-slot absolute positions and ``decode_step_slots`` takes
    # a (B,) position vector instead of ``decode_step``'s uniform scalar.
    # ``prefill`` fills a newly admitted request's per-layer KV from ONE
    # batched forward pass (the cache-returning path §4 called for)
    # instead of a per-position decode scan.

    def _check_slot_support(self) -> None:
        c = self.config
        if c.family not in ("dense", "vlm", "moe"):
            raise NotImplementedError(
                f"slot-resident decode supports the attention-cache "
                f"families (dense/vlm/moe), not {c.family!r}"
            )
        if c.kv_quant:
            raise NotImplementedError(
                "slot-resident decode does not support int8 KV caches yet"
            )
        if c.sliding_window is not None:
            raise NotImplementedError(
                "slot-resident decode allocates full-context caches; "
                "sliding-window models are not supported yet"
            )

    def init_slot_cache(self, batch: int, cache_len: int):
        """Decode state for ``decode_step_slots``: per-slot positions.

        Layout matches ``init_cache``'s attention families except ``pos``
        is (B, cache_len) — each slot tracks its own absolute positions
        (−1 = empty). Shared across layers (every layer writes the same
        positions), so the serve loop can splice a prefilled request into
        one slot with a single row update.
        """
        c = self.config
        self._check_slot_support()
        hd = c.resolved_head_dim
        return {
            "kv": {
                "k": jnp.zeros(
                    (c.num_layers, batch, cache_len, c.num_kv_heads, hd),
                    c.cdtype,
                ),
                "v": jnp.zeros(
                    (c.num_layers, batch, cache_len, c.num_kv_heads, hd),
                    c.cdtype,
                ),
                "pos": jnp.full((batch, cache_len), -1, jnp.int32),
            }
        }

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

        def attn_with_kv(p, h):
            y, k, v = attn_mod.attention(
                p["attn"], L.rmsnorm(p["ln1"], h), positions,
                num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                head_dim=hd, causal=True, window=c.sliding_window,
                rope_theta=c.rope_theta, q_block=c.attn_q_block,
                kv_block=c.attn_kv_block, causal_skip=c.causal_block_skip,
                return_kv=True,
            )
            return h + y, k, v

        if c.family == "moe":
            def block(p, h):
                h, k, v = attn_with_kv(p, h)
                h = h + moe_mod.moe_ffn(
                    p["moe"], L.rmsnorm(p["ln2"], h),
                    num_experts=c.num_experts, top_k=c.top_k,
                    capacity_factor=c.capacity_factor,
                )
                return h, k, v
        else:
            def block(p, h):
                h, k, v = attn_with_kv(p, h)
                h = h + L.mlp(p["mlp"], L.rmsnorm(p["ln2"], h))
                return h, k, v

        if c.scan_layers:
            def body(h, p):
                h, k, v = block(p, h)
                return h, (k, v)

            x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
        else:
            k_list, v_list = [], []
            for i in range(c.num_layers):
                p = jax.tree.map(lambda t: t[i], params["blocks"])
                x, k, v = block(p, x)
                k_list.append(k)
                v_list.append(v)
            ks, vs = jnp.stack(k_list), jnp.stack(v_list)

        last = jnp.clip(length - 1, 0, s - 1).astype(jnp.int32)
        x_last = x[jnp.arange(b), last][:, None]  # (B, 1, D)
        x_last = L.rmsnorm(params["final_norm"], x_last)
        logits = L.unembed(
            params["embed"], x_last, DTYPES_LOGITS[c.logits_dtype]
        )[:, 0]
        return self._mask_pad_logits(logits), ks, vs

    def decode_step_slots(self, params, cache, tokens, pos):
        """One token per slot, each at its OWN position.

        tokens: (B,) int32; pos: (B,) int32 absolute write positions
        (frozen slots simply rewrite the same entry — idempotent).
        Returns (logits (B, V_padded), new_cache).
        """
        c = self.config
        self._check_slot_support()
        hd = c.resolved_head_dim
        x = L.embed(params["embed"], tokens[:, None], c.cdtype)
        kv = cache["kv"]
        b, cache_len = kv["pos"].shape
        pos = jnp.asarray(pos, jnp.int32)
        bidx = jnp.arange(b)
        slot = jnp.mod(pos, cache_len).astype(jnp.int32)
        # one shared position map: every layer writes the same positions
        pos_map = kv["pos"].at[bidx, slot].set(pos)

        def attn_decode(p, h, kv_slice):
            y, new = attn_mod.decode_attention_slots(
                p["attn"], L.rmsnorm(p["ln1"], h), kv_slice, pos_map, pos,
                slot, num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                head_dim=hd, rope_theta=c.rope_theta,
            )
            return h + y, new

        if c.family == "moe":
            def body(h, inp):
                p, kv_slice = inp
                h, new = attn_decode(p, h, kv_slice)
                h = h + moe_mod.moe_ffn(
                    p["moe"], L.rmsnorm(p["ln2"], h),
                    num_experts=c.num_experts, top_k=c.top_k,
                    capacity_factor=c.capacity_factor,
                )
                return h, new
        else:
            def body(h, inp):
                p, kv_slice = inp
                h, new = attn_decode(p, h, kv_slice)
                h = h + L.mlp(p["mlp"], L.rmsnorm(p["ln2"], h))
                return h, new

        layer_kv = {"k": kv["k"], "v": kv["v"]}
        if c.scan_layers:
            x, new_kv = jax.lax.scan(body, x, (params["blocks"], layer_kv))
        else:
            news = []
            for i in range(c.num_layers):
                inp = jax.tree.map(lambda t: t[i], (params["blocks"], layer_kv))
                x, new = body(x, inp)
                news.append(new)
            new_kv = jax.tree.map(lambda *ts: jnp.stack(ts), *news)

        x = L.rmsnorm(params["final_norm"], x)
        logits = L.unembed(params["embed"], x, DTYPES_LOGITS[c.logits_dtype])
        return self._mask_pad_logits(logits[:, 0]), {
            "kv": {**new_kv, "pos": pos_map}
        }

    # --------------------------------------------------- paged KV decode
    # Paged serving (DESIGN.md §13): physical KV memory is a fixed pool
    # of (block_len,)-token blocks shared across slots, and each slot
    # maps logical positions to pool blocks through a block table. The
    # program's shapes depend only on (num_blocks, block_len, S) — never
    # on any request's length — so admitting an arbitrarily long prompt
    # (prefilled chunk-by-chunk across admit rounds) retraces nothing.

    def init_paged_cache(self, num_blocks: int, block_len: int):
        """KV block pool for ``decode_step_paged``/``prefill_paged``.

        Allocates ``num_blocks + 1`` physical blocks per layer: the last
        block is the write SINK — inactive/frozen/padded rows scatter
        there, so a frozen slot can never corrupt a block that was freed
        and reassigned. No position array: validity is derived from the
        per-dispatch block tables and positions (runtime arguments).
        """
        c = self.config
        self._check_slot_support()
        hd = c.resolved_head_dim
        shape = (c.num_layers, num_blocks + 1, block_len, c.num_kv_heads, hd)
        return {
            "kv": {
                "k": jnp.zeros(shape, c.cdtype),
                "v": jnp.zeros(shape, c.cdtype),
            }
        }

    def _paged_stack_apply(self, body, x, blocks, cache):
        """Scan-or-unroll over layers carrying per-layer pool slices."""
        layer_kv = {"k": cache["kv"]["k"], "v": cache["kv"]["v"]}
        with jax.named_scope(SCOPE_LAYERS):
            if self.config.scan_layers:
                x, new_kv = jax.lax.scan(body, x, (blocks, layer_kv))
            else:
                news = []
                for i in range(self.config.num_layers):
                    inp = jax.tree.map(lambda t: t[i], (blocks, layer_kv))
                    x, new = body(x, inp)
                    news.append(new)
                new_kv = jax.tree.map(lambda *ts: jnp.stack(ts), *news)
        return x, {"kv": new_kv}

    def _paged_block_body(self, attn_fn):
        """Residual block body around a paged attention fn (dense/moe),
        each half in its named scope (DESIGN.md §14)."""
        c = self.config
        if c.family == "moe":
            def ffn(p, h):
                return moe_mod.moe_ffn(
                    p["moe"], L.rmsnorm(p["ln2"], h),
                    num_experts=c.num_experts, top_k=c.top_k,
                    capacity_factor=c.capacity_factor,
                )
        else:
            def ffn(p, h):
                return L.mlp(p["mlp"], L.rmsnorm(p["ln2"], h))

        def body(h, inp):
            p, kv_slice = inp
            with jax.named_scope(SCOPE_ATTENTION):
                h, new = attn_fn(p, h, kv_slice)
            with jax.named_scope(SCOPE_MLP):
                h = h + ffn(p, h)
            return h, new
        return body

    def decode_step_paged(self, params, cache, tokens, pos, table, active,
                          *, use_kernel: bool = False):
        """One token per slot against the shared block pool.

        tokens: (S,) int32; pos: (S,) write positions; table: (S, MB)
        block table; active: (S,) bool (inactive rows write to the
        sink). Returns (logits (S, V_padded), new_cache). The attend
        math bit-matches ``decode_step_slots`` under an order-preserving
        block layout.
        """
        c = self.config
        self._check_slot_support()
        hd = c.resolved_head_dim
        x = L.embed(params["embed"], tokens[:, None], c.cdtype)
        pos = jnp.asarray(pos, jnp.int32)
        table = jnp.asarray(table, jnp.int32)
        active = jnp.asarray(active, bool)

        def attn_fn(p, h, kv_slice):
            y, new = attn_mod.decode_attention_paged(
                p["attn"], L.rmsnorm(p["ln1"], h), kv_slice, table, pos,
                active, num_heads=c.num_heads, num_kv_heads=c.num_kv_heads,
                head_dim=hd, rope_theta=c.rope_theta, use_kernel=use_kernel,
            )
            return h + y, new

        x, new_cache = self._paged_stack_apply(
            self._paged_block_body(attn_fn), x, params["blocks"], cache
        )
        with jax.named_scope(SCOPE_UNEMBED):
            x = L.rmsnorm(params["final_norm"], x)
            logits = L.unembed(
                params["embed"], x, DTYPES_LOGITS[c.logits_dtype]
            )
            return self._mask_pad_logits(logits[:, 0]), new_cache

    def prefill_paged(self, params, cache, tokens, start, chunk_len, table):
        """One chunked-prefill admit round: C prompt tokens per slot.

        tokens: (S, C) int32 — row s holds prompt positions
        ``[start[s], start[s] + chunk_len[s])`` of slot s's request
        (right-padded; rows with ``chunk_len == 0`` are slots not
        prefilling this round). KV for the chunk is scattered into the
        slot's pool blocks, every query attends the slot's full gathered
        history (earlier chunks included), and the returned logits are
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

        def attn_fn(p, h, kv_slice):
            y, new = attn_mod.prefill_attention_paged(
                p["attn"], L.rmsnorm(p["ln1"], h), kv_slice, table, start,
                chunk_len, num_heads=c.num_heads,
                num_kv_heads=c.num_kv_heads, head_dim=hd,
                rope_theta=c.rope_theta,
            )
            return h + y, new

        x, new_cache = self._paged_stack_apply(
            self._paged_block_body(attn_fn), x, params["blocks"], cache
        )
        with jax.named_scope(SCOPE_UNEMBED):
            last = jnp.clip(chunk_len - 1, 0, cc - 1)
            x_last = x[jnp.arange(b), last][:, None]  # (S, 1, D)
            x_last = L.rmsnorm(params["final_norm"], x_last)
            logits = L.unembed(
                params["embed"], x_last, DTYPES_LOGITS[c.logits_dtype]
            )[:, 0]
            return self._mask_pad_logits(logits), new_cache

    # --------------------------------------------------------- analytics
    def param_count(self) -> int:
        shapes = jax.eval_shape(
            lambda k: self.init_params(k), jax.random.PRNGKey(0)
        )
        return sum(int(np.prod(t.shape)) for t in jax.tree.leaves(shapes))

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k of num_experts FFNs)."""
        total = self.param_count()
        c = self.config
        if c.family != "moe" or not c.num_experts:
            return total
        expert_p = 3 * c.d_model * c.d_ff * c.num_experts * c.num_layers
        active = expert_p * c.top_k / c.num_experts
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
