"""Attention: GQA/MQA with flash-style chunked softmax, pure JAX.

Train/prefill path processes queries and keys in blocks with an online
softmax (running max + normalizer) so the full (S x S) score matrix is
never materialized — the working set per step is (B, H, qblk, kblk).
Causal masking is applied per block pair; block pairs that are entirely
above the diagonal still lower (masked) in the baseline — the §Perf
hillclimb replaces this with lower-triangular block iteration.

Decode path attends a single query against a KV cache; sliding-window
models use a rolling (modulo) cache so a 4k window serves a 500k context
in O(window) memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import _dense_init, rope

NEG_INF = -1e30


def init_attention(key, d_model, num_heads, num_kv_heads, head_dim, dtype,
                   qk_norm=False, with_rope=True):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(k1, (d_model, num_heads * head_dim), dtype),
        "wk": _dense_init(k2, (d_model, num_kv_heads * head_dim), dtype),
        "wv": _dense_init(k3, (d_model, num_kv_heads * head_dim), dtype),
        "wo": _dense_init(k4, (num_heads * head_dim, d_model), dtype),
    }
    if qk_norm:
        p["q_norm"] = jnp.ones((head_dim,), dtype=dtype)
        p["k_norm"] = jnp.ones((head_dim,), dtype=dtype)
    return p


def _qkv(params, x, xkv, num_heads, num_kv_heads, head_dim):
    b, s, _ = x.shape
    skv = xkv.shape[1]
    q = (x @ params["wq"].astype(x.dtype)).reshape(b, s, num_heads, head_dim)
    k = (xkv @ params["wk"].astype(x.dtype)).reshape(b, skv, num_kv_heads, head_dim)
    v = (xkv @ params["wv"].astype(x.dtype)).reshape(b, skv, num_kv_heads, head_dim)
    return q, k, v


def _maybe_qk_norm(params, q, k, eps=1e-6):
    if "q_norm" not in params:
        return q, k

    def rn(t, scale):
        t32 = t.astype(jnp.float32)
        var = jnp.mean(t32 * t32, axis=-1, keepdims=True)
        return (t32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(
            t.dtype
        )

    return rn(q, params["q_norm"]), rn(k, params["k_norm"])


def _block_attn_scores(q, k, scale):
    # q: (B, qb, KV, G, hd), k: (B, kb, KV, hd) -> (B, KV, G, qb, kb)
    return jnp.einsum("bqkgh,bskh->bkgqs", q, k) * scale


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_heads", "num_kv_heads", "head_dim", "causal", "window",
        "q_block", "kv_block", "causal_skip",
    ),
)
def chunked_attention(
    q, k, v, q_pos, kv_pos, *,
    num_heads, num_kv_heads, head_dim,
    causal=True, window=None, q_block=512, kv_block=1024,
    causal_skip=False,
):
    """Flash-style attention. q: (B,S,H,hd); k,v: (B,Skv,KV,hd).

    q_pos: (S,) absolute positions of queries; kv_pos: (Skv,) of keys.
    Returns (B, S, H, hd).

    causal_skip: iterate kv blocks with DYNAMIC bounds so blocks that are
    entirely above the causal diagonal (or entirely outside the sliding
    window) are never computed — ~2x attention-FLOP cut at long seq
    (§Perf hillclimb; baseline lowers every masked block).
    """
    b, s, _, _ = q.shape
    skv = k.shape[1]
    g = num_heads // num_kv_heads
    scale = 1.0 / np.sqrt(head_dim)
    qb = min(q_block, s)
    kb = min(kv_block, skv)
    nq, nk = s // qb, skv // kb
    assert s % qb == 0 and skv % kb == 0, (s, qb, skv, kb)

    qr = q.reshape(b, nq, qb, num_kv_heads, g, head_dim)
    kr = k.reshape(b, nk, kb, num_kv_heads, head_dim)
    vr = v.reshape(b, nk, kb, num_kv_heads, head_dim)
    qp = q_pos.reshape(nq, qb)
    kp = kv_pos.reshape(nk, kb)

    def q_step(_, qi):
        q_i = qr[:, qi]  # (B, qb, KV, G, hd)
        qp_i = qp[qi]

        def kv_body(carry, kj):
            m, l, acc = carry
            k_j = kr[:, kj]
            v_j = vr[:, kj]
            kp_j = kp[kj]
            sc = _block_attn_scores(q_i, k_j, scale).astype(jnp.float32)
            # (B, KV, G, qb, kb). kv_pos < 0 marks padded key slots.
            mask = jnp.broadcast_to(kp_j[None, :] >= 0, (qb, kb))
            if causal:
                mask &= qp_i[:, None] >= kp_j[None, :]
            if window is not None:
                mask &= qp_i[:, None] - kp_j[None, :] < window
            sc = jnp.where(mask[None, None, None], sc, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bkgqs,bskh->bkgqh", p.astype(v_j.dtype), v_j
            ).astype(jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new)

        m0 = jnp.full((b, num_kv_heads, g, qb), NEG_INF, dtype=jnp.float32)
        l0 = jnp.zeros((b, num_kv_heads, g, qb), dtype=jnp.float32)
        a0 = jnp.zeros((b, num_kv_heads, g, qb, head_dim), dtype=jnp.float32)
        if causal_skip:
            # runtime-skip blocks entirely above the causal diagonal (or
            # outside the sliding window): scan over all block indices
            # with a lax.cond — only the needed branch executes, and the
            # construct stays reverse-differentiable (a dynamic-bound
            # fori_loop would not be).
            qmax = jnp.max(qp_i)
            qmin = jnp.min(qp_i)
            kmins = jnp.min(kp, axis=1)  # (nk,)
            kmaxs = jnp.max(kp, axis=1)
            needed = jnp.ones((nk,), bool)
            if causal:
                needed &= kmins <= qmax
            if window is not None:
                needed &= kmaxs >= qmin - window + 1

            def maybe(carry, inp):
                kj, need = inp
                new = jax.lax.cond(
                    need, lambda c: kv_body(c, kj), lambda c: c, carry
                )
                return new, None

            (m, l, acc), _ = jax.lax.scan(
                maybe, (m0, l0, a0), (jnp.arange(nk), needed)
            )
        else:
            (m, l, acc), _ = jax.lax.scan(
                lambda c, kj: (kv_body(c, kj), None), (m0, l0, a0),
                jnp.arange(nk),
            )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        # (B, KV, G, qb, hd) -> (B, qb, KV*G, hd)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, qb, num_heads, head_dim)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))
    # outs: (nq, B, qb, H, hd) -> (B, S, H, hd)
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, num_heads, head_dim)


def attention(
    params, x, positions, *,
    num_heads, num_kv_heads, head_dim,
    causal=True, window=None, use_rope=True, rope_theta=10_000.0,
    yarn=None, xkv=None, kv_positions=None, q_block=512, kv_block=1024,
    causal_skip=False, return_kv=False,
):
    """Full attention layer (train/prefill). x: (B, S, D).

    Sequences that do not divide the block sizes are padded: queries with
    continuation positions (output sliced back), keys with position -1
    (masked inside the online softmax).

    ``return_kv``: additionally return the post-rope (B, S, KV, hd) key
    and value tensors — exactly what ``decode_attention`` would have
    written into its cache one position at a time, so a batched prefill
    can fill a decode cache from this single pass (DESIGN.md §4/§10).
    """
    xkv = x if xkv is None else xkv
    kv_positions = positions if kv_positions is None else kv_positions
    q, k, v = _qkv(params, x, xkv, num_heads, num_kv_heads, head_dim)
    q, k = _maybe_qk_norm(params, q, k)
    if use_rope:
        q = rope(q, jnp.broadcast_to(positions, x.shape[:1] + positions.shape[-1:]),
                 rope_theta, yarn)
        k = rope(k, jnp.broadcast_to(kv_positions, xkv.shape[:1] + kv_positions.shape[-1:]),
                 rope_theta, yarn)
    k_cache, v_cache = k, v  # pre-padding views (the decode-cache payload)
    b, s = x.shape[:2]
    skv = k.shape[1]
    qb = min(q_block, s)
    kb = min(kv_block, skv)
    pad_q = (-s) % qb
    pad_k = (-skv) % kb
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        last = positions[-1]
        positions = jnp.concatenate(
            [positions, last + 1 + jnp.arange(pad_q, dtype=positions.dtype)]
        )
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        kv_positions = jnp.concatenate(
            [kv_positions, jnp.full((pad_k,), -1, dtype=kv_positions.dtype)]
        )
    out = chunked_attention(
        q, k, v, positions, kv_positions,
        num_heads=num_heads, num_kv_heads=num_kv_heads, head_dim=head_dim,
        causal=causal, window=window, q_block=qb, kv_block=kb,
        causal_skip=causal_skip,
    )
    if pad_q:
        out = out[:, :s]
    y = out.reshape(b, s, num_heads * head_dim) @ params["wo"].astype(x.dtype)
    if return_kv:
        return y, k_cache, v_cache
    return y


def init_attn_cache(batch, cache_len, num_kv_heads, head_dim, dtype,
                    quantized: bool = False):
    """KV cache. cache_len = full context, or window size (rolling).

    quantized: int8 storage with per-(token, head) symmetric scales —
    halves the dominant decode cache-read bytes at ~0.4% quantization
    noise (scales add 2/head_dim relative overhead).
    """
    if quantized:
        return {
            "k": jnp.zeros((batch, cache_len, num_kv_heads, head_dim), jnp.int8),
            "v": jnp.zeros((batch, cache_len, num_kv_heads, head_dim), jnp.int8),
            "k_scale": jnp.zeros((batch, cache_len, num_kv_heads), jnp.float16),
            "v_scale": jnp.zeros((batch, cache_len, num_kv_heads), jnp.float16),
            "pos": jnp.full((cache_len,), -1, dtype=jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, cache_len, num_kv_heads, head_dim), dtype=dtype),
        "v": jnp.zeros((batch, cache_len, num_kv_heads, head_dim), dtype=dtype),
        "pos": jnp.full((cache_len,), -1, dtype=jnp.int32),  # absolute pos per slot
    }


def _quantize_kv(t):
    """(B, 1, KV, hd) -> int8 values + per-(B,1,KV) f16 scales."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(
        jnp.round(t.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def decode_attention(
    params, x, cache, pos, *,
    num_heads, num_kv_heads, head_dim,
    window=None, use_rope=True, rope_theta=10_000.0, yarn=None,
):
    """Single-token decode. x: (B, 1, D); pos: scalar int32 (uniform batch).

    Writes the new KV at slot ``pos % cache_len`` (rolling when the cache
    is smaller than the context — sliding-window models), then attends
    over every valid slot. Cost is one matvec per head over the cache:
    exactly the paper's matvec shape.
    """
    b = x.shape[0]
    q, k_new, v_new = _qkv(params, x, x, num_heads, num_kv_heads, head_dim)
    q, k_new = _maybe_qk_norm(params, q, k_new)
    if use_rope:
        p = jnp.full((1,), pos, dtype=jnp.int32)
        q = rope(q, jnp.broadcast_to(p, (b, 1)), rope_theta, yarn)
        k_new = rope(k_new, jnp.broadcast_to(p, (b, 1)), rope_theta, yarn)
    cache_len = cache["k"].shape[1]
    slot = jnp.mod(pos, cache_len).astype(jnp.int32)
    zero = jnp.zeros((), jnp.int32)  # all indices same dtype (x64-safe)
    quantized = "k_scale" in cache
    if quantized:
        k_q, k_s = _quantize_kv(k_new)
        v_q, v_s = _quantize_kv(v_new)
        k_int = jax.lax.dynamic_update_slice(cache["k"], k_q, (zero, slot, zero, zero))
        v_int = jax.lax.dynamic_update_slice(cache["v"], v_q, (zero, slot, zero, zero))
        k_sc = jax.lax.dynamic_update_slice(cache["k_scale"], k_s, (zero, slot, zero))
        v_sc = jax.lax.dynamic_update_slice(cache["v_scale"], v_s, (zero, slot, zero))
        k = k_int.astype(x.dtype) * k_sc[..., None].astype(x.dtype)
        v = v_int.astype(x.dtype) * v_sc[..., None].astype(x.dtype)
        new_cache = {"k": k_int, "v": v_int, "k_scale": k_sc, "v_scale": v_sc}
    else:
        k = jax.lax.dynamic_update_slice(cache["k"], k_new, (zero, slot, zero, zero))
        v = jax.lax.dynamic_update_slice(cache["v"], v_new, (zero, slot, zero, zero))
        new_cache = {"k": k, "v": v}
    slot_pos = jax.lax.dynamic_update_slice(
        cache["pos"], jnp.full((1,), pos, dtype=jnp.int32), (slot,)
    )
    new_cache["pos"] = slot_pos
    g = num_heads // num_kv_heads
    scale = 1.0 / np.sqrt(head_dim)
    qr = q.reshape(b, num_kv_heads, g, head_dim)
    sc = jnp.einsum("bkgh,bskh->bkgs", qr, k).astype(jnp.float32) * scale
    valid = (slot_pos >= 0) & (slot_pos <= pos)
    if window is not None:
        valid &= pos - slot_pos < window
    sc = jnp.where(valid[None, None, None, :], sc, NEG_INF)
    w = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgs,bskh->bkgh", w, v)
    out = out.reshape(b, 1, num_heads * head_dim)
    y = out @ params["wo"].astype(x.dtype)
    return y, new_cache


def decode_attention_paged(
    params, x, cache, table, pos, active, *,
    num_heads, num_kv_heads, head_dim,
    use_rope=True, rope_theta=10_000.0, yarn=None, window=None,
    use_kernel=False, layer=None,
):
    """Per-slot decode against a shared KV block pool (DESIGN.md §13).

    Every batch row (slot) advances at its OWN position, and the KV
    state is a fixed pool of physical blocks shared across slots:
    ``cache`` holds ``{"k", "v"}`` of shape (num_blocks + 1, block_len,
    KV * hd) (last block = write sink), or with ``layer`` the stacked
    (L, num_blocks + 1, block_len, KV * hd) pool of which this call
    writes and reads layer ``layer`` in place; ``table``: (S,
    max_blocks) maps each slot's logical blocks to pool blocks (−1 =
    unallocated), ``pos``: (S,) write positions, ``active``: (S,) bool —
    inactive rows write to the sink so frozen slots can never corrupt
    reassigned blocks. ``window``: a query at position i attends keys j
    with i - j < window. Returns (y, the pool as given, written). The
    served logits are checked against the plain float32 references
    (``tests/test_routed_window.py``).
    """
    from repro.kernels.paged_attention import ops as paged_ops

    b = x.shape[0]
    q, k_new, v_new = _qkv(params, x, x, num_heads, num_kv_heads, head_dim)
    q, k_new = _maybe_qk_norm(params, q, k_new)
    if use_rope:
        p = pos[:, None].astype(jnp.int32)
        q = rope(q, p, rope_theta, yarn)
        k_new = rope(k_new, p, rope_theta, yarn)
    k_pool, v_pool = paged_ops.scatter_decode(
        cache["k"], cache["v"], k_new[:, 0], v_new[:, 0], table, pos, active,
        layer,
    )
    g = num_heads // num_kv_heads
    qr = q.reshape(b, num_kv_heads, g, head_dim)
    if use_kernel:
        if window is not None:
            raise NotImplementedError("the paged decode kernel has no window")
        view = lambda t: t if layer is None else t[layer]
        out = paged_ops.paged_decode_attend_kernel(
            qr, view(k_pool), view(v_pool), table, pos
        )
    else:
        out = paged_ops.paged_decode_attend(qr, k_pool, v_pool, table, pos,
                                            window=window, layer=layer)
    out = out.reshape(b, 1, num_heads * head_dim)
    y = out @ params["wo"].astype(x.dtype)
    return y, {"k": k_pool, "v": v_pool}


def prefill_attention_paged(
    params, x, cache, table, start, chunk_len, *,
    num_heads, num_kv_heads, head_dim,
    use_rope=True, rope_theta=10_000.0, yarn=None, window=None, layer=None,
):
    """One chunked-prefill pass of C prompt tokens per slot into the pool.

    x: (S, C, D) chunk embeddings; chunk row ``i`` of slot ``s`` is the
    prompt token at absolute position ``start[s] + i`` (rows past
    ``chunk_len[s]`` are padding — their KV goes to the sink and their
    outputs are discarded by the caller). KV for the chunk is scattered
    FIRST, then every query attends the slot's full gathered history up
    to itself, so cross-chunk context (earlier admit rounds) and
    in-chunk causality share one mask. ``cache`` and ``layer`` are as in
    ``decode_attention_paged``: with ``layer`` the chunk is written into
    and read from that layer of the stacked pool in place.
    """
    from repro.kernels.paged_attention import ops as paged_ops

    b, c = x.shape[:2]
    q, k_new, v_new = _qkv(params, x, x, num_heads, num_kv_heads, head_dim)
    q, k_new = _maybe_qk_norm(params, q, k_new)
    p = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]  # (S, C)
    if use_rope:
        q = rope(q, p, rope_theta, yarn)
        k_new = rope(k_new, p, rope_theta, yarn)
    k_pool, v_pool = paged_ops.scatter_chunk(
        cache["k"], cache["v"], k_new, v_new, table, start, chunk_len, layer
    )
    g = num_heads // num_kv_heads
    qr = q.reshape(b, c, num_kv_heads, g, head_dim)
    out = paged_ops.paged_chunk_attend(qr, k_pool, v_pool, table, p,
                                       window=window, layer=layer)
    out = out.reshape(b, c, num_heads * head_dim).astype(x.dtype)
    y = out @ params["wo"].astype(x.dtype)
    return y, {"k": k_pool, "v": v_pool}
