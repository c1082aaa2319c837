"""Routed experts at a chip's share, window and full attention mixed,
YaRN and an untied head, served through ``Server.serve`` on the paged
pool and compared with the plain reference (``bench/reference/mellum.py``)
at a small size with seeded weights: two periods of three window layers
and a full one, window 16 with contexts past 48, 8 of 32 experts held,
top-4, YaRN factor 4. The same serve, on the harness's tiny qwen3-shaped
configuration, is compared with ``bench/reference/dense_decoder.py``:
the float32 references are the paged serve path's oracle."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "bench")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "tests"))

import tiny  # noqa: E402
import weights as W  # noqa: E402
from reference import dense_decoder, mellum  # noqa: E402

from repro.configs import ARCHS  # noqa: E402
from repro.configs.base import Yarn  # noqa: E402
from repro.core import ClusterSpec  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.runtime.serve_loop import ServeConfig, Server  # noqa: E402
from repro.serve import Request  # noqa: E402

SEED = 2 ** 31 + 17
WINDOW, HELD, ROUTED, TOP_K = 16, 8, 32, 4
YARN = {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
        "original_max_position_embeddings": 32, "beta_fast": 32, "beta_slow": 1,
        "attention_factor": 1.1386294361119891}
#: largest |served - reference| logit at float32 (read 2.6e-6 and 2.9e-6 at
#: seeds 2**31 + 17 and + 99): accumulation order and the program's float32
#: RoPE and softmax. The same runs in bfloat16 read 0.20 and 0.23
TOL = 2e-4
#: the same for ``tiny.CFG`` (qwen3-shaped) against ``dense_decoder``: read
#: 9.1e-7 and 8.5e-7 at seeds 2**31 + 17 and + 99. The same runs in
#: bfloat16 read 0.018 and 0.013 (the reference widens the served bfloat16
#: weights, so only the activations' rounding shows)
QWEN_TOL = 5e-5


def tiny_cfg(dtype="float32"):
    return {
        "architectures": ["MellumForCausalLM"], "hidden_size": 64,
        "moe_intermediate_size": 32, "num_hidden_layers": 8,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512, "rms_norm_eps": 1e-06, "hidden_act": "silu",
        "attention_bias": False, "tie_word_embeddings": False,
        "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 2,
        "mlp_layer_types": ["sparse"] * 8, "sliding_window": WINDOW,
        "rope_parameters": {"full_attention": YARN, "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000}},
        "num_experts": HELD, "num_experts_per_tok": TOP_K, "norm_topk_prob": True,
        "deployment": {"num_experts": ROUTED}, "use_qk_norm": True,
        "torch_dtype": dtype,
    }


def tiny_model(dtype="float32") -> Model:
    y = YARN
    return Model(dataclasses.replace(
        ARCHS["mellum2-12b-a2.5b"], name="tiny-mellum", num_layers=8, d_model=64,
        num_heads=4, num_kv_heads=2, head_dim=16, d_ff=32, vocab_size=512,
        sliding_window=WINDOW, rope_theta=10000.0,
        yarn=Yarn(float(y["factor"]), y["original_max_position_embeddings"],
                  32.0, 1.0, y["attention_factor"]),
        num_experts=HELD, routed_experts=ROUTED, top_k=TOP_K,
        param_dtype=dtype, compute_dtype=dtype, attn_q_block=16, attn_kv_block=16,
    ))


def tiny_qwen_cfg(dtype="float32"):
    return {**tiny.CFG, "torch_dtype": dtype}


def tiny_qwen_model(dtype="float32") -> Model:
    return Model(dataclasses.replace(tiny.model_config(), param_dtype=dtype,
                                     compute_dtype=dtype))


#: reference module, its configuration file, the program's model, and the
#: float32 tolerance, by architecture
REFS = {"mellum": (mellum, tiny_cfg, tiny_model, TOL),
        "qwen3": (dense_decoder, tiny_qwen_cfg, tiny_qwen_model, QWEN_TOL)}


def seeded(model: Model, seed: int = SEED, stacked=mellum.STACKED):
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    return W.program_params(shapes, seed, stacked)


def test_tiny_file_matches_the_program_config():
    d = mellum.Dims.of(tiny_cfg())
    c = tiny_model().config
    assert {k: getattr(c, k) for k in d.program_sizes()} == d.program_sizes()
    assert d.constants_off() == {}


class Served:
    """Wraps the paged serve step: after each dispatch, the pending logits
    of every slot whose logits are new, beside that slot's sequence, and
    each decode step's active slots and processed positions."""

    def __init__(self, fn):
        self.fn, self.seqs, self.records, self.steps = fn, [None] * 8, [], []

    def __call__(self, params, cache, logits, pos, chunk_tokens, chunk_start,
                 chunk_lens, finishing, tables, active, *rest, steps):
        out = jax.block_until_ready(self.fn(
            params, cache, logits, pos, chunk_tokens, chunk_start, chunk_lens,
            finishing, tables, active, *rest, steps=steps))
        lens, act, toks = map(np.asarray, (chunk_lens, active, out[3]))
        start, fin, pos_out = map(np.asarray, (chunk_start, finishing, out[2]))
        for s in np.flatnonzero(lens):
            if start[s] == 0:
                self.seqs[s] = []
            self.seqs[s].extend(np.asarray(chunk_tokens)[s, : lens[s]].tolist())
        for t in range(steps):
            self.steps.append([(self.seqs[s], pos_out[s] - steps + t)
                               for s in np.flatnonzero(act)])
            for s in np.flatnonzero(act):
                self.seqs[s].append(int(toks[t, s]))
        new = fin | (act & (steps > 0))
        for s in np.flatnonzero(new):
            self.records.append((self.seqs[s], int(pos_out[s]) - 1,
                                 np.asarray(out[1][s])))
        return out


def serve(model, params, prompts=(52, 57, 49, 61, 50, 55), out_len=7,
          decode_block=3):
    server = Server(model, params, ClusterSpec.make([2, 2], [4.0, 0.8]),
                    ServeConfig(block_rows=64, block_len=8,
                                prefill_chunk=16))
    rec = Served(server._serve_step_paged_fn)
    server._serve_step_paged_fn = rec
    rng = np.random.default_rng(5)
    trace = [Request(rid=i, arrival=0.0,
                     prompt=tuple(int(t) for t in rng.integers(0, 512, n)),
                     out_len=out_len, deadline_class="batch")
             for i, n in enumerate(prompts)]
    report = server.serve(trace, slots=3, decode_block=decode_block, block_len=8,
                          prefill_chunk=16)
    return server, rec, report


def reference_rows(cfg, seqs, seed=SEED, choices=False, ref=mellum):
    """Reference logits (and, of ``mellum``, choices) of each distinct
    final sequence."""
    rows = {}
    for seq in {id(s): s for s in seqs}.values():
        row = np.zeros((1, 80), np.int32)
        row[0, : len(seq)] = seq
        rows[id(seq)] = (ref.forward(cfg, seed, row, choices=True) if choices
                         else ref.forward(cfg, seed, row))
    return rows


@pytest.mark.parametrize("arch,dtype", [
    pytest.param("mellum", "float32", id="float32"),
    pytest.param("mellum", "bfloat16", id="bfloat16"),
    pytest.param("qwen3", "float32", id="qwen3-float32"),
    pytest.param("qwen3", "bfloat16", id="qwen3-bfloat16"),
])
def test_served_logits_match_the_reference(arch, dtype):
    """Chunked prefill (16-token chunks, contexts 49-68; past Mellum's
    16-token window) then paged decode, with the coded head: every
    pending logits row the serve program produced is the reference's at
    its position. The bfloat16 run shows the tolerance would catch a
    lower precision."""
    ref, cfg, make, tol = REFS[arch]
    model = make(dtype)
    _, rec, report = serve(model, seeded(model, stacked=ref.STACKED),
                           decode_block=1)
    assert report.admitted == 6 and not report.shed
    rows = reference_rows(cfg(dtype), [s for s, _, _ in rec.records],
                          ref=ref)
    err = max(float(np.max(np.abs(got[:512] - np.asarray(rows[id(seq)][0, p]))))
              for seq, p, got in rec.records)
    # one after each decode step: the dispatch that finishes a prompt
    # samples from the prompt's logits and decodes in the same call
    assert len(rec.records) == 6 * 7
    print(f"{arch} {dtype}: largest logit error {err!r}")
    if dtype == "float32":
        assert err < tol
    else:
        assert err > 10 * tol


def test_expert_counters_equal_a_host_recount():
    model = tiny_model()
    _, rec, report = serve(model, seeded(model))
    seqs = [seq for step in rec.steps for seq, _ in step]
    picked = {k: np.asarray(v[1])[:, 0]
              for k, v in reference_rows(tiny_cfg(), seqs, choices=True).items()}
    pairs = hit = 0
    for step in rec.steps:
        for layer in range(8):
            chosen = [picked[id(seq)][layer, p] for seq, p in step]
            held = np.concatenate(chosen)[np.concatenate(chosen) < HELD]
            pairs += held.size
            hit += np.unique(held).size
    assert report.decode_rounds == len(rec.steps)
    assert (report.held_expert_pairs, report.held_experts_hit) == (pairs, hit)
    assert 0 < hit < pairs


def test_counters_are_zero_without_experts():
    c = ARCHS["qwen3-0.6b"].reduced()
    m = Model(c)
    server = Server(m, m.init_params(jax.random.PRNGKey(0)),
                    ClusterSpec.make([2, 2], [4.0, 0.8]), ServeConfig(block_rows=64))
    rep = server.serve([Request(rid=0, arrival=0.0, prompt=(1, 2, 3), out_len=3,
                                deadline_class="batch")], slots=2)
    assert (rep.held_expert_pairs, rep.held_experts_hit) == (0, 0)


def _expert_params(key, experts, d=64, f=32):
    ks = jax.random.split(key, 4)
    return {
        "w_router": jax.random.normal(ks[0], (d, experts)) / 8,
        "w_gate": jax.random.normal(ks[1], (experts, d, f)) / 8,
        "w_up": jax.random.normal(ks[2], (experts, d, f)) / 8,
        "w_down": jax.random.normal(ks[3], (experts, f, d)) / 6,
    }


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Four chips of 8 experts each: what each computes for its own
    experts, summed, is the reference's layer with all 32 held (the
    attention every chip computes alike is left out of the sum: counted
    once)."""
    whole = _expert_params(jax.random.PRNGKey(1), ROUTED)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 64))
    parts = []
    for chip in range(4):
        mine = slice(HELD * chip, HELD * (chip + 1))
        share = {k: v[mine] for k, v in whole.items() if k != "w_router"}
        # the router's order: this chip's experts first
        share["w_router"] = jnp.roll(whole["w_router"], -HELD * chip, axis=1)
        y, counts = moe_mod.moe_ffn(share, x, top_k=TOP_K)
        parts.append(np.asarray(y))
    dims = mellum.Dims.of({**tiny_cfg(), "num_experts": ROUTED,
                           "deployment": {"num_experts": ROUTED}})
    p = {f"blocks/moe/{k}": v for k, v in whole.items()}
    with jax.default_matmul_precision("highest"):
        want, _ = mellum.experts(x, p, dims, fp8=False)
    np.testing.assert_allclose(sum(parts), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_every_token_routed_to_one_held_expert_is_served():
    """All 64 tokens choose the same four held experts: each expert gets
    64 rows, nothing is dropped, and each token's output is its own
    dense sum."""
    params = _expert_params(jax.random.PRNGKey(3), ROUTED)
    params = {k: v[:HELD] if k != "w_router" else v for k, v in params.items()}
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (4, 16, 64))) + 0.1
    bias = jnp.zeros((64, ROUTED)).at[:, :TOP_K].set(5.0)  # x > 0: 0-3 win
    params["w_router"] = params["w_router"] * 0.01 + bias
    y, counts = moe_mod.moe_ffn(params, x, top_k=TOP_K)
    xf = np.asarray(x, np.float64).reshape(-1, 64)
    logits = xf @ np.asarray(params["w_router"], np.float64)
    top = np.argsort(-logits, axis=1)[:, :TOP_K]
    assert np.all(np.sort(top, axis=1) == np.arange(TOP_K))
    probs = np.exp(logits - logits.max(1, keepdims=True))
    gates = np.take_along_axis(probs, top, 1)
    gates /= gates.sum(1, keepdims=True)
    want = np.zeros_like(xf)
    for e in range(TOP_K):
        wg, wu, wd = (np.asarray(params[k][e], np.float64)
                      for k in ("w_gate", "w_up", "w_down"))
        g = xf @ wg
        h = (g / (1 + np.exp(-g))) * (xf @ wu)
        want += np.take_along_axis(gates, np.argwhere(top == e)[:, 1:], 1) * (h @ wd)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 64), want, rtol=1e-4, atol=1e-5)
    assert counts.tolist() == [64 * TOP_K, TOP_K]


def _one_layer_window_pool(model, params, seq, pos):
    """Prefill ``seq[:pos]`` into a pool, then decode the token at
    ``pos``; returns the decode logits."""
    bl, nb = 8, 12
    cache = model.init_paged_cache(nb, bl)
    table = jnp.asarray(np.arange(nb, dtype=np.int32)[None, :])
    for a in range(0, pos, 16):
        n = min(16, pos - a)
        chunk = np.zeros((1, 16), np.int32)
        chunk[0, :n] = seq[a:a + n]
        _, cache = model.prefill_paged(params, cache, jnp.asarray(chunk),
                                       jnp.asarray([a]), jnp.asarray([n]), table)
    logits, _ = model.decode_step_paged(params, cache, jnp.asarray(seq[pos:pos + 1]),
                                        jnp.asarray([pos]), table,
                                        jnp.asarray([True]))
    return np.asarray(logits[0])


@pytest.mark.parametrize("layer", ["window", "full"])
def test_a_key_just_outside_the_window_changes_nothing(layer):
    """One layer, so a key reaches the last query only through attention:
    the query at 60 attends keys 45-60 through a 16-token window. A key at
    44 changes nothing there and one at 45 does; a full layer sees both."""
    model = tiny_model()
    c = dataclasses.replace(model.config, num_layers=1,
                            full_attn_every=0 if layer == "window" else 1)
    model = Model(c)
    params = seeded(model)
    pos = 60
    seq = np.random.default_rng(0).integers(0, 512, pos + 1).astype(np.int32)
    base = _one_layer_window_pool(model, params, seq, pos)
    moved = {}
    for j in (pos - WINDOW, pos - WINDOW + 1):
        alt = seq.copy()
        alt[j] = (alt[j] + 1) % 512
        moved[j] = float(np.max(np.abs(_one_layer_window_pool(model, params, alt, pos)
                                       - base)))
    assert moved[pos - WINDOW + 1] > 1e-4
    if layer == "window":
        assert moved[pos - WINDOW] == 0.0
    else:
        assert moved[pos - WINDOW] > 1e-4


def yarn_transcription(hd, theta, factor, orig, beta_fast, beta_slow):
    """``transformers``' ``_compute_yarn_parameters``, in float64 numpy."""
    import math

    def find_correction_dim(num_rotations, dim, base, max_pos):
        return (dim * math.log(max_pos / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    low = math.floor(find_correction_dim(beta_fast, hd, theta, orig))
    high = math.ceil(find_correction_dim(beta_slow, hd, theta, orig))
    low, high = max(low, 0), min(high, hd - 1)
    if low == high:
        high += 0.001
    linear = (np.arange(hd // 2, dtype=np.float64) - low) / (high - low)
    extrapolation_factor = 1 - np.clip(linear, 0, 1)
    pos_freqs = theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    return (1.0 / (factor * pos_freqs) * (1 - extrapolation_factor)
            + 1.0 / pos_freqs * extrapolation_factor)


@pytest.mark.parametrize("hd, theta, yarn", [
    (16, 10000.0, Yarn(4.0, 32, 32.0, 1.0, 1.1386294361119891)),
    (128, 500000.0, Yarn(16.0, 8192, 32.0, 1.0, 1.2772588722239782)),
    (64, 10000.0, Yarn(8.0, 4096, 32.0, 1.0, None)),
])
def test_yarn_frequencies_match_a_float64_transcription(hd, theta, yarn):
    want = yarn_transcription(hd, theta, yarn.factor, yarn.original_max_position,
                              yarn.beta_fast, yarn.beta_slow)
    inv, mult = L.yarn_frequencies(hd, theta, yarn)
    np.testing.assert_allclose(inv, want, rtol=1e-12)
    ref_inv, ref_mult = mellum.yarn_inv_freq(hd, theta, (
        *yarn[:4], mult))
    np.testing.assert_allclose(ref_inv, want, rtol=1e-12)
    factor = yarn.attention_factor or 0.1 * np.log(yarn.factor) + 1
    assert mult == pytest.approx(factor, rel=1e-12)
    # cos and sin carry the factor: position 0 scales x by it
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 2, hd))
    got = L.rope(x, jnp.zeros((1, 1), jnp.int32), theta, yarn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x) * factor, rtol=1e-6)


def test_the_coded_head_decodes_from_the_untied_head():
    model = tiny_model()
    params = seeded(model)
    server = Server(model, params, ClusterSpec.make([2, 2], [4.0, 0.8]),
                    ServeConfig(block_rows=64))
    head = server.coded_head
    w = np.asarray(params["lm_head"]["w"], np.float32)
    assert np.array_equal(np.asarray(head.table), w.T)
    assert not np.allclose(np.asarray(head.table), np.asarray(params["embed"]["table"]))
    h = jax.random.normal(jax.random.PRNGKey(6), (3, 64))
    products = np.asarray(head.worker_products(h))
    logits, ok = head.decode_logits(products, np.ones(4, bool))
    assert ok
    np.testing.assert_allclose(logits[:, :512], np.asarray(h, np.float64) @ w,
                               rtol=1e-4, atol=1e-4)
