"""The Mellum2 reference (``bench/reference/mellum.py``) under the
reference contract: its ``Dims`` of the cell's file, the files it
refuses, its ``STACKED`` prefixes, a tiny run of the harness through it,
and the readers of the routed-expert and window-attention metrics on
hand-made events."""
import copy
import dataclasses
import json
import os
import types

import pytest

import tiny

import run as R
import weights
from record import Dispatch
from reference import mellum
from xplane import Event, Trace

with open(os.path.join(tiny.BENCH, "configs", "mellum2-12b-a2.5b.json")) as f:
    CFG = json.load(f)

#: two periods of three window layers and a full one, 8 of 32 experts
TINY = {
    **{k: CFG[k] for k in ("architectures", "rms_norm_eps", "hidden_act",
                           "attention_bias", "norm_topk_prob", "torch_dtype",
                           "use_qk_norm", "tie_word_embeddings")},
    "hidden_size": 64, "moe_intermediate_size": 32, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "layer_types": CFG["layer_types"][:8],
    "mlp_layer_types": ["sparse"] * 8, "sliding_window": 16,
    "rope_parameters": CFG["rope_parameters"], "num_experts": 8,
    "num_experts_per_tok": 4, "deployment": {"num_experts": 32},
    "system": {**CFG["system"], "block_rows": 16},
}
#: the widest served-token gap at this size: sound runs read 0 to 0.125
#: (seeds 2**31 + 5, 2**31 + 11, 3, 2**33 + 1; bfloat16 routing flips at
#: near-ties of 32 experts over a 64-wide hidden state) and the float8
#: control 0.31 to 0.40 (seeds 2**31 + 5, 2**31 + 11, 2**33 + 1)
LIMIT = 0.2


def tiny_model_config():
    from repro.configs import get_arch
    from repro.configs.base import Yarn

    y = CFG["rope_parameters"]["full_attention"]
    return dataclasses.replace(
        get_arch("mellum2-12b-a2.5b"), name="tiny-mellum", num_layers=8,
        d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=32,
        vocab_size=512, sliding_window=16, num_experts=8, routed_experts=32,
        top_k=4, attn_q_block=16, attn_kv_block=16,
        yarn=Yarn(float(y["factor"]), y["original_max_position_embeddings"],
                  float(y["beta_fast"]), float(y["beta_slow"]), y["attention_factor"]))


def test_dims_of_the_cell_file():
    d = mellum.Dims.of(CFG)
    assert (d.held, d.routed, d.top_k, d.window, d.period) == (16, 64, 8, 1024, 4)
    assert d.params() == 3_826_326_784
    assert d.weight_bytes() == 2 * d.params()
    assert d.expert_bytes() == 3 * 2304 * 896 * 2
    assert d.layers * d.held * d.expert_bytes() == 5_549_064_192
    # 56 KiB a token; 21 of 28 layers see at most 1,024 positions
    assert d.kv_bytes(1) == 56 * 1024
    assert d.kv_bytes(2000) == (7 * 2000 + 21 * 1024) * 2 * 4 * 128 * 2
    every = d.decode_step_bytes([10, 20])
    none = d.decode_step_bytes([10, 20], experts_hit=0)
    assert every - none == d.layers * d.held * d.expert_bytes()
    assert none == (d.weight_bytes() - 98304 * 2304 * 2 - 5_549_064_192
                    + d.kv_bytes(10) + d.kv_bytes(20))
    assert d.token_flops(5000) - d.token_flops(4000) == 4 * 1000 * 7 * 32 * 128
    assert d.expert_flops(3) == 6 * 3 * 2304 * 896
    assert mellum.STACKED == ("blocks/",)


def test_the_registry_entry_runs_the_file():
    mc = R.program_config(CFG)
    assert (mc.num_experts, mc.router_width, mc.param_dtype) == (16, 64, "bfloat16")


def _changed(path, value):
    cfg = copy.deepcopy(CFG)
    *head, last = path
    node = cfg
    for k in head:
        node = node[k]
    node[last] = value
    return cfg


@pytest.mark.parametrize("path, value, match", [
    (("architectures",), ["Qwen3MoeForCausalLM"], "reference knows"),
    (("tie_word_embeddings",), True, "untied"),
    (("norm_topk_prob",), False, "renormalised"),
    (("mlp_layer_types", 0), "dense", "every layer"),
    (("layer_types", 2), "full_attention", "not a repeat"),
    (("rope_parameters", "full_attention", "rope_type"), "default", "YaRN"),
    (("num_experts",), 65, "held experts"),
    (("shared_expert_intermediate_size",), 896, "shared expert"),
])
def test_dims_refuse_a_file_they_do_not_cover(path, value, match):
    with pytest.raises(ValueError, match=match):
        mellum.Dims.of(_changed(path, value))


@pytest.mark.parametrize("path, value, off", [
    (("rms_norm_eps",), 1e-5, "rms_norm_eps"),
    (("rope_parameters", "sliding_attention", "rope_theta"), 10000, "rope_theta"),
    (("moe_intermediate_size",), 768, "d_ff"),
    (("sliding_window",), 4096, "sliding_window"),
    (("use_qk_norm",), False, "qk_norm"),
    (("rope_parameters", "full_attention", "factor"), 8, "yarn"),
])
def test_program_config_refuses_a_file_the_program_would_not_run(path, value, off):
    with pytest.raises(SystemExit, match=off):
        R.program_config(_changed(path, value))


def test_stacked_leaves_draw_experts_at_their_fan_in():
    import jax

    from repro.models.model import Model

    model = Model(dataclasses.replace(tiny_model_config(), param_dtype="float32"))
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params = weights.program_params(shapes, 7, mellum.STACKED)
    moe = params["blocks"]["moe"]
    assert moe["w_gate"].shape == (8, 8, 64, 32) and moe["w_router"].shape == (8, 64, 32)
    # an expert stack draws at its contracting axis, as the reference does
    assert weights.half_width("blocks/moe/w_gate", (8, 64, 32)) == 2.0 ** -2
    assert weights.half_width("lm_head/w", (2304, 98304)) == 2.0 ** -5
    assert params["lm_head"]["w"].shape == (64, 512)
    layer = weights.leaf(weights.seed_key(7), "blocks/moe/w_up", (8, 64, 32), 3)
    assert bool((moe["w_up"][3] == layer).all())


@pytest.fixture
def mellum_tiny(monkeypatch):
    monkeypatch.setattr(tiny, "model_config", tiny_model_config)


@pytest.mark.parametrize("seed", [2 ** 31 + 5, 3])
def test_a_tiny_run_through_the_reference_is_correct(mellum_tiny, seed):
    out = tiny.run(seed, limit=LIMIT, cfg=TINY)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0


def test_the_float8_control_is_refused(mellum_tiny, monkeypatch):
    import correct

    gaps = correct.gaps
    monkeypatch.setattr(correct, "gaps", lambda *a, **kw: gaps(*a, **kw, control=True))
    out = tiny.run(2 ** 31 + 5, limit=LIMIT, cfg=TINY)
    assert not out["correct"]


# ------------------------------------------------- readers, hand-made events
PROGRAM = "jit__serve_step_paged_program"
SCOPES = {2: {"%fusion.1": "model/moe", "%fusion.2": "model/window_attention",
              "%fusion.3": "model/attention", "%copy.4": "model/layers"},
          0: {"%fusion.1": "prefill"}}


def op(name, start, end):
    return Event(f"{name} = f32[4]{{0}} fusion(%p)", start, end)


def make(scopes=SCOPES, hit=6):
    mods = [Event(PROGRAM, 100, 200), Event(PROGRAM, 300, 400),
            Event(PROGRAM, 500, 600)]
    ops = [op("%fusion.1", 100, 130), op("%fusion.2", 130, 150),
           op("%fusion.3", 150, 170), op("%copy.4", 170, 180),
           op("%fusion.1", 300, 390),
           op("%fusion.1", 500, 550), op("%fusion.2", 550, 560)]
    report = types.SimpleNamespace(scopes=scopes, decode_rounds=3,
                                   held_experts_hit=hit)
    return types.SimpleNamespace(
        trace=Trace([ops], [mods], [Event("replay", 50, 700)]), reports=[report],
        dispatches=[Dispatch(False, 2, [], [[3], [4]]), Dispatch(True, 0, [1, 2], []),
                    Dispatch(False, 2, [], [[5], [6]])],
        program="_serve_step_paged_program", spans=[],
        dims=mellum.Dims.of(CFG), peaks={"hbm_bytes_per_s": 819e9})


def read(metric, run):
    return R._load_metric(metric).read(run)


def test_expert_and_window_readers():
    run = make()
    assert read("moe_ms", run) == pytest.approx(80e-6 / 4)
    assert read("window_attention_ms", run) == pytest.approx(30e-6 / 4)
    d = run.dims
    per_step = d.router_bytes() + 6 / 3 * d.expert_bytes()
    assert read("moe_hbm_share", run) == pytest.approx(
        100 * per_step / (80e-6 / 4 * 1e-3) / 819e9)


@pytest.mark.parametrize("broken", ["no_scope", "no_maps", "no_counter"])
def test_expert_readers_give_nothing_they_cannot_read(broken):
    if broken == "no_scope":  # a program with dense blocks only
        run = make({k: {i: ("model/mlp" if s == "model/moe" else s)
                        for i, s in m.items()} for k, m in SCOPES.items()})
        assert read("moe_ms", run) is None and read("moe_hbm_share", run) is None
        assert read("window_attention_ms", run) is not None
        run = make({k: {i: ("model/attention" if s == "model/window_attention" else s)
                        for i, s in m.items()} for k, m in SCOPES.items()})
        assert read("window_attention_ms", run) is None
        return
    if broken == "no_maps":
        run = make()
        run.reports = [types.SimpleNamespace(decode_rounds=3)]
    else:  # a program that counts no experts
        run = make()
        run.reports = [types.SimpleNamespace(scopes=SCOPES, decode_rounds=3)]
    for metric in ("moe_ms", "window_attention_ms", "moe_hbm_share"):
        if broken == "no_counter" and metric != "moe_hbm_share":
            continue
        assert read(metric, run) is None
