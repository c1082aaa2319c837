"""The harness learns an architecture only from the reference module that
the configuration names (``cfg["system"]["reference"]``): its ``Dims``
(sizes, the check of the program's registry entry, byte and operation
counts), its ``STACKED`` prefixes and its ``forward``."""
import dataclasses
import os
import sys
import types

import pytest

import tiny

import peaks
import run as R
import weights
from record import Dispatch
from reference import dense_decoder
from xplane import Event, Trace

#: what the harness may ask of a reference's ``Dims`` besides ``of``
COUNTED = ("program_sizes", "constants_off", "weight_bytes",
           "decode_step_bytes", "token_flops")


def make_probe(calls: list):
    """A reference module that is the dense decoder, and records in
    ``calls`` each thing the harness asks of it."""

    def recorded(name):
        def method(self, *a, **kw):
            calls.append(name)
            return getattr(dense_decoder.Dims, name)(self, *a, **kw)
        return method

    class Dims(dense_decoder.Dims):
        @classmethod
        def of(cls, cfg):
            calls.append("of")
            return super().of(cfg)

    for name in COUNTED:
        setattr(Dims, name, recorded(name))

    def forward(cfg, seed, tokens, *, precision="float32"):
        calls.append("forward")
        return dense_decoder.forward(cfg, seed, tokens, precision=precision)

    mod = types.ModuleType("reference.probe")
    mod.Dims, mod.forward = Dims, forward
    mod.STACKED = tuple(dense_decoder.STACKED)  # its own object: seen by identity
    return mod


@pytest.fixture
def probe(monkeypatch):
    calls, stacked, dims = [], [], []
    mod = make_probe(calls)
    monkeypatch.setitem(sys.modules, "reference.probe", mod)
    fill, traced_run = weights.program_params, R.TracedRun

    def program_params(shapes, seed, prefixes=("blocks/",)):
        stacked.append(prefixes)
        return fill(shapes, seed, prefixes)

    def record_dims(**kw):
        dims.append(kw["dims"])
        return traced_run(**kw)

    monkeypatch.setattr(weights, "program_params", program_params)
    monkeypatch.setattr(R, "TracedRun", record_dims)
    # the CPU has no published peaks
    monkeypatch.setattr(peaks, "peaks", lambda kind: peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(R, "TRACE_SECONDS", 0.05)
    cfg = {**tiny.CFG, "system": {**tiny.CFG["system"], "reference": "probe"}}
    return types.SimpleNamespace(mod=mod, calls=calls, stacked=stacked,
                                 dims=dims, cfg=cfg)


@pytest.mark.parametrize("trace", [0, 1])
def test_a_run_asks_the_reference_the_file_names(probe, trace):
    out = tiny.run(2 ** 31 + 71, trace=trace, seconds=0.1, cfg=probe.cfg)
    assert out["correct"], out["checks"]
    assert [s is probe.mod.STACKED for s in probe.stacked] == [True]
    asked = set(probe.calls)
    assert {"of", "program_sizes", "constants_off", "forward"} <= asked
    if trace:
        # the weight log line, and step_mfu over the traced dispatches
        assert {"weight_bytes", "token_flops"} <= asked
        assert [type(d) for d in probe.dims] == [probe.mod.Dims]
        assert out["metrics"]["step_mfu"]["value"] > 0


def test_step_readers_count_through_the_reference(probe):
    dims = probe.mod.Dims.of(probe.cfg)
    mods = [Event("jit__serve_step_paged_program", 1_000, 2_001_000)]
    run = types.SimpleNamespace(
        trace=Trace([[Event("%fusion.1", 1_000, 2_000_000)]], [mods],
                    [Event("replay", 0, 4_000_000)]),
        dispatches=[Dispatch(False, 2, [], [[3, 4], [4, 5]])],
        program="_serve_step_paged_program", dims=dims,
        peaks=peaks.PEAKS["TPU v5 lite"])
    probe.calls.clear()
    hbm = R._load_metric("step_hbm_share").read(run)
    mfu = R._load_metric("step_mfu").read(run)
    assert set(probe.calls) == {"decode_step_bytes", "weight_bytes", "token_flops"}
    step_bytes = 2 * dims.weight_bytes() + 16 * dims.kv_bytes_per_token()
    assert hbm == pytest.approx(100 * step_bytes / 2e-3 / 819e9)
    flops = sum(dims.token_flops(c) for c in (3, 4, 4, 5))
    assert mfu == pytest.approx(100 * flops / 4e-3 / 197e12)


@pytest.mark.parametrize("change, off", [
    ({"cfg": {"rms_norm_eps": 1e-5}}, "eps/attention_multiplier"),
    ({"cfg": {"embedding_multiplier": 12.0}}, "multipliers"),
    ({"cfg": {"intermediate_size": 512}}, "d_ff"),
    ({"base": {"family": "moe"}}, "family"),
    ({"base": {"sliding_window": 64}}, "sliding_window"),
])
def test_program_config_refuses_a_file_the_program_would_not_run(change, off):
    cfg = {**tiny.CFG, **change.get("cfg", {})}
    base = dataclasses.replace(tiny.model_config(), **change.get("base", {}))
    with pytest.raises(SystemExit, match=off):
        R.program_config(cfg, base)


def test_program_config_takes_the_file_it_matches():
    mc = R.program_config(tiny.CFG, tiny.model_config())
    assert (mc.d_model, mc.param_dtype) == (128, "bfloat16")


def test_no_harness_file_names_a_reference():
    """Outside ``reference/`` and ``tests/``, no file of the harness knows
    which reference a configuration names."""
    named = []
    for top, dirs, files in os.walk(tiny.BENCH):
        dirs[:] = [d for d in dirs if d not in ("reference", "tests", "__pycache__")]
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(top, f)) as fh:
                    if "dense_decoder" in fh.read():
                        named.append(f)
    assert named == []
