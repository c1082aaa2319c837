"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phases pass at reduced size (the rehearsal of the chip run)."""
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs import get_arch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _has_ok_line(text: str) -> bool:
    return '"ok"' in text


def test_main_refuses_on_cpu(smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    rc = smoke.main([])
    out = capsys.readouterr()
    assert rc != 0
    assert not _has_ok_line(out.out)
    assert "needs a TPU" in out.err


def test_script_alone_fails_without_ok_line(tmp_path):
    """Copied into a directory holding nothing else of the repo, the
    script exits non-zero and prints no result line."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, capture_output=True,
        text=True, timeout=300,
        env={"PATH": os.environ.get("PATH", ""), "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode != 0
    assert not _has_ok_line(r.stdout)


def test_compare_logits_flags_errors_and_token_flips(smoke):
    rng = np.random.default_rng(0)
    plain = rng.standard_normal((3, 40)).astype(np.float32)
    vocab = 32
    good = smoke.compare_logits(plain + 1e-6, plain, vocab)
    assert good["ok"] and good["greedy_checked"] == good["greedy_agree"]
    big = smoke.compare_logits(plain + 0.1, plain, vocab)
    assert not big["ok"]
    # a flipped argmax inside the error bound still fails the greedy check
    flipped = plain.copy()
    top = np.argmax(plain[:, :vocab], -1)
    flipped[0, top[0]] -= 10.0
    assert not smoke.compare_logits(flipped, plain, vocab)["ok"]
    # padded vocab columns are ignored
    padded = plain.copy()
    padded[:, vocab:] = 1e30
    assert smoke.compare_logits(padded, plain, vocab)["ok"]


def test_serve_phase_reduced(smoke):
    """The one-chip phase end to end at reduced widths, kernels
    interpreted: serve, coded-vs-plain logits, kernel parity."""
    logs = []
    with pltpu.force_tpu_interpret_mode():
        out = smoke.serve_phase(
            get_arch("qwen3-0.6b").reduced(), erased=(0,), log=logs.append
        )
    assert out["done"] + out["shed"] == 8 and out["tokens"] > 0
    assert out["fallback_rounds"] == 0
    assert out["coded_logits"]["erased"]["ok"]
    assert set(out["kernels"]) >= {"worker_products", "paged_decode"}
    assert any(line.startswith("set-up s:") for line in logs)


FOUR_DEVICE_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.getcwd())
import jax
import chip_smoke
out = chip_smoke.four_chip_phase(jax.devices(), rows=64 * 256, d=128)
assert out["erased"]["err_mesh_one"] <= out["bound"]
print("FOUR_OK")
"""


def test_four_chip_phase_on_virtual_devices():
    """The --four-chips phase on 4 virtual CPU devices (own process so the
    device-count flag never leaks into this session)."""
    r = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICE_SCRIPT], cwd=ROOT,
        capture_output=True, text=True, timeout=600,
        env={"PATH": os.environ.get("PATH", ""), "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": "src"},
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "FOUR_OK" in r.stdout
    assert "over 4 devices" in r.stdout
