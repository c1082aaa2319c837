"""Byte and operation counts of the reference's ``Dims`` against
hand-reckoned values."""
import json
import os

import tiny  # noqa: F401  (puts bench/ on the path)

from reference.dense_decoder import Dims

CONFIGS = os.path.join(tiny.BENCH, "configs")


def dims(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return Dims.of(json.load(f))


def test_qwen3_weights_and_kv():
    d = dims("qwen3-0.6b")
    # per layer: wq 1024x2048 + wk, wv 1024x1024 + wo 2048x1024 + 3 x 1024x3072
    per_layer = 1024 * 2048 * 2 + 1024 * 1024 * 2 + 3 * 1024 * 3072
    assert per_layer == 15_728_640
    assert d.matmul_params() == 28 * per_layer + 151_936 * 1024
    # norms: ln1, ln2 (1024 each), q/k norms (128 each) per layer, final 1024
    assert d.params() == 595_984_384 + 28 * (2048 + 256) + 1024
    assert d.weight_bytes() == 1_192_099_840  # 1.19 GB at bf16
    assert d.kv_bytes_per_token() == 112 * 1024  # 28 x 2 x 8 x 128 x 2 B


def test_lmsys_chat_pool_and_context_bytes():
    d = dims("qwen3-0.6b")
    # 308 blocks of 16 tokens at 112 KiB a token: the pool the mix allocates
    assert 308 * 16 * d.kv_bytes_per_token() == 565_182_464
    # a decode step over four slots at contexts 1,220, 600, 300 and 50
    assert d.decode_step_bytes([1220, 600, 300, 50]) == (
        1_192_099_840 + 2170 * 114_688)


def test_token_flops_and_step_bytes():
    d = dims("qwen3-0.6b")
    # 2 x matmul params + 4 x context x 16 heads x 128 x 28 layers
    assert d.token_flops(100) == 2 * 595_984_384 + 4 * 100 * 16 * 128 * 28
    assert d.decode_step_bytes([10, 20]) == 1_192_099_840 + 30 * 114_688
