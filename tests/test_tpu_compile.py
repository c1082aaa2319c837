"""The serve path's kernels and coded decode compile for a TPU v5e.

Compiled against a DESCRIBED v5e topology (no chip attached): the TPU
compiler refuses what interpret mode accepts — misaligned block layouts,
VMEM overruns, unsupported ops. Nothing here runs; the chip run is
``chip_smoke.py``. The topology is described inside a module fixture,
never at import, so every test worker collects the same tests and only
the worker given this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core.coding import decode_systematic_jit
from repro.core.runtime_model import ClusterSpec
from repro.kernels.coded_matvec.ops import blocked_matvec
from repro.kernels.paged_attention.kernel import paged_decode_kernel
from repro.models.model import padded_vocab
from repro.runtime.executor import CodedRoundExecutor

BLOCK_ROWS = 256  # ServeConfig.block_rows


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_config():
    """Compile as the program runs on the chip: 32-bit types (the test
    suite turns x64 on; the launchers leave it off), and no persistent
    cache, since a described-TPU compile cannot be read back without a
    chip."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def head_shape():
    """(nb, kb, max_load) of the coded head: qwen3-0.6b's vocab on the
    6:2.0,6:0.5 fleet (the serve CLI's default)."""
    kb = padded_vocab(get_arch("qwen3-0.6b").vocab_size) // BLOCK_ROWS
    ex = CodedRoundExecutor(ClusterSpec.parse("6:2.0,6:0.5"), kb, "optimal")
    return ex.plan.n, kb, ex.plan.max_load


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_paged_decode_kernel_compiles_at_qwen3_widths(one_chip, chip_config):
    c = get_arch("qwen3-0.6b")
    s, bl, mb = 4, 16, 64
    kv, hd = c.num_kv_heads, c.resolved_head_dim
    g = c.num_heads // kv
    compiled = paged_decode_kernel.lower(
        _sds((s, kv, g, hd), jnp.bfloat16, one_chip),
        _sds((s * mb + 1, bl, kv, hd), jnp.bfloat16, one_chip),
        _sds((s * mb + 1, bl, kv, hd), jnp.bfloat16, one_chip),
        _sds((s, mb), jnp.int32, one_chip),
        _sds((s,), jnp.int32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("route", ["encode_logits", "worker_products",
                                   "decode_pipeline_shard"])
def test_coded_matvec_kernel_compiles_at_head_shapes(route, one_chip,
                                                     chip_config,
                                                     head_shape):
    """The shapes ``CodedLMHead.encode_logits`` / ``worker_products`` and
    one chip's shard of ``DecodePipeline`` (3 of 12 workers) give it."""
    nb, kb, max_load = head_shape
    d, batch = get_arch("qwen3-0.6b").d_model, 4
    a_shape, x_shape = {
        "encode_logits": ((nb, kb), (batch * BLOCK_ROWS, kb)),
        "worker_products": ((nb * BLOCK_ROWS, d), (batch, d)),
        "decode_pipeline_shard": ((3 * max_load * BLOCK_ROWS, d), (d,)),
    }[route]
    compiled = blocked_matvec.lower(
        _sds(a_shape, jnp.float32, one_chip),
        _sds(x_shape, jnp.float32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_systematic_jit_compiles_at_head_shape(one_chip,
                                                      chip_config,
                                                      head_shape):
    nb, kb, _ = head_shape
    assert kb == 594
    compiled = decode_systematic_jit.lower(
        _sds((nb, kb), jnp.float32, one_chip),
        _sds((nb, 4 * BLOCK_ROWS), jnp.float32, one_chip),
        _sds((nb,), np.bool_, one_chip),
    ).compile()
    assert compiled.as_text()
