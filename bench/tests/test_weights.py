"""The program's weights and the reference's are the same bits."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny

import weights
from reference.dense_decoder import STACKED, Dims, _pad_vocab


def test_program_params_match_leaves_drawn_one_layer_at_a_time():
    from repro.models.model import Model

    model = Model(tiny.model_config())
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), shapes)
    seed = 2 ** 32 + 17
    params = weights.program_params(shapes, seed, STACKED)
    key = weights.seed_key(seed)
    d = Dims.of(tiny.CFG)
    for name, shape in d.layer_shapes().items():
        parts = name.split("/")
        leaf = params
        for p in parts:
            leaf = leaf[p]
        for layer in range(d.layers):
            want = weights.leaf(key, name, shape, np.uint32(layer)).astype(jnp.bfloat16)
            assert np.array_equal(np.asarray(leaf[layer]), np.asarray(want)), (name, layer)
    table = weights.leaf(key, "embed/table", (_pad_vocab(d.vocab), d.d))
    assert np.array_equal(np.asarray(params["embed"]["table"]),
                          np.asarray(table.astype(jnp.bfloat16)))


def test_seeds_differ_and_scales_are_powers_of_two():
    a = weights.leaf(weights.seed_key(1), "blocks/attn/wq", (64, 8), 0)
    b = weights.leaf(weights.seed_key(2), "blocks/attn/wq", (64, 8), 0)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert weights.half_width("blocks/mlp/w_down", (3072, 1024)) == 2.0 ** -5
    assert weights.half_width("blocks/attn/wq", (1024, 2048)) == 2.0 ** -4
    norm = np.asarray(weights.leaf(weights.seed_key(1), "final_norm/scale", (512,)))
    assert np.all(np.abs(norm - 1) <= 0.125)


def _half_width_before(path, shape):
    """``half_width`` as it was when every weight leaf was 2-D per layer:
    the fan-in is the first axis."""
    if path == "embed/table":
        return weights.EMBED_HALF_WIDTH
    return 2.0 ** round(math.log2(math.sqrt(3.0 / shape[0])))


def _program_params_before(shapes, seed):
    """``program_params`` as it was then: ``blocks/`` alone is stacked."""

    def draw(key, name, shape, layer=None):
        k = jax.random.fold_in(key, weights._path_id(name))
        if layer is not None:
            k = jax.random.fold_in(k, layer)
        bits = jax.random.bits(k, tuple(shape), jnp.uint32)
        u = ((bits >> 16).astype(jnp.int32) - 32768).astype(jnp.float32) * 2.0 ** -15
        if name.rsplit("/", 1)[-1] in weights.NORM_LEAVES:
            return 1.0 + u * 0.125
        return u * _half_width_before(name, shape)

    def fill(key):
        def one(path, sds):
            name = weights._path_str(path)
            if name.startswith("blocks/"):
                layers = jnp.arange(sds.shape[0], dtype=jnp.uint32)
                vals = jax.vmap(lambda i: draw(key, name, sds.shape[1:], i))(layers)
            else:
                vals = draw(key, name, sds.shape)
            return vals.astype(sds.dtype)

        return jax.tree_util.tree_map_with_path(one, shapes)

    return jax.jit(fill)(weights.seed_key(seed))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_tiny_tree_draws_the_bits_it_drew_before(dtype):
    from repro.models.model import Model

    shapes = jax.eval_shape(Model(tiny.model_config()).init_params,
                            jax.random.PRNGKey(0))
    shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, dtype), shapes)
    seed = 2 ** 33 + 5
    now = weights.program_params(shapes, seed, STACKED)
    before = _program_params_before(shapes, seed)
    flat, _ = jax.tree_util.tree_flatten_with_path(now)
    for path, leaf in flat:
        old = before
        for p in path:
            old = old[p.key]
        assert np.array_equal(np.asarray(leaf), np.asarray(old)), weights._path_str(path)


def test_an_expert_stack_draws_at_its_contracting_width():
    # a layer's (experts, d_in, d_out) leaf: sqrt(3 / 128) = 0.153 -> 2**-3,
    # not the 2**-1 of fan-in 8
    assert weights.half_width("blocks/moe/w_gate", (8, 128, 256)) == 2.0 ** -3
    vals = np.asarray(weights.leaf(weights.seed_key(3), "blocks/moe/w_gate",
                                   (8, 128, 256), 0))
    assert 2.0 ** -4 < np.max(np.abs(vals)) <= 2.0 ** -3


def test_a_second_stacked_prefix_is_drawn_one_layer_at_a_time():
    sds = jax.ShapeDtypeStruct
    shapes = {"dense_blocks": {"mlp": {"w_up": sds((2, 64, 96), jnp.bfloat16)},
                               "ln1": {"scale": sds((2, 64), jnp.bfloat16)}},
              "blocks": {"moe": {"w_gate": sds((3, 8, 64, 32), jnp.bfloat16)}},
              "final_norm": {"scale": sds((64,), jnp.bfloat16)}}
    seed = 2 ** 31 + 11
    params = weights.program_params(shapes, seed, ("blocks/", "dense_blocks/"))
    key = weights.seed_key(seed)
    for name, leaf in [("dense_blocks/mlp/w_up", params["dense_blocks"]["mlp"]["w_up"]),
                       ("dense_blocks/ln1/scale", params["dense_blocks"]["ln1"]["scale"]),
                       ("blocks/moe/w_gate", params["blocks"]["moe"]["w_gate"])]:
        for layer in range(leaf.shape[0]):
            want = weights.leaf(key, name, leaf.shape[1:], np.uint32(layer))
            assert np.array_equal(np.asarray(leaf[layer]),
                                  np.asarray(want.astype(jnp.bfloat16))), (name, layer)
    # drawn as one leaf, the same shape gives other bits
    whole = weights.leaf(key, "dense_blocks/mlp/w_up", (2, 64, 96))
    assert not np.array_equal(np.asarray(whole.astype(jnp.bfloat16)),
                              np.asarray(params["dense_blocks"]["mlp"]["w_up"]))
