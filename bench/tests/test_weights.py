"""The program's weights and the reference's are the same bits."""
import jax
import jax.numpy as jnp
import numpy as np

import tiny

import weights
from reference.dense_decoder import Dims, _pad_vocab


def test_program_params_match_leaves_drawn_one_layer_at_a_time():
    from repro.models.model import Model

    model = Model(tiny.model_config())
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), shapes)
    seed = 2 ** 32 + 17
    params = weights.program_params(shapes, seed)
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
