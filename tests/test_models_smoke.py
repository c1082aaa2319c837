"""Per-arch smoke tests: reduced config, one forward/train step on CPU,
shape + finiteness assertions, and prefill<->decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, shapes_for
from repro.data.pipeline import make_extras
from repro.models.model import Model, padded_vocab

KEY = jax.random.PRNGKey(0)
B, S = 2, 32


def _batch(c):
    toks = jax.random.randint(KEY, (B, S), 0, c.vocab_size).astype(jnp.int32)
    labels = jax.random.randint(
        jax.random.fold_in(KEY, 1), (B, S), 0, c.vocab_size
    ).astype(jnp.int32)
    batch = {"tokens": toks, "labels": labels}
    extras = make_extras(c, B)
    if extras:
        batch["extras"] = extras
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_forward_and_loss(arch):
    c = ARCHS[arch].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    batch = _batch(c)
    logits = m.lm_logits(params, batch["tokens"], batch.get("extras"))
    assert logits.shape == (B, S, padded_vocab(c.vocab_size))
    assert bool(jnp.all(jnp.isfinite(logits)))
    loss, metrics = jax.jit(m.loss_fn)(params, batch)
    assert bool(jnp.isfinite(loss)) and float(loss) > 0
    assert 0.0 <= float(metrics["accuracy"]) <= 1.0


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_one_train_step_changes_params_no_nan(arch):
    from repro.optim import AdamWConfig, adamw_init
    from repro.runtime.train_loop import make_train_step

    c = ARCHS[arch].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10)
    opt = adamw_init(opt_cfg, params)
    step = make_train_step(m, opt_cfg, donate=False)
    new_params, _, metrics = step(params, opt, _batch(c))
    assert bool(jnp.isfinite(metrics["loss"]))
    leaves_old = jax.tree.leaves(params)
    leaves_new = jax.tree.leaves(new_params)
    assert any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(leaves_old, leaves_new)
    )
    assert all(bool(jnp.all(jnp.isfinite(l))) for l in leaves_new)


@pytest.mark.parametrize(
    "arch",
    ["granite-3-2b", "moonshot-v1-16b-a3b", "zamba2-1.2b", "xlstm-125m",
     "whisper-tiny", "h2o-danube-3-4b", "mellum2-12b-a2.5b"],
)
def test_decode_matches_prefill(arch):
    """Teacher-forced prefill logits == step-by-step decode logits.

    The expert layer drops no token, so a token's output does not depend
    on how many others are routed with it (B*S in prefill, B in decode).
    """
    c = ARCHS[arch].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    toks = jax.random.randint(KEY, (B, S), 0, c.vocab_size).astype(jnp.int32)
    extras = make_extras(c, B)
    full = m.lm_logits(params, toks, extras)

    cache_extras = None
    if c.family == "audio":
        cache_extras = {"enc_out": m.encode(params, extras["frames"])}
    cache = m.init_cache(B, S, cache_extras)
    step = jax.jit(m.decode_step)
    outs = []
    for pos in range(S):
        logits, cache = step(params, cache, toks[:, pos], jnp.int32(pos))
        outs.append(logits)
    stepped = jnp.stack(outs, axis=1)  # (B, S, V)
    np.testing.assert_allclose(
        np.asarray(stepped, np.float32), np.asarray(full, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_shape_cells_count():
    """44-cell grid: 11 archs x 4 shapes minus documented long_500k skips
    (a model with full-attention layers among its window layers has no
    O(window) state, so mellum2 is skipped too)."""
    cells = [(c.name, s.name) for c in ARCHS.values() for s in shapes_for(c)]
    long_archs = {a for a, s in cells if s == "long_500k"}
    assert long_archs == {"zamba2-1.2b", "xlstm-125m", "h2o-danube-3-4b"}
    assert len(cells) == 11 * 3 + 3


def test_vlm_image_prefix_changes_logits():
    c = ARCHS["paligemma-3b"].reduced()
    m = Model(c)
    params = m.init_params(KEY)
    toks = jnp.zeros((1, 8), jnp.int32)
    e0 = {"image_embeds": jnp.zeros((1, c.num_image_tokens, c.d_model))}
    e1 = {"image_embeds": jnp.ones((1, c.num_image_tokens, c.d_model))}
    l0 = m.lm_logits(params, toks, e0)
    l1 = m.lm_logits(params, toks, e1)
    assert not np.allclose(np.asarray(l0), np.asarray(l1))


def test_sliding_window_attention_ignores_far_past():
    """Tokens beyond the window do not affect the current logits.

    Single layer only: with L layers the receptive field is L x window,
    so depth legitimately carries far-past information forward.
    """
    import dataclasses

    c = dataclasses.replace(
        ARCHS["h2o-danube-3-4b"].reduced(), num_layers=1
    )  # window = 64
    assert c.sliding_window == 64
    m = Model(c)
    params = m.init_params(KEY)
    s = 96
    t1 = jax.random.randint(KEY, (1, s), 0, c.vocab_size).astype(jnp.int32)
    t2 = t1.at[:, :16].set((t1[:, :16] + 7) % c.vocab_size)  # differ only <16
    l1 = m.lm_logits(params, t1)
    l2 = m.lm_logits(params, t2)
    # last position attends [s-window, s) = [32, 96): unaffected by 0..16
    np.testing.assert_allclose(
        np.asarray(l1[:, -1], np.float32), np.asarray(l2[:, -1], np.float32),
        rtol=1e-4, atol=1e-4,
    )
