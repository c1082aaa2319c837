"""The harness's check catches a broken timed path.

Each case drives the rest of a run at a small size on the CPU with one
fault planted in the program underneath, and sees ``correct`` come out
false with the served-token gap over the cell's limit. The one-chip serve
path has no exchange between chips, so that fault has no case here.
"""
import jax.numpy as jnp
import pytest

import tiny

from repro.models.model import Model
from repro.runtime.serve_loop import Server

LIMIT = tiny.LIMIT
SEED = 2 ** 31 + 11


def token_altered(monkeypatch):
    """The coded head hands the sampler logits shifted by one token."""
    select = Server._coded_select

    def shifted(self, *a, **kw):
        sel, ok = select(self, *a, **kw)
        return jnp.roll(sel, 1, axis=-1), ok

    monkeypatch.setattr(Server, "_coded_select", shifted)


def state_unchanged(monkeypatch):
    """A decode step returns the KV pool it was given."""
    step = Model.decode_step_paged

    def stale(self, params, cache, *a, **kw):
        logits, _ = step(self, params, cache, *a, **kw)
        return logits, cache

    monkeypatch.setattr(Model, "decode_step_paged", stale)


def half_batch_left_out(monkeypatch):
    """A decode step computes only the first half of the slots."""
    step = Model.decode_step_paged

    def half(self, *a, **kw):
        logits, cache = step(self, *a, **kw)
        keep = jnp.arange(logits.shape[0]) < logits.shape[0] // 2
        return jnp.where(keep[:, None], logits, 0.0), cache

    monkeypatch.setattr(Model, "decode_step_paged", half)


def test_sound_run_is_correct():
    out = tiny.run(SEED, limit=LIMIT)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0


@pytest.mark.parametrize("fault", [token_altered, state_unchanged,
                                   half_batch_left_out])
def test_fault_makes_the_run_incorrect(monkeypatch, fault):
    fault(monkeypatch)
    out = tiny.run(SEED, limit=LIMIT)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > LIMIT
