"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and always holding
the longest, is run through the plain reference once: each prompt with
its served tokens. For each served token, the gap is how far its
reference logit lies below the reference's best at that position. The
number compared is the widest gap over the sample; its limit is the
cell's ``logit_gap`` in ``bench/checks/<cell>.json``.

The control reads the same prompts and tokens through the reference in
float8 (``precision="fp8"``): at each position the token that the control
puts first, and that token's gap under the float32 reference.
"""
from __future__ import annotations

import importlib

import numpy as np

#: requests compared per run, the longest among them
SAMPLE = 8


def sample(served: list, seed: int, k: int = SAMPLE) -> list:
    """``k`` served requests drawn from the seed, the longest first."""
    if not served:
        return []
    longest = max(range(len(served)), key=lambda i: len(served[i].tokens))
    rest = [i for i in range(len(served)) if i != longest]
    rng = np.random.default_rng([seed, 0xC0DE])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [served[longest]] + [served[rest[i]] for i in sorted(pick)]


def row_length(max_context: int) -> int:
    """Length every reference row is padded to: the traffic's longest
    request (prompt + output), rounded up to 64, so one shape compiles."""
    return -(-max_context // 64) * 64


def _rows(reqs, length: int) -> tuple[np.ndarray, np.ndarray]:
    """Right-padded rows of prompt + served tokens (the last served token
    is never an input), and each row's token served at each position
    (-1: none)."""
    seqs = [r.prompt + r.tokens[:-1] for r in reqs]
    length = max(length, max(len(s) for s in seqs))
    rows = np.zeros((len(seqs), length), np.int32)
    served = np.full((len(seqs), length), -1, np.int32)
    for i, (s, r) in enumerate(zip(seqs, reqs)):
        rows[i, : len(s)] = s
        first = len(r.prompt) - 1
        served[i, first : first + len(r.tokens)] = r.tokens
    return rows, served


def reference(cfg: dict):
    return importlib.import_module(f"reference.{cfg['system']['reference']}")


def gaps(cfg: dict, seed: int, reqs, *, length: int = 64,
         control: bool = False) -> np.ndarray:
    """Gap of every served token (or, with ``control``, of the token the
    float8 reference puts first) below the float32 reference's best.
    One row at a time, each padded to ``length``."""
    import jax.numpy as jnp

    ref = reference(cfg)
    rows, served = _rows(reqs, length)
    out = []
    for row, srv in zip(rows, served):
        logits = ref.forward(cfg, seed, row[None])[0]
        chosen = (jnp.argmax(ref.forward(cfg, seed, row[None], precision="fp8")[0], -1)
                  if control else jnp.asarray(np.maximum(srv, 0)))
        best = np.asarray(jnp.max(logits, -1), np.float64)
        got = np.asarray(jnp.take_along_axis(logits, chosen[:, None], -1)[:, 0],
                         np.float64)
        out.append((best - got)[srv >= 0])
    return np.concatenate(out)
