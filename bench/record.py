"""What the timed path produced, kept without a program change.

``Recorder`` wraps the server's compiled paged serve step on the
harness's own ``Server`` instance. The step donates its KV pool, logits
and positions, but returns the tokens it chose undonated, so the wrapper
keeps those and the per-dispatch inputs (prompt chunks, active slots).
It keeps device arrays and reads nothing back, so no dispatch waits.

After the window, ``replay_requests`` rebuilds every request that the
window served: its prompt, from the prompt chunks its slot received, and
its served tokens, from the steps in which its slot was active.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np


@dataclasses.dataclass
class Call:
    steps: int
    chunk_tokens: object
    chunk_start: object
    chunk_lens: object
    active: object
    toks: object


class Recorder:
    """Drop-in for ``Server._serve_step_paged_fn`` that keeps its tokens."""

    def __init__(self, fn):
        self.fn = fn
        self.calls: list[Call] = []
        self.steps_seen: set[int] = set()

    def __call__(self, params, cache, logits, pos, chunk_tokens, chunk_start,
                 chunk_lens, finishing, tables, active, *rest, steps):
        out = self.fn(params, cache, logits, pos, chunk_tokens, chunk_start,
                      chunk_lens, finishing, tables, active, *rest, steps=steps)
        self.calls.append(Call(steps, chunk_tokens, chunk_start, chunk_lens,
                               active, out[3]))
        self.steps_seen.add(steps)
        return out

    def take(self) -> list[Call]:
        """The calls so far, read back to the host; the record is cleared."""
        calls, self.calls = self.calls, []
        host = jax.device_get([
            (c.chunk_tokens, c.chunk_start, c.chunk_lens, c.active, c.toks)
            for c in calls
        ])
        return [Call(c.steps, *map(np.asarray, h)) for c, h in zip(calls, host)]


@dataclasses.dataclass
class Served:
    prompt: list
    tokens: list


@dataclasses.dataclass
class Dispatch:
    """One dispatch, for counting bytes and operations."""

    prefill: bool
    steps: int
    #: context length each prefill token attends, over all slots
    prefill_contexts: list
    #: per decode step, the context length each active slot attends
    decode_contexts: list


def replay_requests(calls: list[Call]) -> tuple[list[Served], list[Dispatch]]:
    """Requests served by ``calls`` (in order), and the dispatches."""
    slots = calls[0].active.shape[0] if calls else 0
    cur: list[Served | None] = [None] * slots
    ctx = [0] * slots
    done: list[Served] = []
    dispatches = []
    for c in calls:
        pre_ctx = []
        for s in range(slots):
            n = int(c.chunk_lens[s])
            if n == 0:
                continue
            start = int(c.chunk_start[s])
            if start == 0:
                if cur[s] is not None:
                    done.append(cur[s])
                cur[s], ctx[s] = Served([], []), 0
            cur[s].prompt.extend(int(t) for t in c.chunk_tokens[s, :n])
            pre_ctx.extend(range(start + 1, start + n + 1))
            ctx[s] = start + n
        dec_ctx = []
        for t in range(c.steps):
            step = []
            for s in range(slots):
                if c.active[s]:
                    cur[s].tokens.append(int(c.toks[t, s]))
                    ctx[s] += 1
                    step.append(ctx[s])
            dec_ctx.append(step)
        dispatches.append(Dispatch(bool(pre_ctx), c.steps, pre_ctx, dec_ctx))
    done.extend(s for s in cur if s is not None)
    return done, dispatches
