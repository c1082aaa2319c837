"""One general generator for every traffic mix: a mix is a data file of
parameters, ``bench/traffic/<mix>.json``.

Keys of a mix file:

- ``source``: the public trace or dataset the lengths follow, and where;
- ``requests``: requests per replay;
- ``message``, ``answer``: ``{"mean": m, "sigma": s}``, the length in
  tokens of a user's message and of the model's answer, lognormal with
  mean ``m`` (the source's) and log-space spread ``s``;
- ``turns``: turns per conversation. The prompt of turn k holds its
  conversation's k - 1 earlier messages and answers, then its own message;
- ``arrival``: ``{"kind": "backlog"}``, every request queued at round 0
  (the program's clock counts rounds, so a rate could only be one of
  requests per round);
- ``deadline_class``: the scheduler class of every request;
- serve settings passed to ``Server.serve``: ``slots``, ``queue_cap``,
  ``decode_block``, ``block_len``, ``num_blocks``, ``prefill_chunk``.

Every seed gives the same sizes: each length distribution is covered
evenly (stratified, one draw per quantile), messages and answers are
dealt to conversations in one fixed order, and the queue holds every
first turn, then every second turn, and so on, each in one fixed order.
The order sets the schedule in rounds, and a seeded order would change
the work from seed to seed. The seed draws the prompt tokens.
"""
from __future__ import annotations

import json
import os
import statistics

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
#: the fixed order of the sizes, the same for every seed
ORDER_SEED = 0


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def lognormal_quantiles(mean: float, sigma: float, n: int) -> np.ndarray:
    """n token counts covering a lognormal of log-space spread ``sigma``
    evenly: its quantiles at the midpoints (i + 1/2) / n, scaled so that
    their mean is ``mean`` (the midpoints alone leave out the far tail),
    rounded, at least 1."""
    z = np.asarray([statistics.NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    q = np.exp(sigma * z)
    return np.maximum(1, np.rint(q * mean / q.mean())).astype(np.int64)


def sizes(mix: dict) -> tuple[np.ndarray, np.ndarray]:
    """(prompt, output) lengths of one replay's requests, in queue order."""
    n, turns = int(mix["requests"]), int(mix.get("turns", 1))
    if n % turns:
        raise ValueError(f"{n} requests do not split into {turns}-turn conversations")
    rng = np.random.default_rng(ORDER_SEED)
    msg = rng.permutation(lognormal_quantiles(**mix["message"], n=n))
    ans = rng.permutation(lognormal_quantiles(**mix["answer"], n=n))
    msg, ans = msg.reshape(-1, turns), ans.reshape(-1, turns)
    history = np.cumsum(msg + ans, axis=1) - (msg + ans)
    prompts, outs = (history + msg).T.ravel(), ans.T.ravel()
    return prompts, outs


def max_context(mix: dict) -> int:
    """The longest request's prompt + output, the pool's unit of sizing."""
    prompts, outs = sizes(mix)
    return int((prompts + outs).max())


def make_trace(mix: dict, seed, vocab: int, request_type):
    """The replay's requests, as ``request_type(rid, arrival, prompt,
    out_len, deadline_class)`` objects (the program's ``Request``);
    ``seed`` is a whole number or a list of them."""
    if mix["arrival"] != {"kind": "backlog"}:
        raise ValueError(f"unknown arrival {mix['arrival']!r}")
    rng = np.random.default_rng(seed)
    prompts, outs = sizes(mix)
    return [
        request_type(
            rid=i, arrival=0.0,
            prompt=tuple(int(t) for t in rng.integers(0, vocab, int(prompts[i]))),
            out_len=int(outs[i]), deadline_class=mix["deadline_class"],
        )
        for i in range(int(mix["requests"]))
    ]
