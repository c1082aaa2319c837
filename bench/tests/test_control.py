"""The control, the reference in float8 put in the program's place, fails
the cell's limit, and the reference's own greedy tokens pass it."""
import numpy as np
import pytest

import tiny

import correct
import traffic
from record import Served

LIMIT = tiny.LIMIT


def greedy(cfg, seed, requests):
    """Each request's tokens as the float32 reference decodes them."""
    ref = correct.reference(cfg)
    out = []
    for r in requests:
        toks = list(r.prompt)
        for _ in range(r.out_len):
            logits = np.asarray(ref.forward(cfg, seed, np.array([toks])))
            toks.append(int(np.argmax(logits[0, -1])))
        out.append(Served(list(r.prompt), toks[len(r.prompt):]))
    return out


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5, 2 ** 33 + 9])
def test_fp8_control_fails_the_limit(seed):
    from repro.serve.workload import Request

    reqs = traffic.make_trace(tiny.MIX, seed, tiny.CFG["vocab_size"], Request)
    served = greedy(tiny.CFG, seed, reqs)
    assert correct.gaps(tiny.CFG, seed, served).max() == 0.0
    assert correct.gaps(tiny.CFG, seed, served, control=True).max() > LIMIT
