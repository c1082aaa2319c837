"""The generator gives every seed the same sizes in the same order, drawn
evenly from the source's distributions, and the pool holds them."""
import collections

import numpy as np
import pytest

import tiny  # noqa: F401

import traffic

MIXES = ("lmsys-chat", "alpaca")


class Req(collections.namedtuple("Req", "rid arrival prompt out_len deadline_class")):
    pass


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_has_the_same_sizes_in_the_same_order(name):
    mix = traffic.load(name)
    a = traffic.make_trace(mix, 1, 151_936, Req)
    b = traffic.make_trace(mix, [2 ** 33 + 5, 3], 151_936, Req)
    assert len(a) == mix["requests"]
    for key in (lambda r: len(r.prompt), lambda r: r.out_len):
        assert list(map(key, a)) == list(map(key, b))
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert all(r.arrival == 0.0 and r.deadline_class == "batch" for r in a)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_keep_the_source_means(name):
    mix = traffic.load(name)
    n = mix["requests"]
    for part in ("message", "answer"):
        q = traffic.lognormal_quantiles(**mix[part], n=n)
        assert abs(q.mean() - mix[part]["mean"]) <= 0.5
        assert np.all(np.diff(q) >= 0) and q.min() >= 1


def test_later_turns_carry_their_conversation():
    mix = traffic.load("lmsys-chat")
    prompts, outs = traffic.sizes(mix)
    half = mix["requests"] // 2
    # the queue holds every first turn, then every second turn
    history = prompts[half:] - prompts[:half] - outs[:half]
    assert np.all(history >= 1)


@pytest.mark.parametrize("name", MIXES)
def test_the_pool_holds_every_slot_s_largest_request(name):
    mix = traffic.load(name)
    per_req = -(-(traffic.max_context(mix) + 1) // mix["block_len"])
    assert mix["num_blocks"] == mix["slots"] * per_req


def test_same_seed_same_trace():
    mix = traffic.load("alpaca")
    assert traffic.make_trace(mix, 7, 1000, Req) == traffic.make_trace(mix, 7, 1000, Req)
