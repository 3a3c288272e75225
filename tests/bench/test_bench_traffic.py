"""The traffic generator: the same seed gives the same requests, any
seed up to and past 64 bits' worth works, and the chat and
code mixes are block-aligned, so that every seed has the same requests
due in the window, in another order."""

from __future__ import annotations

import json

import numpy as np
import pytest

from bench import traffic
from bench_helpers import REPO

SEEDS = (1, 77, 2 ** 31 + 5, 3000000019, 2 ** 40 + 3)


def window(mix, seed, seconds):
    """(prompt length, answer length) of each request due in the window
    that opens after the warm-in."""
    lo, hi = mix["warm_in_s"], mix["warm_in_s"] + seconds
    out = []
    for a in traffic.arrivals(mix, seed, 151936):
        if a.due_s >= hi:
            return out
        if a.due_s >= lo:
            out.append((len(a.prompt), a.max_new_tokens))


def test_same_seed_same_requests():
    mix = traffic.load_mix("chat")
    a = [next(traffic.arrivals(mix, 2 ** 40 + 3, 512)) for _ in range(2)]
    assert a[0].due_s == a[1].due_s
    np.testing.assert_array_equal(a[0].prompt, a[1].prompt)
    assert a[0].prompt.min() >= 2 and a[0].prompt.max() < 512


@pytest.mark.parametrize("name,due", [("chat", 32), ("code", 123)])
def test_every_seed_has_the_same_requests_in_the_window(name, due):
    mix = traffic.load_mix(name)
    seconds = json.loads((REPO / "BENCHMARK.json").read_text())["run_seconds"]
    got = [window(mix, seed, seconds) for seed in SEEDS]
    assert {len(w) for w in got} == {due}
    for lengths in zip(*(map(sorted, zip(*w)) for w in got)):
        assert len(set(map(tuple, lengths))) == 1
    assert len({tuple(w) for w in got}) > 1      # the order differs


@pytest.mark.parametrize("name", ["chat", "code"])
def test_warm_up_buckets_are_the_padded_lengths_drawn(name):
    mix = traffic.load_mix(name)
    drawn = {len(a.prompt) for a, _ in zip(traffic.arrivals(mix, 5, 512),
                                            range(4 * mix["block"]))}
    want = {min(-(-n // 256) * 256, 4096) for n in drawn}
    assert set(traffic.prompt_buckets(mix, 256, 4096)) == want
