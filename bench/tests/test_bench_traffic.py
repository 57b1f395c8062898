"""Every seed offers the same work: the seed changes the order of the
requests and their token ids, never their sizes or their arrival gaps."""
import json
import os

import numpy as np
import pytest

from bench import run
from bench.generators import closed_backlog, poisson_open

SEEDS = [1, 2, 2**31 + 11, 2**32 + 17]


def _traffic(name):
    with open(os.path.join(run.BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def _window(seed, window_s=45.0):
    t = _traffic("chat-0.8knee")
    reqs = poisson_open.schedule(t, seed, t["warm_s"] + window_s, 1000, 64)
    return t, reqs, [r for r in reqs if r["due"] >= t["warm_s"]]


def _cyclic_gaps(win, period):
    due = np.array([r["due"] for r in win])
    return np.diff(np.concatenate([due, [due[0] + period]]))


@pytest.mark.parametrize("seed", SEEDS)
def test_open_loop_window_holds_one_cycle(seed):
    t, reqs, win = _window(seed)
    _, _, base = _window(SEEDS[0])
    assert len(win) == round(t["rate_rps"] * 45.0)
    assert [r["due"] for r in reqs] == sorted(r["due"] for r in reqs)
    assert min(r["due"] for r in reqs) >= 0.0
    key = lambda w: sorted((len(r["prompt"]), r["max_new"]) for r in w)
    assert key(win) == key(base)
    # each request keeps the same gap to the next, around the cycle
    rot = lambda w: [(len(r["prompt"]), r["max_new"]) for r in w]
    a, b = rot(win), rot(base)
    k = next(i for i in range(len(a)) if a[i:] + a[:i] == b)
    ga, gb = _cyclic_gaps(win, 45.0), _cyclic_gaps(base, 45.0)
    assert np.allclose(np.roll(ga, -k), gb)


def test_open_loop_seeds_differ_in_order_and_tokens():
    _, _, a = _window(SEEDS[0])
    _, _, b = _window(SEEDS[1])
    assert [r["due"] for r in a] != [r["due"] for r in b]
    assert a[0]["prompt"][:8] != b[0]["prompt"][:8]


@pytest.mark.parametrize("seed", SEEDS)
def test_closed_loop_rounds_hold_the_same_sizes(seed):
    t = _traffic("long-decode-backlog")
    mine = closed_backlog.streams(t, seed, 1000, 4)
    base = closed_backlog.streams(t, SEEDS[0], 1000, 4)
    want_p = sorted(closed_backlog.prompt_lengths(t, 0.0, 4))
    for k in range(4):
        got = [(len(m(k)["prompt"]), m(k)["max_new"]) for m in mine]
        # each client's k-th request has the same sizes for every seed
        assert got == [(len(m(k)["prompt"]), m(k)["max_new"]) for m in base]
        assert sorted(set(x[0] for x in got)) == want_p
    # the seed picks the tokens
    if seed != SEEDS[0]:
        assert mine[0](1)["prompt"][:8] != base[0](1)["prompt"][:8]
