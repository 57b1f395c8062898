"""Closed loop: ``clients_per_slot`` x ``max_slots`` clients, each sending
its next request when its last one finishes, so every decode slot stays
full: throughput jobs over long documents.

Requests go out in rounds: the k-th request of every client is one round,
and each round's prompt and output lengths are the same mid-quantiles of
their distributions, one to a client, in an order fixed by ``ORDER``.
``--seed`` picks the token ids (and the driver the weights), not the
sizes: in a closed loop the order of the sizes decides which prefills
land in the window, and on one v5e chip that moved ``tokens_per_s`` by 9%
from seed to seed where two runs of one seed agreed within 0.3%.  The
first round's output budgets are cut to a share that grows with the
client's index, so completions are spread from the start instead of
arriving in lockstep."""
from __future__ import annotations

from bench.generators import quantiles, rng, tokens

DRIVER = "serve"
WAIT_FIRST = False    # TTFT is not judged here: nothing waits past the window
ORDER = 20240718      # fixes which client gets which size in each round


def streams(t: dict, seed: int, vocab: int, slots: int):
    """One request maker per client: ``make(k)`` gives the k-th request of
    that client as a dict with ``prompt`` and ``max_new``."""
    C = t["clients_per_slot"] * slots
    p, o = quantiles(t["prompt"], C), quantiles(t["output"], C)
    rounds = {}

    def round_(k):
        if k not in rounds:
            r = rng(ORDER, k)
            rounds[k] = (r.permutation(p), r.permutation(o))
        return rounds[k]

    def maker(c):
        def make(k):
            pk, ok = round_(k)
            n_out = int(ok[c])
            if k == 0:
                n_out = max(1, int(o[c]) * (c + 1) // C)
            return {"prompt": tokens(seed, c + k * C, int(pk[c]), vocab),
                    "max_new": n_out}
        return make
    return [maker(c) for c in range(C)]


def prompt_lengths(t: dict, span_s: float, slots: int):
    C = t["clients_per_slot"] * slots
    return sorted(set(quantiles(t["prompt"], C).tolist()))
