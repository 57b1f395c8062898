"""Open-loop arrivals at a fixed rate: independent users who send whether
or not earlier requests have finished.  Exponential gaps at ``rate_rps``;
prompt and output lengths from their distributions.  Requests run to their
full output budget (no EOS), decoded greedily.

The arrivals are one cycle as long as the window, repeated: ``rate_rps`` x
window requests whose gaps and sizes are the quantiles of their
distributions, in one fixed order (``ORDER``), so that bursts come as a
Poisson process brings them.  ``--seed`` picks where in the cycle the
window opens, and the token ids: every seed puts the same requests, each
with the same neighbours, in the window, in its own order, and the lead-in
(``warm_s``) is the end of the cycle before it."""
from __future__ import annotations

import numpy as np

from bench.generators import exp_gaps, quantiles, rng, tokens

DRIVER = "serve"
WAIT_FIRST = True     # wait past the window for the first token of every
                      # request due in it (TTFT is judged here)
ORDER = 20231120      # fixes the order of the cycle's gaps and sizes


def cycle(t: dict, period: float):
    """The cycle's arrival times in ``[0, period)``, prompt and output
    lengths."""
    n = max(1, int(round(t["rate_rps"] * period)))
    r = rng(ORDER, 0)
    g = r.permutation(exp_gaps(t["rate_rps"], n))
    g = g * (period / g.sum())
    at = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    return (at, r.permutation(quantiles(t["prompt"], n)),
            r.permutation(quantiles(t["output"], n)))


def schedule(t: dict, seed: int, span_s: float, vocab: int, slots: int):
    """Requests due in ``[0, span_s)``, the window being its last
    ``span_s - warm_s`` seconds: dicts with ``due``, ``prompt``,
    ``max_new``, in order of ``due``."""
    warm = t["warm_s"]
    period = span_s - warm
    at, p, o = cycle(t, period)
    x = np.mod(at - rng(seed, 0).uniform(0.0, period), period)
    reqs = []
    for i in range(len(at)):
        due = warm + x[i]
        while due >= 0.0:               # the window's copy, then the lead-in
            reqs.append((float(due), int(p[i]), int(o[i])))
            due -= period
    reqs.sort()
    return [{"due": d, "max_new": n_out,
             "prompt": tokens(seed, j, n_in, vocab)}
            for j, (d, n_in, n_out) in enumerate(reqs)]


def prompt_lengths(t: dict, span_s: float, slots: int):
    return sorted({int(x) for x in cycle(t, span_s - t["warm_s"])[1]})
