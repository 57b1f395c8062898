"""Find a serving cell's knee on the chip: the highest open-loop rate the
system sustains without a growing backlog.

    python bench/sweep.py --workload qwen2-0.5b.chat-0.8knee --seed 11 \\
        --seconds 30 --rates 1 1.5 2 2.5 3

Runs the cell's traffic at each rate in turn, in one process, and prints
one JSON line per rate: offered and completed tokens per second, the
requests waiting for their first token when the window opens and when it
closes, and the TTFT and ITL tails.  A backlog that grows through the
window means the rate is past the knee.  The chosen rate goes into the
traffic file by hand, with the sweep's lines in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main(argv=None):
    from bench import generators, peaks, run
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    ctx = run.load_cell(args.workload)
    if not run.devices_ok(ctx["cell"]["chips"]):
        return 2
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    clock = run.CompileClock()
    ctx["peak"] = peaks.peak(jax.devices()[0].device_kind)
    mean_out = generators.mean(ctx["traffic"]["output"])
    for rate in args.rates:
        ctx["traffic"] = dict(ctx["traffic"], rate_rps=rate)
        res = ctx["driver"].run(ctx, args.seed, args.seconds, False, clock,
                                replay=False)
        e = res["e2e"]
        print(json.dumps({
            "rate_rps": rate, "offered_tokens_per_s": rate * mean_out,
            "tokens_per_s": e["tokens_per_s"],
            "backlog_open": e["_backlog_open"],
            "backlog_close": e["_backlog_close"],
            "ttft_p50_ms": e["_ttft_p50_ms"], "ttft_p90_ms": e["ttft_p90_ms"],
            "itl_p50_ms": e["_itl_p50_ms"], "itl_p95_ms": e["itl_p95_ms"],
            "attempted": res["attempted"], "failed": res["failed"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
