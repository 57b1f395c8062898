"""The two readings a correctness limit is set from, on the chip.

    python bench/readings.py --workload <cell> --seconds 20 --seeds 1 2 3 ...

For each seed, in one process: a run of the cell at its own load with a
short window, then the seeded sample of finished requests replayed through
the float32 reference, and the control, the same reference computed with
int8 operands in every matmul, read on the same prompts and tokens.  One
JSON line per seed: the gaps of the served tokens below the reference's
best (the program's reading) and of the control's first choices (the
control's reading), each as its widest gap, tail, mean and off-top share,
and ``correct`` for each of the two as the harness's ``judge`` decides it.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main(argv=None, require_tpu=True):
    from bench import peaks, run
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    ctx = run.load_cell(args.workload)
    if require_tpu and not run.devices_ok(ctx["cell"]["chips"]):
        return 2
    import jax
    if require_tpu:
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
    clock = run.CompileClock()
    ctx["peak"] = peaks.PEAKS["TPU v5 lite"]
    for seed in args.seeds:
        res = ctx["driver"].run(ctx, seed, args.seconds, False, clock,
                                control=True)
        gaps = res["gaps"]
        # the control in the program's place, judged by the harness's rule
        as_control = dict(res["checks"], mean_logit_gap=(
            gaps["control"]["mean"],) + res["checks"]["mean_logit_gap"][1:])
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program": gaps["program"], "control": gaps["control"],
            "replayed_tokens": gaps["tokens"],
            "limit": res["checks"]["mean_logit_gap"][2],
            "correct": run.judge(res["checks"]),
            "control_correct": run.judge(as_control),
            "attempted": res["attempted"], "failed": res["failed"],
            "e2e": {k: v for k, v in res["e2e"].items()},
            "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
