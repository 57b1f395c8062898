"""The benchmark's one command.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration in
``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json`` and the mix's generator in
``bench/generators/<generator>.py``; the generator names the driver in
``bench/drivers/``.  With ``--trace 0`` it prints the cell's end-to-end
metrics; with ``--trace 1`` it traces part of the window and prints the
per-layer metrics, each read by ``bench/metrics/<metric>.py``.  The last
line of standard output is one JSON object; the numbers compared to decide
``correct`` are the last lines of standard error and the last key of that
object.  With no TPU, or fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

BENCH = os.path.join(ROOT, "bench")
TRACE_S = 10.0          # seconds of the window a --trace 1 run records


def log(*a):
    print("[bench]", *a, file=sys.stderr, flush=True)


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> dict:
    """Everything a run needs, found by the names in BENCHMARK.json."""
    bm = _json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf_entry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    conf = _json(ROOT, conf_entry["file"])
    traffic = _json(BENCH, "traffic", cell["traffic"] + ".json")
    gen = importlib.import_module("bench.generators." + traffic["generator"])
    return {"name": name, "cell": cell, "config": conf,
            "model": conf["model"], "traffic": traffic, "gen": gen,
            "driver": importlib.import_module("bench.drivers." + gen.DRIVER),
            "end_to_end": [m for m in bm["end_to_end"] if applies(m, name)],
            "per_layer": [m for m in bm["per_layer"] if applies(m, name)],
            "trace_s": TRACE_S}


def reader(metric: str):
    """``bench/metrics/<metric>.py``'s ``read``."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileClock:
    """Programs compiled or loaded from the persistent cache, and their
    seconds, counted through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1


def devices_ok(chips: int) -> bool:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s)")
        return False
    return True


def result_line(ctx, res, trace: bool, strict: bool = True) -> dict:
    """The contract's JSON object.  With ``strict``, a per-layer metric that
    the cell declares and whose reader finds nothing is an error: its
    reader's keys (a kernel's name, a span) no longer match the program."""
    import jax
    dev = jax.devices()[0]
    chips = ctx["cell"]["chips"]
    if trace:
        metrics = {}
        rec = res["record"]
        for m in ctx["per_layer"]:
            v = reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            elif strict:
                raise RuntimeError(f"per-layer metric {m['name']!r} read "
                                   f"nothing in this traced run")
    else:
        vals = dict(res["e2e"], setup_s=res["setup_s"])
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in ctx["end_to_end"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips, "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = res["trace"]["busy_s"]
        device["window_s"] = res["trace"]["window_s"]
        line["breakdown"] = res["trace"]["breakdown"]
    line["checks"] = {k: {"value": v, "limit": lim, "holds": op}
                      for k, (v, op, lim) in res["checks"].items()}
    return line


def judge(checks: dict) -> bool:
    ok = True
    for v, op, lim in checks.values():
        ok &= (v <= lim) if op == "<=" else (v >= lim)
    return bool(ok)


def run(argv=None, require_tpu: bool = True,
        compile_cache: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    ctx = load_cell(args.workload)
    if require_tpu and not devices_ok(ctx["cell"]["chips"]):
        return 2
    import jax
    from bench import peaks
    from repro.launch.compile_cache import use_compile_cache
    if compile_cache:
        log(f"compilation cache: {use_compile_cache()}")
    clock = CompileClock()
    ctx["peak"] = peaks.peak(jax.devices()[0].device_kind) if require_tpu \
        else peaks.PEAKS["TPU v5 lite"]
    res = ctx["driver"].run(ctx, args.seed, args.seconds, bool(args.trace),
                            clock)
    res["setup_s"] = res["t_open"] - T_START
    res["correct"] = judge(res["checks"])
    log(f"setup {res['setup_s']:.3f} s, of which compile or cache load "
        f"{clock.seconds:.3f} s over {clock.programs} programs")
    line = result_line(ctx, res, bool(args.trace), strict=require_tpu)
    for k, c in line["checks"].items():
        print(f"[bench] check {k}: {c['value']!r} (must be {c['holds']} "
              f"{c['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
