"""Serving driver: ``serving.ServingLoop`` over ``Engine.pump()``.

Set-up makes the weights on the device from the seed, builds the engine
with the configuration's ``ServeConfig``, runs every prefill shape the
traffic can reach and the decode step once (so nothing compiles in the
window), then starts the traffic ``warm_s`` before the window opens so the
window sees a loaded system.  Clients live on an asyncio loop in this
process and time every token as they receive it.  After the window the
driver waits for the first token of every request due in it (a minute at
most), cancels what is still running, frees the engine, and replays a
seeded sample of finished requests through the plain reference.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import math
import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import trace_reduce, weights
from bench.reference import dense

FIRST_TOKEN_WAIT_S = 60.0


def log(*a):
    print("[bench]", *a, file=sys.stderr, flush=True)


def arch_config(m: dict, arch: str):
    """The program's ArchConfig for ``arch``, checked against ``m``."""
    from repro.configs import get_arch
    cfg = dataclasses.replace(get_arch(arch), remat="none")
    want = {"d_model": m["hidden_size"], "n_layers": m["num_hidden_layers"],
            "n_heads": m["num_attention_heads"],
            "n_kv_heads": m["num_key_value_heads"],
            "head_dim_": m.get("head_dim") or m["hidden_size"]
            // m["num_attention_heads"],
            "d_ff": m["intermediate_size"], "vocab": m["vocab_size"],
            "mlp_gated": m["mlp_gated"], "qkv_bias": m["attention_bias"],
            "rope_theta": m["rope_theta"], "norm": m["norm"],
            "tie_embeddings": m["tie_word_embeddings"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"program config {arch} differs from the bench "
                         f"configuration: {got} vs {want}")
    return cfg


def peak_bytes(chips):
    """Peak device memory of the fullest chip used (0 where the backend
    keeps no statistics)."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:chips])


def pct(xs, q):
    """Nearest-rank percentile (inf-safe)."""
    if not xs:
        return float("nan")
    s = sorted(xs)
    return s[max(0, math.ceil(q / 100 * len(s)) - 1)]


def prefill_shapes(lengths, scfg, table_width):
    """Every (rows, bucket, table pages) prefill call that prompts of these
    lengths can reach under the scheduler's batching rules: rows of one
    batch share a bucket, their tokens stay within the chunk budget, rows
    pad to a power of two, and the table view to a power-of-two page count
    covering the batch's furthest row."""
    from repro.serving.engine import _pow2_pad
    ps, chunk = scfg.page_size, scfg.chunk_tokens
    pow2 = lambda n: 1 << max(0, (n - 1).bit_length())
    groups: Dict = {}
    for p in lengths:
        s = 0
        while s < p:
            end = min(p, (s + chunk) // ps * ps) if chunk else p
            n = end - s
            W = min(pow2(-(-(s + n) // ps)), table_width)
            groups.setdefault((s > 0, scfg.bucket_of(n)), []).append((n, W))
            s = end
    shapes = set()
    for (_, bucket), rows in groups.items():
        for W in {w for _, w in rows}:
            top = min(n for n, w in rows if w == W)
            rest = sorted(n for n, w in rows if w <= W)
            rest.remove(top)
            k, total = 1, top
            for n in rest:
                if chunk and total + n > chunk:
                    break
                if not chunk and k >= scfg.max_slots:
                    break
                k, total = k + 1, total + n
            for j in range(1, k + 1):
                shapes.add((_pow2_pad(j, scfg.max_slots), bucket, W))
    return sorted(shapes)


def warm(eng, shapes, cfg):
    """Run each prefill shape and the decode step once on the engine's own
    jitted programs, with every row a padding row."""
    from repro.models.attn_backend import prefill_meta
    from repro.serving.kv_pool import NULL_PAGE
    ps, B0 = eng.scfg.page_size, eng.scfg.max_slots
    for B, T, W in shapes:
        tables = np.full((B, W), NULL_PAGE, np.int32)
        meta = {k: jnp.asarray(v) for k, v in prefill_meta(
            cfg, ps, tables, np.full((B,), B0, np.int32),
            np.zeros((B,), np.int32), np.zeros((B,), np.int32), T).items()}
        out, eng.pool.kv, _ = eng._prefill(
            eng.params, eng.pool.kv, {}, meta,
            jnp.asarray(np.zeros((B, T), np.int32)), {})
        out.block_until_ready()
    nxt, ok, eng.pool.kv, _ = eng._decode(
        eng.params, eng.pool.kv, {}, eng._decode_plan([]),
        jnp.asarray(np.zeros((B0,), np.int32)))
    nxt.block_until_ready()


def profile_options():
    """Device ops and host TraceMe spans; no Python function tracing,
    which would slow the host loop being measured."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def annotate(eng):
    """Host spans on the profiler's clock around the engine's phases, so
    the trace's idle gaps can be named by what the host was doing."""
    for attr, name in (("_dispatch_next", "engine.dispatch"),
                       ("_finish_step", "engine.collect"),
                       ("_stage_next", "engine.stage")):
        fn = getattr(eng, attr)

        def wrapped(*a, _fn=fn, _name=name, **k):
            with jax.profiler.TraceAnnotation(_name):
                return _fn(*a, **k)
        setattr(eng, attr, wrapped)


class Clients:
    """Per-request client records: due and submit times, receive time of
    every token, the tokens, and the terminal event."""

    def __init__(self):
        self.recs: List[dict] = []

    async def one(self, serving, req, due):
        now = time.perf_counter()
        if due > now:
            await asyncio.sleep(due - now)
        rec = {"due": due, "sub": time.perf_counter(), "times": [],
               "tokens": [], "prompt": req["prompt"],
               "max_new": req["max_new"], "end": None, "error": None}
        self.recs.append(rec)
        rid, q = serving.submit(req["prompt"], req["max_new"])
        rec["rid"] = rid
        while True:
            ev = await q.get()
            t = time.perf_counter()
            if ev["type"] == "token":
                rec["times"].append(t)
                rec["tokens"].append(ev["token"])
            else:
                rec["end"] = t
                if ev["type"] == "error":
                    rec["error"] = ev["error"]
                else:
                    rec["tokens"] = list(ev["tokens"])
                serving.forget(rid)
                return rec


async def drive(serving, gen, t, seed, vocab, slots, t_open, t_close):
    """Run the cell's traffic; returns the client records."""
    cl = Clients()
    start = t_open - t["warm_s"]
    tasks = []
    if hasattr(gen, "schedule"):
        for i, req in enumerate(gen.schedule(
                t, seed, t["warm_s"] + (t_close - t_open), vocab, slots)):
            tasks.append(asyncio.ensure_future(
                cl.one(serving, req, start + req["due"])))
    else:
        async def client(make):
            k = 0
            while time.perf_counter() < t_close:
                await cl.one(serving, make(k), time.perf_counter())
                k += 1
        for make in gen.streams(t, seed, vocab, slots):
            tasks.append(asyncio.ensure_future(client(make)))
    # wait for the window to close, then for the first token of every
    # request due in it, then cancel whatever still runs
    await asyncio.sleep(max(0.0, t_close - time.perf_counter()))
    limit = t_close + (FIRST_TOKEN_WAIT_S if gen.WAIT_FIRST else 0.0)
    while time.perf_counter() < limit:
        due = [r for r in cl.recs if t_open <= r["due"] < t_close]
        if all(r["times"] or r["end"] for r in due):
            break
        await asyncio.sleep(0.01)
    cut = set()
    for r in cl.recs:
        if r["end"] is None:
            cut.add(r["rid"])
            serving.cancel(r["rid"])
    for task in tasks:
        if not task.done():
            task.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    for r in cl.recs:
        r["cut"] = r["rid"] in cut
    return cl.recs


def end_to_end(recs, t_open, t_close):
    seconds = t_close - t_open
    got = sum(1 for r in recs for x in r["times"] if t_open <= x < t_close)
    due = [r for r in recs if t_open <= r["due"] < t_close]
    ttft = [(r["times"][0] - r["due"]) if r["times"] and not r["error"]
            else math.inf for r in due]
    gaps = [b - a for r in recs for a, b in zip(r["times"], r["times"][1:])
            if t_open <= a and b < t_close]
    def backlog(x):          # submitted by x, no first token by x
        return sum(1 for r in recs if r["sub"] <= x and
                   not (r["times"] and r["times"][0] <= x))
    return {"tokens_per_s": got / seconds,
            "_backlog_open": backlog(t_open),
            "_backlog_close": backlog(t_close),
            "ttft_p90_ms": pct(ttft, 90) * 1e3,
            "itl_p95_ms": pct(gaps, 95) * 1e3,
            "_n_ttft": len(ttft), "_n_itl": len(gaps),
            "_ttft_p50_ms": pct(ttft, 50) * 1e3,
            "_itl_p50_ms": pct(gaps, 50) * 1e3}


def sample(recs, seed, want_tokens):
    """Finished requests to replay: the longest, then others drawn from the
    seed, until ``want_tokens`` served tokens are covered."""
    done = [r for r in recs if r["end"] and not r["error"] and not r["cut"]]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r["tokens"]), r["rid"]))
    pick, n = [done[0]], len(done[0]["tokens"])
    order = np.random.default_rng([seed, 7]).permutation(len(done) - 1) + 1
    for i in order:
        if n >= want_tokens:
            break
        pick.append(done[i])
        n += len(done[i]["tokens"])
    return pick


def gap_stats(g: np.ndarray) -> dict:
    """The mean gap, which ``correct`` compares, and for the readings the
    widest gap, its p99 and the share of positions whose token is not the
    reference's first choice."""
    if not len(g):
        return {"max": 0.0, "p99": 0.0, "mean": 0.0, "off_top": 0.0}
    return {"max": float(g.max()), "p99": float(np.quantile(g, 0.99)),
            "mean": float(g.mean()), "off_top": float((g > 0).mean())}


def check(m, params, picked, control=False):
    """Gaps of the served tokens below the reference's best (and of the
    control's first choices, when asked), over the sampled requests."""
    prog, ctrl = [], []
    for r in picked:
        prog.append(dense.served_gaps(m, params, r["prompt"], r["tokens"]))
        if control:
            ctrl.append(dense.served_gaps(m, params, r["prompt"],
                                          r["tokens"], control=True))
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros(0)
    out = {"program": gap_stats(cat(prog)),
           "tokens": sum(len(r["tokens"]) for r in picked)}
    if control:
        out["control"] = gap_stats(cat(ctrl))
    return out


def run(ctx, seed, seconds, trace, clock, control=False, replay=True):
    """One run of a serving cell; returns the harness's result dict."""
    from repro.configs import ServeConfig
    from repro.models.registry import init_params
    from repro.serving import Engine, ServingLoop, Tracer
    m, conf, t, gen = ctx["model"], ctx["config"], ctx["traffic"], ctx["gen"]
    cfg = arch_config(m, conf["arch"])
    scfg = ServeConfig(**conf["serve"])
    weights.check_layout(m, jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    params = weights.make(m, seed)
    eng = Engine(cfg, scfg, params=params, seed=seed,
                 tracer=Tracer(jax_annotations=bool(trace)))
    lengths = gen.prompt_lengths(t, t["warm_s"] + seconds, scfg.max_slots)
    shapes = prefill_shapes(lengths, scfg, eng.pool.table_width)
    c0 = clock.programs
    warm(eng, shapes, cfg)
    log(f"warmed {len(shapes)} prefill shapes and the decode step "
        f"({clock.programs - c0} programs compiled or loaded)")
    if trace:
        annotate(eng)
    tokens_seen = []                  # (engine time, rid, index)
    serving = ServingLoop(eng, overlap=True)
    hook = eng.on_token

    def on_token(rid, index, token, tt):
        tokens_seen.append((tt, rid, index))
        hook(rid, index, token, tt)
    eng.on_token = on_token
    marks = {}

    async def at_open(t_open):
        await asyncio.sleep(max(0.0, t_open - time.perf_counter()))
        marks["programs"] = clock.programs
        if trace:
            import tempfile
            marks["dir"] = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(marks["dir"],
                                     profiler_options=profile_options())
            with jax.profiler.TraceAnnotation(trace_reduce.OPEN):
                marks["t0"] = time.perf_counter()
            await asyncio.sleep(min(seconds, ctx["trace_s"]))
            with jax.profiler.TraceAnnotation(trace_reduce.CLOSE):
                marks["t1"] = time.perf_counter()
            # stopping takes seconds; in a worker thread, so that the
            # clients on this loop keep sending and receiving meanwhile
            await asyncio.get_running_loop().run_in_executor(
                None, jax.profiler.stop_trace)

    async def main():
        await serving.start()
        t_open = time.perf_counter() + t["warm_s"]
        t_close = t_open + seconds
        side = asyncio.ensure_future(at_open(t_open))
        recs = await drive(serving, gen, t, seed, m["vocab_size"],
                           scfg.max_slots, t_open, t_close)
        await side
        marks["programs_close"] = clock.programs
        await serving.stop()
        return recs, t_open, t_close

    recs, t_open, t_close = asyncio.run(main())
    e2e = end_to_end(recs, t_open, t_close)
    due = [r for r in recs if t_open <= r["due"] < t_close]
    late = [r["sub"] - r["due"] for r in due]
    log(f"window {seconds} s: {len(due)} requests due, {e2e['_n_ttft']} "
        f"TTFT samples (p50 {e2e['_ttft_p50_ms']:.1f} ms), {e2e['_n_itl']} "
        f"token gaps (p50 {e2e['_itl_p50_ms']:.2f} ms); generator late by "
        f"p50 {pct(late, 50) * 1e3:.2f} ms, max "
        f"{(max(late) if late else 0) * 1e3:.2f} ms; programs compiled or "
        f"loaded in the window {marks['programs_close'] - marks['programs']}")
    failed = sum(1 for r in due if r["error"] and not r["cut"])
    short = sum(1 for r in recs if r["end"] and not r["error"]
                and len(r["tokens"]) != r["max_new"])
    out = {"e2e": e2e, "attempted": len(due), "failed": failed,
           "memory_peak_bytes": peak_bytes(1),
           "t_open": t_open}
    if trace:
        import shutil
        raw = trace_reduce.read_xplane(marks["dir"])
        ops0 = raw["devices"].get(0, [])
        log("trace clocks (ns): device ops "
            + (f"{min(s for _, s, _ in ops0)}..{max(e for *_, e in ops0)}"
               if ops0 else "none") + "; marks "
            + ", ".join(f"{n} {s}" for n, s, _ in raw["host"]
                        if n in (trace_reduce.OPEN, trace_reduce.CLOSE)))
        tr = trace_reduce.window(raw)
        shutil.rmtree(marks["dir"], ignore_errors=True)
        out["trace"] = trace_reduce.summarize(tr, 1)
        log("device op families (name, events, s): "
            + "; ".join(f"{n} {c} {t:.4f}" for n, c, t in
                        trace_reduce.families(tr["devices"].get(0, []))))
        out["record"] = {
            "model": m, "e2e": e2e, "t0": marks["t0"], "t1": marks["t1"],
            "trace": tr,
            "summary": out["trace"], "tracer": eng.tracer,
            "tokens": tokens_seen,
            "prompt_len": {r["rid"]: len(r["prompt"]) for r in recs},
            "peak": ctx["peak"]}
    # free the program's state before the reference runs
    picked = sample(recs, seed, conf["check"]["tokens"]) if replay else []
    eng.pool.kv = None
    del serving, eng
    gc.collect()
    got = check(m, params, picked, control=control)
    log(f"replayed {len(picked)} requests, {got['tokens']} served tokens, "
        f"through the reference")
    out["checks"] = {
        "mean_logit_gap": (got["program"]["mean"], "<=",
                           conf["check"]["mean_logit_gap"]),
        "programs_in_window": (marks["programs_close"] - marks["programs"],
                               "<=", 0),
        "short_requests": (short, "<=", 0),
        "replayed_tokens": (got["tokens"], ">=",
                            conf["check"]["tokens"] // 2)}
    out["gaps"] = got
    return out
