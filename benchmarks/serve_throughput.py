"""Serving throughput: static vs continuous vs continuous + prefix cache.

Offered load: N concurrent requests drawn from ``families`` distinct prompt
*families* — every request is a shared family prefix plus a unique suffix
(mixed lengths), with a head-of-line-blocking budget mix: every ``C``-request
arrival group is short chat-style turns plus one long-form generation.  The
shared prefixes are the redundancy the source paper complains about
("redundant data aggravates the system workload"): without a prefix cache
every request prefills its family prefix from scratch.

Three serving paths are timed at the same concurrency cap C:

* ``static``   — arrival-order batches of C, padded together, each batch
  gated by its slowest member (the pre-paging baseline);
* ``continuous`` — paged KV pool + continuous batching, prefix cache off;
* ``continuous_prefix_cache`` — same engine with the radix prefix cache:
  matched prefix pages are shared/refcounted and only uncached tails are
  prefilled.

All paths are fully warmed (every jit shape compiled) before timing and all
greedy tokens are checked to match; the cache row additionally reports
cached/prefilled prompt tokens, hit rate, and TTFT — the win to look for is
``prefill_tokens`` dropping by roughly the duplicated-prefix mass and TTFT
p50 shrinking with it.

A second section (``cache_families``) serves one reduced arch per cache
family — paged KV, MLA latent pages, sliding-window page ring, SSM and
RG-LRU state slots, enc-dec pinned cross cache — through the same
continuous-vs-static comparison, reporting per-family tokens/s and TTFT
(exact-match checked against the single-request baseline).

A third section (``chunked_prefill``) runs the head-of-line adversarial mix
— one 2048-token prompt arriving behind live short decodes plus a queue of
shorts — with and without ``prefill_chunk_tokens``, reporting short-request
``ttft_p50/p95``, per-engine ``decode_stall_ms`` percentiles, and prefill
padding waste (``prefill_padded_tokens`` vs ``prefill_actual_tokens``).

A fourth section (``poisson_openloop``) offers the workload *open-loop*
through the async streaming front-end (``ServingLoop`` driving the
overlapped ``Engine.pump()``): Poisson arrivals at a machine-calibrated
rate, per-request TTFT/TPOT deadlines, reporting goodput (tokens from
SLO-meeting requests only), SLO attainment, and TTFT/TPOT percentiles —
streamed tokens exact-checked against the static baseline.

A fifth section (``quantization``) serves the same mixed workload with
``kv_dtype=int8`` (int8 KV pages + per-page bf16 absmax scales, dequant
in-kernel) vs ``bf16``, reporting KV bytes/token, tokens/s, max concurrent
residency at a fixed pool byte budget, and the dual-gate parity stats
(bounded max-abs logit error + exact greedy match at high-margin tokens,
see ``serving.quant_verify``).

A sixth section (``speculation``) serves a greedy-repetitive workload
(periodic prompts whose continuation the n-gram prompt-lookup proposer
nails) and an adversarial-random one (i.i.d. tokens, accept rate ~0)
with and without ``speculate_tokens``, reporting decode tokens/s both
ways, draft accept rate, and exact token match vs the non-speculative
engine — the win to look for is the repetitive speedup with the
adversarial overhead bounded.

Emits BENCH_serve.json and appends one summary line per (kv_dtype,
spec_tokens) to BENCH_history.jsonl (the perf trajectory across runs;
``kv_dtype`` and ``spec_tokens`` keep the bf16 / int8 / speculative
series in separate regression-gate groups).

  PYTHONPATH=src python -m benchmarks.serve_throughput [--requests 16]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import numpy as np


def make_workload(vocab: int, requests: int, families: int, prefix_len: int,
                  suffix_lo: int, suffix_hi: int, slots: int, gen_short: int,
                  gen_long: int, seed: int):
    rng = np.random.RandomState(seed)
    fams = [rng.randint(1, vocab, size=prefix_len).tolist()
            for _ in range(families)]
    prompts = [fams[i % families] + rng.randint(1, vocab, size=int(
        rng.randint(suffix_lo, suffix_hi + 1))).tolist()
        for i in range(requests)]
    # one long-form generation per arrival group of `slots`: each static
    # batch stalls on its straggler while continuous retires + backfills
    budgets = [gen_long if i % slots == slots - 1 else gen_short
               for i in range(requests)]
    return prompts, budgets


def adversarial_mix(arch: str = "qwen2-0.5b", slots: int = 4,
                    long_len: int = 2048, n_short: int = 15, gen: int = 4,
                    chunk: int = 256, seed: int = 0,
                    attn_backend: str = "auto"):
    """Head-of-line adversarial mix: one ``long_len``-token prompt arriving
    behind the first admission wave of short prompts, plus more shorts
    queued behind it.  The unchunked engine stalls every decoding short for
    the long prompt's whole monolithic prefill and makes the queued shorts
    wait it out; chunked prefill (``prefill_chunk_tokens``) bounds each
    stall at one chunk.  Reports short-request ttft percentiles and
    decode-stall times for both engine configs (exact-token checked against
    each other and the static single-request baseline) — the chunking win
    the ISSUE acceptance bar reads off this section is
    ``ttft_short_p50_ratio >= 2``."""
    import dataclasses as _dc

    from repro.configs import ServeConfig, get_arch, reduced
    from repro.serving import Engine, generate_static

    cfg = _dc.replace(reduced(get_arch(arch)), remat="none")
    rng = np.random.RandomState(seed)
    ps = 16
    max_len = ((long_len + gen + ps - 1) // ps) * ps
    prompts = [rng.randint(1, cfg.vocab,
                           size=int(rng.randint(8, 25))).tolist()
               for _ in range(n_short)]
    long_prompt = rng.randint(1, cfg.vocab, size=long_len).tolist()
    # long prompt arrives after the first admission wave fills the slots, so
    # its prefill competes with live decodes (the stall being measured)
    prompts.insert(slots - 1, long_prompt)
    budgets = [gen] * len(prompts)
    short_rids = [i for i, p in enumerate(prompts) if len(p) < long_len]

    base = ServeConfig(page_size=ps, max_slots=slots, max_len=max_len,
                       attn_backend=attn_backend)
    chunked = dataclasses.replace(base, prefill_chunk_tokens=chunk)
    eng = Engine(cfg, base, seed=seed)
    params = eng.params
    # warm every jit shape both configs use before the timed runs
    eng.run_offline(prompts, budgets)
    Engine(cfg, chunked, params).run_offline(prompts, budgets)

    res_mono, m_mono = Engine(cfg, base, params).run_offline(prompts, budgets)
    res_chnk, m_chnk = Engine(cfg, chunked, params).run_offline(prompts,
                                                               budgets)
    ref, _ = generate_static(cfg, params, prompts, budgets, base,
                             batch_size=1)
    match = ([r.tokens for r in res_mono] == ref
             and [r.tokens for r in res_chnk] == ref)

    def short_ttft(results, q):
        return float(np.percentile(
            [r.ttft for r in results if r.rid in short_rids], q))

    out = {
        "arch": cfg.name,
        "long_len": long_len,
        "n_short": n_short,
        "prefill_chunk_tokens": chunk,
        "tokens_match_static": match,
        "monolithic": {
            "ttft_short_p50_s": short_ttft(res_mono, 50),
            "ttft_short_p95_s": short_ttft(res_mono, 95),
            "decode_stall_ms_p50": m_mono["decode_stall_ms_p50"],
            "decode_stall_ms_p95": m_mono["decode_stall_ms_p95"],
            "decode_stall_ms_max": m_mono["decode_stall_ms_max"],
            "prefill_padded_tokens": m_mono["prefill_padded_tokens"],
            "prefill_actual_tokens": m_mono["prefill_actual_tokens"],
            "prefill_padding_waste": m_mono["prefill_padding_waste"],
            "tokens_per_s": m_mono["tokens_per_s"],
        },
        "chunked": {
            "ttft_short_p50_s": short_ttft(res_chnk, 50),
            "ttft_short_p95_s": short_ttft(res_chnk, 95),
            "decode_stall_ms_p50": m_chnk["decode_stall_ms_p50"],
            "decode_stall_ms_p95": m_chnk["decode_stall_ms_p95"],
            "decode_stall_ms_max": m_chnk["decode_stall_ms_max"],
            "chunked_prefill_steps": m_chnk["chunked_prefill_steps"],
            "prefill_padded_tokens": m_chnk["prefill_padded_tokens"],
            "prefill_actual_tokens": m_chnk["prefill_actual_tokens"],
            "prefill_padding_waste": m_chnk["prefill_padding_waste"],
            "tokens_per_s": m_chnk["tokens_per_s"],
        },
    }
    out["ttft_short_p50_ratio"] = (
        out["monolithic"]["ttft_short_p50_s"]
        / max(out["chunked"]["ttft_short_p50_s"], 1e-9))
    out["decode_stall_max_ratio"] = (
        out["monolithic"]["decode_stall_ms_max"]
        / max(out["chunked"]["decode_stall_ms_max"], 1e-9))
    print(f"serve_throughput,adversarial,long={long_len},chunk={chunk},"
          f"ttft_short_p50_ms="
          f"{out['monolithic']['ttft_short_p50_s']*1e3:.1f}"
          f"->{out['chunked']['ttft_short_p50_s']*1e3:.1f}"
          f" (x{out['ttft_short_p50_ratio']:.1f}),"
          f"stall_max_ms={out['monolithic']['decode_stall_ms_max']:.1f}"
          f"->{out['chunked']['decode_stall_ms_max']:.1f},match={match}")
    return out


def poisson_openloop(arch: str = "qwen2-0.5b", requests: int = 16,
                     slots: int = 4, gen: int = 8, prompt_lo: int = 4,
                     prompt_hi: int = 24, rate_scale: float = 0.7,
                     slo_scale: float = 2.0, seed: int = 0,
                     attn_backend: str = "auto"):
    """Open-loop Poisson arrivals through the async streaming front-end.

    Unlike the closed-loop sections (all requests offered at t=0), arrivals
    here follow an exponential inter-arrival clock that does NOT wait for
    the server — the serving regime of the paper's "millions of users"
    deployment.  Each request carries TTFT and TPOT deadlines calibrated on
    this machine (``slo_scale`` x the warm closed-loop p50s — absolute
    deadlines would be meaningless on an arbitrary CI box); the offered
    rate is ``rate_scale`` x the warm closed-loop request throughput, i.e.
    below saturation so attainment is expected high.  Reports **goodput**
    (tokens from SLO-meeting requests per second — tokens that merely
    arrive late count for nothing), SLO attainment, and TTFT/TPOT
    percentiles, with every streamed token checked exact against the
    static single-request baseline."""
    import asyncio
    import dataclasses as _dc

    from repro.configs import ServeConfig, get_arch, reduced
    from repro.serving import Engine, ServingLoop, generate_static

    cfg = _dc.replace(reduced(get_arch(arch)), remat="none")
    rng = np.random.RandomState(seed)
    ps = 16
    max_len = ((prompt_hi + gen + ps - 1) // ps) * ps
    scfg = ServeConfig(page_size=ps, max_slots=slots, max_len=max_len,
                       attn_backend=attn_backend)
    prompts = [rng.randint(1, cfg.vocab, size=int(
        rng.randint(prompt_lo, prompt_hi + 1))).tolist()
        for _ in range(requests)]
    budgets = [gen] * requests

    # warm every jit shape AND calibrate the machine: the closed-loop run's
    # ttft/decode-step p50s set the deadlines, its request rate the load
    warm_eng = Engine(cfg, scfg, seed=seed)
    params = warm_eng.params
    _, warm = warm_eng.run_offline(prompts, budgets)
    ttft_slo_s = slo_scale * max(warm["ttft_p50_s"], 1e-3)
    tpot_slo_s = slo_scale * max(warm["decode_step_ms_p50"] / 1e3, 1e-4)
    offered_rate = rate_scale * max(warm["requests_per_s"], 1e-9)
    arrivals = np.cumsum(rng.exponential(1.0 / offered_rate, size=requests))

    eng = Engine(cfg, scfg, params)
    serving = ServingLoop(eng, overlap=True)

    async def client(i: int, t0: float):
        delay = t0 + arrivals[i] - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        t_submit = time.perf_counter()
        rid, q = serving.submit(prompts[i], budgets[i])
        toks, t_first = [], None
        while True:
            ev = await q.get()
            if ev["type"] == "token":
                if t_first is None:
                    t_first = time.perf_counter()
                toks.append(ev["token"])
            elif ev["type"] in ("done", "error"):
                serving.forget(rid)
                t_done = time.perf_counter()
                t_first = t_first if t_first is not None else t_done
                return {
                    "i": i, "tokens": toks,
                    "ok": ev["type"] == "done",
                    "ttft_s": t_first - t_submit,
                    "tpot_s": ((t_done - t_first)
                               / max(len(toks) - 1, 1)),
                    "latency_s": t_done - t_submit}

    async def drive():
        await serving.start()
        t0 = time.perf_counter()
        rows = await asyncio.gather(*[client(i, t0)
                                      for i in range(requests)])
        wall = time.perf_counter() - t0
        await serving.stop()
        return rows, wall

    rows, wall = asyncio.run(drive())
    rows.sort(key=lambda r: r["i"])
    ref, _ = generate_static(cfg, params, prompts, budgets, scfg,
                             batch_size=1, seed=seed)
    match = all(r["ok"] for r in rows) \
        and [r["tokens"] for r in rows] == ref
    met = [r for r in rows
           if r["ttft_s"] <= ttft_slo_s and r["tpot_s"] <= tpot_slo_s]
    good_tokens = sum(len(r["tokens"]) for r in met)
    ttfts = [r["ttft_s"] for r in rows]
    tpots = [r["tpot_s"] for r in rows]
    out = {
        "arch": cfg.name,
        "requests": requests,
        "offered_rate_req_s": float(offered_rate),
        "ttft_slo_s": float(ttft_slo_s),
        "tpot_slo_s": float(tpot_slo_s),
        "wall_s": wall,
        "tokens_match_static": match,
        "tokens_per_s": sum(len(r["tokens"]) for r in rows)
        / max(wall, 1e-9),
        "goodput_tokens_per_s": good_tokens / max(wall, 1e-9),
        "slo_attainment": len(met) / max(requests, 1),
        "ttft_attainment": (sum(r["ttft_s"] <= ttft_slo_s for r in rows)
                            / max(requests, 1)),
        "tpot_attainment": (sum(r["tpot_s"] <= tpot_slo_s for r in rows)
                            / max(requests, 1)),
        "ttft_p50_s": float(np.percentile(ttfts, 50)),
        "ttft_p95_s": float(np.percentile(ttfts, 95)),
        "tpot_p50_s": float(np.percentile(tpots, 50)),
        "tpot_p95_s": float(np.percentile(tpots, 95)),
        "overlap_staged": eng.metrics.value("engine.overlap_staged"),
        "overlap_used": eng.metrics.value("engine.overlap_used"),
        "overlap_dropped": eng.metrics.value("engine.overlap_dropped"),
    }
    print(f"serve_throughput,poisson,rate={offered_rate:.2f}req/s,"
          f"goodput_tok_s={out['goodput_tokens_per_s']:.1f},"
          f"slo_attainment={out['slo_attainment']:.2f},"
          f"ttft_p95_ms={out['ttft_p95_s']*1e3:.1f},"
          f"overlap_used={out['overlap_used']}/{out['overlap_staged']},"
          f"match={match}")
    return out


def overload(arch: str = "qwen2-0.5b", requests: int = 16,
             slots: int = 4, gen: int = 8, prompt_lo: int = 4,
             prompt_hi: int = 24, rate_scale: float = 1.5,
             deadline_scale: float = 3.0, seed: int = 0,
             attn_backend: str = "auto"):
    """Overload section: deadline goodput at 1.5x the calibrated rate,
    with vs without admission control.

    The open-loop Poisson workload is offered at ``rate_scale`` x the warm
    closed-loop request rate — past saturation, so a queue *must* build —
    with per-request total deadlines at ``deadline_scale`` x the warm p50
    latency.  Served twice with identical arrivals:

    * **admission off**: every request is accepted; late ones burn slots
      and pages producing tokens that count for nothing;
    * **admission on** (``ServeConfig.admission_control``): requests whose
      calibrated queue-wait estimate blows the deadline are shed at the
      door with a ``retry_after_s`` backoff hint, and expired requests are
      evicted mid-flight.

    Reports **goodput** (tokens from deadline-meeting requests per second),
    shed rate, deadline attainment, and the terminal accounting the
    fault-tolerance contract requires: every submission ends in
    ``finished`` / ``shed`` / ``deadline_exceeded`` (``unaccounted`` must
    be 0).  ``overload_goodput_tokens_per_s`` (admission on) lands in the
    history; `check_regression` gates a >20% drop."""
    import asyncio
    import dataclasses as _dc

    from repro.configs import ServeConfig, get_arch, reduced
    from repro.serving import Engine, ServingLoop

    cfg = _dc.replace(reduced(get_arch(arch)), remat="none")
    rng = np.random.RandomState(seed)
    ps = 16
    max_len = ((prompt_hi + gen + ps - 1) // ps) * ps
    base = ServeConfig(page_size=ps, max_slots=slots, max_len=max_len,
                       attn_backend=attn_backend)
    prompts = [rng.randint(1, cfg.vocab, size=int(
        rng.randint(prompt_lo, prompt_hi + 1))).tolist()
        for _ in range(requests)]
    budgets = [gen] * requests

    # warm the jit shapes and calibrate: deadlines and the offered rate are
    # machine-relative, absolute numbers would be meaningless on CI
    warm_eng = Engine(cfg, base, seed=seed)
    params = warm_eng.params
    _, warm = warm_eng.run_offline(prompts, budgets)
    deadline_s = deadline_scale * max(warm["latency_p50_s"], 1e-3)
    offered_rate = rate_scale * max(warm["requests_per_s"], 1e-9)
    arrivals = np.cumsum(rng.exponential(1.0 / offered_rate, size=requests))

    def serve_once(admission: bool):
        scfg = dataclasses.replace(base, admission_control=admission)
        eng = Engine(cfg, scfg, params)
        serving = ServingLoop(eng, overlap=True)

        async def client(i: int, t0: float):
            delay = t0 + arrivals[i] - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            t_submit = time.perf_counter()
            rid, q = serving.submit(prompts[i], budgets[i],
                                    deadline_s=deadline_s)
            toks = []
            while True:
                ev = await q.get()
                if ev["type"] == "token":
                    toks.append(ev["token"])
                    continue
                serving.forget(rid)
                err = ev.get("error", "") if ev["type"] == "error" else ""
                if ev["type"] == "done":
                    terminal = "finished"
                elif "shed" in err:
                    terminal = "shed"
                elif "deadline_exceeded" in err:
                    terminal = "deadline_exceeded"
                else:
                    terminal = f"other:{err}"
                return {"i": i, "tokens": toks, "terminal": terminal,
                        "retry_after_s": float(ev.get("retry_after_s", 0.0)),
                        "latency_s": time.perf_counter() - t_submit}

        async def drive():
            await serving.start()
            t0 = time.perf_counter()
            rows = await asyncio.gather(*[client(i, t0)
                                          for i in range(requests)])
            wall = time.perf_counter() - t0
            await serving.stop()
            return rows, wall

        rows, wall = asyncio.run(drive())
        met = [r for r in rows
               if r["terminal"] == "finished" and r["latency_s"] <= deadline_s]
        sheds = [r for r in rows if r["terminal"] == "shed"]
        evicted = [r for r in rows if r["terminal"] == "deadline_exceeded"]
        finished = [r for r in rows if r["terminal"] == "finished"]
        unaccounted = requests - len(finished) - len(sheds) - len(evicted)
        return {
            "wall_s": wall,
            "tokens_per_s": sum(len(r["tokens"]) for r in rows)
            / max(wall, 1e-9),
            "goodput_tokens_per_s": sum(len(r["tokens"]) for r in met)
            / max(wall, 1e-9),
            "deadline_attainment": len(met) / max(requests, 1),
            "shed_rate": len(sheds) / max(requests, 1),
            "evicted_rate": len(evicted) / max(requests, 1),
            "unaccounted": unaccounted,
            "sheds_with_backoff_hint": sum(
                r["retry_after_s"] > 0 for r in sheds),
            "deadline_evictions": eng.metrics.value(
                "engine.deadline_evictions"),
            "shed_total": len(sheds),
        }

    out = {
        "arch": cfg.name,
        "requests": requests,
        "offered_rate_req_s": float(offered_rate),
        "deadline_s": float(deadline_s),
        "without_admission": serve_once(False),
        "with_admission": serve_once(True),
    }
    w, wo = out["with_admission"], out["without_admission"]
    out["goodput_ratio"] = (w["goodput_tokens_per_s"]
                            / max(wo["goodput_tokens_per_s"], 1e-9))
    out["terminal_accounting_ok"] = (
        w["unaccounted"] == 0 and wo["unaccounted"] == 0
        and w["sheds_with_backoff_hint"] == w["shed_total"])
    print(f"serve_throughput,overload,rate={offered_rate:.2f}req/s,"
          f"deadline_ms={deadline_s*1e3:.0f},"
          f"goodput_tok_s={wo['goodput_tokens_per_s']:.1f}"
          f"->{w['goodput_tokens_per_s']:.1f}"
          f" (x{out['goodput_ratio']:.2f}),"
          f"shed_rate={w['shed_rate']:.2f},"
          f"attainment={wo['deadline_attainment']:.2f}"
          f"->{w['deadline_attainment']:.2f},"
          f"accounting_ok={out['terminal_accounting_ok']}")
    return out


def quantization(arch: str = "qwen2-0.5b", requests: int = 8,
                 slots: int = 4, gen: int = 8, prompt_lo: int = 8,
                 prompt_hi: int = 24, pool_budget_mib: float = 64.0,
                 seed: int = 0, attn_backend: str = "auto"):
    """Quantized-KV section: int8 paged pool vs bf16 on the same workload.

    Serves one mixed-length closed-loop workload twice — ``kv_dtype=bf16``
    and ``kv_dtype=int8`` (same params, same backend, both warmed) — and
    reports the three numbers the int8 mode is judged on:

    * ``kv_bytes_per_token`` both ways (int8 pages + bf16 per-page scales
      vs bf16 pages; the acceptance bar is a ratio <= 0.55x);
    * decode throughput both ways (tokens/s and decode-step p50 — the HBM
      gather moves half the bytes, so int8 must not be slower);
    * max concurrent residency at a *fixed pool byte budget*: how many
      max-length requests fit if the whole pool is capped at
      ``pool_budget_mib`` — the capacity win quantization buys (bar:
      >= 1.8x).

    The int8 run's tokens then go through the dual-gate verifier
    (``serving.quant_verify``): bounded max-abs logit error vs a bf16
    replay plus exact greedy match at high-margin positions.  The error
    stats land in the payload so the quantization noise level is tracked
    run-over-run alongside throughput."""
    import dataclasses as _dc

    from repro.configs import ServeConfig, get_arch, reduced
    from repro.serving import Engine, dual_gate_verify

    cfg = _dc.replace(reduced(get_arch(arch)), remat="none")
    rng = np.random.RandomState(seed)
    ps = 16
    max_len = ((prompt_hi + gen + ps - 1) // ps) * ps
    base = ServeConfig(page_size=ps, max_slots=slots, max_len=max_len,
                       attn_backend=attn_backend)
    int8 = dataclasses.replace(base, kv_dtype="int8")
    prompts = [rng.randint(1, cfg.vocab, size=int(
        rng.randint(prompt_lo, prompt_hi + 1))).tolist()
        for _ in range(requests)]
    budgets = [gen] * requests

    eng_b = Engine(cfg, base, seed=seed)
    params = eng_b.params
    if not eng_b.pool.spec.paged:
        return {"arch": cfg.name, "skipped":
                "kv_dtype only applies to paged attention families"}
    # warm every jit shape for both dtypes before the timed runs
    eng_b.run_offline(prompts, budgets)
    Engine(cfg, int8, params).run_offline(prompts, budgets)

    _, m_b = Engine(cfg, base, params).run_offline(prompts, budgets)
    eng_i = Engine(cfg, int8, params)
    res_i, m_i = eng_i.run_offline(prompts, budgets)

    # capacity at a fixed byte budget: page_nbytes counts payload AND scale
    # leaves for int8 (a page id owns its slice of both), so the residency
    # ratio is the honest capacity win, not payload-only accounting
    pages_req = eng_b.pool.pages_for(prompt_hi + gen)
    budget = int(pool_budget_mib * 2 ** 20)
    resident_b = budget // (eng_b.pool.page_nbytes * pages_req)
    resident_i = budget // (eng_i.pool.page_nbytes * pages_req)

    report = dual_gate_verify(cfg, int8, params, prompts,
                              [r.tokens for r in res_i],
                              attn_backend=m_i["attn_backend"])
    verify = {k: v for k, v in report.items() if k != "per_request"}
    verify["per_request_max_err"] = [r["max_err"]
                                    for r in report["per_request"]]

    out = {
        "arch": cfg.name,
        "attn_backend": m_i["attn_backend"],
        "requests": requests,
        "bf16": {
            "kv_bytes_per_token": eng_b.pool.kv_bytes_per_token,
            "page_nbytes": eng_b.pool.page_nbytes,
            "tokens_per_s": m_b["tokens_per_s"],
            "decode_step_ms_p50": m_b["decode_step_ms_p50"],
        },
        "int8": {
            "kv_bytes_per_token": eng_i.pool.kv_bytes_per_token,
            "page_nbytes": eng_i.pool.page_nbytes,
            "tokens_per_s": m_i["tokens_per_s"],
            "decode_step_ms_p50": m_i["decode_step_ms_p50"],
        },
        "kv_bytes_ratio": (eng_i.pool.kv_bytes_per_token
                           / max(eng_b.pool.kv_bytes_per_token, 1e-9)),
        "tokens_per_s_ratio": (m_i["tokens_per_s"]
                               / max(m_b["tokens_per_s"], 1e-9)),
        "pool_budget_mib": pool_budget_mib,
        "pages_per_request": pages_req,
        "max_resident_bf16": int(resident_b),
        "max_resident_int8": int(resident_i),
        "residency_ratio": resident_i / max(resident_b, 1),
        "quant_verify": verify,
        "dual_gate_ok": report["ok"],
    }
    print(f"serve_throughput,quantization,arch={cfg.name},"
          f"kv_bytes_per_token={out['bf16']['kv_bytes_per_token']:.0f}"
          f"->{out['int8']['kv_bytes_per_token']:.0f}"
          f" (x{out['kv_bytes_ratio']:.3f}),"
          f"tok_s={out['bf16']['tokens_per_s']:.1f}"
          f"->{out['int8']['tokens_per_s']:.1f},"
          f"residency={out['max_resident_bf16']}"
          f"->{out['max_resident_int8']}"
          f" (x{out['residency_ratio']:.2f})")
    print(f"serve_throughput,quantization,max_logit_err="
          f"{verify['max_logit_err']:.4f} (tol {verify['tol']:.2f}),"
          f"high_margin_mismatches={verify['high_margin_mismatches']}/"
          f"{verify['high_margin_tokens']},"
          f"dual_gate_ok={report['ok']}")
    return out


def speculation(arch: str = "qwen2-0.5b", requests: int = 1, slots: int = 1,
                gen: int = 64, spec_tokens: int = 4, seed: int = 0,
                attn_backend: str = "auto"):
    """Speculative-decoding section: n-gram drafts + small-q verify.

    Two workloads bracket the proposer's range, both decoded with
    ``speculate_tokens`` on and off (same params, same backend, warmed):

    * ``repetitive`` — periodic prompts (a short token motif repeated), the
      greedy continuation keeps the period, so prompt lookup drafts the
      right tokens nearly every step: the best case the ISSUE acceptance
      bar reads (``decode speedup >= 1.5``);
    * ``adversarial`` — i.i.d. uniform-random prompts: trailing n-grams of
      the *prompt* almost never recur, so early drafts are empty/rejected
      and the section bounds speculation overhead (``speedup >= 0.95``).

    The section pins the regime speculation actually targets: the
    latency-bound single stream (``requests = slots = 1``).  Speculation
    trades extra verify FLOPs for fewer sequential steps, so it wins where
    a decode step's cost is dominated by per-step fixed work (dispatch,
    gather, host scheduling) rather than per-row math; at batch >= 4 on a
    compute-bound host each verify row costs as much as a decode row and
    the win collapses toward 1x — batched throughput serving is already
    covered by the other sections.  Speculation also only changes the
    *decode* loop, so the headline ``speedup`` is decode-phase-attributed:
    with one admission wave (``requests <= slots``) every request decodes
    from one batched prefill, and ``decode_tokens_per_s`` divides
    post-first-token tokens by the window from the earliest first token to
    the last finish (arrival-relative stamps share an epoch —
    ``run_offline`` queues all requests up front).  Whole-run
    ``tokens_per_s`` is reported alongside (``speedup_total``) but dilutes
    the win with prefill/admission time.

    Both runs are exact-token-checked against the non-speculative engine —
    greedy accept means speculation may only change launch count, never
    tokens."""
    import dataclasses as _dc

    from repro.configs import ServeConfig, get_arch, reduced
    from repro.serving import Engine

    cfg = _dc.replace(reduced(get_arch(arch)), remat="none")
    rng = np.random.RandomState(seed)
    ps = 16
    motif = rng.randint(1, cfg.vocab, size=6).tolist()
    workloads = {
        "repetitive": [motif * 4 + rng.randint(
            1, cfg.vocab, size=2).tolist() for _ in range(requests)],
        "adversarial": [rng.randint(1, cfg.vocab, size=26).tolist()
                        for _ in range(requests)],
    }
    max_len = ((26 + gen + ps - 1) // ps) * ps
    base = ServeConfig(page_size=ps, max_slots=slots, max_len=max_len,
                       attn_backend=attn_backend)
    spec = dataclasses.replace(base, speculate_tokens=spec_tokens)

    eng = Engine(cfg, base, seed=seed)
    params = eng.params
    if not Engine(cfg, spec, params).spec_k:
        return {"arch": cfg.name, "skipped":
                "speculation needs a paged non-enc-dec cache family"}
    # warm every jit shape (incl. the small-q verify step) for both configs
    for prompts in workloads.values():
        Engine(cfg, base, params).run_offline(prompts, gen)
        Engine(cfg, spec, params).run_offline(prompts, gen)

    out = {"arch": cfg.name, "spec_tokens": spec_tokens,
           "attn_backend": "", "requests": requests}
    def _decode_tok_s(res):
        # post-first-token tokens over the concurrent decode window
        window = (max(r.finish_s for r in res)
                  - min(r.ttft_s for r in res))
        return sum(len(r.tokens) - 1 for r in res) / max(window, 1e-9)

    for name, prompts in workloads.items():
        res_b, m_b = Engine(cfg, base, params).run_offline(prompts, gen)
        res_s, m_s = Engine(cfg, spec, params).run_offline(prompts, gen)
        match = ([r.tokens for r in res_s] == [r.tokens for r in res_b])
        out["attn_backend"] = m_s["attn_backend"]
        dec_b, dec_s = _decode_tok_s(res_b), _decode_tok_s(res_s)
        out[name] = {
            "tokens_per_s_base": m_b["tokens_per_s"],
            "tokens_per_s_spec": m_s["tokens_per_s"],
            "decode_tokens_per_s_base": dec_b,
            "decode_tokens_per_s_spec": dec_s,
            "speedup": dec_s / max(dec_b, 1e-9),
            "speedup_total": (m_s["tokens_per_s"]
                              / max(m_b["tokens_per_s"], 1e-9)),
            "spec_proposed": m_s["spec_proposed"],
            "spec_accepted": m_s["spec_accepted"],
            "accept_rate": m_s["spec_accept_rate"],
            "tokens_match": match,
        }
        print(f"serve_throughput,speculation,{name},K={spec_tokens},"
              f"decode_tok_s={dec_b:.1f}->{dec_s:.1f}"
              f" (x{out[name]['speedup']:.2f}),"
              f"total x{out[name]['speedup_total']:.2f},"
              f"accept_rate={m_s['spec_accept_rate']:.2f},match={match}")
    return out


# one reduced arch per cache family (see src/repro/models/cache_spec.py)
FAMILY_MATRIX = (
    ("paged_kv", "qwen2-0.5b"),
    ("paged_mla", "deepseek-v2-236b"),
    ("windowed_kv", "starcoder2-7b"),
    ("state_slot_ssm", "mamba2-780m"),
    ("state_slot_hybrid", "recurrentgemma-2b"),
    ("cross_kv_encdec", "seamless-m4t-large-v2"),
)


def family_matrix(requests: int = 8, slots: int = 4, gen: int = 16,
                  seed: int = 0, attn_backend: str = "auto"):
    """Continuous-vs-static throughput for one arch per cache family.

    Every family runs the same mixed-length workload; tokens are checked
    exact against the single-request static baseline (the verify contract
    the engine upholds for every family), and the timed static path uses
    the same concurrency cap as the engine."""
    import dataclasses as _dc

    from repro.configs import ServeConfig, get_arch, reduced
    from repro.serving import Engine, generate_static

    rng = np.random.RandomState(seed)
    lens = [int(rng.randint(6, 28)) for _ in range(requests)]
    # head-of-line mix: one long-form generation per arrival group of
    # ``slots`` — the static batch stalls on it, continuous backfills
    budgets = [gen * 4 if i % slots == slots - 1 else max(gen // 4, 2)
               for i in range(requests)]
    out = {}
    for family, arch in FAMILY_MATRIX:
        cfg = _dc.replace(reduced(get_arch(arch)), remat="none")
        ps = 8
        max_len = ((max(lens) + max(budgets) + ps - 1) // ps) * ps
        scfg = ServeConfig(page_size=ps, max_slots=slots, max_len=max_len,
                           attn_backend=attn_backend)
        prompts = [rng.randint(1, cfg.vocab, size=n).tolist() for n in lens]
        eng = Engine(cfg, scfg, seed=seed)
        params = eng.params
        # warm every jit shape both paths will use — the exact workload,
        # since batched prefill admission makes the prefill shapes
        # (bucket, pow2 batch rows) depend on budgets too
        eng.run_offline(prompts, budgets)
        generate_static(cfg, params, prompts, budgets, scfg,
                        batch_size=slots, seed=seed)
        results, cont_m = Engine(cfg, scfg, params,
                                 seed=seed).run_offline(prompts, budgets)
        _, static_m = generate_static(cfg, params, prompts, budgets, scfg,
                                      batch_size=slots, seed=seed)
        ref, _ = generate_static(cfg, params, prompts, budgets, scfg,
                                 batch_size=1, seed=seed)
        out[family] = {
            "arch": cfg.name,
            "tokens_match_static": [r.tokens for r in results] == ref,
            "tokens_per_s": cont_m["tokens_per_s"],
            "static_tokens_per_s": static_m["tokens_per_s"],
            "speedup_tokens_per_s": (cont_m["tokens_per_s"]
                                     / max(static_m["tokens_per_s"], 1e-9)),
            "ttft_p50_s": cont_m["ttft_p50_s"],
            "multi_admit_prefills": cont_m["multi_admit_prefills"],
            "attn_backend": cont_m["attn_backend"],
            "decode_step_ms_p50": cont_m["decode_step_ms_p50"],
            "decode_step_ms_p95": cont_m["decode_step_ms_p95"],
        }
        print(f"serve_throughput,family={family},arch={cfg.name},"
              f"cont_tok_s={cont_m['tokens_per_s']:.1f},"
              f"static_tok_s={static_m['tokens_per_s']:.1f},"
              f"ttft_p50_ms={cont_m['ttft_p50_s']*1e3:.1f},"
              f"match={out[family]['tokens_match_static']}")
    return out


def run(arch: str = "qwen2-0.5b", requests: int = 16, slots: int = 4,
        families: int = 4, prefix_len: int = 24, suffix_lo: int = 4,
        suffix_hi: int = 24, gen_short: int = 4, gen_long: int = 128,
        seed: int = 0, out: str = "BENCH_serve.json",
        attn_backend: str = "auto", chunk: int = 256,
        adversarial_long: int = 2048):
    from repro.configs import ServeConfig, get_arch, reduced
    from repro.serving import Engine, generate_static

    cfg = dataclasses.replace(reduced(get_arch(arch)), remat="none")
    ps = 16
    max_len = ((prefix_len + suffix_hi + gen_long + ps - 1) // ps) * ps
    scfg = ServeConfig(page_size=ps, max_slots=slots, max_len=max_len,
                       attn_backend=attn_backend)
    scfg_cache = dataclasses.replace(scfg, prefix_cache=True)

    prompts, budgets = make_workload(cfg.vocab, requests, families,
                                     prefix_len, suffix_lo, suffix_hi, slots,
                                     gen_short, gen_long, seed)

    eng = Engine(cfg, scfg, seed=seed)
    params = eng.params

    # warm-up: replay the whole workload so every prefill shape — bucket
    # AND pow2 admission-batch rows, which depend on the budget mix now that
    # admission is batched — and decode step all three paths will use is
    # compiled before the timed runs (jitted steps are cached per
    # ArchConfig, so the timed engines below reuse these compilations)
    eng.run_offline(prompts, budgets)
    Engine(cfg, scfg_cache, params).run_offline(prompts, budgets)
    generate_static(cfg, params, prompts, budgets, scfg, batch_size=slots)

    # timed: static
    static_tokens, static_m = generate_static(
        cfg, params, prompts, budgets, scfg, batch_size=slots)

    # timed: continuous, prefix cache off (fresh pool, same params)
    results, cont_m = Engine(cfg, scfg, params).run_offline(prompts, budgets)

    # timed: continuous, prefix cache on; keep the engine around — its
    # metrics-registry snapshot (pool occupancy, radix hit accounting,
    # admission/preemption counters) goes into the payload
    eng_c = Engine(cfg, scfg_cache, params)
    results_c, cache_m = eng_c.run_offline(prompts, budgets)

    match = ([r.tokens for r in results] == static_tokens
             and [r.tokens for r in results_c] == static_tokens)
    speedup = cont_m["tokens_per_s"] / max(static_m["tokens_per_s"], 1e-9)
    cache_speedup = (cache_m["tokens_per_s"]
                     / max(cont_m["tokens_per_s"], 1e-9))
    payload = {
        "arch": cfg.name,
        "requests": requests,
        "concurrency": slots,
        # resolved backend + decode-step percentiles also sit inside each
        # engine metrics dict; top-level copy for easy trajectory diffing
        "attn_backend": cont_m["attn_backend"],
        "decode_step_ms_p50": cont_m["decode_step_ms_p50"],
        "decode_step_ms_p95": cont_m["decode_step_ms_p95"],
        "prefix_families": families,
        "prefix_len": prefix_len,
        "prompt_lens": [len(p) for p in prompts],
        "token_budgets": budgets,
        "tokens_match_static": match,
        "static": static_m,
        "continuous": cont_m,
        "continuous_prefix_cache": cache_m,
        # full registry snapshot of the prefix-cache run: every pool /
        # radix / scheduler / engine counter-gauge-histogram in one place
        "telemetry_prefix_cache": eng_c.metrics_snapshot(),
        "speedup_tokens_per_s": speedup,
        "prefix_cache_speedup_tokens_per_s": cache_speedup,
        "prefix_cache_prefill_tokens_saved":
            cont_m["prefill_tokens"] - cache_m["prefill_tokens"],
        "prefix_cache_ttft_p50_ratio":
            cache_m["ttft_p50_s"] / max(cont_m["ttft_p50_s"], 1e-9),
        "cache_families": family_matrix(slots=slots, seed=seed,
                                        attn_backend=attn_backend),
        "chunked_prefill": adversarial_mix(
            arch=arch, slots=slots, long_len=adversarial_long, chunk=chunk,
            seed=seed, attn_backend=attn_backend),
        "poisson_openloop": poisson_openloop(
            arch=arch, requests=requests, slots=slots, seed=seed,
            attn_backend=attn_backend),
        "overload": overload(
            arch=arch, requests=requests, slots=slots, seed=seed,
            attn_backend=attn_backend),
        "quantization": quantization(
            arch=arch, slots=slots, seed=seed, attn_backend=attn_backend),
        # speculation keeps its own single-stream defaults (see docstring):
        # the latency regime it targets, not the batched-throughput one
        "speculation": speculation(
            arch=arch, seed=seed, attn_backend=attn_backend),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), out) if not os.path.isabs(out) else out
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    # append-style perf trajectory: one summary line per benchmark run, so
    # regressions show as a series instead of a silent overwrite
    adv = payload["chunked_prefill"]
    poi = payload["poisson_openloop"]
    ovl = payload["overload"]
    quant = payload["quantization"]
    spec = payload["speculation"]
    with open(os.path.join(os.path.dirname(path), "BENCH_history.jsonl"),
              "a") as f:
        # kv_dtype and spec_tokens are part of every line so
        # check_regression groups never mix modes — an int8 or speculative
        # run must not drag down the bf16 non-speculative baseline
        # (or vice versa)
        f.write(json.dumps({
            "timestamp": payload["timestamp"],
            "arch": payload["arch"],
            "attn_backend": payload["attn_backend"],
            "kv_dtype": "bf16",
            "spec_tokens": 0,
            "tokens_per_s_static": static_m["tokens_per_s"],
            "tokens_per_s_continuous": cont_m["tokens_per_s"],
            "tokens_per_s_prefix_cache": cache_m["tokens_per_s"],
            "decode_step_ms_p50": cont_m["decode_step_ms_p50"],
            "ttft_p50_s": cont_m["ttft_p50_s"],
            "cache_hit_rate": cache_m["cache_hit_rate"],
            "decode_stall_ms_max": cont_m["decode_stall_ms_max"],
            "prefill_padding_waste": cont_m["prefill_padding_waste"],
            "adversarial_ttft_short_p50_ratio": adv["ttft_short_p50_ratio"],
            "adversarial_stall_max_ratio": adv["decode_stall_max_ratio"],
            "poisson_goodput_tokens_per_s": poi["goodput_tokens_per_s"],
            "poisson_slo_attainment": poi["slo_attainment"],
            "poisson_ttft_p95_s": poi["ttft_p95_s"],
            "overload_goodput_tokens_per_s":
                ovl["with_admission"]["goodput_tokens_per_s"],
            "overload_shed_rate": ovl["with_admission"]["shed_rate"],
            "overload_deadline_attainment":
                ovl["with_admission"]["deadline_attainment"],
            "overload_accounting_ok": ovl["terminal_accounting_ok"],
            **({"kv_bytes_per_token":
                quant["bf16"]["kv_bytes_per_token"]}
               if "bf16" in quant else {}),
            "tokens_match": bool(match and adv["tokens_match_static"]
                                 and poi["tokens_match_static"]),
        }) + "\n")
        if "int8" in quant:
            # second trajectory line for the quantized mode: its own
            # (arch, backend, kv_dtype=int8) group gates int8 throughput
            # and bytes/token without polluting the bf16 series
            f.write(json.dumps({
                "timestamp": payload["timestamp"],
                "arch": payload["arch"],
                "attn_backend": quant["attn_backend"],
                "kv_dtype": "int8",
                "spec_tokens": 0,
                "tokens_per_s_continuous":
                    quant["int8"]["tokens_per_s"],
                "decode_step_ms_p50":
                    quant["int8"]["decode_step_ms_p50"],
                "kv_bytes_per_token":
                    quant["int8"]["kv_bytes_per_token"],
                "max_logit_err": quant["quant_verify"]["max_logit_err"],
                "tokens_match": bool(quant["dual_gate_ok"]),
            }) + "\n")
        if "repetitive" in spec:
            # third trajectory line for the speculative mode: its own
            # (arch, backend, kv_dtype, spec_tokens=K) group gates the
            # repetitive-workload speedup and the adversarial overhead
            f.write(json.dumps({
                "timestamp": payload["timestamp"],
                "arch": payload["arch"],
                "attn_backend": spec["attn_backend"],
                "kv_dtype": "bf16",
                "spec_tokens": spec["spec_tokens"],
                "tokens_per_s_continuous":
                    spec["repetitive"]["tokens_per_s_spec"],
                "spec_speedup_repetitive":
                    spec["repetitive"]["speedup"],
                "spec_speedup_adversarial":
                    spec["adversarial"]["speedup"],
                "spec_accept_rate_repetitive":
                    spec["repetitive"]["accept_rate"],
                "tokens_match":
                    bool(spec["repetitive"]["tokens_match"]
                         and spec["adversarial"]["tokens_match"]),
            }) + "\n")
    print(f"serve_throughput,arch={cfg.name},requests={requests},"
          f"concurrency={slots},families={families},"
          f"static_tok_s={static_m['tokens_per_s']:.1f},"
          f"cont_tok_s={cont_m['tokens_per_s']:.1f},"
          f"cache_tok_s={cache_m['tokens_per_s']:.1f},"
          f"speedup={speedup:.2f},cache_speedup={cache_speedup:.2f},"
          f"match={match}")
    print(f"serve_throughput,prefill_tokens="
          f"{cont_m['prefill_tokens']}->{cache_m['prefill_tokens']},"
          f"hit_rate={cache_m['cache_hit_rate']:.2f},"
          f"ttft_p50_ms={cont_m['ttft_p50_s']*1e3:.1f}"
          f"->{cache_m['ttft_p50_s']*1e3:.1f}")
    print(f"serve_throughput,wrote={path}")
    return payload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--families", type=int, default=4)
    ap.add_argument("--prefix-len", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--attn-backend",
                    choices=("auto", "reference", "pallas"), default="auto",
                    help="paged-attention backend for the continuous paths "
                         "(recorded in BENCH_serve.json)")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=256,
                    help="chunk budget for the adversarial long+short mix")
    ap.add_argument("--adversarial-long", type=int, default=2048,
                    help="long-prompt length for the adversarial mix")
    args = ap.parse_args()
    run(arch=args.arch, requests=args.requests, slots=args.slots,
        families=args.families, prefix_len=args.prefix_len,
        seed=args.seed, out=args.out, attn_backend=args.attn_backend,
        chunk=args.prefill_chunk_tokens,
        adversarial_long=args.adversarial_long)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
