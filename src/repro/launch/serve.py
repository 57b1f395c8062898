"""Serving CLI — a thin front-end over ``repro.serving``.

  # continuous batching (paged KV pool + request scheduler)
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --engine continuous --requests 16 --mixed --gen 16

  # static batching (contiguous caches, the pre-paging path)
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --engine static --requests 4 --prompt-len 32 --gen 16

  # radix prefix cache: share KV pages across requests with common prefixes
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --engine continuous --requests 16 --shared-prefix 4 --prefix-cache \
      --verify

``--verify`` additionally replays every request through the static
single-request baseline and checks the greedy tokens agree per request.

Chaos mode (``--inject``) runs the same workload under a deterministic
fault plan (see ``serving.faults``) and — with ``--verify`` — checks the
**exact-survivor contract**: every non-targeted request's tokens are
byte-identical to the fault-free static baseline, targeted requests fail
terminally with the expected error (their partial tokens a strict prefix
of the baseline), every planned fault actually fired, and the page pool
balances after drain::

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --requests 6 --mixed --gen 8 --verify \
      --inject "nan_logits:rid=2,at=3;pool_pressure:at=2,pages=8,steps=3"

Observability (continuous engine only)::

  # Chrome-trace JSON for Perfetto + full metrics-registry snapshot
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
      --engine continuous --requests 16 --mixed --verify \
      --trace trace.json --metrics-json metrics.json

then ``python -m repro.launch.trace_report trace.json`` for a time-in-phase
breakdown and per-request TTFT/TPOT table.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import numpy as np

from ..configs import ServeConfig, get_arch, reduced as make_reduced
from ..models.registry import build_model
from ..serving import Engine, Tracer, generate_static


def make_prompts(args, vocab: int):
    """Deterministic synthetic prompts; ``--mixed`` varies length + budget,
    ``--shared-prefix F`` draws each prompt as one of F family prefixes plus
    a unique suffix (the workload a prefix cache pays off on)."""
    rng = np.random.RandomState(args.seed)
    fams = [rng.randint(1, vocab, size=max(args.prompt_len // 2, 1)).tolist()
            for _ in range(args.shared_prefix)] if args.shared_prefix else []
    prompts, budgets = [], []
    for i in range(args.requests):
        if args.mixed:
            n = int(rng.randint(args.min_prompt_len, args.prompt_len + 1))
            g = int(rng.randint(max(1, args.gen // 4), args.gen + 1))
        else:
            n, g = args.prompt_len, args.gen
        if fams:
            fam = fams[i % len(fams)]
            tail = max(n - len(fam), 1)
            prompts.append(fam + rng.randint(1, vocab, size=tail).tolist())
        else:
            prompts.append(rng.randint(1, vocab, size=n).tolist())
        budgets.append(g)
    return prompts, budgets


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--engine", choices=("auto", "static", "continuous"),
                    default="auto",
                    help="auto: continuous when the arch's cache is pageable "
                         "(dense/GQA/MoE), else the static contiguous path")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of requests (static: also the batch size)")
    ap.add_argument("--batch", type=int, default=0,
                    help="static batch size / continuous max_slots "
                         "(0 -> min(requests, 8))")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--min-prompt-len", type=int, default=4)
    ap.add_argument("--mixed", action="store_true",
                    help="mixed prompt lengths and token budgets")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="F",
                    help="draw prompts from F shared prefix families "
                         "(0: every prompt independent)")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree prefix cache: share KV pages across "
                         "requests with common prompt prefixes")
    ap.add_argument("--cache-eviction", choices=("lru", "none"),
                    default="lru")
    ap.add_argument("--attn-backend", choices=("auto", "reference", "pallas"),
                    default="auto",
                    help="paged-attention backend for the continuous engine: "
                         "reference = XLA gather+attend, pallas = fused "
                         "paged-attention decode kernel (interpret mode on "
                         "CPU); auto picks pallas exactly on TPU")
    ap.add_argument("--kv-dtype", choices=("bf16", "int8"), default="bf16",
                    help="paged-KV storage dtype: int8 stores absmax-"
                         "quantized pages + per-token scale pools and "
                         "dequantizes inside the attend (half the decode "
                         "HBM bytes); --verify then checks the bounded-"
                         "error + high-margin dual gate instead of exact "
                         "token match")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=0,
                    help="per-step prefill token budget: long prompts split "
                         "into page-aligned chunks that interleave with "
                         "decode steps (0 = one monolithic prefill per "
                         "admission)")
    ap.add_argument("--speculate-tokens", type=int, default=0, metavar="K",
                    help="speculative decoding: draft up to K tokens per "
                         "slot from the request's own history (n-gram "
                         "prompt lookup) and verify them in one small-q "
                         "step; greedy accept keeps tokens identical to "
                         "non-speculative decode (0 = off)")
    ap.add_argument("--max-len", type=int, default=0,
                    help="per-request length cap (0 -> fitted to workload)")
    ap.add_argument("--overlap", action="store_true",
                    help="drive the overlapped host/device pipeline "
                         "(Engine.pump(): step N+1's host plan staged while "
                         "step N runs on device) instead of the synchronous "
                         "step loop; tokens are identical either way")
    ap.add_argument("--verify", action="store_true",
                    help="check tokens against the static single-request path")
    ap.add_argument("--trace", metavar="PATH", default="",
                    help="write the request-lifecycle trace as Chrome-trace-"
                         "event JSON (open in Perfetto / chrome://tracing)")
    ap.add_argument("--metrics-json", metavar="PATH", default="",
                    help="write the run metrics + full metrics-registry "
                         "snapshot as JSON")
    ap.add_argument("--jax-annotations", action="store_true",
                    help="also emit the engine loop's phases (admit, "
                         "dispatch: schedule/plan/upload/launch.<kind>, "
                         "stage, collect: sync.<kind>/emit, results) as "
                         "jax.profiler TraceAnnotations, visible on the "
                         "device trace's clock when a jax profiler trace "
                         "is also being captured")
    ap.add_argument("--inject", metavar="SPEC", default="",
                    help="deterministic fault plan, e.g. "
                         "'nan_logits:rid=2,at=3;step_error:rid=0,at=2'; "
                         "kinds: nan_logits, step_error, pool_pressure, "
                         "client_disconnect, detok_stall (continuous engine "
                         "only; combine with --verify for the exact-survivor "
                         "chaos check)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    cfg = dataclasses.replace(cfg, remat="none")

    slots = args.batch or min(args.requests, 8)
    ps = args.page_size
    max_len = args.max_len or ((args.prompt_len + args.gen + ps - 1) // ps) * ps
    scfg = ServeConfig(page_size=ps, max_slots=slots, max_len=max_len,
                       prefix_cache=args.prefix_cache,
                       cache_eviction=args.cache_eviction,
                       attn_backend=args.attn_backend,
                       prefill_chunk_tokens=args.prefill_chunk_tokens,
                       kv_dtype=args.kv_dtype,
                       speculate_tokens=args.speculate_tokens)

    prompts, budgets = make_prompts(args, cfg.vocab)

    engine = args.engine
    if engine == "auto":
        # every registered cache family pages now (see models.cache_spec);
        # auto is continuous across the board
        ok, _ = build_model(cfg).supports_paged_decode()
        engine = "continuous" if ok else "static"
    if engine == "static" and args.prefix_cache:
        print("[serve] WARNING: --prefix-cache only applies to the "
              "continuous engine; the static path serves without it")
    if engine == "static" and args.attn_backend != "auto":
        print("[serve] WARNING: --attn-backend only applies to the "
              "continuous engine; the static path uses contiguous caches")
    if engine == "static" and args.kv_dtype != "bf16":
        print("[serve] WARNING: --kv-dtype only applies to the continuous "
              "engine's paged pool; the static path serves bf16")
    if engine == "static" and (args.trace or args.jax_annotations):
        print("[serve] WARNING: --trace/--jax-annotations only apply to the "
              "continuous engine; no trace will be written")
    if engine == "static" and args.speculate_tokens:
        print("[serve] WARNING: --speculate-tokens only applies to the "
              "continuous engine; the static path decodes one token a step")
    plan = None
    if args.inject:
        if engine != "continuous":
            raise SystemExit("[serve] --inject requires the continuous "
                             "engine (faults target its seams)")
        from ..serving import FaultPlan
        plan = FaultPlan.parse(args.inject, seed=args.seed)
    eng = None
    if engine == "continuous":
        tracer = Tracer(jax_annotations=args.jax_annotations)
        eng = Engine(cfg, scfg, seed=args.seed,   # init_params inside
                     tracer=tracer, faults=plan)
        params = eng.params
        results, metrics = eng.run_offline(prompts, budgets,
                                           overlap=args.overlap)
        tokens = [r.tokens for r in results]
        ttft = [r.ttft for r in results]
        print(f"[serve] attention backend: {metrics['attn_backend']} "
              f"(decode step p50 {metrics['decode_step_ms_p50']:.1f} ms)")
        if args.overlap:
            print(f"[serve] overlap: "
                  f"{eng.metrics.value('engine.overlap_staged')} plans "
                  f"staged, {eng.metrics.value('engine.overlap_used')} used, "
                  f"{eng.metrics.value('engine.overlap_dropped')} dropped "
                  f"(host meta build hidden behind device steps)")
        if args.speculate_tokens and not eng.spec_k:
            print(f"[serve] WARNING: speculation disabled for {cfg.name}: "
                  f"cache family {eng.spec.describe()} has no paged small-q "
                  f"verify step; serving non-speculatively")
        elif eng.spec_k:
            print(f"[serve] speculation: K={eng.spec_k}, "
                  f"{metrics['spec_proposed']} drafted, "
                  f"{metrics['spec_accepted']} accepted "
                  f"(accept rate {metrics['spec_accept_rate']:.2f})")
        if args.prefill_chunk_tokens:
            print(f"[serve] chunked prefill: budget "
                  f"{scfg.chunk_tokens} tokens, "
                  f"{metrics['chunked_prefill_steps']} continuation chunks, "
                  f"padding waste {metrics['prefill_padding_waste']:.2f}, "
                  f"decode stall max "
                  f"{metrics['decode_stall_ms_max']:.1f} ms")
        print(f"[serve] {cfg.name} continuous: {metrics['n_requests']} reqs, "
              f"{metrics['new_tokens']} toks in {metrics['wall_s']*1e3:.1f} ms "
              f"({metrics['tokens_per_s']:.1f} tok/s, "
              f"{metrics['requests_per_s']:.2f} req/s); "
              f"latency p50 {metrics['latency_p50_s']*1e3:.1f} / "
              f"p95 {metrics['latency_p95_s']*1e3:.1f} ms; "
              f"ttft p50 {np.percentile(ttft, 50)*1e3:.1f} ms")
        if args.prefix_cache:
            print(f"[serve] prefix cache: {metrics['cached_tokens']}/"
                  f"{metrics['prompt_tokens']} prompt tokens served from "
                  f"cache (hit rate {metrics['cache_hit_rate']:.2f}, "
                  f"prefilled {metrics['prefill_tokens']})")
    else:
        from ..models.registry import init_params
        import jax
        params = init_params(cfg, jax.random.PRNGKey(args.seed))
        tokens, metrics = generate_static(cfg, params, prompts, budgets, scfg,
                                          batch_size=slots, seed=args.seed)
        print(f"[serve] {cfg.name} static(batch={slots}): "
              f"{metrics['n_requests']} reqs, {metrics['new_tokens']} toks in "
              f"{metrics['wall_s']*1e3:.1f} ms "
              f"({metrics['tokens_per_s']:.1f} tok/s)")
    print("[serve] sample generations:", [t[:8] for t in tokens[:2]])

    # write artifacts before --verify so a failed verify still leaves the
    # trace around for diagnosis
    if args.trace and eng is not None:
        eng.tracer.save(args.trace)
        print(f"[serve] trace: {len(eng.tracer.events)} events -> "
              f"{args.trace} (load in https://ui.perfetto.dev)")
    if args.metrics_json:
        out = {"arch": cfg.name, "engine": engine, "metrics": metrics}
        if eng is not None:
            out["registry"] = eng.metrics_snapshot()
        with open(args.metrics_json, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
        print(f"[serve] metrics -> {args.metrics_json}")

    if plan is not None:
        fired = [f.describe() for f in plan.faults if f.fired]
        print(f"[serve] chaos: {len(fired)}/{len(plan.faults)} planned "
              f"faults fired; quarantined="
              f"{eng.metrics.value('engine.quarantined')} cancelled="
              f"{eng.metrics.value('engine.cancelled')} pages_scrubbed="
              f"{eng.metrics.value('pool.pages_scrubbed')}")

    if args.verify and plan is not None:
        if args.kv_dtype == "int8":
            raise SystemExit("[serve] --inject --verify needs the token-"
                             "exact bf16 path; int8 verify is a bounded-"
                             "error gate")
        expected = {}      # rid -> substring expected in the terminal error
        for f in plan.faults:
            if f.kind in ("nan_logits", "step_error") and f.rid >= 0:
                expected[f.rid] = f.kind
            elif f.kind == "client_disconnect" and f.rid >= 0:
                expected[f.rid] = "cancelled"
        ref, _ = generate_static(cfg, params, prompts, budgets, scfg,
                                 batch_size=1, seed=args.seed)
        bad = []
        for why in plan.unfired():     # already human-readable descriptions
            bad.append(f"planned fault never fired: {why}")
        for i, res in enumerate(results):
            if i in expected:
                if not res.failed or expected[i] not in (res.error or ""):
                    bad.append(f"request {i}: expected terminal "
                               f"{expected[i]!r}, got error={res.error!r}")
                elif res.tokens != ref[i][:len(res.tokens)]:
                    bad.append(f"request {i}: partial tokens are not a "
                               f"prefix of the clean baseline")
            elif res.failed:
                bad.append(f"request {i}: survivor failed: {res.error!r}")
            elif res.tokens != ref[i]:
                bad.append(f"request {i}: survivor tokens diverge from the "
                           f"fault-free baseline")
        if not eng.pool.conservation_ok():
            bad.append("page-pool conservation violated after drain")
        if bad:
            for why in bad:
                print(f"[serve] CHAOS VERIFY FAILED: {why}")
            raise SystemExit(f"[serve] CHAOS VERIFY FAILED "
                             f"({len(bad)} violations)")
        n_surv = len(results) - len(expected)
        print(f"[serve] chaos verify OK: {n_surv} survivors byte-identical "
              f"to the fault-free baseline, {len(expected)} targeted "
              f"requests quarantined with clean terminals, pool conserved")
        return tokens

    if args.verify and args.kv_dtype == "int8" and engine == "continuous":
        # quantized pages are not token-exact vs the bf16 static baseline;
        # the contract is the bounded-error + high-margin dual gate
        from ..serving import dual_gate_verify, format_report
        report = dual_gate_verify(cfg, scfg, params, prompts, tokens,
                                  attn_backend=scfg.attn_backend)
        print(format_report(report))
        if not report["ok"]:
            raise SystemExit("[serve] QUANT VERIFY FAILED: max logit err "
                             f"{report['max_logit_err']:.4f} (tol "
                             f"{report['tol']:.4f}), "
                             f"{report['high_margin_mismatches']} high-"
                             "margin mismatches, "
                             f"{report['replay_failures']} replay failures")
        print(f"[serve] verify OK: dual gate passed for {len(tokens)} "
              "requests (bounded logit error + high-margin greedy match)")
        return tokens

    if args.verify:
        lens = {len(p) for p in prompts}
        length_bound = cfg.family in ("ssm", "hybrid") or cfg.sliding_window
        if engine == "static" and length_bound and len(lens) > 1 and slots > 1:
            # recurrent state absorbs pad tokens and the sliding-window ring
            # is filled from the padded sequence end, so batched static
            # output is approximate for mixed lengths — exact comparison
            # would be unfair
            print("[serve] verify skipped: batched static serving of mixed-"
                  "length prompts is approximate for recurrent/sliding-"
                  "window families (padding enters the state/ring); rerun "
                  "with --batch 1")
            return tokens
        ref, _ = generate_static(cfg, params, prompts, budgets, scfg,
                                 batch_size=1, seed=args.seed)
        bad = [i for i, (a, b) in enumerate(zip(tokens, ref)) if a != b]
        if bad:
            raise SystemExit(f"[serve] VERIFY FAILED for requests {bad}")
        print(f"[serve] verify OK: {len(tokens)} requests match the "
              f"single-request static baseline exactly")
    return tokens


if __name__ == "__main__":
    from .compile_cache import use_compile_cache
    use_compile_cache()
    main()
