"""Telemetry subsystem: metrics registry, conservation invariants, tracing.

* registry primitives — counters / gauges / histograms / labeled families,
  idempotent registration, snapshot shape
* conservation after every run_offline drain (plain, prefix-cache,
  mid-prefill preemption): ``pool.pages_allocated == pool.pages_released +
  pool.pages_live`` and ``radix.hit_tokens + radix.miss_tokens ==
  radix.lookup_tokens``
* trace well-formedness (validate_trace finds nothing on real runs, and
  does find planted defects), per-request result fields sourced from the
  tracer, trace_report's per-phase sums covering wall clock
* token-exactness with tracing on: telemetry must never change a token
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.configs import ServeConfig, get_arch, reduced
from repro.launch.trace_report import phase_breakdown, report, request_rows
from repro.models.registry import init_params
from repro.serving import Engine, generate_static
from repro.serving.telemetry import (
    ENGINE_PID, REQUEST_PID, SHARED_METRIC_KEYS, MetricsRegistry, Tracer,
    percentile, shared_metrics, validate_trace)

jax.config.update("jax_platform_name", "cpu")


def _cfg(name="qwen2-0.5b"):
    return dataclasses.replace(reduced(get_arch(name)), remat="none")


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab, size=n).tolist() for n in lens]


# ----------------------------------------------------------- registry basics

def test_registry_primitives():
    m = MetricsRegistry()
    c = m.counter("c", "a counter")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(AssertionError):
        c.inc(-1)                          # counters are monotonic

    g = m.gauge("g", "a gauge")
    g.set(7)
    g.dec(3)
    g.inc()
    assert g.value == 5

    h = m.histogram("h", "a histogram")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.count == 4 and h.total == 10.0 and h.max == 4.0
    assert h.percentile(50) == pytest.approx(2.5)

    lab = m.counter("admits", "by kind", labels=("kind",))
    lab.labels(kind="fresh").inc(2)
    lab.labels(kind="restore").inc()
    assert lab.labels(kind="fresh").value == 2

    snap = m.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == 5
    assert snap["histograms"]["h"]["count"] == 4
    assert snap["counters"]['admits{kind=fresh}'] == 2
    json.dumps(snap)                       # snapshot is JSON-serializable


def test_registry_idempotent_and_type_checked():
    m = MetricsRegistry()
    c1 = m.counter("x", "first")
    c2 = m.counter("x", "second registration returns the same object")
    assert c1 is c2
    with pytest.raises(AssertionError):
        m.gauge("x", "same name, different kind")


def test_percentile_edge_cases():
    assert percentile([], 50) == 0.0
    assert percentile([3.0], 50) == 3.0
    assert percentile([1.0, 2.0, 3.0], 0) == 1.0
    assert percentile([1.0, 2.0, 3.0], 100) == 3.0


def test_shared_metrics_schema_is_closed():
    out = shared_metrics(2, 10, [0.1, 0.2], 0.5)
    assert set(out) == set(SHARED_METRIC_KEYS)


# ------------------------------------------------- conservation invariants

def _assert_conserved(eng):
    snap = eng.metrics_snapshot()
    c, g = snap["counters"], snap["gauges"]
    assert c["pool.pages_allocated"] == \
        c["pool.pages_released"] + g["pool.pages_live"]
    if "radix.lookup_tokens" in c:
        assert c["radix.hit_tokens"] + c["radix.miss_tokens"] == \
            c["radix.lookup_tokens"]
        assert c["radix.partial_hit_tokens"] <= c["radix.hit_tokens"]
    return snap


def test_conservation_plain_drain():
    cfg = _cfg()
    scfg = ServeConfig(page_size=8, max_slots=3, max_len=48)
    eng = Engine(cfg, scfg, seed=0)
    eng.run_offline(_prompts(cfg, [5, 21, 12, 9]), 6)
    snap = _assert_conserved(eng)
    # no radix cache: every allocated page was released at retirement
    assert snap["gauges"]["pool.pages_live"] == 0
    assert snap["gauges"]["sched.slots_live"] == 0
    assert snap["gauges"]["sched.queue_depth"] == 0
    assert snap["counters"]["pool.pages_allocated"] > 0


def test_conservation_prefix_cache_drain():
    """With the radix cache the tree legitimately keeps pages live after the
    drain; conservation must hold with those counted, and reset() must bring
    live back to zero."""
    cfg = _cfg()
    scfg = ServeConfig(page_size=8, max_slots=3, max_len=48,
                       prefix_cache=True)
    eng = Engine(cfg, scfg, seed=0)
    shared = _prompts(cfg, [24], seed=1)[0]
    prompts = [shared + p for p in _prompts(cfg, [6, 3, 9, 5], seed=2)]
    results, _ = eng.run_offline(prompts, 5)
    snap = _assert_conserved(eng)
    assert snap["counters"]["radix.hit_tokens"] > 0
    assert snap["gauges"]["pool.pages_live"] > 0        # the tree's pages
    assert snap["gauges"]["radix.cached_pages"] == \
        len(eng.sched.radix.cached_pages)
    eng.sched.radix.reset()
    snap = _assert_conserved(eng)
    assert snap["gauges"]["pool.pages_live"] == 0
    assert snap["gauges"]["radix.cached_pages"] == 0


def test_conservation_mid_prefill_preemption():
    cfg = _cfg()
    scfg = ServeConfig(page_size=8, max_slots=2, max_len=64, num_pages=10,
                       prefill_chunk_tokens=8)
    eng = Engine(cfg, scfg, seed=0)
    results, _ = eng.run_offline(_prompts(cfg, [40, 35, 22, 17], seed=7),
                                 [20, 18, 12, 9])
    assert sum(r.n_preemptions for r in results) > 0    # pressure was real
    snap = _assert_conserved(eng)
    assert snap["gauges"]["pool.pages_live"] == 0
    pre = [v for k, v in snap["counters"].items()
           if k.startswith("sched.preemptions")]
    assert sum(pre) == sum(r.n_preemptions for r in results)


def test_admission_counters_label_kinds():
    cfg = _cfg()
    scfg = ServeConfig(page_size=8, max_slots=2, max_len=48,
                       prefix_cache=True)
    eng = Engine(cfg, scfg, seed=0)
    shared = _prompts(cfg, [16], seed=3)[0]
    prompts = [shared + p for p in _prompts(cfg, [4, 6, 8], seed=4)]
    eng.run_offline(prompts, 4)
    c = eng.metrics_snapshot()["counters"]
    admits = sum(v for k, v in c.items() if k.startswith("sched.admissions"))
    assert admits >= len(prompts)
    assert c.get("sched.admissions{kind=cache_hit}", 0) > 0
    assert c["sched.queued"] == len(prompts)


# --------------------------------------------------------- tracing / report

def test_trace_well_formed_and_request_fields():
    cfg = _cfg()
    scfg = ServeConfig(page_size=8, max_slots=3, max_len=64,
                       prefill_chunk_tokens=16)
    eng = Engine(cfg, scfg, seed=0)
    prompts = _prompts(cfg, [40, 7, 23, 11], seed=5)
    results, metrics = eng.run_offline(prompts, 6)
    trace = eng.tracer.to_dict()
    assert validate_trace(trace) == []

    # per-request result fields are tracer-sourced and consistent
    for r in results:
        assert 0 < r.ttft_s <= r.finish_s
        assert r.n_prefill_chunks >= 1
        assert r.preempted == (r.n_preemptions > 0)
    long_rid = max(range(len(prompts)), key=lambda i: len(prompts[i]))
    assert results[long_rid].n_prefill_chunks > 1       # 40 toks / 16 budget

    rows = request_rows(trace)
    assert [row["rid"] for row in rows] == sorted(r.rid for r in results)
    by_rid = {row["rid"]: row for row in rows}
    for r in results:
        assert by_rid[r.rid]["ttft_s"] == pytest.approx(r.ttft_s)
        assert by_rid[r.rid]["n_tokens"] == len(r.tokens)

    # every engine step produced exactly one span on the step track (tid 0;
    # the engine loop's phases have a track of their own)
    # (chunked_prefill_steps is a subset of prefill_steps, not additive)
    steps = [e for e in trace["traceEvents"]
             if e.get("ph") == "X" and e.get("pid") == ENGINE_PID
             and e.get("tid") == 0]
    assert len(steps) == metrics["prefill_steps"] \
        + metrics["decode_steps"] + metrics["state_restores"]
    assert metrics["chunked_prefill_steps"] > 0         # 40 toks / 16 budget


def test_trace_phase_sums_cover_wall_clock():
    """Acceptance bar: per-phase durations + host gap reconstruct the wall
    clock within 10%."""
    cfg = _cfg()
    scfg = ServeConfig(page_size=8, max_slots=3, max_len=48)
    eng = Engine(cfg, scfg, seed=0)
    _, metrics = eng.run_offline(_prompts(cfg, [9, 25, 14, 6], seed=6), 5)
    bd = phase_breakdown(eng.tracer.to_dict())
    covered = sum(bd["per_phase_s"].values()) + bd["other_s"] + bd["host_s"]
    assert covered == pytest.approx(bd["wall_s"], rel=1e-6)
    assert bd["wall_s"] <= metrics["wall_s"] * 1.10
    assert bd["wall_s"] >= metrics["wall_s"] * 0.50     # spans are real
    text = report(eng.tracer.to_dict())
    assert "time in phase" in text and "decode" in text


def test_tracing_is_token_invariant():
    """Telemetry on (default), with profiler annotations, and tracer
    disabled: identical tokens."""
    cfg = _cfg()
    scfg = ServeConfig(page_size=8, max_slots=2, max_len=48)
    params = init_params(cfg, jax.random.PRNGKey(2))
    prompts = _prompts(cfg, [5, 17, 9], seed=8)
    on, _ = Engine(cfg, scfg, params).run_offline(prompts, 5)
    off_eng = Engine(cfg, scfg, params, tracer=Tracer(enabled=False))
    off, _ = off_eng.run_offline(prompts, 5)
    assert [r.tokens for r in on] == [r.tokens for r in off]
    assert off_eng.tracer.events == []                  # truly off
    # the phases as profiler annotations too, on both drive loops
    for overlap in (False, True):
        ann, _ = Engine(cfg, scfg, params,
                        tracer=Tracer(jax_annotations=True)).run_offline(
            prompts, 5, overlap=overlap)
        assert [r.tokens for r in ann] == [r.tokens for r in on]
    ref, _ = generate_static(cfg, params, prompts, 5, scfg, batch_size=1)
    assert [r.tokens for r in on] == ref


def test_validate_trace_catches_planted_defects():
    def ev(**kw):
        base = {"ph": "X", "pid": ENGINE_PID, "tid": 0, "name": "s",
                "ts": 0.0, "dur": 10.0, "args": {}}
        base.update(kw)
        return base

    assert validate_trace({"traceEvents": [ev()]}) == []
    assert validate_trace({"traceEvents": [ev(ts=-5.0)]})       # negative ts
    assert validate_trace({"traceEvents": [ev(dur=-1.0)]})      # negative dur
    assert validate_trace({"traceEvents": [ev(ts=float("nan"))]})
    # partial overlap on one track: [0, 10] vs [5, 15]
    assert validate_trace({"traceEvents": [ev(), ev(ts=5.0, dur=10.0)]})
    # admitted request that never finishes
    orphan = ev(pid=REQUEST_PID, tid=3, name="queued")
    assert any("never reached" in p
               for p in validate_trace({"traceEvents": [orphan]}))
    # proper nesting [0, 10] containing [2, 6] is fine
    assert validate_trace(
        {"traceEvents": [ev(), ev(ts=2.0, dur=4.0)]}) == []


def test_generate_static_emits_shared_schema():
    cfg = _cfg()
    scfg = ServeConfig(page_size=8, max_slots=2, max_len=48)
    params = init_params(cfg, jax.random.PRNGKey(3))
    prompts = _prompts(cfg, [6, 11, 9, 4], seed=9)
    _, sm = generate_static(cfg, params, prompts, 5, scfg, batch_size=2)
    assert set(sm) == set(SHARED_METRIC_KEYS)
    assert sm["ttft_p50_s"] > 0
    assert sm["prefill_steps"] == 2                     # 4 prompts / batch 2
    assert sm["decode_steps"] > 0
    assert sm["prefill_padded_tokens"] >= sm["prefill_actual_tokens"]
    # engine metrics are a superset of the shared schema
    eng = Engine(cfg, scfg, params)
    _, em = eng.run_offline(prompts, 5)
    assert set(SHARED_METRIC_KEYS) <= set(em)
