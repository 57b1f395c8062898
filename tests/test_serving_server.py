"""Streaming front-end + request-lifecycle bugfix coverage.

* graceful zero-budget rejection: a too-long prompt surfaces as a failed
  RequestResult mid-batch (counted under ``sched.rejections``) while the
  rest of the batch drains token-exact; only a rid collision raises
* preemption does not reset TTFT: the legacy ``ttft`` agrees with the
  tracer-sourced ``ttft_s`` even for preempted-and-replayed requests
* the decode-stall accumulator is flushed on drain and reset between runs
* the overlapped pipeline (``Engine.pump`` / ``run_offline(overlap=True)``)
  is token-exact with staged plans actually consumed
* ``ServingLoop`` streams every token exactly once, in order, token-exact
  vs the static baseline; rejection and cancellation surface as terminal
  error events; traces with rejected requests validate clean
"""
import asyncio
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, ServeConfig, reduced
from repro.models.registry import init_params
from repro.serving import (Engine, ServingLoop, generate_static,
                           stream_request, validate_trace)


def _cfg(name="qwen2-0.5b"):
    return dataclasses.replace(reduced(ARCHS[name]), remat="none")


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab, size=n).tolist() for n in lens]


# ----------------------------------------------- request-lifecycle bugfixes

def test_zero_budget_rejected_mid_batch_others_drain():
    """One hopeless prompt in a batch must not strand the others: it comes
    back failed, they come back token-exact."""
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    scfg = ServeConfig(page_size=8, max_slots=2, max_len=16)
    good = _prompts(cfg, [5, 9, 4], seed=1)
    too_long = list(range(1, 17))            # len == max_len: zero budget
    eng = Engine(cfg, scfg, params)
    results, metrics = eng.run_offline(
        [good[0], too_long, good[1], good[2]], [4, 4, 4, 4])

    bad = [r for r in results if r.failed]
    ok = [r for r in results if not r.failed]
    assert len(bad) == 1 and bad[0].rid == 1
    assert "no_budget" in bad[0].error and bad[0].tokens == []
    assert metrics["rejected_requests"] == 1
    reject = eng.metrics.get("sched.rejections").labels(reason="no_budget")
    assert reject.value == 1

    ref, _ = generate_static(cfg, params, good, 4, scfg, batch_size=1)
    assert [r.tokens for r in ok] == ref


def test_rid_collision_is_the_only_add_request_raise():
    cfg = _cfg()
    eng = Engine(cfg, ServeConfig(page_size=8, max_slots=2, max_len=32),
                 init_params(cfg, jax.random.PRNGKey(0)))
    p = _prompts(cfg, [6], seed=2)[0]
    eng.add_request(p, 4, rid=7)
    with pytest.raises(ValueError, match="collides"):
        eng.add_request(p, 4, rid=7)
    # a fresh rid is fine, and a rejected rid is still in flight (it holds
    # a pending failed result) until collected
    eng.add_request(list(range(1, 40)), 4, rid=8)     # zero budget: rejected
    with pytest.raises(ValueError, match="collides"):
        eng.add_request(p, 4, rid=8)
    eng.collect()
    eng.add_request(p, 4, rid=8)                      # collectable again


def test_preemption_does_not_reset_ttft():
    """TTFT is the time to the first token *ever* produced: a preemption
    replay regenerates the same prefix and must not move it.  The legacy
    wall-clock ``ttft`` and the tracer-sourced ``ttft_s`` must agree."""
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(8))
    scfg = ServeConfig(page_size=8, max_slots=3, max_len=32, num_pages=7)
    prompts = _prompts(cfg, [7, 15, 9, 12], seed=9)
    budgets = [9, 8, 10, 7]
    eng = Engine(cfg, scfg, params)
    results, _ = eng.run_offline(prompts, budgets)
    assert sum(r.n_preemptions for r in results) > 0   # pressure was real
    for r in results:
        assert r.ttft == pytest.approx(r.ttft_s, rel=1e-6, abs=1e-9), r.rid
        assert r.ttft <= r.latency


def test_stall_accumulator_flushed_on_drain_and_reset_between_runs():
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(3))
    eng = Engine(cfg, ServeConfig(page_size=8, max_slots=2, max_len=32),
                 params)
    # drain flush: trailing stall behind the last non-decode step must land
    # in the histogram when the engine goes idle, not evaporate
    eng._stall_accum = 0.5
    assert eng.step() is False                 # idle -> flush
    assert eng._stall_accum == 0.0
    assert 0.5 in eng._h_stall.values
    # reset between runs: a stale accumulator must not leak into the next
    # run's stall accounting
    eng._stall_accum = 123.0
    results, metrics = eng.run_offline(_prompts(cfg, [5, 9, 14], seed=4), 4)
    assert eng._stall_accum == 0.0
    assert all(v < 123.0 for v in eng._h_stall.values)


# ------------------------------------------------------- overlapped pipeline

def test_overlap_run_offline_token_exact_and_staging_used():
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(5))
    scfg = ServeConfig(page_size=8, max_slots=4, max_len=48)
    prompts = _prompts(cfg, [3, 30, 11, 7, 22, 15], seed=6)
    budgets = [6, 4, 8, 5, 7, 3]
    eng = Engine(cfg, scfg, params)
    results, _ = eng.run_offline(prompts, budgets, overlap=True)
    ref, _ = generate_static(cfg, params, prompts, budgets, scfg,
                             batch_size=1)
    assert [r.tokens for r in results] == ref
    staged = eng.metrics.value("engine.overlap_staged")
    used = eng.metrics.value("engine.overlap_used")
    dropped = eng.metrics.value("engine.overlap_dropped")
    assert staged > 0 and used > 0             # the pipeline actually staged
    assert used + dropped == staged            # every plan is accounted for
    # engine-loop phases made it into the trace (dispatch, stage and
    # collect on every step)
    trace = eng.tracer.to_dict()
    from repro.serving.telemetry import ENGINE_PID, HOST_TID
    host = [e for e in trace["traceEvents"]
            if e.get("pid") == ENGINE_PID and e.get("tid") == HOST_TID
            and e.get("ph") == "X"]
    names = {e["name"] for e in host}
    assert {"dispatch", "stage", "collect"} <= names
    assert validate_trace(trace) == []


def test_preemption_under_pressure_overlap_still_exact():
    """Staged plans must be invalidated by preemption/admission churn, not
    replayed stale: the pressure workload stays exact under pump()."""
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(8))
    scfg = ServeConfig(page_size=8, max_slots=3, max_len=32, num_pages=7)
    prompts = _prompts(cfg, [7, 15, 9, 12], seed=9)
    budgets = [9, 8, 10, 7]
    eng = Engine(cfg, scfg, params)
    results, _ = eng.run_offline(prompts, budgets, overlap=True)
    ref, _ = generate_static(cfg, params, prompts, budgets, scfg,
                             batch_size=1)
    assert [r.tokens for r in results] == ref
    assert sum(r.n_preemptions for r in results) > 0


# --------------------------------------------------------- streaming server

def _serving_engine(seed=0, max_len=48, slots=4, num_pages=None):
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(seed))
    kw = {"num_pages": num_pages} if num_pages else {}
    scfg = ServeConfig(page_size=8, max_slots=slots, max_len=max_len, **kw)
    return cfg, params, scfg, Engine(cfg, scfg, params)


def test_serving_loop_streams_token_exact():
    cfg, params, scfg, eng = _serving_engine(seed=11)
    prompts = _prompts(cfg, [4, 18, 9, 13, 6], seed=12)
    budgets = [5, 7, 4, 6, 8]

    async def main():
        serving = ServingLoop(eng, overlap=True, collect_queue_size=4)
        await serving.start()
        try:
            streams = await asyncio.gather(*[
                stream_request(serving, p, g, timeout_s=300.0)
                for p, g in zip(prompts, budgets)])
        finally:
            await serving.stop()
        return streams

    streams = asyncio.run(main())
    ref, _ = generate_static(cfg, params, prompts, budgets, scfg,
                             batch_size=1)
    for events, want in zip(streams, ref):
        toks = [e for e in events if e["type"] == "token"]
        done = events[-1]
        assert done["type"] == "done"
        # every token exactly once, in order, each matching the baseline
        assert [e["index"] for e in toks] == list(range(len(want)))
        assert [e["token"] for e in toks] == want
        assert done["tokens"] == want
        assert done["text"] == "".join(f"<{t}>" for t in want)
        assert [e["text"] for e in toks] == [f"<{t}>" for t in want]
        assert done["ttft_s"] <= done["finish_s"]


def test_serving_loop_rejection_and_cancel_events():
    cfg, params, scfg, eng = _serving_engine(seed=13, max_len=16, slots=2)

    async def main():
        serving = ServingLoop(eng, overlap=True)
        await serving.start()
        try:
            # zero-budget prompt -> terminal error event, no tokens
            rejected = await stream_request(
                serving, list(range(1, 17)), 4, timeout_s=300.0)
            # live cancel: wait for the first token, then disconnect
            rid, q = serving.submit(_prompts(cfg, [5], seed=14)[0],
                                    max_new_tokens=12)
            first = await asyncio.wait_for(q.get(), timeout=300.0)
            serving.cancel(rid)
            while True:
                last = await asyncio.wait_for(q.get(), timeout=300.0)
                if last["type"] in ("done", "error"):
                    break
            serving.forget(rid)
        finally:
            await serving.stop()
        return rejected, first, last

    rejected, first, last = asyncio.run(main())
    assert len(rejected) == 1
    assert rejected[0]["type"] == "error"
    assert "no_budget" in rejected[0]["error"]
    assert first["type"] == "token" and first["index"] == 0
    assert last["type"] == "error" and "cancelled" in last["error"]
    # the cancelled request released its slot and pages
    assert eng.pool.num_allocated == 0


def test_trace_with_rejection_validates_clean():
    """A rejected rid reaches a terminal event ("rejected"), so the
    well-formedness checker must accept traces containing them."""
    cfg, params, scfg, eng = _serving_engine(seed=15, max_len=16, slots=2)
    eng.add_request(list(range(1, 17)), 4)            # rejected
    eng.run_offline(_prompts(cfg, [5, 9], seed=16), 4)
    trace = eng.tracer.to_dict()
    assert validate_trace(trace) == []
    rejected = [e for e in trace["traceEvents"]
                if e.get("ph") == "i" and e.get("name") == "rejected"]
    assert len(rejected) == 1
