"""The engine loop's phases (``Tracer.phase``), the staging-skip counter,
the tracer's event cap and the names of the jitted serving steps.

* with both sinks off a phase is one shared no-op and records nothing
* the tracer keeps at most ``max_events`` events and counts the rest
* every engine-loop phase lands on the Chrome trace's engine-loop track,
  nested under its parent, on both ``step()`` and ``pump()`` and in the
  ``ServingLoop`` (``admit`` / ``results``); traces still validate
* an idle poll drops only its own phases: a terminal event that the
  deadline sweep or a disconnect wrote during that poll stays
* decode step spans say whether their launch used a staged plan, and
  ``engine.overlap_skipped{reason}`` counts why a step staged nothing
* the lowered serving steps carry distinct module names
"""
import asyncio
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ServeConfig, get_arch, reduced
from repro.launch.trace_report import host_pipeline, report
from repro.models.attn_backend import prefill_meta, verify_meta
from repro.models.registry import init_params
from repro.serving import Engine, FaultPlan, ServingLoop, stream_request
from repro.serving.kv_pool import NULL_PAGE
from repro.serving.telemetry import (
    ENGINE_PID, HOST_TID, Tracer, validate_trace)

jax.config.update("jax_platform_name", "cpu")


def _cfg(name="qwen2-0.5b"):
    return dataclasses.replace(reduced(get_arch(name)), remat="none")


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab, size=n).tolist() for n in lens]


def _loop_spans(trace):
    return [e for e in trace["traceEvents"]
            if e.get("ph") == "X" and e.get("pid") == ENGINE_PID
            and e.get("tid") == HOST_TID]


def test_phase_with_both_sinks_off_records_nothing():
    tr = Tracer(enabled=False)
    with tr.phase("dispatch") as ph:
        with tr.phase("plan", kind="decode"):
            pass
        ph.discard()
    assert tr.phase("a") is tr.phase("b")          # one shared object
    assert tr.events == []


def test_phase_nests_and_discard_drops_children():
    tr = Tracer()
    with tr.phase("dispatch"):
        with tr.phase("plan"):
            pass
    with tr.phase("dispatch") as ph:
        with tr.phase("schedule"):
            pass
        ph.discard()
    names = [e["name"] for e in tr.events]
    assert names == ["plan", "dispatch"]
    plan, dispatch = tr.events
    assert dispatch["ts"] <= plan["ts"]
    assert plan["ts"] + plan["dur"] <= dispatch["ts"] + dispatch["dur"]


def test_discard_keeps_events_of_other_tracks():
    tr = Tracer()
    with tr.phase("dispatch") as ph:
        with tr.phase("schedule"):
            tr.on_queued(0, tr.now())
            tr.instant(ENGINE_PID, 0, "marker", tr.now())
        ph.discard()
    assert [e["name"] for e in tr.events] == ["marker"]


def _last_request_ends_in_idle_poll(how):
    """The only live request ends inside the poll that then finds no work:
    by a deadline the sweep evicts, or by a disconnect the injector fires."""
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(3))
    prompt = _prompts(cfg, [6], seed=8)[0]
    if how == "deadline":
        scfg = ServeConfig(page_size=8, max_slots=2, max_len=48,
                           admission_control=True)
        eng = Engine(cfg, scfg, params)
        rid = eng.add_request(prompt, 12, deadline_s=120.0)
        assert eng.step()                              # prefill
        eng.sched.slots[0].req.deadline = time.perf_counter() - 1.0
    else:
        scfg = ServeConfig(page_size=8, max_slots=2, max_len=48)
        eng = Engine(cfg, scfg, params,
                     faults=FaultPlan.parse("client_disconnect:rid=0,at=3"))
        rid = eng.add_request(prompt, 12)
    while eng.step():
        pass
    (res,) = eng.collect()
    assert res.rid == rid and res.failed
    return eng, rid


def test_idle_poll_keeps_the_last_requests_terminal_event():
    for how in ("deadline", "disconnect"):
        eng, rid = _last_request_ends_in_idle_poll(how)
        trace = eng.tracer.to_dict()
        assert validate_trace(trace) == [], how
        terminal = [e for e in trace["traceEvents"]
                    if e.get("ph") == "i" and e.get("pid") != ENGINE_PID
                    and e.get("tid") == rid
                    and e["name"] in ("finished", "rejected")]
        assert len(terminal) == 1, how


def test_tracer_caps_its_events():
    tr = Tracer(max_events=5)
    for i in range(8):
        tr.span(ENGINE_PID, 0, "decode", tr.t0 + i, tr.t0 + i + 0.5)
    assert len(tr.events) == 5
    assert tr.dropped_events == 3
    assert tr.to_dict()["otherData"] == {"dropped_events": 3}


def test_engine_loop_phases_nest_under_their_parents():
    cfg = _cfg()
    scfg = ServeConfig(page_size=8, max_slots=3, max_len=64,
                       prefill_chunk_tokens=16)
    for overlap in (False, True):
        eng = Engine(cfg, scfg, seed=0)
        eng.run_offline(_prompts(cfg, [40, 7, 23], seed=5), 6,
                        overlap=overlap)
        trace = eng.tracer.to_dict()
        assert validate_trace(trace) == []
        paths = set(host_pipeline(trace)["counts"])
        assert {"dispatch", "dispatch/schedule", "dispatch/plan",
                "dispatch/upload", "dispatch/launch.prefill",
                "dispatch/launch.prefill_chunk", "dispatch/launch.decode",
                "collect", "collect/sync.prefill",
                "collect/sync.prefill_chunk", "collect/sync.decode",
                "collect/emit"} <= paths
        # every step dispatched and collected once; idle polls leave no span
        counts = host_pipeline(trace)["counts"]
        n_steps = sum(1 for e in trace["traceEvents"]
                      if e.get("ph") == "X" and e.get("pid") == ENGINE_PID
                      and e.get("tid") == 0)
        assert counts["dispatch"] == counts["collect"] == n_steps
        assert counts["dispatch/launch.decode"] \
            == counts["collect/sync.decode"]
        # only pump() stages the next step
        assert ("stage" in paths) == ("stage/plan" in paths) == overlap
        assert "engine loop" in report(trace)


def test_decode_spans_say_staged_and_skips_are_counted():
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(5))
    # two slots, four requests: decode steps run while two requests wait
    scfg = ServeConfig(page_size=8, max_slots=2, max_len=48)
    eng = Engine(cfg, scfg, params)
    eng.run_offline(_prompts(cfg, [3, 11, 7, 5], seed=6), [9, 6, 8, 7],
                    overlap=True)
    decodes = [e for e in eng.tracer.events
               if e.get("tid") == 0 and e.get("pid") == ENGINE_PID
               and e["name"] == "decode"]
    assert all(isinstance(e["args"]["staged"], bool) for e in decodes)
    n_staged = sum(e["args"]["staged"] for e in decodes)
    assert n_staged == eng.metrics.value("engine.overlap_used") > 0
    c = eng.metrics_snapshot()["counters"]
    assert c["engine.overlap_skipped{reason=queued}"] > 0
    assert c["engine.overlap_skipped{reason=not_decode}"] > 0
    skipped = sum(v for k, v in c.items()
                  if k.startswith("engine.overlap_skipped"))
    # one decision per pumped step: staged or skipped for a reason
    n_steps = sum(1 for e in eng.tracer.events
                  if e.get("tid") == 0 and e.get("pid") == ENGINE_PID
                  and e["ph"] == "X")
    assert skipped + c["engine.overlap_staged"] == n_steps


def test_serving_loop_times_admit_and_results():
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(1))
    scfg = ServeConfig(page_size=8, max_slots=4, max_len=48)
    eng = Engine(cfg, scfg, params)

    async def main():
        serving = ServingLoop(eng, overlap=True)
        await serving.start()
        try:
            return await asyncio.gather(*[
                stream_request(serving, p, 4, timeout_s=300.0)
                for p in _prompts(cfg, [5, 9, 7], seed=2)])
        finally:
            await serving.stop()

    streams = asyncio.run(main())
    assert all(s[-1]["type"] == "done" for s in streams)
    trace = eng.tracer.to_dict()
    assert validate_trace(trace) == []
    names = {e["name"] for e in _loop_spans(trace)}
    assert {"admit", "results", "dispatch", "collect"} <= names
    results = [e for e in _loop_spans(trace) if e["name"] == "results"]
    assert 1 <= len(results) <= 3            # only iterations that finished


def test_lowered_serving_steps_have_distinct_module_names():
    cfg = _cfg()
    scfg = ServeConfig(page_size=8, max_slots=2, max_len=32,
                       speculate_tokens=2)
    eng = Engine(cfg, scfg, seed=0)
    B, T, Q = scfg.max_slots, 16, eng.spec_k + 1
    tables = np.full((B, 1), NULL_PAGE, np.int32)
    zeros = np.zeros((B,), np.int32)
    pmeta = {k: jnp.asarray(v) for k, v in prefill_meta(
        cfg, scfg.page_size, tables, np.full((B,), B, np.int32), zeros,
        zeros, T).items()}
    vmeta = {k: jnp.asarray(v) for k, v in verify_meta(
        cfg, scfg.page_size, np.full((B, eng.pool.table_width), NULL_PAGE,
                                     np.int32),
        zeros, np.ones((B,), np.int32), Q).items()}
    kv = eng.pool.kv
    lowered = {
        "prefill": eng._prefill.lower(eng.params, kv, {}, pmeta,
                                      jnp.zeros((B, T), jnp.int32), {}),
        "prefill_cont": eng._prefill_cont.lower(
            eng.params, kv, {}, pmeta, jnp.zeros((B, T), jnp.int32), {}),
        "decode": eng._decode.lower(eng.params, kv, {},
                                    eng._decode_plan([]),
                                    jnp.zeros((B,), jnp.int32)),
        "verify": eng._verify.lower(eng.params, kv, {}, vmeta,
                                    jnp.zeros((B, Q), jnp.int32)),
    }
    names = {k: v.as_text().split("module @", 1)[1].split(" ", 1)[0]
             for k, v in lowered.items()}
    assert names == {"prefill": "jit_prefill_paged",
                     "prefill_cont": "jit_prefill_paged_cont",
                     "decode": "jit_decode_paged",
                     "verify": "jit_verify_paged"}
