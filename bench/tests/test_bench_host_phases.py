"""The engine-loop readers (``host_gap_ms.decode``, ``device_step_ms.decode``,
``replan_share.decode``) on a hand-built trace with known gaps; the
program's phases on a recorded CPU profile; and a traced CPU rehearsal of
``bench/run.py`` with the three metrics added to the cell, which prints
them."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from bench import phases, run
from bench import trace_reduce as tr
from bench.tests.conftest import tiny_cell

US = 1000                                 # the trace's clock is in ns

# the readers' per-layer entries: a program without the engine-loop phases
# reads nothing for them, so BENCHMARK.json does not declare them yet
PHASE_METRICS = [
    {"name": name, "unit": unit, "better": "lower", "source": source,
     "layer": layer, "moves": "itl_p95_ms"}
    for name, unit, source, layer in [
        ("host_gap_ms.decode", "ms", "program_span", "host (engine loop)"),
        ("device_step_ms.decode", "ms", "device_trace", "model step"),
        ("replan_share.decode", "%", "program_span", "host (engine loop)")]]

# three decode launches, then a prefill and a decode: two decode pairs,
# (A, B) with a gap of 18 us and (B, E) with one of 28 us
HOST = [(n, s * US, e * US) for n, s, e in [
    ("dispatch", -2, 10), ("launch.decode", 0, 10),            # A
    ("collect", 50, 62), ("sync.decode", 50, 60), ("emit", 60, 62),
    ("results", 63, 64),
    ("dispatch", 65, 78), ("schedule", 66, 67), ("plan", 67, 70),
    ("upload", 70, 74), ("launch.decode", 74, 78),             # B
    ("stage", 78, 90),
    ("sync.decode", 120, 130), ("emit", 130, 140),
    ("launch.decode", 150, 158),                               # E
    ("sync.decode", 190, 200),
    ("launch.prefill", 210, 215), ("sync.prefill", 230, 240),
    ("launch.decode", 250, 255),
    ("engine.dispatch", 64, 79)]]          # not a phase of the program
# device 0 busy [5, 45] in [0, 74); [80, 140] and [148, 150) in [74, 150),
# where a copy still runs at launch E's start
OPS = [(n, s * US, e * US) for n, s, e in [
    ("fusion.1", 5, 40), ("paged_attention_decode.2", 30, 45),
    ("fusion.3", 80, 110), ("fusion.4", 100, 140), ("copy.6", 148, 153),
    ("fusion.5", 152, 160)]]


def _rec(tracer=None):
    return {"trace": {"host": HOST, "devices": {0: OPS}},
            "tracer": tracer, "t0": 0.0, "t1": 1.0}


def test_decode_pairs_and_host_gap(capsys):
    pairs = phases.decode_pairs(HOST)
    assert [(a[1], s[1], b[1]) for a, s, b in pairs] == [
        (0, 50 * US, 74 * US), (74 * US, 120 * US, 150 * US)]
    gap = run.reader("host_gap_ms.decode")(_rec())
    assert gap == pytest.approx(23e-3)           # median of 18 and 28 us
    log = capsys.readouterr().err
    assert "2 pairs" in log and "launch.decode 0.006" in log


def test_innermost_phase_split():
    # the gap after A: emit 2, none 1 (62-63), results 1, none 1 (64-65),
    # dispatch 1 (65-66), schedule 1, plan 3, upload 4, launch.decode 4
    split = phases.innermost(HOST, 60 * US, 78 * US)
    assert split == {"emit": 2 * US, "none": 2 * US, "results": US,
                     "dispatch": US, "schedule": US, "plan": 3 * US,
                     "upload": 4 * US, "launch.decode": 4 * US}


def test_device_step(capsys):
    # busy in [0, 74): 40 us; in [74, 150): 60 + 2 = 62 us
    got = run.reader("device_step_ms.decode")(_rec())
    assert got == pytest.approx(51e-3)
    # periods 74 and 76 us; the device's last op ends 15 and 0 us before
    # the sync returns; its first op starts 2 us after launch B ends and
    # 6 us before launch E ends (fusion.5, though copy.6 spans E's start)
    assert "2 pairs; median ms: period 0.075, busy 0.051, last op to " \
        "sync.decode end 0.007" in capsys.readouterr().err
    busy = tr.union((s, e) for _, s, e in OPS)
    assert phases.last_end(busy, 60 * US) == 45 * US
    assert phases.last_end(busy, 130 * US) == 130 * US
    starts = sorted(s for _, s, _ in OPS)
    assert phases.first_start(starts, 74 * US) == 80 * US
    assert phases.first_start(starts, 150 * US) == 152 * US
    assert phases.first_start(starts, 200 * US) == float("inf")
    rec = _rec()
    rec["trace"]["devices"] = {}
    assert run.reader("device_step_ms.decode")(rec) is None


def test_replan_share():
    from repro.serving.telemetry import Tracer
    t = Tracer()
    for i, staged in enumerate([False, True, True, False]):
        t.step_span("decode", t.t0 + 0.1 * i, t.t0 + 0.1 * i + 0.05,
                    staged=staged)
    t.step_span("prefill", t.t0 + 0.5, t.t0 + 0.6)
    t.step_span("decode", t.t0 + 2.0, t.t0 + 2.1, staged=False)  # outside
    rec = {"tracer": t, "t0": t.t0, "t1": t.t0 + 1.0}
    assert run.reader("replan_share.decode")(rec) == pytest.approx(50.0)
    # a program whose decode spans say nothing of staging reads nothing
    old = Tracer()
    old.step_span("decode", old.t0, old.t0 + 0.1)
    assert run.reader("replan_share.decode")(
        {"tracer": old, "t0": old.t0, "t1": old.t0 + 1.0}) is None


def test_phases_reach_the_profilers_host_plane(tmp_path):
    from repro.configs import ServeConfig, get_arch, reduced
    from repro.serving import Engine
    from repro.serving.telemetry import Tracer, validate_trace
    cfg = dataclasses.replace(reduced(get_arch("qwen2-0.5b")), remat="none")
    scfg = ServeConfig(page_size=8, max_slots=3, max_len=64,
                       prefill_chunk_tokens=16)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab, size=n).tolist()
               for n in (40, 7, 23)]
    eng = Engine(cfg, scfg, seed=0, tracer=Tracer(jax_annotations=True))
    eng.run_offline(prompts, 2)                 # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    eng.run_offline(prompts, 6, overlap=True)
    jax.profiler.stop_trace()
    host = tr.read_xplane(str(tmp_path))["host"]
    names = {n for n, _, _ in host}
    assert {"dispatch", "schedule", "plan", "upload", "launch.prefill",
            "launch.prefill_chunk", "launch.decode", "stage", "collect",
            "sync.prefill", "sync.prefill_chunk", "sync.decode",
            "emit"} <= names
    # each launch.decode is followed by its sync.decode
    steps = sorted((s, n) for n, s, _ in host
                   if n.startswith(("launch.", "sync.")))
    follow = [b for (_, a), (_, b) in zip(steps, steps[1:])
              if a == "launch.decode"]
    assert follow and set(follow) == {"sync.decode"}
    assert phases.decode_pairs(host)
    assert validate_trace(eng.tracer.to_dict()) == []


def test_traced_rehearsal_prints_the_phase_metrics(capsys, monkeypatch):
    name = "qwen2-0.5b.chat-0.8knee"
    ctx = tiny_cell(name, monkeypatch, {"rate_rps": 5.0, "warm_s": 0.3})
    ctx["per_layer"] = ctx["per_layer"] + PHASE_METRICS
    rc = run.run(["--workload", name, "--seed", "5", "--seconds", "1.5",
                  "--trace", "1"], require_tpu=False, compile_cache=False)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    assert {"host_gap_ms.decode", "replan_share.decode"} \
        <= set(line["metrics"])
    assert line["metrics"]["host_gap_ms.decode"]["value"] > 0
    assert 0 <= line["metrics"]["replan_share.decode"]["value"] <= 100
