"""CPU rehearsals of ``bench/run.py`` at tiny widths in interpret mode: one
cell of each traffic kind prints one contract line; a run without a TPU
prints nothing; and a run with its timed path broken underneath reads
``correct`` false, once for each fault a serving cell can have."""
import json

import jax
import pytest

from bench import run
from bench.tests.conftest import tiny_cell

CELLS = {"poisson_open": ("qwen2-0.5b.chat-0.8knee", {"rate_rps": 5.0}),
         "closed_backlog": ("minitron-4b.long-decode-backlog", {})}
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def _run(capsys, name, seed, trace=0):
    rc = run.run(["--workload", name, "--seed", str(seed), "--seconds",
                  "1.5", "--trace", str(trace)], require_tpu=False,
                 compile_cache=False)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_one_contract_line(kind, capsys, monkeypatch):
    name, over = CELLS[kind]
    ctx = tiny_cell(name, monkeypatch, dict(over, warm_s=0.3))
    rc, line = _run(capsys, name, 2**32 + 17)      # a seed past 32 bits
    assert rc == 0 and KEYS <= set(line) and list(line)[-1] == "checks"
    assert line["correct"] is True and line["attempted"] > 0
    want = {m["name"] for m in ctx["end_to_end"]}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert m["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1


def test_traced_run_prints_per_layer_metrics(capsys, monkeypatch):
    name, over = CELLS["poisson_open"]
    ctx = tiny_cell(name, monkeypatch, dict(over, warm_s=0.3))
    rc, line = _run(capsys, name, 5, trace=1)
    allowed = {m["name"] for m in ctx["per_layer"]}
    assert rc == 0 and set(line["metrics"]) <= allowed
    assert "ttft_p90_ms.chat" in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_tpu_exits_without_a_result(capsys, monkeypatch):
    name, _ = CELLS["poisson_open"]
    tiny_cell(name, monkeypatch)
    rc = run.run(["--workload", name, "--seed", "1", "--seconds", "1",
                  "--trace", "0"], compile_cache=False)
    assert rc != 0
    assert capsys.readouterr().out == ""


def _broken_decode(kind):
    """The engine's decode step with one fault planted underneath."""
    from repro.models.steps import make_serve_step
    from repro.serving import engine

    real = engine._paged_steps

    def steps(cfg, mesh=None, attn_backend="reference"):
        out = list(real(cfg, mesh, attn_backend))
        inner = make_serve_step(cfg, mesh, "decode_paged", attn_backend)
        if kind == "token_altered":
            def f(p, kv, st, meta, tok):
                nxt, ok, kv, st = inner(p, kv, st, meta, tok)
                return (nxt + 1) % cfg.vocab, ok, kv, st
        else:                                    # "state_unchanged"
            def f(p, kv, st, meta, tok):
                nxt, ok, _, _ = inner(p, kv, st, meta, tok)
                return nxt, ok, kv, st
        out[2] = jax.jit(f)
        return tuple(out)
    return steps


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(fault, capsys, monkeypatch):
    from repro.serving import engine
    name, over = CELLS["poisson_open"]
    tiny_cell(name, monkeypatch, dict(over, warm_s=0.3))
    monkeypatch.setattr(engine, "_paged_steps", _broken_decode(fault))
    rc, line = _run(capsys, name, 9)
    assert rc == 0
    assert line["correct"] is False, line["checks"]
    gap = line["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_declared_metric_that_reads_nothing_is_an_error():
    ctx = {"cell": {"chips": 1},
           "per_layer": [{"name": "decode_attn_roofline", "unit": "%"}]}
    rec = {"trace": {"devices": {}}, "t0": 0.0, "t1": 1.0, "tokens": [],
           "prompt_len": {}}
    res = {"record": rec, "memory_peak_bytes": 0,
           "trace": {"busy_s": 0.0, "window_s": 1.0, "breakdown": {}},
           "correct": True, "attempted": 1, "failed": 0, "checks": {}}
    assert run.result_line(ctx, res, True, strict=False)["metrics"] == {}
    with pytest.raises(RuntimeError, match="decode_attn_roofline"):
        run.result_line(ctx, res, True, strict=True)
