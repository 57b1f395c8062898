"""A new cell, traffic mix or per-layer metric is a new file: the harness
finds it by the name in BENCHMARK.json, with no edit to a file it has."""
import json
import os
import shutil

from bench import run


def _tree(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(run.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), root)
    return root


def test_new_cell_and_metric_are_found_by_name(tmp_path, monkeypatch):
    root = _tree(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    # a later PR adds a traffic file, a metric reader and two entries
    (root / "bench" / "traffic" / "bursty-chat.json").write_text(json.dumps(
        {"generator": "poisson_open", "rate_rps": 1.5, "warm_s": 2.0,
         "prompt": {"dist": "uniform", "min": 64, "max": 512},
         "output": {"dist": "uniform", "min": 16, "max": 64}}))
    (root / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(rec):\n    return rec['x'] * 2\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["workloads"].append({"name": "qwen2-0.5b.bursty-chat",
                            "config": "qwen2-0.5b-serve",
                            "traffic": "bursty-chat", "chips": 1,
                            "why": "test"})
    bm["per_layer"].append({"name": "new_metric", "unit": "ms",
                            "better": "lower", "source": "program_span",
                            "layer": "scheduler", "moves": "ttft_p90_ms",
                            "workloads": ["qwen2-0.5b.bursty-chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    monkeypatch.setattr(run, "ROOT", str(root))
    monkeypatch.setattr(run, "BENCH", str(root / "bench"))
    ctx = run.load_cell("qwen2-0.5b.bursty-chat")
    assert ctx["traffic"]["rate_rps"] == 1.5
    assert ctx["gen"].__name__ == "bench.generators.poisson_open"
    assert ctx["driver"].__name__ == "bench.drivers.serve"
    assert ctx["config"]["arch"] == "qwen2-0.5b"
    assert [m["name"] for m in ctx["per_layer"]] == ["new_metric"]
    names = [m["name"] for m in ctx["end_to_end"]]
    assert "setup_s" in names and "ttft_p90_ms" not in names
    assert run.reader("new_metric")({"x": 21}) == 42
    after = {p: p.read_bytes() for p in before}
    assert after == before               # no existing file was touched


def test_every_named_file_exists():
    bm = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    for c in bm["configs"]:
        assert os.path.isfile(os.path.join(run.ROOT, c["file"]))
    for w in bm["workloads"]:
        ctx = run.load_cell(w["name"])
        assert hasattr(ctx["gen"], "DRIVER")
        for m in ctx["per_layer"]:
            assert callable(run.reader(m["name"]))
