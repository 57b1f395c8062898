"""CPU fixtures: a tiny same-family model in interpret mode, and cells
built from it the way ``run.load_cell`` builds them from files."""
import copy
import dataclasses
import os
import sys

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

jax.config.update("jax_platform_name", "cpu")

TINY = {"hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "vocab_size": 512}
# the mean-gap limit at these widths, from CPU readings of the program and
# the int8 control on the prompts of test_bench_control (seeds 11-14, 360
# served tokens each): the program reads 4.0e-5 to 8.2e-5, the control
# 1.6e-4 to 4.9e-4
TINY_LIMIT = 1.5e-4
# the rehearsals of bench/run.py replay only some 40 tokens, so one
# rounding flip (gaps up to 0.0084 above) reads up to 2.1e-4 there; the
# planted faults of test_bench_run read 0.048 (KV pool unchanged) and 0.66
# (token altered)
RUN_LIMIT = 0.002
TINY_SERVE = {"page_size": 16, "max_slots": 4, "max_len": 256,
              "prefill_chunk_tokens": 64, "attn_backend": "pallas",
              "kv_dtype": "bf16", "prefix_cache": False,
              "speculate_tokens": 0}


def tiny_cell(name, monkeypatch, traffic_over=None):
    """The cell ``name`` from the repository's files, at tiny widths."""
    from bench import run
    from bench.drivers import serve
    from repro.configs import get_arch, reduced
    ctx = run.load_cell(name)
    ctx["config"] = conf = copy.deepcopy(ctx["config"])
    conf["model"].update(TINY)
    conf["serve"] = dict(TINY_SERVE)
    conf["check"] = dict(conf["check"], tokens=40, mean_logit_gap=RUN_LIMIT)
    ctx["model"] = conf["model"]
    t = ctx["traffic"] = copy.deepcopy(ctx["traffic"])
    t["prompt"].update(median=40, min=16, max=96)
    t["output"].update(min=4, max=16)
    if "median" in t["output"]:
        t["output"]["median"] = 8
    t["warm_s"] = 0.5
    t.update(traffic_over or {})
    arch = reduced(get_arch(conf["arch"]))
    monkeypatch.setattr(serve, "arch_config",
                        lambda m, a: dataclasses.replace(
                            arch, norm=m["norm"]))
    monkeypatch.setattr(run, "load_cell", lambda n: ctx)
    return ctx
