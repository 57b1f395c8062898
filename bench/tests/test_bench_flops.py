"""Work counts come from the traffic's live lengths, never from the
engine's table width, the pages it sweeps or its padded rows."""

from bench import flops, records
from bench.tests.conftest import TINY

M = dict(TINY, mlp_gated=True)


def test_causal_attention_counts_each_query_at_its_own_context():
    start, n = 48, 16
    brute = sum(4 * 4 * 32 * (start + j + 1) * 2 for j in range(n))
    assert flops.attn_flops(M, start, n) == brute
    # K and V of every key once, plus q in and o out
    assert flops.attn_bytes(M, start, n) == 2 * (
        (start + n) * 2 * 2 * 32 * 2 + 2 * n * 4 * 32 * 2)
    f, b = flops.decode_attn(M, 100)
    assert (f, b) == (flops.attn_flops(M, 99, 1), flops.attn_bytes(M, 99, 1))


def test_roofline_names_its_bound():
    pk = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_s(1000, 10, pk) == (10.0, "compute")
    assert flops.roofline_s(10, 1000, pk) == (100.0, "memory")


def _served_work(max_len, buckets):
    """Serve the same requests on a tiny engine; return the decode and
    prefill work the readers count, and the engine's own geometry."""
    from repro.configs import ServeConfig, get_arch, reduced
    from repro.serving import Engine
    cfg = reduced(get_arch("qwen2-0.5b"))
    scfg = ServeConfig(page_size=16, max_slots=4, max_len=max_len,
                       prefill_chunk_tokens=32, prefill_buckets=buckets,
                       attn_backend="reference")
    eng = Engine(cfg, scfg, seed=3)
    seen = []
    eng.on_token = lambda rid, i, tok, t: seen.append((t, rid, i))
    prompts = [list(range(1, 1 + n)) for n in (20, 45, 70)]
    eng.run_offline(prompts, [6, 9, 4])
    rec = {"t0": 0.0, "t1": float("inf"), "tokens": seen, "tracer": eng.tracer,
           "prompt_len": {i: len(p) for i, p in enumerate(prompts)}}
    dec = [c for _, c in records.decoded(rec)]
    pre = [(s, n) for _, s, n, *_ in records.prefill_rows(rec)]
    return (sorted(dec), sorted(pre), eng.pool.table_width,
            eng.metrics.value("engine.prefill_padded_tokens"))


def test_counts_unchanged_when_table_width_doubles():
    d1, p1, w1, pad1 = _served_work(128, ())
    d2, p2, w2, pad2 = _served_work(256, (64,))
    assert w2 == 2 * w1                  # the kernel sweeps twice the pages
    assert pad2 != pad1                  # and the engine pads differently
    assert d1 == d2 and p1 == p2         # the traffic's work is the same
    assert sum(flops.decode_attn(M, c)[1] for c in d1) == \
        sum(flops.decode_attn(M, c)[1] for c in d2)
    assert len(d1) == (6 - 1) + (9 - 1) + (4 - 1)
    # prompt tokens prefilled: every prompt token exactly once
    assert sum(n for _, n in p1) == 20 + 45 + 70
