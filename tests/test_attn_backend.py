"""Attention-backend registry + fused paged-attention decode kernel parity.

Three rungs of the same contract, all on CPU with the Pallas kernels in
interpret mode:

1. *Attend-core* parity — the ``pallas`` backend's fused decode against the
   ``reference`` gather+attend oracle, swept across page sizes, GQA ratios
   (incl. MQA and MHA), partially-filled pages, sliding-window rings, softcap,
   dtypes, and the MLA absorbed-latent form.
2. *Block* parity — one full paged decode block (QKV + RoPE + scatter +
   attend + out-proj) per family through both backends.
3. *Engine* parity — ``ServeConfig(attn_backend="pallas")`` serving the three
   acceptance families (qwen2 paged_kv, starcoder2 windowed_kv, deepseek-v2
   paged_mla) with exact greedy-token match against the reference backend,
   which is itself verified against ``generate_static(batch_size=1)`` by
   ``tests/test_serving_families.py`` — the same check
   ``launch/serve.py --attn-backend pallas --verify`` runs.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ServeConfig, get_arch, reduced
from repro.models.attention import quantize_int8
from repro.models.attn_backend import (available_backends, decode_meta,
                                       get_backend, resolve_backend)

from pool_layout import LAYER, gqa_pool, latent_pool, stack_layer

jax.config.update("jax_platform_name", "cpu")


def _pool(rng, P, ps, K, D, dtype):
    """K and V pools of per-head values drawn [P, ps, K, D], stacked."""
    k = jnp.asarray(rng.randn(P, ps, K, D), dtype)
    v = jnp.asarray(rng.randn(P, ps, K, D), dtype)
    return gqa_pool(k), gqa_pool(v)


def _tables(rng, B, maxp, P):
    """Disjoint per-row physical pages, never the reserved null page 0."""
    perm = rng.permutation(np.arange(1, P))[:B * maxp]
    return jnp.asarray(perm.reshape(B, maxp), jnp.int32)


# --------------------------------------------------------------- attend cores

CORE_CASES = [
    # (B, H, K, D, ps, maxp, window)
    (3, 4, 2, 32, 8, 5, 0),          # GQA 2:1
    (2, 4, 4, 16, 4, 7, 0),          # MHA
    (2, 6, 1, 64, 16, 3, 0),         # MQA
    (3, 4, 2, 32, 8, 5, 20),         # sliding-window ring, window < ring
    (2, 4, 2, 16, 4, 4, 16),         # window == ring (every slot in window)
    # tables of several page blocks (the pallas walk takes 128 tokens a
    # step), not a multiple of the block, so rows end mid-block; with more
    # than three rows the last is idle (null table, pos 0) and ring rows
    # draw positions over three turns of the ring
    (5, 4, 2, 32, 16, 20, 0),        # 2.5 blocks of 8 pages
    (4, 6, 1, 16, 8, 37, 0),         # MQA, 2.3 blocks of 16 pages
    (5, 4, 2, 16, 16, 19, 200),      # ring of 2.4 blocks, window < ring
    (4, 8, 4, 16, 8, 21, 168),       # ring of 1.3 blocks, window == ring
]


@pytest.mark.parametrize("B,H,K,D,ps,maxp,window", CORE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, "int8"])
def test_decode_attend_matches_reference(B, H, K, D, ps, maxp, window, dtype):
    rng = np.random.RandomState(0)
    P = max(4, B + 1) * maxp
    scales = {}
    if dtype == "int8":
        q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
        kp, ks = quantize_int8(jnp.asarray(rng.randn(P, ps, K, D),
                                           jnp.float32))
        vp, vs = quantize_int8(jnp.asarray(rng.randn(P, ps, K, D),
                                           jnp.float32))
        kp, vp = gqa_pool(kp), gqa_pool(vp)
        scales = dict(k_scale=stack_layer(ks), v_scale=stack_layer(vs))
    else:
        q = jnp.asarray(rng.randn(B, H, D), dtype)
        kp, vp = _pool(rng, P, ps, K, D, dtype)
    tables = _tables(rng, B, maxp, P)
    # positions straddle page boundaries; row 0 pins the pos == 0 edge
    hi = maxp * ps * (3 if window and B > 3 else 1)
    pos = np.concatenate([[0], rng.randint(1, hi, size=B - 1)])
    if B > 3:
        tables, pos[-1] = tables.at[-1].set(0), 0
    pos = jnp.asarray(pos, jnp.int32)
    scale = 1.0 / math.sqrt(D)
    ref = get_backend("reference").decode_attend(
        q, kp, vp, LAYER, tables, pos, kv_heads=K, scale=scale,
        window=window, **scales)
    out = get_backend("pallas").decode_attend(
        q, kp, vp, LAYER, tables, pos, kv_heads=K, scale=scale,
        window=window, **scales)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_walk_schedule_visits_live_blocks_and_carries_dead_slots():
    """The decode walk's schedule: one step per live block of each row, in
    order; a slot past its row's extent holds the page it held one step
    before (the pipeline then fetches nothing), or the null page in a
    row's first block."""
    from repro.kernels.paged_attention.kernel import walk_schedule
    ps, ppb, width = 4, 8, 21                 # 21 pages: blocks of 8, 8, 5
    tables = np.arange(1, 5 * width + 1, dtype=np.int32).reshape(5, width)
    tables[3] = 0                             # an idle row
    pos = np.array([0, 30, 83, 0, 50], np.int32)
    sched, pages, n_steps = walk_schedule(jnp.asarray(tables),
                                          jnp.asarray(pos), ps, ppb)
    live = np.minimum(pos // ps + 1, width)   # 1, 8, 21, 1, 13 pages
    blocks = -(-live // ppb)
    assert int(n_steps) == blocks.sum() == 8
    n_blk = -(-width // ppb)
    steps = [(int(x) // n_blk, int(x) % n_blk)
             for x in np.asarray(sched)[:int(n_steps)]]
    assert steps == [(r, b) for r in range(5) for b in range(blocks[r])]
    pages = np.asarray(pages).reshape(-1, ppb)
    held = np.zeros(ppb, np.int32)
    for r, b in steps:
        slots = pages[r * n_blk + b]
        for j in range(ppb):
            col = b * ppb + j
            want = tables[r, col] if col < live[r] else \
                (held[j] if b else 0)
            assert slots[j] == want, (r, b, j)
        held = slots


def test_decode_attend_softcap():
    rng = np.random.RandomState(1)
    B, H, K, D, ps, maxp = 2, 4, 2, 32, 8, 4
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    kp, vp = _pool(rng, 16, ps, K, D, jnp.float32)
    tables = _tables(rng, B, maxp, 16)
    pos = jnp.asarray([7, 29], jnp.int32)
    ref = get_backend("reference").decode_attend(
        q, kp, vp, LAYER, tables, pos, kv_heads=K, scale=1 / math.sqrt(D),
        softcap=30.0)
    out = get_backend("pallas").decode_attend(
        q, kp, vp, LAYER, tables, pos, kv_heads=K, scale=1 / math.sqrt(D),
        softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


MLA_CASES = [
    # (B, H, L, R, ps, maxp)
    (3, 4, 16, 8, 8, 5),
    (2, 8, 32, 16, 4, 6),
    (1, 2, 8, 4, 16, 2),
    # the latent walk takes 128 tokens a step: tables of several blocks,
    # not a multiple of the block, rows ending mid-block; with more than
    # three rows the last is idle (null table, pos 0)
    (5, 4, 16, 8, 16, 20),           # 2.5 blocks of 8 pages
    (4, 8, 32, 16, 8, 37),           # 2.3 blocks of 16 pages
]


@pytest.mark.parametrize("B,H,L,R,ps,maxp", MLA_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, "int8"])
def test_mla_decode_attend_matches_reference(B, H, L, R, ps, maxp, dtype):
    rng = np.random.RandomState(2)
    P = max(4, B + 1) * maxp
    scales = {}
    if dtype == "int8":
        q_eff = jnp.asarray(rng.randn(B, H, L), jnp.float32)
        q_rope = jnp.asarray(rng.randn(B, H, R), jnp.float32)
        cc, cs = quantize_int8(jnp.asarray(rng.randn(P, ps, L), jnp.float32))
        cr, rs = quantize_int8(jnp.asarray(rng.randn(P, ps, R), jnp.float32))
        scales = dict(ckv_scale=stack_layer(cs), krope_scale=stack_layer(rs))
    else:
        q_eff = jnp.asarray(rng.randn(B, H, L), dtype)
        q_rope = jnp.asarray(rng.randn(B, H, R), dtype)
        cc = jnp.asarray(rng.randn(P, ps, L), dtype)
        cr = jnp.asarray(rng.randn(P, ps, R), dtype)
    tables = _tables(rng, B, maxp, P)
    pos = np.concatenate([[0], rng.randint(1, maxp * ps, size=B - 1)]) \
        if B > 1 else np.zeros(1, np.int64)
    if B > 3:
        tables, pos[-1] = tables.at[-1].set(0), 0
    pos = jnp.asarray(pos, jnp.int32)
    scale = 1.0 / math.sqrt(L + R)
    pages = latent_pool(cc, cr)
    ref = get_backend("reference").mla_decode_attend(
        q_eff, q_rope, pages, LAYER, tables, pos, scale=scale, **scales)
    out = get_backend("pallas").mla_decode_attend(
        q_eff, q_rope, pages, LAYER, tables, pos, scale=scale, **scales)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


# ------------------------------------------------------------------- registry

def test_registry_contract():
    assert set(available_backends()) >= {"reference", "pallas"}
    assert resolve_backend("reference") == "reference"
    assert resolve_backend("pallas") == "pallas"
    # auto resolves to the XLA reference path off-TPU
    assert resolve_backend("auto") == "reference"
    with pytest.raises(ValueError):
        resolve_backend("cuda")
    assert get_backend("pallas").name == "pallas"
    # pallas fuses all four cores now: decode (PR 4) and ragged prefill
    assert type(get_backend("pallas")).prefill_attend \
        is not type(get_backend("reference")).prefill_attend
    assert type(get_backend("pallas")).mla_prefill_attend \
        is not type(get_backend("reference")).mla_prefill_attend


def test_decode_meta_write_targets():
    cfg = reduced(get_arch("qwen2-0.5b"))
    tables = np.asarray([[3, 4, 5], [6, 7, 8]], np.int32)
    pos = np.asarray([0, 17], np.int32)
    m = decode_meta(cfg, 8, tables, pos)
    assert m["write_page"].tolist() == [3, 8]      # pages 0//8=0, 17//8=2
    assert m["write_off"].tolist() == [0, 1]
    # sliding-window: the column wraps at the ring horizon (window_pages
    # gives 32 // 8 + 1 == 5 pages so the page being written never evicts an
    # in-window token)
    wcfg = reduced(get_arch("starcoder2-7b"))
    assert wcfg.sliding_window == 32
    tables = np.asarray([[3, 4, 5, 6, 7, 9]], np.int32)
    m = decode_meta(wcfg, 8, tables, np.asarray([33], np.int32))
    assert m["write_page"].tolist() == [7]         # col (33//8) % 5 == 4
    assert m["write_off"].tolist() == [1]


# ---------------------------------------------------------------- block level

@pytest.mark.parametrize("arch", ["qwen2-0.5b", "starcoder2-7b",
                                  "deepseek-v2-236b"])
def test_paged_decode_block_parity(arch):
    """One full decode block (QKV + scatter + attend + out-proj) through both
    backends, from identical pool contents."""
    from repro.models.registry import build_model, init_params

    cfg = dataclasses.replace(reduced(get_arch(arch)), remat="none")
    model_ref = build_model(cfg, "reference")
    model_pal = build_model(cfg, "pallas")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(3)
    B, ps, maxp = 2, 8, 4
    P = B * maxp + 1
    # a pool pre-filled with plausible values: entries past pos are masked by
    # both backends, so random stale data is part of the contract under test
    kv = jax.tree.map(
        lambda a: jnp.asarray(rng.randn(*a.shape).astype(np.float32) * 0.3,
                              a.dtype),
        _abstract(model_ref.paged_cache_defs(P, ps)))
    tables = np.asarray(
        rng.permutation(np.arange(1, P))[:B * maxp].reshape(B, maxp),
        np.int32)
    pos = np.asarray([5, 19], np.int32)
    tokens = jnp.asarray(rng.randint(1, cfg.vocab, size=B), jnp.int32)
    meta = {k: jnp.asarray(v)
            for k, v in decode_meta(cfg, ps, tables, pos).items()}
    lr, kr, _ = model_ref.decode_paged(params, kv, {}, meta, tokens)
    lp, kp, _ = model_pal.decode_paged(params, kv, {}, meta, tokens)
    np.testing.assert_allclose(np.asarray(lr, np.float32),
                               np.asarray(lp, np.float32), atol=3e-2,
                               rtol=3e-2)
    # both backends write the new token to the same physical slots; deeper
    # layers' writes inherit the residual stream, so bf16-ulp drift from the
    # layer-0 attend is allowed but nothing structural may differ
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        atol=3e-2, rtol=3e-2), kr, kp)
    assert [int(t) for t in jnp.argmax(lr, -1)] \
        == [int(t) for t in jnp.argmax(lp, -1)]


def _abstract(defs):
    from repro.models.params import init_tree
    return init_tree(defs, jax.random.PRNGKey(0))


# -------------------------------------------------------------------- engine

@pytest.mark.parametrize("arch", ["qwen2-0.5b", "starcoder2-7b",
                                  "deepseek-v2-236b"])
def test_engine_pallas_exact_token_match(arch):
    """The acceptance contract: pallas-backend serving produces exactly the
    reference backend's greedy tokens for all three paged cache families."""
    from repro.serving import Engine

    cfg = dataclasses.replace(reduced(get_arch(arch)), remat="none")
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, cfg.vocab,
                           size=int(rng.randint(4, 28))).tolist()
               for _ in range(6)]
    budgets = [int(rng.randint(3, 10)) for _ in range(6)]
    scfg = ServeConfig(page_size=8, max_slots=4, max_len=48,
                       attn_backend="reference")
    eng = Engine(cfg, scfg, seed=0)
    ref, ref_m = eng.run_offline(prompts, budgets)
    pal, pal_m = Engine(
        cfg, dataclasses.replace(scfg, attn_backend="pallas"),
        eng.params, seed=0).run_offline(prompts, budgets)
    assert ref_m["attn_backend"] == "reference"
    assert pal_m["attn_backend"] == "pallas"
    assert pal_m["decode_steps"] > 0 and pal_m["decode_step_ms_p50"] > 0
    assert [r.tokens for r in ref] == [p.tokens for p in pal]


def test_engine_decode_pages_counter(monkeypatch):
    """``engine.decode_pages``: ``walked`` sums each decode plan's live
    extents (every row through its position's page, an idle row its one
    null page), ``table`` the rows times the table width."""
    from repro.serving import Engine
    from repro.serving import engine as engine_mod

    plans = []

    def recording(cfg, page_size, tables, pos):
        plans.append((page_size, tables.shape, pos.copy()))
        return decode_meta(cfg, page_size, tables, pos)

    monkeypatch.setattr(engine_mod, "decode_meta", recording)
    cfg = dataclasses.replace(reduced(get_arch("qwen2-0.5b")), remat="none")
    rng = np.random.RandomState(6)
    prompts = [rng.randint(1, cfg.vocab, size=n).tolist()
               for n in (5, 17, 30)]
    eng = Engine(cfg, ServeConfig(page_size=8, max_slots=4, max_len=48),
                 seed=0)
    _, metrics = eng.run_offline(prompts, [6, 9, 4])
    walked = sum(min(p // ps + 1, width) for ps, (_, width), pos in plans
                 for p in pos.tolist())
    table = sum(rows * width for _, (rows, width), _ in plans)
    counters = eng.metrics.snapshot()["counters"]
    assert metrics["decode_steps"] > 0 and plans
    assert counters["engine.decode_pages{kind=walked}"] == walked
    assert counters["engine.decode_pages{kind=table}"] == table
    assert 0 < walked < table


def test_engine_pallas_with_prefix_cache():
    """Backend choice composes with the radix prefix cache: cached-prefix
    pages written by one request are read back through the fused kernel."""
    from repro.serving import Engine

    cfg = dataclasses.replace(reduced(get_arch("qwen2-0.5b")), remat="none")
    rng = np.random.RandomState(5)
    fam = rng.randint(1, cfg.vocab, size=18).tolist()
    prompts = [fam + rng.randint(1, cfg.vocab, size=6).tolist()
               for _ in range(4)]
    scfg = ServeConfig(page_size=8, max_slots=4, max_len=48,
                       prefix_cache=True, attn_backend="pallas")
    eng = Engine(cfg, scfg, seed=0)
    res, m = eng.run_offline(prompts, 6)
    assert m["cached_tokens"] > 0          # later requests hit the cache
    ref_eng = Engine(
        cfg, dataclasses.replace(scfg, prefix_cache=False,
                                 attn_backend="reference"),
        eng.params, seed=0)
    ref, _ = ref_eng.run_offline(prompts, 6)
    assert [r.tokens for r in res] == [r.tokens for r in ref]
