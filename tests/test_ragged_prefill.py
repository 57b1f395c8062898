"""Fused ragged paged-prefill kernel parity (interpret mode on CPU).

Same three rungs as the decode-kernel suite (``test_attn_backend``):

1. *Attend-core* parity — the ``pallas`` backend's ragged prefill against
   the ``reference`` gather+attend oracle, swept across page sizes, GQA
   ratios (incl. MQA and MHA), chunk offsets (``start > 0``), ragged live
   lengths, sliding-window rings, softcap, dtypes, and the MLA
   materialized-K form.
2. *Block* parity — one full paged prefill block (QKV + RoPE + scatter +
   attend + out-proj) per family through both backends from identical pool
   contents.
3. *Engine* parity — chunked-prefill serving (``prefill_chunk_tokens``)
   with the pallas backend, exact greedy-token match against the reference
   backend for all three paged cache families.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ServeConfig, get_arch, reduced
from repro.models.attn_backend import get_backend, prefill_meta

from pool_layout import LAYER, gqa_pool, latent_pool

jax.config.update("jax_platform_name", "cpu")


def _pool(rng, P, ps, K, D, dtype):
    """K and V pools of per-head values drawn [P, ps, K, D], stacked."""
    k = jnp.asarray(rng.randn(P, ps, K, D), dtype)
    v = jnp.asarray(rng.randn(P, ps, K, D), dtype)
    return gqa_pool(k), gqa_pool(v)


def _tables(rng, B, maxp, P):
    perm = rng.permutation(np.arange(1, P))[:B * maxp]
    return jnp.asarray(perm.reshape(B, maxp), jnp.int32)


def _assert_close(out, ref, dtype):
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


# --------------------------------------------------------------- attend cores

VANILLA_CASES = [
    # (B, H, K, D, ps, maxp, T)
    (3, 4, 2, 32, 8, 5, 16),         # GQA 2:1, multi-page chunk
    (2, 4, 4, 16, 4, 7, 12),         # MHA, T not a page multiple
    (2, 6, 1, 64, 16, 3, 16),        # MQA
    (1, 4, 2, 32, 8, 6, 40),         # one long chunk spanning many pages
]


@pytest.mark.parametrize("B,H,K,D,ps,maxp,T", VANILLA_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prefill_attend_matches_reference(B, H, K, D, ps, maxp, T, dtype):
    """Vanilla GQA: chunk K/V already resident (post-write pool); per-row
    offsets exercise first chunks (start 0), continuations, and COW-style
    unaligned starts."""
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, T, H, D), dtype)
    kp, vp = _pool(rng, 4 * maxp, ps, K, D, dtype)
    tables = _tables(rng, B, maxp, 4 * maxp)
    starts = [0, ps + 1, 2 * ps]                     # aligned and unaligned
    start = jnp.asarray([starts[b % len(starts)] for b in range(B)],
                        jnp.int32)
    n_live = jnp.asarray(
        np.concatenate([[T], rng.randint(1, T + 1, size=B - 1)]), jnp.int32)
    ref = get_backend("reference").prefill_attend(
        q, None, None, kp, vp, LAYER, tables, start, n_live, kv_heads=K)
    out = get_backend("pallas").prefill_attend(
        q, q[:, :, :K], q[:, :, :K], kp, vp, LAYER, tables, start, n_live,
        kv_heads=K)
    _assert_close(out, ref, dtype)


def test_prefill_attend_softcap():
    rng = np.random.RandomState(1)
    B, H, K, D, ps, maxp, T = 2, 4, 2, 32, 8, 4, 16
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    kp, vp = _pool(rng, 16, ps, K, D, jnp.float32)
    tables = _tables(rng, B, maxp, 16)
    start = jnp.asarray([0, ps], jnp.int32)
    n_live = jnp.asarray([T, T - 3], jnp.int32)
    ref = get_backend("reference").prefill_attend(
        q, None, None, kp, vp, LAYER, tables, start, n_live, kv_heads=K,
        softcap=30.0)
    out = get_backend("pallas").prefill_attend(
        q, q[:, :, :K], q[:, :, :K], kp, vp, LAYER, tables, start, n_live,
        kv_heads=K, softcap=30.0)
    _assert_close(out, ref, jnp.float32)


WINDOW_CASES = [
    # (B, H, K, D, ps, n_ring, T, window)
    (2, 4, 2, 32, 8, 4, 16, 20),     # chunk crosses the window
    (2, 4, 1, 16, 4, 5, 8, 16),      # MQA ring
    (1, 4, 2, 32, 8, 3, 24, 17),     # unaligned window, chunk > ring span
]


@pytest.mark.parametrize("B,H,K,D,ps,n_ring,T,window", WINDOW_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_windowed_prefill_attend_matches_reference(B, H, K, D, ps, n_ring, T,
                                                   window, dtype):
    """Sliding-window ring: fresh chunk K/V + the pre-write page ring, at
    offsets that exercise both the no-history (start 0) and ring-history
    paths."""
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(B, T, H, D), dtype)
    kn = jnp.asarray(rng.randn(B, T, K, D), dtype)
    vn = jnp.asarray(rng.randn(B, T, K, D), dtype)
    kp, vp = _pool(rng, 4 * n_ring, ps, K, D, dtype)
    tables = _tables(rng, B, n_ring, 4 * n_ring)
    start = jnp.asarray(
        [0] + [int(rng.randint(1, 3 * n_ring * ps)) for _ in range(B - 1)],
        jnp.int32)
    n_live = jnp.asarray(
        np.concatenate([[T], rng.randint(1, T + 1, size=B - 1)]), jnp.int32)
    ref = get_backend("reference").prefill_attend(
        q, kn, vn, kp, vp, LAYER, tables, start, n_live, kv_heads=K,
        window=window)
    out = get_backend("pallas").prefill_attend(
        q, kn, vn, kp, vp, LAYER, tables, start, n_live, kv_heads=K,
        window=window)
    _assert_close(out, ref, dtype)


@pytest.mark.parametrize("B,H,L,R,nope,vd,ps,maxp,T", [
    (2, 4, 16, 8, 32, 32, 8, 5, 16),
    (1, 2, 8, 4, 16, 16, 4, 6, 12),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mla_prefill_attend_matches_reference(B, H, L, R, nope, vd, ps, maxp,
                                              T, dtype):
    """MLA materialized-K: per-head K/V rebuilt from latent pages inside the
    kernel, at the reference einsum's rounding point."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(B, T, H, nope + R), dtype)
    P = 4 * maxp
    cc = jnp.asarray(rng.randn(P, ps, L), dtype)
    cr = jnp.asarray(rng.randn(P, ps, R), dtype)
    wkv_b = jnp.asarray(rng.randn(L, H, nope + vd) * 0.3, dtype)
    tables = _tables(rng, B, maxp, P)
    start = jnp.asarray([0, ps + 3][:B], jnp.int32)
    n_live = jnp.asarray([T, max(T - 5, 1)][:B], jnp.int32)
    pages = latent_pool(cc, cr)
    ref = get_backend("reference").mla_prefill_attend(
        q, pages, LAYER, wkv_b, tables, start, n_live, nope=nope)
    out = get_backend("pallas").mla_prefill_attend(
        q, pages, LAYER, wkv_b, tables, start, n_live, nope=nope)
    _assert_close(out, ref, dtype)


# ---------------------------------------------------------------- block level

@pytest.mark.parametrize("arch", ["qwen2-0.5b", "starcoder2-7b",
                                  "deepseek-v2-236b"])
def test_paged_prefill_block_parity(arch):
    """One full chunk-prefill step (QKV + RoPE + scatter + attend +
    out-proj, all layers) through both backends from identical pool
    contents, at a mid-prompt chunk offset."""
    from repro.models.params import init_tree
    from repro.models.registry import build_model, init_params

    cfg = dataclasses.replace(reduced(get_arch(arch)), remat="none")
    model_ref = build_model(cfg, "reference")
    model_pal = build_model(cfg, "pallas")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(4)
    B, ps, maxp, T = 2, 8, 4, 8
    P = B * maxp + 1
    kv = jax.tree.map(
        lambda a: jnp.asarray(rng.randn(*a.shape).astype(np.float32) * 0.3,
                              a.dtype),
        init_tree(model_ref.paged_cache_defs(P, ps), jax.random.PRNGKey(0)))
    tables = np.asarray(
        rng.permutation(np.arange(1, P))[:B * maxp].reshape(B, maxp),
        np.int32)
    start = np.asarray([0, ps], np.int32)            # first + second chunk
    n_tail = np.asarray([T, T - 2], np.int32)
    slots = np.asarray([0, 1], np.int32)
    tokens = jnp.asarray(rng.randint(1, cfg.vocab, size=(B, T)), jnp.int32)
    meta = {k: jnp.asarray(v) for k, v in prefill_meta(
        cfg, ps, tables, slots, start, n_tail, T).items()}
    lr, kr, _ = model_ref.prefill_paged(params, kv, {}, meta, tokens)
    lp, kp, _ = model_pal.prefill_paged(params, kv, {}, meta, tokens)
    np.testing.assert_allclose(np.asarray(lr, np.float32),
                               np.asarray(lp, np.float32), atol=3e-2,
                               rtol=3e-2)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        atol=3e-2, rtol=3e-2), kr, kp)
    assert [int(t) for t in jnp.argmax(lr, -1)] \
        == [int(t) for t in jnp.argmax(lp, -1)]


# -------------------------------------------------------------------- engine

@pytest.mark.parametrize("arch", ["qwen2-0.5b", "starcoder2-7b",
                                  "deepseek-v2-236b"])
def test_engine_chunked_pallas_exact_token_match(arch):
    """Chunked prefill through the ragged kernel produces exactly the
    reference backend's greedy tokens for all three paged cache families."""
    from repro.serving import Engine

    cfg = dataclasses.replace(reduced(get_arch(arch)), remat="none")
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, cfg.vocab,
                           size=int(rng.randint(4, 40))).tolist()
               for _ in range(6)]
    budgets = [int(rng.randint(3, 10)) for _ in range(6)]
    scfg = ServeConfig(page_size=8, max_slots=4, max_len=56,
                       prefill_chunk_tokens=16, attn_backend="reference")
    eng = Engine(cfg, scfg, seed=0)
    ref, ref_m = eng.run_offline(prompts, budgets)
    pal, pal_m = Engine(
        cfg, dataclasses.replace(scfg, attn_backend="pallas"),
        eng.params, seed=0).run_offline(prompts, budgets)
    assert ref_m["chunked_prefill_steps"] > 0      # long prompts did chunk
    assert pal_m["chunked_prefill_steps"] > 0
    assert [r.tokens for r in ref] == [p.tokens for p in pal]
