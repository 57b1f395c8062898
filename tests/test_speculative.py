"""Speculative decoding: small-q verify cores, proposer, and engine parity.

Five rungs of the speculation contract (``ServeConfig.speculate_tokens=K``):

1. *Verify-core parity* — the Pallas small-q ``verify_attend`` /
   ``mla_verify_attend`` kernels against the reference backend's XLA
   gather+mask oracle, swept over q_len 1..K, page sizes, GQA ratios,
   sliding-window rings, softcap, and int8 scale operands; dead query rows
   (``j >= n_q``) return exact zeros on every backend.
2. *q_len=1 degeneracy* — a verify step with no draft IS a decode step:
   the Pallas verify core at Q=1 reproduces the existing decode core
   bit-exactly (``assert_array_equal``, not allclose), bf16 and int8, so
   speculation can never perturb the non-speculative path it falls back to.
3. *Proposer + acceptance units* — ``NgramProposer`` (longest trailing
   n-gram, most recent occurrence, self-overlap, no-match), ``verify_meta``
   write targets (ring wrap, dead-row null-page routing), ``accept_length``
   planted divergence at every position, and the ``speculation_k`` family
   gate (state-slot and enc-dec families serve non-speculatively).
4. *Engine parity* — accepted tokens match the non-speculative greedy
   stream token-for-token across the three paged families x both backends
   x K in {2, 4, 8}, composed with the radix prefix cache, chunked
   prefill, the overlapped pump loop, and the int8 KV pool.
5. *Falsifiability* — a planted oracle proposer (drafts the true
   continuation) must accept everything and an anti-oracle (drafts
   guaranteed-wrong tokens, including rejects landing exactly on page
   boundaries) must accept nothing, while BOTH emit the identical token
   stream — acceptance bookkeeping and rollback are observable, not
   vacuous, and rejected drafts never poison the prefix cache.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ServeConfig, get_arch, reduced
from repro.models import build_model
from repro.models.attention import quantize_int8
from repro.models.attn_backend import get_backend, verify_meta
from repro.serving import (Engine, NgramProposer, accept_length,
                           speculation_k)

jax.config.update("jax_platform_name", "cpu")


def _cfg(name="qwen2-0.5b"):
    return dataclasses.replace(reduced(get_arch(name)), remat="none")


# ------------------------------------------------------- verify-core parity

def _tables(rng, B, maxp, P):
    perm = rng.permutation(np.arange(1, P))[:B * maxp]
    return jnp.asarray(perm.reshape(B, maxp), jnp.int32)


def _quant_pool(rng, P, ps, K, D):
    kf = rng.randn(P, ps, K, D).astype(np.float32)
    vf = rng.randn(P, ps, K, D).astype(np.float32)
    kq, ks = quantize_int8(jnp.asarray(kf))
    vq, vs = quantize_int8(jnp.asarray(vf))
    return kq, ks, vq, vs


VERIFY_CASES = [
    # (B, H, K, D, ps, maxp, window, softcap)
    (3, 4, 2, 32, 8, 5, 0, 0.0),       # GQA 2:1
    (2, 6, 1, 64, 16, 3, 0, 0.0),      # MQA
    (2, 4, 4, 16, 4, 6, 0, 0.0),       # MHA-ish, small pages
    (2, 4, 2, 32, 8, 5, 0, 30.0),      # logit softcap
    (3, 4, 2, 32, 8, 5, 20, 0.0),      # sliding-window ring
    (2, 4, 2, 32, 8, 4, 12, 0.0),      # tighter ring, window < page span
    # tables of several page blocks (the pallas walk takes 128 tokens a
    # step), not a multiple of the block, so rows end mid-block; with more
    # than three rows the last is idle (null table, pos 0, one query) and
    # ring rows draw positions over three turns of the ring
    (5, 4, 2, 32, 16, 20, 0, 0.0),     # 2.5 blocks of 8 pages
    (4, 4, 2, 16, 8, 37, 0, 30.0),     # 2.3 blocks of 16 pages, softcap
    (5, 4, 2, 16, 16, 19, 200, 0.0),   # ring of 2.4 blocks
]


def _verify_inputs(rng, B, H, K, D, ps, maxp, Q, window=0):
    P = max(4, B + 1) * maxp
    q = jnp.asarray(rng.randn(B, Q, H, D), jnp.float32)
    kp = jnp.asarray(rng.randn(P, ps, K, D), jnp.float32)
    vp = jnp.asarray(rng.randn(P, ps, K, D), jnp.float32)
    tables = _tables(rng, B, maxp, P)
    # row 0 keeps the degenerate fresh-sequence case (pos=0, single query);
    # the rest sit anywhere the Q-token window still fits the table span
    hi = maxp * ps * (3 if window and B > 3 else 1) - Q
    pos = np.concatenate([[0], rng.randint(1, hi, size=B - 1)])
    n_q = np.concatenate([[1], rng.randint(1, Q + 1, size=B - 1)])
    if B > 3:
        tables, pos[-1], n_q[-1] = tables.at[-1].set(0), 0, 1
    return q, kp, vp, tables, jnp.asarray(pos, jnp.int32), \
        jnp.asarray(n_q, jnp.int32)


@pytest.mark.parametrize("Q", [1, 2, 3, 5])
@pytest.mark.parametrize("B,H,K,D,ps,maxp,window,softcap", VERIFY_CASES)
def test_verify_attend_matches_reference(B, H, K, D, ps, maxp, window,
                                         softcap, Q):
    rng = np.random.RandomState(B * 100 + ps + Q)
    q, kp, vp, tables, pos, n_q = _verify_inputs(rng, B, H, K, D, ps,
                                                 maxp, Q, window)
    scale = 1.0 / math.sqrt(D)
    ref = get_backend("reference").verify_attend(
        q, kp, vp, tables, pos, n_q, scale=scale, softcap=softcap,
        window=window)
    out = get_backend("pallas").verify_attend(
        q, kp, vp, tables, pos, n_q, scale=scale, softcap=softcap,
        window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-5, rtol=2e-5)
    # dead query rows are exact zeros on both backends — the engine relies
    # on this to ignore the padded tail without masking on the host
    for arr in (np.asarray(ref, np.float32), np.asarray(out, np.float32)):
        for b in range(B):
            assert np.all(arr[b, int(n_q[b]):] == 0.0)


@pytest.mark.parametrize("Q", [1, 2, 4])
def test_int8_verify_attend_matches_reference(Q):
    B, H, K, D, ps, maxp = 3, 4, 2, 32, 8, 5
    rng = np.random.RandomState(10 + Q)
    q = jnp.asarray(rng.randn(B, Q, H, D), jnp.float32)
    kq, ks, vq, vs = _quant_pool(rng, 4 * maxp, ps, K, D)
    tables = _tables(rng, B, maxp, 4 * maxp)
    pos = jnp.asarray(np.concatenate(
        [[0], rng.randint(1, maxp * ps - Q, size=B - 1)]), jnp.int32)
    n_q = jnp.asarray(np.concatenate(
        [[1], rng.randint(1, Q + 1, size=B - 1)]), jnp.int32)
    scale = 1.0 / math.sqrt(D)
    ref = get_backend("reference").verify_attend(
        q, kq, vq, tables, pos, n_q, scale=scale, k_scale=ks, v_scale=vs)
    out = get_backend("pallas").verify_attend(
        q, kq, vq, tables, pos, n_q, scale=scale, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-5, rtol=2e-5)


# (B, H, L, R, ps, maxp): one block of pages, and tables of 2.4 blocks
# (the latent walk takes 128 tokens a step) whose last row is idle
MLA_WALK = [(3, 4, 16, 8, 8, 5), (5, 4, 16, 8, 16, 19)]


def _idle_last(tables, pos, n_q):
    """With more than three rows, the last is idle: null table, pos 0, one
    query."""
    if tables.shape[0] > 3:
        tables = tables.at[-1].set(0)
        pos, n_q = pos.at[-1].set(0), n_q.at[-1].set(1)
    return tables, pos, n_q


# the one-block cases keep their ids ([1], [2], [4])
@pytest.mark.parametrize("Q,shape", [
    pytest.param(Q, shape, id=f"{Q}" + ("-walk" if i else ""))
    for i, shape in enumerate(MLA_WALK) for Q in (1, 2, 4)])
def test_mla_verify_attend_matches_reference(Q, shape):
    B, H, L, R, ps, maxp = shape
    P = max(4, B + 1) * maxp
    rng = np.random.RandomState(20 + Q)
    q_eff = jnp.asarray(rng.randn(B, Q, H, L), jnp.float32)
    q_rope = jnp.asarray(rng.randn(B, Q, H, R), jnp.float32)
    cc = jnp.asarray(rng.randn(P, ps, L), jnp.float32)
    cr = jnp.asarray(rng.randn(P, ps, R), jnp.float32)
    tables = _tables(rng, B, maxp, P)
    pos = jnp.asarray(np.concatenate(
        [[0], rng.randint(1, maxp * ps - Q, size=B - 1)]), jnp.int32)
    n_q = jnp.asarray(np.concatenate(
        [[1], rng.randint(1, Q + 1, size=B - 1)]), jnp.int32)
    tables, pos, n_q = _idle_last(tables, pos, n_q)
    scale = 1.0 / math.sqrt(L + R)
    ref = get_backend("reference").mla_verify_attend(
        q_eff, q_rope, cc, cr, tables, pos, n_q, scale=scale)
    out = get_backend("pallas").mla_verify_attend(
        q_eff, q_rope, cc, cr, tables, pos, n_q, scale=scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-5, rtol=2e-5)


def test_int8_mla_verify_attend_matches_reference():
    B, H, L, R, ps, maxp, Q = 3, 4, 16, 8, 8, 5, 3
    P = 4 * maxp
    rng = np.random.RandomState(30)
    q_eff = jnp.asarray(rng.randn(B, Q, H, L), jnp.float32)
    q_rope = jnp.asarray(rng.randn(B, Q, H, R), jnp.float32)
    cq, cs = quantize_int8(jnp.asarray(rng.randn(P, ps, L), jnp.float32))
    rq, rs = quantize_int8(jnp.asarray(rng.randn(P, ps, R), jnp.float32))
    tables = _tables(rng, B, maxp, P)
    pos = jnp.asarray(np.concatenate(
        [[0], rng.randint(1, maxp * ps - Q, size=B - 1)]), jnp.int32)
    n_q = jnp.asarray(np.concatenate(
        [[1], rng.randint(1, Q + 1, size=B - 1)]), jnp.int32)
    scale = 1.0 / math.sqrt(L + R)
    ref = get_backend("reference").mla_verify_attend(
        q_eff, q_rope, cq, rq, tables, pos, n_q, scale=scale,
        ckv_scale=cs, krope_scale=rs)
    out = get_backend("pallas").mla_verify_attend(
        q_eff, q_rope, cq, rq, tables, pos, n_q, scale=scale,
        ckv_scale=cs, krope_scale=rs)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-5, rtol=2e-5)


# -------------------------------------------------------- q_len=1 degeneracy

QLEN1_CASES = [
    # (window, softcap, int8, maxp); 8-token pages, 16 a block
    pytest.param(0, 0.0, False, 5, id="0-0.0-False"),
    pytest.param(0, 30.0, False, 5, id="0-30.0-False"),
    pytest.param(20, 0.0, False, 5, id="20-0.0-False"),
    pytest.param(0, 0.0, True, 5, id="0-0.0-True"),
    pytest.param(0, 0.0, True, 37, id="0-0.0-True-multiblock"),
]


@pytest.mark.parametrize("window,softcap,int8,maxp", QLEN1_CASES)
def test_verify_qlen1_reproduces_decode_bitexact(window, softcap, int8, maxp):
    """A verify step with an empty draft must BE a decode step: same pool,
    same masks, same launch math — Pallas vs Pallas is checked bit-exact,
    reference vs reference to fp32 ulp (its two paths order the einsums
    differently)."""
    B, H, K, D, ps = 3, 4, 2, 32, 8
    rng = np.random.RandomState(40 + window + int(softcap) + int8)
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    if int8:
        kp, ks, vp, vs = _quant_pool(rng, 4 * maxp, ps, K, D)
    else:
        kp = jnp.asarray(rng.randn(4 * maxp, ps, K, D), jnp.float32)
        vp = jnp.asarray(rng.randn(4 * maxp, ps, K, D), jnp.float32)
        ks = vs = None
    tables = _tables(rng, B, maxp, 4 * maxp)
    pos = jnp.asarray(np.concatenate(
        [[0], rng.randint(1, maxp * ps, size=B - 1)]), jnp.int32)
    ones = jnp.ones((B,), jnp.int32)
    scale = 1.0 / math.sqrt(D)
    kw = dict(scale=scale, softcap=softcap, window=window,
              k_scale=ks, v_scale=vs)
    pal = get_backend("pallas")
    np.testing.assert_array_equal(
        np.asarray(pal.verify_attend(q[:, None], kp, vp, tables, pos, ones,
                                     **kw)[:, 0]),
        np.asarray(pal.decode_attend(q, kp, vp, tables, pos, **kw)))
    ref = get_backend("reference")
    np.testing.assert_allclose(
        np.asarray(ref.verify_attend(q[:, None], kp, vp, tables, pos, ones,
                                     **kw)[:, 0], np.float32),
        np.asarray(ref.decode_attend(q, kp, vp, tables, pos, **kw),
                   np.float32),
        atol=1e-6, rtol=1e-6)


def test_mla_verify_qlen1_reproduces_decode_bitexact():
    _mla_verify_qlen1_is_decode(MLA_WALK[0])


def test_mla_verify_qlen1_reproduces_decode_bitexact_multiblock():
    _mla_verify_qlen1_is_decode(MLA_WALK[1])


def _mla_verify_qlen1_is_decode(shape):
    B, H, L, R, ps, maxp = shape
    P = max(4, B + 1) * maxp
    rng = np.random.RandomState(50)
    q_eff = jnp.asarray(rng.randn(B, H, L), jnp.float32)
    q_rope = jnp.asarray(rng.randn(B, H, R), jnp.float32)
    cc = jnp.asarray(rng.randn(P, ps, L), jnp.float32)
    cr = jnp.asarray(rng.randn(P, ps, R), jnp.float32)
    tables = _tables(rng, B, maxp, P)
    pos = jnp.asarray(np.concatenate(
        [[0], rng.randint(1, maxp * ps, size=B - 1)]), jnp.int32)
    ones = jnp.ones((B,), jnp.int32)
    tables, pos, ones = _idle_last(tables, pos, ones)
    scale = 1.0 / math.sqrt(L + R)
    pal = get_backend("pallas")
    np.testing.assert_array_equal(
        np.asarray(pal.mla_verify_attend(q_eff[:, None], q_rope[:, None],
                                         cc, cr, tables, pos, ones,
                                         scale=scale)[:, 0]),
        np.asarray(pal.mla_decode_attend(q_eff, q_rope, cc, cr, tables,
                                         pos, scale=scale)))


# ------------------------------------------------- proposer/acceptance units

def test_ngram_proposer_longest_match_wins():
    # trailing 3-gram (4,2,3) never recurs; 2-gram (2,3) does, at index 1,
    # so the draft is the two tokens that followed it
    assert NgramProposer(2).propose([1, 2, 3, 4, 2, 3]) == [4, 2]


def test_ngram_proposer_prefers_most_recent_occurrence():
    # (1,2) occurs at index 0 and index 3 — recency must pick index 3,
    # whose continuation is 7, not index 0's 9
    assert NgramProposer(1).propose([1, 2, 9, 1, 2, 7, 1, 2]) == [7]


def test_ngram_proposer_self_overlap_and_history_cap():
    # periodic text: the match's continuation runs into the suffix itself;
    # the proposer reads through the overlap but never fabricates tokens
    # past the end of the history
    assert NgramProposer(4).propose([1, 2, 1, 2, 1, 2]) == [1, 2]


def test_ngram_proposer_no_match_and_degenerate_histories():
    assert NgramProposer(3).propose([1, 2, 3, 4, 5]) == []
    assert NgramProposer(3).propose([7]) == []
    assert NgramProposer(3).propose([]) == []


def test_accept_length_planted_divergence_every_position():
    draft = [5, 6, 7, 8]
    assert accept_length(draft, [5, 6, 7, 8]) == 4
    for j in range(4):
        verified = list(draft)
        verified[j] += 1
        assert accept_length(draft, verified) == j
    assert accept_length([], []) == 0


def test_verify_meta_write_targets_and_dead_rows():
    cfg = _cfg()
    tables = np.asarray([[3, 5, 7], [4, 6, 8]], np.int32)
    pos = np.asarray([5, 0], np.int32)
    n_q = np.asarray([3, 1], np.int32)
    meta = verify_meta(cfg, 4, tables, pos, n_q, 3)
    # row 0: positions 5,6,7 all land in table column 1 -> page 5
    np.testing.assert_array_equal(meta["write_page"][0], [5, 5, 5])
    np.testing.assert_array_equal(meta["write_off"][0], [1, 2, 3])
    # row 1: only query 0 is live; the dead tail routes to the null page
    np.testing.assert_array_equal(meta["write_page"][1], [4, 0, 0])
    assert meta["write_off"][1][0] == 0


def test_verify_meta_ring_wraps_at_table_width():
    cfg = dataclasses.replace(_cfg(), sliding_window=8)
    tables = np.asarray([[11, 13]], np.int32)
    meta = verify_meta(cfg, 4, tables, np.asarray([7], np.int32),
                       np.asarray([2], np.int32), 2)
    # positions 7, 8 -> columns 1, 2 % 2 = 0: the ring recycles column 0
    np.testing.assert_array_equal(meta["write_page"][0], [13, 11])
    np.testing.assert_array_equal(meta["write_off"][0], [3, 0])


def test_speculation_k_family_gate():
    scfg = ServeConfig(page_size=8, max_len=32, speculate_tokens=4)
    for arch, want in [("qwen2-0.5b", 4), ("starcoder2-7b", 4),
                       ("deepseek-v2-236b", 4), ("mamba2-780m", 0),
                       ("recurrentgemma-2b", 0),
                       ("seamless-m4t-large-v2", 0)]:
        cfg = _cfg(arch)
        spec = build_model(cfg).cache_spec()
        assert speculation_k(cfg, spec, scfg) == want, arch
        assert speculation_k(cfg, spec,
                             dataclasses.replace(scfg,
                                                 speculate_tokens=0)) == 0


# ------------------------------------------------------------- engine parity

def _prompts(cfg, rng, n=3, rep=True):
    """Mixed workload: repetitive prompts (prompt-lookup's best case, so the
    run exercises real acceptance) plus iid-random ones (accept ~0)."""
    out = []
    for i in range(n):
        if rep and i % 2 == 0:
            motif = rng.randint(1, cfg.vocab, size=4).tolist()
            out.append((motif * 4)[:14])
        else:
            out.append(rng.randint(1, cfg.vocab, size=12).tolist())
    return out


ENGINE_CASES = [
    # (arch, attn_backend, K) — three paged families x backends x K
    ("qwen2-0.5b", "reference", 2),
    ("qwen2-0.5b", "reference", 8),
    ("qwen2-0.5b", "pallas", 4),
    ("starcoder2-7b", "reference", 4),
    ("starcoder2-7b", "pallas", 2),
    ("deepseek-v2-236b", "reference", 4),
    ("deepseek-v2-236b", "pallas", 4),
]


@pytest.mark.parametrize("arch,attn_backend,K", ENGINE_CASES)
def test_engine_speculative_token_identity(arch, attn_backend, K):
    """The absolute contract: the speculative engine's emitted stream is
    token-for-token the non-speculative greedy stream."""
    cfg = _cfg(arch)
    rng = np.random.RandomState(60)
    prompts = _prompts(cfg, rng)
    ps = 16 if K >= 8 else 8
    base = ServeConfig(page_size=ps, max_slots=2, max_len=3 * ps + ps,
                       attn_backend=attn_backend)
    eng = Engine(cfg, dataclasses.replace(base, speculate_tokens=K), seed=0)
    assert eng.spec_k == K
    res, m = eng.run_offline(prompts, 12)
    assert m["spec_tokens"] == K and m["spec_proposed"] > 0
    ref, _ = Engine(cfg, base, eng.params, seed=0).run_offline(prompts, 12)
    assert [r.tokens for r in res] == [r.tokens for r in ref]


@pytest.mark.parametrize("attn_backend", ["reference", "pallas"])
def test_speculation_composes_cache_and_chunking(attn_backend):
    """Radix prefix sharing + Sarathi chunked prefill + speculation stay
    token-exact against the plain uncached non-speculative engine."""
    cfg = _cfg()
    rng = np.random.RandomState(61)
    fam = (rng.randint(1, cfg.vocab, size=4).tolist() * 5)[:18]
    prompts = [fam + rng.randint(1, cfg.vocab, size=4).tolist()
               for _ in range(4)]
    scfg = ServeConfig(page_size=8, max_slots=2, max_len=48,
                       prefix_cache=True, prefill_chunk_tokens=8,
                       speculate_tokens=3, attn_backend=attn_backend)
    eng = Engine(cfg, scfg, seed=0)
    res, m = eng.run_offline(prompts, 8)
    assert m["cached_tokens"] > 0 and m["spec_proposed"] > 0
    plain = ServeConfig(page_size=8, max_slots=2, max_len=48,
                        attn_backend=attn_backend)
    ref, _ = Engine(cfg, plain, eng.params, seed=0).run_offline(prompts, 8)
    assert [r.tokens for r in res] == [r.tokens for r in ref]


def test_speculation_under_overlap_pump():
    """The pipelined pump() loop emits the same stream as synchronous
    step() under speculation (staging auto-disables for verify steps)."""
    cfg = _cfg()
    rng = np.random.RandomState(62)
    prompts = _prompts(cfg, rng)
    scfg = ServeConfig(page_size=8, max_slots=2, max_len=32,
                       speculate_tokens=2)
    eng = Engine(cfg, scfg, seed=0)
    sync, _ = eng.run_offline(prompts, 8)
    ovl, _ = Engine(cfg, scfg, eng.params, seed=0).run_offline(
        prompts, 8, overlap=True)
    assert [r.tokens for r in sync] == [r.tokens for r in ovl]


@pytest.mark.parametrize("attn_backend", ["reference", "pallas"])
def test_int8_speculative_token_identity(attn_backend):
    """Speculation composes with the quantized pool: int8+spec matches
    int8 non-spec exactly (same pool contents -> same argmax stream)."""
    cfg = _cfg()
    rng = np.random.RandomState(63)
    prompts = _prompts(cfg, rng)
    base = ServeConfig(page_size=8, max_slots=2, max_len=32,
                       kv_dtype="int8", attn_backend=attn_backend)
    eng = Engine(cfg, dataclasses.replace(base, speculate_tokens=4), seed=0)
    res, m = eng.run_offline(prompts, 10)
    assert m["spec_proposed"] > 0
    ref, _ = Engine(cfg, base, eng.params, seed=0).run_offline(prompts, 10)
    assert [r.tokens for r in res] == [r.tokens for r in ref]


def test_state_family_serves_non_speculatively():
    """ssm has no paged pool: the engine must quietly gate speculation off
    (spec_k == 0, no proposer) and serve the stream unchanged."""
    cfg = _cfg("mamba2-780m")
    rng = np.random.RandomState(64)
    prompts = [rng.randint(1, cfg.vocab, size=8).tolist() for _ in range(2)]
    scfg = ServeConfig(page_size=8, max_slots=2, max_len=32,
                       speculate_tokens=4)
    eng = Engine(cfg, scfg, seed=0)
    assert eng.spec_k == 0 and eng.proposer is None
    res, m = eng.run_offline(prompts, 6)
    assert "spec_tokens" not in m
    ref, _ = Engine(cfg, dataclasses.replace(scfg, speculate_tokens=0),
                    eng.params, seed=0).run_offline(prompts, 6)
    assert [r.tokens for r in res] == [r.tokens for r in ref]


# ----------------------------------------------- falsifiability and rollback

class _Oracle:
    """Planted proposer: drafts the TRUE greedy continuation (learned from
    a baseline run), matched to the request by its prompt prefix."""

    def __init__(self, k, prompts, continuations):
        self.k = k
        self.plan = [(list(p), list(c))
                     for p, c in zip(prompts, continuations)]

    def propose(self, tokens):
        toks = list(tokens)
        for p, cont in self.plan:
            if toks[:len(p)] == p:
                g = len(toks) - len(p)
                return cont[g:g + self.k]
        return []


class _AntiOracle(_Oracle):
    """Drafts guaranteed-WRONG tokens: every draft position differs from
    the true continuation, so greedy verify must reject all of them."""

    def __init__(self, k, prompts, continuations, vocab):
        super().__init__(k, prompts, continuations)
        self.vocab = vocab

    def propose(self, tokens):
        return [(t + 1) % self.vocab for t in super().propose(tokens)]


def test_oracle_accepts_everything_anti_oracle_accepts_nothing():
    """Both planted proposers must reproduce the exact baseline stream;
    only the acceptance counters distinguish them.  An accept/rollback bug
    cannot pass both: over-accepting corrupts the anti-oracle stream,
    under-accepting shows up as oracle accepted < proposed."""
    cfg = _cfg()
    rng = np.random.RandomState(65)
    prompts = [rng.randint(1, cfg.vocab, size=int(n)).tolist()
               for n in rng.randint(6, 13, size=3)]
    base = ServeConfig(page_size=8, max_slots=2, max_len=32)
    ref_eng = Engine(cfg, base, seed=0)
    ref, _ = ref_eng.run_offline(prompts, 8)
    conts = [r.tokens for r in ref]

    scfg = dataclasses.replace(base, speculate_tokens=3)
    eng = Engine(cfg, scfg, ref_eng.params, seed=0)
    eng.proposer = _Oracle(eng.spec_k, prompts, conts)
    res, m = eng.run_offline(prompts, 8)
    assert [r.tokens for r in res] == conts
    assert m["spec_proposed"] > 0
    assert m["spec_accepted"] == m["spec_proposed"]
    assert m["spec_accept_rate"] == 1.0

    eng = Engine(cfg, scfg, ref_eng.params, seed=0)
    eng.proposer = _AntiOracle(eng.spec_k, prompts, conts, cfg.vocab)
    res, m = eng.run_offline(prompts, 8)
    assert [r.tokens for r in res] == conts
    assert m["spec_proposed"] > 0
    assert m["spec_accepted"] == 0


def test_full_accept_page_boundary_growth():
    """With the oracle every step emits K+1 tokens, so positions jump past
    page boundaries mid-step (page_size=4, K=3 -> one full page per step):
    the scheduler must have granted pages for pos..pos+K up front or the
    verify write lands on a clamped/null page and the stream diverges."""
    cfg = _cfg()
    rng = np.random.RandomState(66)
    prompts = [rng.randint(1, cfg.vocab, size=10).tolist()
               for _ in range(2)]
    base = ServeConfig(page_size=4, max_slots=2, max_len=32)
    ref_eng = Engine(cfg, base, seed=0)
    ref, _ = ref_eng.run_offline(prompts, 12)
    conts = [r.tokens for r in ref]
    eng = Engine(cfg, dataclasses.replace(base, speculate_tokens=3),
                 ref_eng.params, seed=0)
    eng.proposer = _Oracle(eng.spec_k, prompts, conts)
    res, m = eng.run_offline(prompts, 12)
    assert [r.tokens for r in res] == conts
    assert m["spec_accepted"] == m["spec_proposed"] > 0


def test_rejected_draft_on_page_boundary_never_reaches_radix():
    """Satellite regression: prompt length 10 with page_size=4 puts the
    first verify step's rejected drafts at positions 11..13 — position 12
    IS a page boundary.  Later identical prompts then restore from the
    radix cache; if rollback had published draft-polluted pages, their
    streams would diverge from the uncached baseline."""
    cfg = _cfg()
    rng = np.random.RandomState(67)
    fam = rng.randint(1, cfg.vocab, size=10).tolist()
    prompts = [list(fam) for _ in range(4)]
    base = ServeConfig(page_size=4, max_slots=2, max_len=32)
    ref_eng = Engine(cfg, base, seed=0)
    ref, _ = ref_eng.run_offline(prompts, 8)
    conts = [r.tokens for r in ref]
    scfg = dataclasses.replace(base, prefix_cache=True, speculate_tokens=3)
    eng = Engine(cfg, scfg, ref_eng.params, seed=0)
    eng.proposer = _AntiOracle(eng.spec_k, prompts, conts, cfg.vocab)
    res, m = eng.run_offline(prompts, 8)
    assert m["cached_tokens"] > 0          # the cache actually restored
    assert m["spec_proposed"] > 0 and m["spec_accepted"] == 0
    assert [r.tokens for r in res] == conts
