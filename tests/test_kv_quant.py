"""int8 quantized paged-KV cache: round-trip, parity, COW, and dual gate.

Five rungs of the quantization contract (``ServeConfig.kv_dtype="int8"``):

1. *Round-trip* — ``quantize_int8``/``dequant_int8`` obey the universal
   error bound ``|back - x| <= 0.5*s + max(0, amax - 127*s)`` per slice
   (half a quantization step plus the clip slack from the bf16-rounded
   scale), with adversarial inputs: all-zero pages, denormal magnitudes,
   single-outlier heads.  Property-tested under hypothesis when installed,
   deterministic sweeps always.
2. *Attend-core parity* — every Pallas kernel's in-register dequant
   (vanilla GQA decode, windowed ring decode, MLA decode, ragged prefill,
   windowed ragged prefill, MLA ragged prefill) against the ``reference``
   backend's XLA gather+dequant oracle, which is itself checked exact
   against attending a pre-dequantized fp32 pool.
3. *Pool accounting* — int8 pools carry bf16 scale leaves on the same page
   axis; ``page_nbytes``/``kv_bytes_per_token`` count both, the int8/bf16
   byte ratio meets the <= 0.55x acceptance bar, and alloc/release
   conservation holds unchanged (one page id owns payload + scales).
4. *COW with scales* — the radix prefix cache under int8 stays token-exact
   against the uncached int8 engine: a partial-page fork that copied
   payload but not scales would diverge immediately.
5. *Dual gate* — the serving parity contract for quantized mode (bounded
   max-abs logit error vs a bf16 replay + exact greedy match at
   high-margin positions, ``serving.quant_verify``) passes for the three
   paged families on both backends.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ServeConfig, get_arch, reduced
from repro.models.attention import dequant_int8, quantize_int8
from repro.models.attn_backend import get_backend
from repro.serving import Engine, PagedKVPool, dual_gate_verify

from pool_layout import LAYER, gqa_pool, latent_pool, stack_layer

jax.config.update("jax_platform_name", "cpu")

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ----------------------------------------------------------------- round-trip

def _assert_roundtrip_bound(x: np.ndarray):
    """The contract's exact error bound, checked slice-wise in float64.

    Rounding contributes <= 0.5*s; the clip at +-127 contributes at most
    ``amax - 127*s`` when the bf16-rounded scale lands below ``amax/127``;
    a zero scale (all-zero or underflowing slice) stores q = 0, where the
    bound degenerates to ``amax`` itself."""
    q, s = quantize_int8(jnp.asarray(x, jnp.float32))
    assert q.dtype == jnp.int8 and s.dtype == jnp.bfloat16
    assert s.shape == x.shape[:-1]
    back = np.asarray(dequant_int8(q, s), np.float64)
    xf = np.asarray(x, np.float64)
    sf = np.asarray(s, np.float64)[..., None]
    amax = np.max(np.abs(xf), axis=-1, keepdims=True)
    bound = 0.5 * sf + np.maximum(0.0, amax - 127.0 * sf)
    assert np.all(np.abs(back - xf) <= bound + 1e-30)
    # zero-scale slices must store exact zeros (no garbage payload)
    zero = np.broadcast_to(sf == 0.0, q.shape)
    assert np.all(np.asarray(q)[zero] == 0)
    return q, s, back


@pytest.mark.parametrize("ps", [4, 8, 16])
@pytest.mark.parametrize("K,D", [(1, 64), (2, 32), (4, 16), (6, 8)])
def test_roundtrip_bounded_error(ps, K, D):
    """Page sizes x GQA ratios (MQA through MHA-ish head counts)."""
    rng = np.random.RandomState(ps * 100 + K)
    x = rng.randn(5, ps, K, D).astype(np.float32) * 3.0
    _assert_roundtrip_bound(x)


def test_roundtrip_all_zero_page_is_exact():
    q, s, back = _assert_roundtrip_bound(np.zeros((2, 8, 2, 16), np.float32))
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.asarray(s, np.float32) == 0.0)
    assert np.all(back == 0.0)


def test_roundtrip_denormal_magnitudes():
    """Scales that underflow bf16 (absmax/127 below the smallest bf16
    subnormal) must collapse the slice to exact zeros, not NaN/Inf; scales
    that survive as bf16 subnormals must still satisfy the bound."""
    rng = np.random.RandomState(7)
    signs = np.where(rng.rand(3, 8, 2, 8) < 0.5, -1.0, 1.0).astype(np.float32)
    for mag in (1e-39, 1e-38, 1e-30):
        x = signs * mag * (0.5 + rng.rand(3, 8, 2, 8).astype(np.float32))
        q, s, back = _assert_roundtrip_bound(x)
        assert np.all(np.isfinite(back))
    # deep underflow: absmax/127 ~ 8e-42 is below bf16's smallest subnormal
    x = signs * 1e-39
    q, s, _ = _assert_roundtrip_bound(x)
    assert np.all(np.asarray(s, np.float32) == 0.0)
    assert np.all(np.asarray(q) == 0)


def test_roundtrip_single_outlier_head_is_isolated():
    """The scale is per-(token-slot, kv-head): a 1e4 outlier in head 0 must
    not coarsen any other head's quantization grid."""
    rng = np.random.RandomState(8)
    base = rng.randn(1, 8, 4, 16).astype(np.float32)
    spiked = base.copy()
    spiked[..., 0, :] *= 1e4
    qb, sb = quantize_int8(jnp.asarray(base))
    qs, ss = quantize_int8(jnp.asarray(spiked))
    np.testing.assert_array_equal(np.asarray(qb)[..., 1:, :],
                                  np.asarray(qs)[..., 1:, :])
    np.testing.assert_array_equal(np.asarray(sb, np.float32)[..., 1:],
                                  np.asarray(ss, np.float32)[..., 1:])
    _assert_roundtrip_bound(spiked)


if HAVE_HYPOTHESIS:
    settings.register_profile("kv_quant_ci", max_examples=25, deadline=None)
    settings.load_profile("kv_quant_ci")

    @given(st.integers(0, 2 ** 31 - 1), st.sampled_from([4, 8, 16]),
           st.sampled_from([(1, 64), (2, 32), (4, 16)]),
           st.integers(-35, 30))
    def test_roundtrip_property(seed, ps, KD, exp):
        """Random pages over ~65 orders of magnitude hold the exact bound."""
        K, D = KD
        rng = np.random.RandomState(seed)
        x = rng.randn(2, ps, K, D).astype(np.float32) * (10.0 ** exp)
        _assert_roundtrip_bound(x)

    @given(st.integers(0, 2 ** 31 - 1), st.integers(1, 6))
    def test_roundtrip_property_flat_latent(seed, L):
        """MLA-shaped slices ([..., L] latent rows, scale per token-slot)."""
        rng = np.random.RandomState(seed)
        x = rng.randn(3, 8, 2 * L).astype(np.float32)
        _assert_roundtrip_bound(x)


# ------------------------------------------------------- attend-core parity

def _tables(rng, B, maxp, P):
    perm = rng.permutation(np.arange(1, P))[:B * maxp]
    return jnp.asarray(perm.reshape(B, maxp), jnp.int32)


def _quant_pool(rng, P, ps, K, D):
    """int8 K/V of per-head values drawn [P, ps, K, D] with their scales,
    as (per-head payloads and scales, the stacked pool leaves)."""
    kf = rng.randn(P, ps, K, D).astype(np.float32)
    vf = rng.randn(P, ps, K, D).astype(np.float32)
    kq, ks = quantize_int8(jnp.asarray(kf))
    vq, vs = quantize_int8(jnp.asarray(vf))
    pools = (gqa_pool(kq), stack_layer(ks), gqa_pool(vq), stack_layer(vs))
    return (kq, ks, vq, vs), pools


DECODE_CASES = [
    # (B, H, K, D, ps, maxp, window)
    (3, 4, 2, 32, 8, 5, 0),          # GQA 2:1
    (2, 6, 1, 64, 16, 3, 0),         # MQA
    (3, 4, 2, 32, 8, 5, 20),         # sliding-window ring
]


@pytest.mark.parametrize("B,H,K,D,ps,maxp,window", DECODE_CASES)
def test_int8_decode_attend_matches_reference(B, H, K, D, ps, maxp, window):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    (kq, ks, vq, vs), (kp, ksp, vp, vsp) = _quant_pool(rng, 4 * maxp, ps, K,
                                                        D)
    tables = _tables(rng, B, maxp, 4 * maxp)
    pos = jnp.asarray(np.concatenate(
        [[0], rng.randint(1, maxp * ps, size=B - 1)]), jnp.int32)
    scale = 1.0 / math.sqrt(D)
    ref = get_backend("reference").decode_attend(
        q, kp, vp, LAYER, tables, pos, kv_heads=K, scale=scale,
        window=window, k_scale=ksp, v_scale=vsp)
    out = get_backend("pallas").decode_attend(
        q, kp, vp, LAYER, tables, pos, kv_heads=K, scale=scale,
        window=window, k_scale=ksp, v_scale=vsp)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-5, rtol=2e-5)
    # the reference dequant path must equal attending a pre-dequantized
    # fp32 pool — the scale gather can hide no rounding of its own
    kf = gqa_pool(dequant_int8(kq, ks))
    vf = gqa_pool(dequant_int8(vq, vs))
    oracle = get_backend("reference").decode_attend(
        q, kf, vf, LAYER, tables, pos, kv_heads=K, scale=scale,
        window=window)
    np.testing.assert_allclose(np.asarray(ref, np.float32),
                               np.asarray(oracle, np.float32),
                               atol=1e-6, rtol=1e-6)


def test_int8_mla_decode_attend_matches_reference():
    rng = np.random.RandomState(1)
    B, H, L, R, ps, maxp = 3, 4, 16, 8, 8, 5
    P = 4 * maxp
    q_eff = jnp.asarray(rng.randn(B, H, L), jnp.float32)
    q_rope = jnp.asarray(rng.randn(B, H, R), jnp.float32)
    cq, cs = quantize_int8(jnp.asarray(rng.randn(P, ps, L), jnp.float32))
    rq, rs = quantize_int8(jnp.asarray(rng.randn(P, ps, R), jnp.float32))
    tables = _tables(rng, B, maxp, P)
    pos = jnp.asarray(np.concatenate(
        [[0], rng.randint(1, maxp * ps, size=B - 1)]), jnp.int32)
    scale = 1.0 / math.sqrt(L + R)
    pages, scales = latent_pool(cq, rq), dict(ckv_scale=stack_layer(cs),
                                              krope_scale=stack_layer(rs))
    ref = get_backend("reference").mla_decode_attend(
        q_eff, q_rope, pages, LAYER, tables, pos, scale=scale, **scales)
    out = get_backend("pallas").mla_decode_attend(
        q_eff, q_rope, pages, LAYER, tables, pos, scale=scale, **scales)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-5, rtol=2e-5)


def test_int8_prefill_attend_matches_reference():
    """Vanilla ragged prefill: the chunk's K/V already quantized into the
    post-write pool, read back dequantized inside the kernel."""
    rng = np.random.RandomState(2)
    B, H, K, D, ps, maxp, T = 2, 4, 2, 32, 8, 5, 16
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    _, (kp, ksp, vp, vsp) = _quant_pool(rng, 4 * maxp, ps, K, D)
    tables = _tables(rng, B, maxp, 4 * maxp)
    start = jnp.asarray([0, ps + 1], jnp.int32)
    n_live = jnp.asarray([T, T - 3], jnp.int32)
    ref = get_backend("reference").prefill_attend(
        q, None, None, kp, vp, LAYER, tables, start, n_live, kv_heads=K,
        k_scale=ksp, v_scale=vsp)
    out = get_backend("pallas").prefill_attend(
        q, q[:, :, :K], q[:, :, :K], kp, vp, LAYER, tables, start, n_live,
        kv_heads=K, k_scale=ksp, v_scale=vsp)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-5, rtol=2e-5)


def test_int8_windowed_prefill_attend_matches_reference():
    """Sliding-window ragged prefill: int8 resident ring + *unquantized*
    fresh chunk (fresh K/V only hit the pool after the attend)."""
    rng = np.random.RandomState(3)
    B, H, K, D, ps, n_ring, T, window = 2, 4, 2, 32, 8, 4, 16, 20
    q = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    kn = jnp.asarray(rng.randn(B, T, K, D), jnp.float32)
    vn = jnp.asarray(rng.randn(B, T, K, D), jnp.float32)
    _, (kp, ksp, vp, vsp) = _quant_pool(rng, 4 * n_ring, ps, K, D)
    tables = _tables(rng, B, n_ring, 4 * n_ring)
    start = jnp.asarray([0, 2 * ps + 3], jnp.int32)
    n_live = jnp.asarray([T, T - 5], jnp.int32)
    ref = get_backend("reference").prefill_attend(
        q, kn, vn, kp, vp, LAYER, tables, start, n_live, kv_heads=K,
        window=window, k_scale=ksp, v_scale=vsp)
    out = get_backend("pallas").prefill_attend(
        q, kn, vn, kp, vp, LAYER, tables, start, n_live, kv_heads=K,
        window=window, k_scale=ksp, v_scale=vsp)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-5, rtol=2e-5)


def test_int8_mla_prefill_attend_matches_reference():
    rng = np.random.RandomState(4)
    B, H, L, R, nope, vd, ps, maxp, T = 2, 4, 16, 8, 32, 32, 8, 5, 16
    P = 4 * maxp
    q = jnp.asarray(rng.randn(B, T, H, nope + R), jnp.float32)
    cq, cs = quantize_int8(jnp.asarray(rng.randn(P, ps, L), jnp.float32))
    rq, rs = quantize_int8(jnp.asarray(rng.randn(P, ps, R), jnp.float32))
    wkv_b = jnp.asarray(rng.randn(L, H, nope + vd) * 0.3, jnp.float32)
    tables = _tables(rng, B, maxp, P)
    start = jnp.asarray([0, ps + 3], jnp.int32)
    n_live = jnp.asarray([T, T - 5], jnp.int32)
    pages, scales = latent_pool(cq, rq), dict(ckv_scale=stack_layer(cs),
                                              krope_scale=stack_layer(rs))
    ref = get_backend("reference").mla_prefill_attend(
        q, pages, LAYER, wkv_b, tables, start, n_live, nope=nope, **scales)
    out = get_backend("pallas").mla_prefill_attend(
        q, pages, LAYER, wkv_b, tables, start, n_live, nope=nope, **scales)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------ pool accounting

def _cfg(name="qwen2-0.5b"):
    return dataclasses.replace(reduced(get_arch(name)), remat="none")


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "deepseek-v2-236b"])
def test_pool_scale_leaves_and_byte_accounting(arch):
    """int8 pools grow bf16 scale leaves on the shared page axis and the
    byte accounting counts them; the int8/bf16 bytes-per-token ratio meets
    the acceptance bar (<= 0.55x) for both GQA and MLA layouts."""
    scfg = ServeConfig(page_size=8, max_slots=2, max_len=32)
    pool_b = PagedKVPool(_cfg(arch), scfg)
    pool_i = PagedKVPool(_cfg(arch),
                         dataclasses.replace(scfg, kv_dtype="int8"))
    scale_keys = {k for k in pool_i.kv if k.endswith("_scale")}
    assert scale_keys and not {k for k in pool_b.kv if k.endswith("_scale")}
    for k in scale_keys:
        assert pool_i.kv[k].dtype == jnp.bfloat16
        assert pool_i.kv[k].shape[1] == pool_i.total_pages
    # same page geometry either way — only the bytes per page shrink
    assert pool_i.total_pages == pool_b.total_pages
    assert pool_i.table_width == pool_b.table_width
    assert pool_i.pages_for(20) == pool_b.pages_for(20)
    ratio = pool_i.kv_bytes_per_token / pool_b.kv_bytes_per_token
    assert ratio <= 0.55, f"{arch}: int8/bf16 bytes ratio {ratio:.3f}"
    assert pool_i.page_nbytes == pool_i.kv_bytes_per_token * scfg.page_size


def test_pool_conservation_under_int8():
    """alloc/share/release reconcile identically under int8: one page id
    owns payload and scales, so the counters never split."""
    scfg = ServeConfig(page_size=8, max_slots=2, max_len=32, kv_dtype="int8")
    pool = PagedKVPool(_cfg(), scfg)
    free0 = pool.num_free
    pages = pool.alloc(3)
    pool.share(pages[:2])
    assert pool.metrics.value("pool.pages_allocated") == 3
    assert pool.metrics.value("pool.refs_shared") == 2
    assert pool.metrics.value("pool.ref_total") == 5
    pool.release(pages[:2])            # shared pages survive one release
    assert pool.num_free == free0 - 3
    pool.release(pages)
    assert pool.num_free == free0
    assert pool.metrics.value("pool.pages_released") == 3
    assert pool.metrics.value("pool.pages_live") == 0
    assert pool.refcounts == {}


# -------------------------------------------------------- COW / prefix cache

@pytest.mark.parametrize("attn_backend", ["reference", "pallas"])
def test_int8_prefix_cache_token_identity(attn_backend):
    """Radix sharing + partial-page COW forks under int8 stay token-exact
    against the uncached int8 engine: the fork copies payload AND scale
    rows of the source page, so re-reads dequantize identically."""
    cfg = _cfg()
    rng = np.random.RandomState(5)
    fam = rng.randint(1, cfg.vocab, size=18).tolist()
    # same family prefix, diverging mid-page: forces COW forks, not shares
    prompts = [fam + rng.randint(1, cfg.vocab, size=6).tolist()
               for _ in range(4)]
    scfg = ServeConfig(page_size=8, max_slots=4, max_len=48,
                       kv_dtype="int8", prefix_cache=True,
                       attn_backend=attn_backend)
    eng = Engine(cfg, scfg, seed=0)
    res, m = eng.run_offline(prompts, 6)
    assert m["cached_tokens"] > 0
    # conservation holds through int8 COW forks: every page the run handed
    # out was either released or is still held (by the radix tree)
    assert (eng.pool.metrics.value("pool.pages_allocated")
            - eng.pool.metrics.value("pool.pages_released")
            == eng.pool.metrics.value("pool.pages_live"))
    ref, _ = Engine(cfg, dataclasses.replace(scfg, prefix_cache=False),
                    eng.params, seed=0).run_offline(prompts, 6)
    assert [r.tokens for r in res] == [r.tokens for r in ref]


# ------------------------------------------------------------------ dual gate

@pytest.mark.parametrize("arch,attn_backend", [
    ("qwen2-0.5b", "reference"),
    ("qwen2-0.5b", "pallas"),
    ("starcoder2-7b", "reference"),
    ("deepseek-v2-236b", "reference"),
])
def test_dual_gate_passes(arch, attn_backend):
    """The quantized serving contract end to end: int8 engine tokens pass
    bounded-logit-error + high-margin-greedy + replay fidelity."""
    cfg = _cfg(arch)
    rng = np.random.RandomState(6)
    prompts = [rng.randint(1, cfg.vocab,
                           size=int(rng.randint(4, 20))).tolist()
               for _ in range(3)]
    budgets = [4] * len(prompts)
    scfg = ServeConfig(page_size=8, max_slots=2, max_len=32,
                       kv_dtype="int8", attn_backend=attn_backend)
    eng = Engine(cfg, scfg, seed=0)
    res, _ = eng.run_offline(prompts, budgets)
    report = dual_gate_verify(cfg, scfg, eng.params, prompts,
                              [r.tokens for r in res],
                              attn_backend=attn_backend)
    assert report["ok"], report
    assert report["max_logit_err"] <= report["tol"]
    assert report["replay_failures"] == 0
    assert report["high_margin_mismatches"] == 0
    assert report["high_margin_tokens"] > 0    # the gate actually gated


def test_dual_gate_catches_planted_divergence():
    """A token the engine could not have produced (wrong at a high-margin
    position) must fail the gate — the gate is falsifiable, not vacuous."""
    cfg = _cfg()
    rng = np.random.RandomState(9)
    prompts = [rng.randint(1, cfg.vocab, size=12).tolist()]
    scfg = ServeConfig(page_size=8, max_slots=2, max_len=32,
                       kv_dtype="int8", attn_backend="reference")
    eng = Engine(cfg, scfg, seed=0)
    res, _ = eng.run_offline(prompts, 4)
    bad = list(res[0].tokens)
    bad[0] = (bad[0] + 1) % cfg.vocab
    report = dual_gate_verify(cfg, scfg, eng.params, prompts, [bad])
    assert not report["ok"]
