"""Fused ragged paged-prefill — Pallas TPU kernels.

A chunk-prefill step attends a ragged batch of prompt chunks — every row at
its own offset (``start``), with its own live length — against that row's
paged KV.  The per-row page table rides in as a *scalar-prefetch* operand so
the K/V BlockSpec index maps resolve ``tables[b, j]`` before the body runs
and the pipeline DMAs exactly the physical pages the row owns: the
``pool[tables]`` gather the XLA reference path materializes in HBM never
exists here, and no row pays for another row's prompt length.

Three kernel bodies cover every paged prefill family in
``models.cache_spec``:

* ``_ragged_prefill_kernel`` — vanilla GQA.  The chunk's K/V are already
  resident (scattered before the attend), so the kernel sweeps the row's
  pages with absolute causal masking (``k_abs <= q_abs``); pages wholly past
  the chunk's last query are skipped.
* ``_windowed_ragged_prefill_kernel`` — sliding-window page rings.  The ring
  is read *pre-write* (writing first would recycle slots still holding
  in-window keys of the chunk's earliest queries): ring slots are masked by
  the absolute position recovered from the ring layout relative to
  ``start - 1``, and the chunk's fresh K/V ride in as extra key blocks with
  the causal+window rule.
* ``_mla_prefill_walk_kernel`` — MLA materialized-K on the decode walk
  (``paged_attention.kernel.walk_schedule``): each query block of a chunk
  row walks the row's latent pages a block of pages at a time, for a group
  of heads.  Per block, the per-head K (``ckv @ w_uk`` ++ roped ``krope``)
  and V (``ckv @ w_uv``) are materialized *inside the kernel* — rounded to
  the cache dtype at exactly the point the reference einsum rounds — so the
  [B, S, H, *] K/V tensors the reference path builds in HBM never exist.

Numerics match the reference chunked path's rounding points exactly: fp32
scores (scale after the dot, softcap after scale), one softmax at the true
global max over the row's full key set (a two-phase page sweep — scores
first, probability-weighted values second — rather than an online softmax,
so the probabilities round at the same max as the reference; the MLA walk
takes the max and normalizer in a first pass over the pages and the
probabilities in a second), probabilities
rounded to the value dtype before the PV product, fp32 PV accumulation, one
cast at the block output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..paged_attention.kernel import (_init, _mla_page, live_pages,
                                      mla_pages_per_block, page_head,
                                      scale_view, walk_schedule)

# the reference mask constant (models.attention.NEG_INF): finite, so a
# fully-masked row softmaxes to the same uniform distribution the reference
# produces instead of NaN
NEG_INF = -1e30


# The score scratch is *key-major*: [table tokens, query rows].  A page's
# scores land at a dynamic sublane offset (a multiple of the page size),
# which Mosaic accepts; the query-major layout would need a dynamic lane
# offset that is not a multiple of 128, which it refuses.
SCORE_VMEM_BYTES = 16 * 2**20     # budget of one grid step's score scratch
VMEM_LIMIT_BYTES = 32 * 2**20     # scoped VMEM limit of the prefill kernels


def score_bytes(n_tokens: int, rows: int) -> int:
    """VMEM bytes of an fp32 [n_tokens, rows] score scratch (lanes pad to
    128)."""
    return n_tokens * (-(-rows // 128) * 128) * 4


def fit_q_block(T: int, group: int, n_tokens: int, q_blk: int = 128) -> int:
    """The query block for a ``T``-token chunk of ``group`` rows per token
    against an ``n_tokens``-wide table: at most ``q_blk`` (and ``T`` rounded
    up to 8), halved until the score scratch fits ``SCORE_VMEM_BYTES``.
    Raises when even an 8-token block does not fit: the widest table a
    kernel compiles for is ``SCORE_VMEM_BYTES / (4 * 128)`` = 32,768 tokens
    for ``8 * group <= 128``."""
    blk = min(q_blk, -(-T // 8) * 8)
    while blk > 8 and score_bytes(n_tokens, blk * group) > SCORE_VMEM_BYTES:
        blk = max(8, (blk // 2) // 8 * 8)
    if score_bytes(n_tokens, blk * group) > SCORE_VMEM_BYTES:
        raise ValueError(
            f"prefill table of {n_tokens} tokens needs "
            f"{score_bytes(n_tokens, blk * group)} B of score scratch, over "
            f"the {SCORE_VMEM_BYTES} B budget")
    return blk


def _scores_t(q, k, scale, softcap=0.0):
    """Key-major fp32 scores [ps, rows] = k @ q.T, scaled after the dot and
    soft-capped after the scale (the reference order)."""
    s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    return s


def _store_scores(s_scr, seg, s, valid):
    s_scr[pl.ds(pl.multiple_of(seg, s.shape[0]), s.shape[0]), :] = jnp.where(
        valid, s, NEG_INF)


def _mask_page(s_scr, seg, page_size):
    """A page wholly past a block's last query: all-causal-masked, so its
    scores are the NEG_INF fill the reference mask produces (no dot)."""
    s_scr[pl.ds(pl.multiple_of(seg, page_size), page_size), :] = jnp.full(
        (page_size, s_scr.shape[1]), NEG_INF, jnp.float32)


def _softmax_rows(s_scr):
    """One softmax over each query row's full key set, at the true global
    max — the same formulation (and degenerate all-masked behavior) as
    ``jax.nn.softmax`` in the reference chunked path."""
    s_scr[...] = jax.nn.softmax(s_scr[...], axis=0)


def _pv_accumulate(acc_scr, s_scr, seg, v, v_dtype):
    """Fold one page of the PV product: probabilities are rounded to the
    value dtype first (the reference's ``a.astype(v.dtype)``), accumulation
    stays fp32."""
    p = s_scr[pl.ds(pl.multiple_of(seg, v.shape[0]), v.shape[0]), :]
    p = p.astype(v_dtype).astype(jnp.float32)                # [ps, rows]
    acc_scr[...] += jax.lax.dot_general(
        p, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _kv_head(ref, kh, scale_ref=None):
    """KV head ``kh`` (a grid index) of a whole-page block [1, ps, K, D] as
    fp32 [ps, D]: a select over the page's static head slices — Mosaic
    cannot prove a dynamic sublane index aligned to the packed tiling."""
    x = page_head(ref, 0, scale_ref)
    for h in range(1, ref.shape[2]):
        x = jnp.where(kh == h, page_head(ref, h, scale_ref), x)
    return x


def _page_spec(ps, K, D, page_of):
    """Whole-page [1, ps, K, D] block (all KV heads) of page ``page_of``;
    the last two block dims equal the pool's, as Mosaic's tiling requires."""
    return pl.BlockSpec((1, ps, K, D), lambda *a: (page_of(*a), 0, 0, 0))


def _scale_spec(ps, K, page_of):
    return pl.BlockSpec((1, ps, K), lambda *a: (page_of(*a), 0, 0))


# ------------------------------------------------------------- vanilla GQA

def _ragged_prefill_kernel(tables_ref, start_ref, n_live_ref, q_ref, k_ref,
                           v_ref, *rest, page_size: int, n_pages: int,
                           q_blk: int, G: int, scale: float, softcap: float,
                           v_dtype, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, s_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, s_scr, acc_scr = rest
    b = pl.program_id(0)
    kh = pl.program_id(1)
    qb = pl.program_id(2)
    i = pl.program_id(3)
    start = start_ref[b]
    rows = q_ref.shape[2]                    # q_blk * G (token, group) rows
    j = jnp.where(i < n_pages, i, i - n_pages)
    # absolute query position of each (token, head-group) row
    q_abs = start + qb * q_blk \
        + jax.lax.broadcasted_iota(jnp.int32, (page_size, rows), 1) // G

    @pl.when(i < n_pages)
    def _():
        k_abs = j * page_size \
            + jax.lax.broadcasted_iota(jnp.int32, (page_size, rows), 0)
        # a page wholly past this block's last query is all-causal-masked;
        # skip the dot, the NEG_INF fill is what the reference mask produces
        live_page = j * page_size <= start + qb * q_blk + q_blk - 1

        @pl.when(live_page)
        def _():
            s = _scores_t(q_ref[0, 0].astype(jnp.float32),      # [rows, D]
                          _kv_head(k_ref, kh, ks_ref), scale, softcap)
            _store_scores(s_scr, j * page_size, s, k_abs <= q_abs)

        @pl.when(jnp.logical_not(live_page))
        def _():
            _mask_page(s_scr, j * page_size, page_size)

    @pl.when(i == n_pages - 1)
    def _():
        _softmax_rows(s_scr)

    @pl.when(i == n_pages)
    def _():
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(i >= n_pages)
    def _():
        _pv_accumulate(acc_scr, s_scr, j * page_size,
                       _kv_head(v_ref, kh, vs_ref), v_dtype)

    @pl.when(i == 2 * n_pages - 1)
    def _():
        o_ref[0, 0] = acc_scr[...].astype(o_ref.dtype)


def ragged_prefill_fwd(q, k_pages, v_pages, tables, start, n_live, *,
                       scale: float, softcap: float = 0.0, q_blk: int = 128,
                       k_scale=None, v_scale=None, interpret: bool = False):
    """q: [B, K, T, G, D] roped chunk queries (T padded to a q_blk multiple);
    k_pages/v_pages: [P, ps, K, D] *post-write* pool; tables: [B, n_pages]
    int32; start/n_live: [B] int32.  Returns [B, K, T, G, D].
    ``k_scale``/``v_scale``: [P, ps, K] bf16 absmax scales when the pool is
    int8 (the fresh chunk was quantized on write, so every page — prefix and
    chunk alike — dequantizes through the same scale pool)."""
    B, K, T, G, D = q.shape
    ps = k_pages.shape[1]
    n_pages = tables.shape[1]
    n_qb = T // q_blk
    quantized = k_scale is not None
    # probabilities round to the value dtype before PV (the reference's
    # ``a.astype(v.dtype)``); the dequantized values are fp32, so quantized
    # runs keep fp32 probabilities exactly like the reference dequant path
    kernel = functools.partial(
        _ragged_prefill_kernel, page_size=ps, n_pages=n_pages, q_blk=q_blk,
        G=G, scale=scale, softcap=softcap,
        v_dtype=jnp.float32 if quantized else v_pages.dtype,
        quantized=quantized)

    def page_of(b, kh, qb, i, tr, st, nl):
        return tr[b, jnp.where(i < n_pages, i, i - n_pages)]

    # (token, group) rows flatten outside the kernel: an in-kernel
    # [T, G, D] -> [T*G, D] reshape is a shape cast Mosaic refuses
    q_spec = pl.BlockSpec((1, 1, q_blk * G, D),
                          lambda b, kh, qb, i, tr, st, nl: (b, kh, qb, 0))
    in_specs = [q_spec, _page_spec(ps, K, D, page_of),
                _page_spec(ps, K, D, page_of)]
    operands = [tables, start, n_live, q.reshape(B, K, T * G, D), k_pages,
                v_pages]
    if quantized:
        in_specs += [_scale_spec(ps, K, page_of), _scale_spec(ps, K, page_of)]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, K, n_qb, 2 * n_pages),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((n_pages * ps, q_blk * G), jnp.float32),
            pltpu.VMEM((q_blk * G, D), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, T * G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands)
    return o.reshape(B, K, T, G, D)


# ------------------------------------------------------ sliding-window ring

def _windowed_ragged_prefill_kernel(tables_ref, start_ref, n_live_ref, q_ref,
                                    kn_ref, vn_ref, k_ref, v_ref, *rest,
                                    page_size: int, n_ring: int, n_fresh: int,
                                    q_blk: int, G: int, window: int,
                                    scale: float, softcap: float, v_dtype,
                                    quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, s_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, s_scr, acc_scr = rest
    b = pl.program_id(0)
    kh = pl.program_id(1)
    qb = pl.program_id(2)
    i = pl.program_id(3)
    start = start_ref[b]
    n_live = n_live_ref[b]
    rows = q_ref.shape[2]
    n_kv = n_ring + n_fresh
    j = jnp.where(i < n_kv, i, i - n_kv)
    ring_n = n_ring * page_size
    q_abs = start + qb * q_blk \
        + jax.lax.broadcasted_iota(jnp.int32, (page_size, rows), 1) // G
    col = jax.lax.broadcasted_iota(jnp.int32, (page_size, rows), 0)

    @pl.when(i < n_kv)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)                      # [rows, D]

        @pl.when(j < n_ring)
        def _():
            # pre-write ring: slot positions recovered relative to start - 1
            # (the last position written before this chunk); start == 0
            # leaves every slot negative, i.e. fully masked
            idx = j * page_size + col
            last = start - 1
            k_abs = last - ((last % ring_n - idx) % ring_n)
            valid = (k_abs >= 0) & (k_abs > q_abs - window)
            # only the resident ring pages are int8; the fresh chunk's K/V
            # below ride in at model dtype
            s = _scores_t(q, _kv_head(k_ref, kh, ks_ref), scale, softcap)
            _store_scores(s_scr, j * page_size, s, valid)

        @pl.when(j >= n_ring)
        def _():
            jf = j - n_ring
            k_abs = start + jf * page_size + col
            valid = (k_abs <= q_abs) & (k_abs > q_abs - window) \
                & (jf * page_size + col < n_live)
            s = _scores_t(q, _kv_head(kn_ref, kh), scale, softcap)
            _store_scores(s_scr, j * page_size, s, valid)

    @pl.when(i == n_kv - 1)
    def _():
        _softmax_rows(s_scr)

    @pl.when(i == n_kv)
    def _():
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(i >= n_kv)
    def _():
        vsel = jnp.where(j < n_ring, _kv_head(v_ref, kh, vs_ref),
                         _kv_head(vn_ref, kh))
        _pv_accumulate(acc_scr, s_scr, j * page_size, vsel, v_dtype)

    @pl.when(i == 2 * n_kv - 1)
    def _():
        o_ref[0, 0] = acc_scr[...].astype(o_ref.dtype)


def windowed_ragged_prefill_fwd(q, k_new, v_new, k_pages, v_pages, tables,
                                start, n_live, *, window: int, scale: float,
                                softcap: float = 0.0, q_blk: int = 128,
                                k_scale=None, v_scale=None,
                                interpret: bool = False):
    """q: [B, K, T, G, D]; k_new/v_new: [B, T, K, D] fresh roped chunk K/V
    (T a multiple of the page size); k_pages/v_pages: [P, ps, K, D]
    *pre-write* pool; tables: [B, n_ring] ring tables.  Returns
    [B, K, T, G, D].  ``k_scale``/``v_scale``: [P, ps, K] bf16 scales for
    the int8 ring pages; the fresh chunk stays at model dtype (it is
    quantized only when written back after the attend)."""
    B, K, T, G, D = q.shape
    ps = k_pages.shape[1]
    Tk = k_new.shape[1]                   # fresh K/V length (un-padded chunk)
    assert Tk % ps == 0, (Tk, ps)
    n_ring = tables.shape[1]
    n_fresh = Tk // ps
    n_kv = n_ring + n_fresh
    n_qb = T // q_blk
    quantized = k_scale is not None
    kernel = functools.partial(
        _windowed_ragged_prefill_kernel, page_size=ps, n_ring=n_ring,
        n_fresh=n_fresh, q_blk=q_blk, G=G, window=window, scale=scale,
        softcap=softcap,
        v_dtype=jnp.float32 if quantized else v_pages.dtype,
        quantized=quantized)

    def ring_page(b, kh, qb, i, tr, st, nl):
        j = jnp.where(i < n_kv, i, i - n_kv)
        return tr[b, jnp.minimum(j, n_ring - 1)]

    def fresh_map(b, kh, qb, i, tr, st, nl):
        j = jnp.where(i < n_kv, i, i - n_kv)
        return (b, jnp.clip(j - n_ring, 0, n_fresh - 1), 0, 0)

    q_spec = pl.BlockSpec((1, 1, q_blk * G, D),
                          lambda b, kh, qb, i, tr, st, nl: (b, kh, qb, 0))
    in_specs = [
        q_spec,
        pl.BlockSpec((1, ps, K, D), fresh_map),
        pl.BlockSpec((1, ps, K, D), fresh_map),
        _page_spec(ps, K, D, ring_page),
        _page_spec(ps, K, D, ring_page),
    ]
    operands = [tables, start, n_live, q.reshape(B, K, T * G, D), k_new,
                v_new, k_pages, v_pages]
    if quantized:
        in_specs += [_scale_spec(ps, K, ring_page),
                     _scale_spec(ps, K, ring_page)]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, K, n_qb, 2 * n_kv),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((n_kv * ps, q_blk * G), jnp.float32),
            pltpu.VMEM((q_blk * G, D), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, T * G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands)
    return o.reshape(B, K, T, G, D)


# ------------------------------------------------------ MLA materialized-K

def _mla_prefill_walk_kernel(sched_ref, pages_ref, base_ref, q_ref, wuk_ref,
                             wuv_ref, *refs, page_size: int, n_pages: int,
                             ppb: int, scale: float, kv_dtype,
                             quantized: bool):
    """One step of the MLA prefill walk, for the grid step's group of
    heads: one block of ``ppb`` latent pages against one query block
    (queries at ``base + j``), in one of its two passes.  Each head's K
    (``ckv @ w_uk`` ++ ``krope``) is materialized from the block, rounded
    to the cache dtype where the reference einsum rounds.  The first pass
    folds the masked scores into each query row's running max and
    normalizer; the second recomputes them, turns them into probabilities
    at the row's true max, rounds those to the value dtype (the reference's
    ``softmax(s).astype(v.dtype)``) and accumulates them against the
    materialized V (``ckv @ w_uv``) in fp32."""
    del pages_ref          # read by the index maps only
    n_ops = 4 if quantized else 2
    pages = [refs[a * ppb:(a + 1) * ppb] for a in range(n_ops)]
    o_ref, m_scr, l_scr, acc_scr = refs[n_ops * ppb:]
    ckv_refs, kr_refs = pages[:2]
    cs_refs, rs_refs = pages[2:] if quantized else ([None] * ppb,) * 2
    ps, n_blk = page_size, -(-n_pages // ppb)
    step = pl.program_id(1)
    vrow, blk = sched_ref[step] // n_blk, sched_ref[step] % n_blk
    second = vrow % 2 == 1
    G, T = q_ref.shape[1], q_ref.shape[2]
    base = base_ref[vrow // 2]
    live = live_pages(base + T - 1, ps, n_pages)

    @pl.when((blk == 0) & jnp.logical_not(second))
    def _():
        _init(m_scr, l_scr, acc_scr)

    block = [_mla_page(ckv_refs[j], kr_refs[j], cs_refs[j], rs_refs[j])
             for j in range(ppb)]
    ckv = jnp.concatenate([c for c, _ in block], axis=0).astype(kv_dtype)
    kr = jnp.concatenate([k for _, k in block], axis=0).astype(kv_dtype)
    idx = blk * ppb * ps + jax.lax.broadcasted_iota(
        jnp.int32, (T, ckv.shape[0]), 1)
    # causal, and slots past the extent hold a carried or null page
    valid = (idx <= base + jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)) \
        & (idx < live * ps)

    def scores(h):
        k_nope = jax.lax.dot_general(
            ckv, wuk_ref[h].astype(kv_dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(kv_dtype)
        k = jnp.concatenate([k_nope, kr], axis=-1)                # [Tk, E]
        return jax.lax.dot_general(
            q_ref[0, h].astype(kv_dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(jnp.logical_not(second))
    def _():
        for h in range(G):
            sc = jnp.where(valid, scores(h), NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            l_scr[h] = l_scr[h] * jnp.exp(m_prev - m_new) + jnp.sum(
                jnp.where(valid, jnp.exp(sc - m_new), 0.0), axis=1,
                keepdims=True)
            m_scr[h] = m_new

    @pl.when(second)
    def _():
        for h in range(G):
            p = jnp.where(valid, jnp.exp(scores(h) - m_scr[h]), 0.0) \
                / l_scr[h]
            vh = jax.lax.dot_general(
                ckv, wuv_ref[h].astype(kv_dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(kv_dtype)
            acc_scr[h] += jax.lax.dot_general(
                p.astype(kv_dtype), vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(second & (blk == (live + ppb - 1) // ppb - 1))
    def _():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def mla_head_group(H: int) -> int:
    """Heads per grid step of the MLA prefill walk: at most 8, dividing H
    (the group's ``w_uk`` / ``w_uv`` blocks stay in VMEM for its walk)."""
    g = min(H, 8)
    while H % g:
        g -= 1
    return g


def mla_ragged_prefill_fwd(q, ckv_pages, krope_pages, w_uk, w_uv, tables,
                           start, n_live, *, scale: float, q_blk: int = 512,
                           ckv_scale=None, krope_scale=None,
                           interpret: bool = False):
    """q: [B, H, T, nope+rope] (rope part roped; T a ``q_blk`` multiple);
    ckv_pages: [P, ps, L]; krope_pages: [P, ps, R]; w_uk: [H, L, nope];
    w_uv: [H, L, vd] (head-major, so a head group's block spans the array's
    last two dims); tables: [B, n_pages].  Returns the attended values
    [B, H, T, vd].  ``ckv_scale``/``krope_scale``: [P, ps] bf16 scales when
    the latent pages are int8 — the dequantized latent is fp32, so the
    in-kernel K/V materialization stays fp32 (``kv_dtype``) exactly like
    the reference dequant einsum.

    The decode walk (``paged_attention.kernel.walk_schedule``) over virtual
    rows: each chunk row's ``T / q_blk`` query blocks, block ``i`` at base
    position ``start + i * q_blk``, each walked twice in a row (the
    kernel's two passes) through its last query's page, ``pages_per_block``
    pages a step, with the grid's outer axis over groups of
    ``mla_head_group`` heads.  ``n_live`` does not mask: padding queries
    attend causally like the reference's, and their rows are sliced off by
    the caller."""
    del n_live
    B, H, T, E = q.shape
    L, vd = ckv_pages.shape[2], w_uv.shape[2]
    ps, n_pages = ckv_pages.shape[1], tables.shape[1]
    n_qb = T // q_blk
    quantized = ckv_scale is not None
    G = mla_head_group(H)
    ppb = mla_pages_per_block(ckv_pages, krope_pages, n_pages, quantized)
    n_blk = -(-n_pages // ppb)
    V = B * n_qb
    base = (start[:, None] + q_blk * jnp.arange(n_qb, dtype=jnp.int32)
            ).reshape(V)
    # each query block is walked twice in a row (the two passes)
    sched, pages, n_steps = walk_schedule(
        jnp.repeat(tables, 2 * n_qb, axis=0),
        jnp.repeat(base, 2) + q_blk - 1, ps, ppb)
    qv = q.reshape(B, H, n_qb, q_blk, E).transpose(0, 2, 1, 3, 4).reshape(
        V, H, q_blk, E)
    pools = [ckv_pages, krope_pages]
    if quantized:
        pools += [scale_view(ckv_scale), scale_view(krope_scale)]

    def page_spec(pool, j):
        return pl.BlockSpec((1,) + pool.shape[1:],
                            lambda g, s, sched_ref, pages_ref, _: (
                                pages_ref[sched_ref[s] * ppb + j], 0, 0))

    def row_spec(width):
        return pl.BlockSpec((1, G, q_blk, width), lambda g, s, sched_ref, *_: (
            sched_ref[s] // n_blk // 2, g, 0, 0))

    def head_spec(width):
        return pl.BlockSpec((G, L, width), lambda g, s, *_: (g, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(H // G, n_steps),
        in_specs=[row_spec(E), head_spec(w_uk.shape[2]), head_spec(vd)]
        + [page_spec(p, j) for p in pools for j in range(ppb)],
        out_specs=row_spec(vd),
        scratch_shapes=[pltpu.VMEM((G, q_blk, 1), jnp.float32),
                        pltpu.VMEM((G, q_blk, 1), jnp.float32),
                        pltpu.VMEM((G, q_blk, vd), jnp.float32)],
    )
    o = pl.pallas_call(
        functools.partial(
            _mla_prefill_walk_kernel, page_size=ps, n_pages=n_pages, ppb=ppb,
            scale=scale, quantized=quantized,
            kv_dtype=jnp.float32 if quantized else ckv_pages.dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((V, H, q_blk, vd), q.dtype),
        # a virtual row's blocks run in order, carrying the softmax state
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(sched, pages, base, qv, w_uk, w_uv,
      *[p for p in pools for _ in range(ppb)])
    return o.reshape(B, n_qb, H, q_blk, vd).transpose(0, 2, 1, 3, 4).reshape(
        B, H, T, vd)
