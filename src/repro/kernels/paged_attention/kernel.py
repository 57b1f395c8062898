"""Fused paged-attention decode — Pallas TPU kernels.

One decode step reads every live token of a request's KV straight out of the
paged pool: the per-request page table rides in as a *scalar-prefetch*
operand, so the K/V BlockSpec index maps resolve ``tables[b, i]`` before the
body runs and the pipeline DMAs exactly the physical pages the request owns —
the ``pool[tables]`` gather that the XLA reference path materializes in HBM
never exists here.  This is the TPU-native shape of vLLM/SGLang
PagedAttention: walk the page table, attend in place.

Two kernel bodies cover every paged decode family in ``models.cache_spec``:

* ``_paged_decode_kernel`` — vanilla GQA (mask ``idx <= pos``) and
  sliding-window page *rings* (``window > 0``: absolute positions are
  recovered from the ring layout and masked to the window, exactly the
  reference ring rule).  Grid ``(B, n_pages)``; the inner dimension sweeps
  the request's pages with online-softmax state (running max ``m``,
  normalizer ``l``, accumulator ``acc``) in fp32 VMEM scratch, one set per
  KV head.  Each grid step fetches a whole page (every KV head) and each
  head's ``G = H // K`` query group attends its slice — GQA never
  replicates KV, and a page crosses HBM once per step.
* ``_mla_paged_decode_kernel`` — DeepSeek-style absorbed-latent decode.
  Scores are ``q_eff·ckv + q_rope·krope`` against the rank-``L`` latent pages
  (one shared "KV head"); the context accumulator stays in latent space
  (``acc += p·ckv``) so the kernel's output is the ``[H, L]`` context that the
  caller up-projects with ``w_uv`` — per-head K/V are never materialized.

Pages whose first token already lies past ``pos`` are skipped via ``pl.when``
(a null-page read would be masked anyway, but skipping saves the DMA wait);
fully-masked pages are absorbed by the -inf-guarded online-softmax update.

Each decode body has a small-q *verify* twin (``_paged_verify_kernel`` /
``_mla_paged_verify_kernel``) for speculative decoding: the q block carries
``Q = 1 + K`` query tokens per row (last emitted token + draft), a third
scalar-prefetch operand ``n_q`` gives each row's live query count, and the
mask becomes per-query causal — query ``j`` sits at absolute position
``pos + j``, so flattened row ``j*G + g`` runs exactly the decode body's ops
at that position and ``Q == 1`` reproduces the decode kernel bit-for-bit.
Dead rows (``j >= n_q``) stay fully masked and finish as exact zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = float("-inf")


def _online_softmax_update(s, v, m_ref, l_ref, acc_ref):
    """Fold one masked score block ``s`` ([rows, ps]) and its values ``v``
    ([ps, d]) into the running (m, l, acc) scratch state.  ``m``/``l`` are
    [rows, 1] columns: kept 2-D so no lane-to-sublane shape cast is needed
    to broadcast them against ``s`` and ``acc``."""
    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # guard fully-masked rows (m_new == -inf)
    finite = jnp.isfinite(m_new)
    safe_m = jnp.where(finite, m_new, 0.0)
    p = jnp.where(finite, jnp.exp(s - safe_m), 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _normalized(l_ref, acc_ref):
    return acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)


def _init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)


def _page_mask(s, page_idx, pos, *, page_size, window, ring):
    """Validity of the ``page_size`` token slots of page ``page_idx`` against
    absolute position ``pos`` — the decode masking contract (see
    kernels/README.md): causal ``idx <= pos`` when ``window == 0``, else the
    ring rule recovering each slot's absolute position from the ring layout."""
    idx = page_idx * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if window == 0:
        return idx <= pos
    slot = pos % ring
    k_abs = pos - ((slot - idx) % ring)
    return (k_abs >= 0) & (k_abs <= pos) & (k_abs > pos - window)


def page_head(ref, kh, scale_ref=None):
    """KV head ``kh`` (static) of a whole-page block ``ref`` [1, ps, K, D] as
    fp32 [ps, D], dequantized in-register when ``scale_ref`` ([1, ps, K]
    per-token-per-head scales) is given: f32(q8) * f32(bf16 scale) — the
    page DMA moved int8, half the bf16 bytes.

    The block spans every KV head of the page because the (8, 128) tiling
    rule forbids a one-head block of the [P, ps, K, D] pool; a static head
    index is a strided sublane load Mosaic accepts for bf16 and int8 alike."""
    x = ref[0, :, kh, :].astype(jnp.float32)
    if scale_ref is not None:
        x = x * scale_ref[0].astype(jnp.float32)[:, kh:kh + 1]
    return x


def _fold_page(s, v, i, pos, m_ref, l_ref, acc_ref, *, page_size,
               window=0, ring=0, row_ok=None):
    """Mask page ``i``'s scores ``s`` [rows, ps] at per-row positions
    ``pos`` (and live rows ``row_ok``) and fold them with values ``v`` into
    the online-softmax state."""
    valid = _page_mask(s, i, pos, page_size=page_size, window=window,
                       ring=ring)
    if row_ok is not None:
        valid = valid & row_ok
    _online_softmax_update(jnp.where(valid, s, NEG_INF), v, m_ref, l_ref,
                           acc_ref)


def _attend_page(q, k, v, i, pos, m_ref, l_ref, acc_ref, *, scale, softcap,
                 **mask):
    """Scores of query rows ``q`` [rows, D] against one page's keys ``k``,
    folded into the online-softmax state (``mask``: ``_fold_page``'s)."""
    # scale after the dot, the reference ordering, so the two backends'
    # fp32 scores round identically
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    _fold_page(s, v, i, pos, m_ref, l_ref, acc_ref, **mask)


def _paged_decode_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                         page_size: int, scale: float, softcap: float,
                         window: int, ring: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        _init(m_scr, l_scr, acc_scr)

    pos = pos_ref[b]
    # vanilla: pages strictly past pos hold no valid token yet; ring: every
    # resident page can hold in-window tokens, sweep them all
    live = (i * page_size <= pos) if window == 0 else (i * page_size < ring)

    @pl.when(live)
    def _():
        for kh in range(q_ref.shape[1]):       # each KV head of the page
            _attend_page(q_ref[0, kh].astype(jnp.float32),     # [G, D]
                         page_head(k_ref, kh, ks_ref),
                         page_head(v_ref, kh, vs_ref), i, pos,
                         m_scr.at[kh], l_scr.at[kh], acc_scr.at[kh],
                         page_size=page_size, scale=scale, softcap=softcap,
                         window=window, ring=ring)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        for kh in range(q_ref.shape[1]):
            o_ref[0, kh] = _normalized(l_scr.at[kh], acc_scr.at[kh]).astype(
                o_ref.dtype)


def _kv_specs(ps, K, D, index_map, quantized):
    """Whole-page K/V blocks [1, ps, K, D] (plus [1, ps, K] scale blocks when
    int8): the last two block dims equal the pool's, as Mosaic requires."""
    page = pl.BlockSpec((1, ps, K, D), lambda *a: index_map(*a) + (0, 0, 0))
    specs = [page, page]
    if quantized:
        sc = pl.BlockSpec((1, ps, K), lambda *a: index_map(*a) + (0, 0))
        specs += [sc, sc]
    return specs


def _online_scratch(rows, D):
    """fp32 (m, l, acc) online-softmax state for query rows ``rows`` (a
    shape tuple, e.g. (K, G) or (H,)) of width ``D``."""
    return [pltpu.VMEM(rows + (1,), jnp.float32),
            pltpu.VMEM(rows + (1,), jnp.float32),
            pltpu.VMEM(rows + (D,), jnp.float32)]


def paged_decode_fwd(q, k_pages, v_pages, tables, pos, *, scale: float,
                     softcap: float = 0.0, window: int = 0,
                     k_scale=None, v_scale=None, interpret: bool = False):
    """q: [B, K, G, D]; k_pages/v_pages: [P, ps, K, D]; tables: [B, n_pages]
    int32 physical page ids; pos: [B] int32 absolute positions.  Returns
    [B, K, G, D].  ``window > 0`` treats the table as a page ring of
    ``n_pages * ps`` token slots.  ``k_scale``/``v_scale``: [P, ps, K] bf16
    per-token-per-head absmax scales when the pool is int8-quantized — the
    kernel dequantizes in-register after the page DMA.  Grid ``(B,
    n_pages)``: each page is fetched once and every KV head attends it."""
    B, K, G, D = q.shape
    ps = k_pages.shape[1]
    n_pages = tables.shape[1]
    quantized = k_scale is not None
    kernel = functools.partial(
        _paged_decode_kernel, page_size=ps, scale=scale, softcap=softcap,
        window=window, ring=n_pages * ps, quantized=quantized)
    in_specs = [pl.BlockSpec((1, K, G, D), lambda b, i, tr, pr: (b, 0, 0, 0))]
    in_specs += _kv_specs(ps, K, D, lambda b, i, tr, pr: (tr[b, i],),
                          quantized)
    operands = [tables, pos, q, k_pages, v_pages]
    if quantized:
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, K, G, D),
                               lambda b, i, tr, pr: (b, 0, 0, 0)),
        scratch_shapes=_online_scratch((K, G), D),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


def _paged_verify_kernel(tables_ref, pos_ref, nq_ref, q_ref, k_ref, v_ref,
                         *rest, page_size: int, scale: float, softcap: float,
                         window: int, ring: int, quantized: bool, G: int):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        _init(m_scr, l_scr, acc_scr)

    pos = pos_ref[b]
    n_q = nq_ref[b]
    # vanilla: pages strictly past the last live query's position hold no
    # attendable token; ring: every resident page can hold in-window tokens
    live = (i * page_size <= pos + n_q - 1) if window == 0 \
        else (i * page_size < ring)

    @pl.when(live)
    def _():
        # row j*G + g is query j of head group g, at absolute position
        # pos + j — the decode mask evaluated per row
        qi = jax.lax.broadcasted_iota(jnp.int32, (q_ref.shape[2], 1), 0) // G
        for kh in range(q_ref.shape[1]):
            _attend_page(q_ref[0, kh].astype(jnp.float32),    # [Q*G, D]
                         page_head(k_ref, kh, ks_ref),
                         page_head(v_ref, kh, vs_ref), i, pos + qi,
                         m_scr.at[kh], l_scr.at[kh], acc_scr.at[kh],
                         page_size=page_size, scale=scale, softcap=softcap,
                         window=window, ring=ring, row_ok=qi < n_q)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        for kh in range(q_ref.shape[1]):
            o_ref[0, kh] = _normalized(l_scr.at[kh], acc_scr.at[kh]).astype(
                o_ref.dtype)


def paged_verify_fwd(q, k_pages, v_pages, tables, pos, n_q, *, scale: float,
                     softcap: float = 0.0, window: int = 0,
                     k_scale=None, v_scale=None, interpret: bool = False):
    """Small-q speculative verify: q [B, K, Q, G, D] — per row the last
    emitted token plus its draft, padded to Q; pos [B] base positions; n_q
    [B] live query counts (1 + draft length).  Same page-table / ring /
    int8-scale contract as ``paged_decode_fwd``; pages are swept once per
    row with all Q queries' masks evaluated against them.  Returns
    [B, K, Q, G, D]; dead query rows (j >= n_q) are exact zeros."""
    B, K, Q, G, D = q.shape
    ps = k_pages.shape[1]
    n_pages = tables.shape[1]
    quantized = k_scale is not None
    kernel = functools.partial(
        _paged_verify_kernel, page_size=ps, scale=scale, softcap=softcap,
        window=window, ring=n_pages * ps, quantized=quantized, G=G)
    # the (Q, G) query rows flatten outside the kernel: an in-kernel
    # [Q, G, D] -> [Q*G, D] reshape is a shape cast Mosaic refuses for G % 8
    q_spec = pl.BlockSpec((1, K, Q * G, D),
                          lambda b, i, tr, pr, nr: (b, 0, 0, 0))
    in_specs = [q_spec] + _kv_specs(
        ps, K, D, lambda b, i, tr, pr, nr: (tr[b, i],), quantized)
    operands = [tables, pos, n_q, q.reshape(B, K, Q * G, D), k_pages, v_pages]
    if quantized:
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_pages),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=_online_scratch((K, Q * G), D),
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, Q * G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return o.reshape(B, K, Q, G, D)


def _mla_page(ckv_ref, krope_ref, cs_ref, rs_ref):
    """One latent page as fp32 ([ps, L], [ps, R]).  int8 pages carry one
    scale per latent token slot ([1, ps, 1] blocks; the latent vector is the
    quantization granule), so the dequantized ckv that feeds the latent
    accumulator picks up the scales too."""
    ckv = ckv_ref[0].astype(jnp.float32)
    kr = krope_ref[0].astype(jnp.float32)
    if cs_ref is not None:
        ckv = ckv * cs_ref[0].astype(jnp.float32)
        kr = kr * rs_ref[0].astype(jnp.float32)
    return ckv, kr


def _mla_attend_page(qe, qr, ckv, kr, i, pos, m_scr, l_scr, acc_scr, *,
                     scale, **mask):
    s = jax.lax.dot_general(qe, ckv, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    # context accumulates in latent space: acc += p @ ckv  -> [rows, L]
    _fold_page(s * scale, ckv, i, pos, m_scr, l_scr, acc_scr, **mask)


def _mla_paged_decode_kernel(tables_ref, pos_ref, q_eff_ref, q_rope_ref,
                             ckv_ref, krope_ref, *rest, page_size: int,
                             scale: float, quantized: bool):
    if quantized:
        cs_ref, rs_ref, ctx_ref, m_scr, l_scr, acc_scr = rest
    else:
        cs_ref = rs_ref = None
        ctx_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        _init(m_scr, l_scr, acc_scr)

    pos = pos_ref[b]

    @pl.when(i * page_size <= pos)
    def _():
        ckv, kr = _mla_page(ckv_ref, krope_ref, cs_ref, rs_ref)
        _mla_attend_page(q_eff_ref[0].astype(jnp.float32),      # [H, L]
                         q_rope_ref[0].astype(jnp.float32),     # [H, R]
                         ckv, kr, i, pos, m_scr, l_scr, acc_scr,
                         page_size=page_size, scale=scale)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        ctx_ref[0] = _normalized(l_scr, acc_scr).astype(ctx_ref.dtype)


def _mla_specs(H, L, R, ps, index_map, quantized):
    """q blocks [1, rows, L] / [1, rows, R], latent page blocks, and — when
    int8 — [1, ps, 1] scale blocks of the [P, ps, 1] scale view."""
    specs = [
        pl.BlockSpec((1, H, L), lambda b, i, *_: (b, 0, 0)),
        pl.BlockSpec((1, H, R), lambda b, i, *_: (b, 0, 0)),
        pl.BlockSpec((1, ps, L), lambda *a: index_map(*a) + (0, 0)),
        pl.BlockSpec((1, ps, R), lambda *a: index_map(*a) + (0, 0)),
    ]
    if quantized:
        sc = pl.BlockSpec((1, ps, 1), lambda *a: index_map(*a) + (0, 0))
        specs += [sc, sc]
    return specs


def scale_view(s):
    """[P, ps] latent scales as [P, ps, 1]: a page's block then equals the
    array's last two dims, which the (8, 128) tiling rule requires."""
    return s.reshape(s.shape[:2] + (1,))


def mla_paged_decode_fwd(q_eff, q_rope, ckv_pages, krope_pages, tables, pos,
                         *, scale: float, ckv_scale=None, krope_scale=None,
                         interpret: bool = False):
    """q_eff: [B, H, L] (w_uk-absorbed queries); q_rope: [B, H, R];
    ckv_pages: [P, ps, L]; krope_pages: [P, ps, R]; tables: [B, n_pages];
    pos: [B].  Returns the latent context [B, H, L].  ``ckv_scale``/
    ``krope_scale``: [P, ps] bf16 per-token absmax scales when the latent
    pages are int8-quantized."""
    B, H, L = q_eff.shape
    R = q_rope.shape[-1]
    ps = ckv_pages.shape[1]
    n_pages = tables.shape[1]
    quantized = ckv_scale is not None
    kernel = functools.partial(_mla_paged_decode_kernel, page_size=ps,
                               scale=scale, quantized=quantized)
    in_specs = _mla_specs(H, L, R, ps, lambda b, i, tr, pr: (tr[b, i],),
                          quantized)
    operands = [tables, pos, q_eff, q_rope, ckv_pages, krope_pages]
    if quantized:
        operands += [scale_view(ckv_scale), scale_view(krope_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, L), lambda b, i, tr, pr: (b, 0, 0)),
        scratch_shapes=_online_scratch((H,), L),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, L), q_eff.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


def _mla_paged_verify_kernel(tables_ref, pos_ref, nq_ref, q_eff_ref,
                             q_rope_ref, ckv_ref, krope_ref, *rest,
                             page_size: int, scale: float, quantized: bool,
                             H: int):
    if quantized:
        cs_ref, rs_ref, ctx_ref, m_scr, l_scr, acc_scr = rest
    else:
        cs_ref = rs_ref = None
        ctx_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        _init(m_scr, l_scr, acc_scr)

    pos = pos_ref[b]
    n_q = nq_ref[b]

    @pl.when(i * page_size <= pos + n_q - 1)
    def _():
        ckv, kr = _mla_page(ckv_ref, krope_ref, cs_ref, rs_ref)
        # row j*H + h is query j of head h, at absolute position pos + j
        qi = jax.lax.broadcasted_iota(jnp.int32, (q_eff_ref.shape[1], 1),
                                      0) // H
        _mla_attend_page(q_eff_ref[0].astype(jnp.float32),      # [Q*H, L]
                         q_rope_ref[0].astype(jnp.float32),     # [Q*H, R]
                         ckv, kr, i, pos + qi, m_scr, l_scr, acc_scr,
                         page_size=page_size, scale=scale, row_ok=qi < n_q)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        ctx_ref[0] = _normalized(l_scr, acc_scr).astype(ctx_ref.dtype)


def mla_paged_verify_fwd(q_eff, q_rope, ckv_pages, krope_pages, tables, pos,
                         n_q, *, scale: float, ckv_scale=None,
                         krope_scale=None, interpret: bool = False):
    """Small-q absorbed-latent verify: q_eff [B, Q, H, L] / q_rope
    [B, Q, H, R] against the latent pages, with pos/n_q as in
    ``paged_verify_fwd``.  Returns the latent context [B, Q, H, L]; dead
    query rows are exact zeros."""
    B, Q, H, L = q_eff.shape
    R = q_rope.shape[-1]
    ps = ckv_pages.shape[1]
    n_pages = tables.shape[1]
    quantized = ckv_scale is not None
    kernel = functools.partial(_mla_paged_verify_kernel, page_size=ps,
                               scale=scale, quantized=quantized, H=H)
    in_specs = _mla_specs(Q * H, L, R, ps,
                          lambda b, i, tr, pr, nr: (tr[b, i],), quantized)
    # query rows flatten outside the kernel (no in-kernel shape cast)
    operands = [tables, pos, n_q, q_eff.reshape(B, Q * H, L),
                q_rope.reshape(B, Q * H, R), ckv_pages, krope_pages]
    if quantized:
        operands += [scale_view(ckv_scale), scale_view(krope_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Q * H, L),
                               lambda b, i, tr, pr, nr: (b, 0, 0)),
        scratch_shapes=_online_scratch((Q * H,), L),
    )
    ctx = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Q * H, L), q_eff.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return ctx.reshape(B, Q, H, L)
