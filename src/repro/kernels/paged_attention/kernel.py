"""Fused paged-attention decode — Pallas TPU kernels.

One decode step reads every live token of a request's KV straight out of the
paged pool: the per-request page table rides in as a *scalar-prefetch*
operand, so the K/V BlockSpec index maps resolve physical page ids before
the body runs and the pipeline DMAs exactly the pages the request owns — the
``pool[tables]`` gather that the XLA reference path materializes in HBM
never exists here.  This is the TPU-native shape of vLLM/SGLang
PagedAttention: walk the page table, attend in place.

Two kernel bodies cover every paged decode family in ``models.cache_spec``:

* ``_walk_kernel`` — vanilla GQA (mask ``idx <= pos``) and sliding-window
  page *rings* (``window > 0``: absolute positions are recovered from the
  ring layout and masked to the window, exactly the reference ring rule).
  It walks each row's live pages only, a block of pages a step (below),
  with online-softmax state (running max ``m``, normalizer ``l``,
  accumulator ``acc``) in fp32 VMEM scratch, one set per KV head.  Each
  page block spans every KV head and each head's ``G = H // K`` query group
  attends its slice — GQA never replicates KV, and a page crosses HBM once
  per call.
* ``_mla_walk_kernel`` — DeepSeek-style absorbed-latent decode on the same
  walk.  Scores are ``q_eff·ckv + q_rope·krope`` against the rank-``L``
  latent pages (one shared "KV head"); the context accumulator stays in
  latent space (``acc += p·ckv``) so the kernel's output is the ``[H, L]``
  context that the caller up-projects with ``w_uv`` — per-head K/V are
  never materialized.

The decode walk (GQA and MLA, decode and verify).  A row's *live extent* is its first
``n_live = min(last // ps + 1, n_pages)`` table columns (``live_pages``),
``last`` being the position of its last query: ``pos`` for decode,
``pos + n_q - 1`` for verify.  The count serves the page ring too: a column
past ``last // ps`` has never been written, and every written one can hold
in-window tokens, so the ring rule masks inside the extent as before.  The
extent splits into blocks of ``ppb`` pages (``pages_per_block``: 128
tokens, fewer for a narrow table or the VMEM budget of the family's page
blocks), and the grid has one
step per live block of every row, rows in order — its length is a dynamic
grid bound that ``walk_schedule`` computes on the device from the
positions, with each step's row and block and each page slot's physical
page.  Each pool (K, V, and the int8 scales) is passed once per page slot
with a whole-page block whose index map reads that page, so the pipeline
fetches the next block's pages while this one computes; a slot past the
row's extent keeps the page it held a step before, and costs no DMA.  Each
KV head's scores are one ``[rows, ppb * ps]`` dot over the block's pages in
table order, folded into the online softmax once per block, slots past the
extent masked ``-inf``.  An idle row (null table, ``pos`` 0) costs one
step, whose one live page is the null page it attends.  Pages are fetched
by BlockSpecs, not by ``make_async_copy`` from an HBM ref: Mosaic pads an
HBM ref's two minor dims to its tiling and refuses a slice of a pool whose
head dim is under 128 lanes (qwen2's 64), or of the ``[P, ps, K]`` int8
scales.

Each decode body has a small-q *verify* twin (``_walk_kernel`` /
``_mla_walk_kernel`` with ``verify``) for speculative decoding: the q
block carries ``Q = 1 + K`` query tokens per row (last emitted token +
draft), a further scalar-prefetch operand ``n_q`` gives each row's live
query count, and the mask becomes per-query causal — query ``j`` sits at
absolute position ``pos + j``, so flattened row ``j*G + g`` runs exactly the
decode body's ops at that position and ``Q == 1`` reproduces the decode
kernel bit-for-bit.  Dead rows (``j >= n_q``) stay fully masked and finish
as exact zeros.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = float("-inf")

# The decode walk's block: about this many tokens per step, with the
# double-buffered K/V (and scale) blocks held to this much VMEM (v5e's
# default scoped limit is 16 MiB; the rest is left to the body's values).
BLOCK_TOKENS = 128
BLOCK_VMEM_BYTES = 8 << 20


def _online_softmax_update(s, v, m_ref, l_ref, acc_ref):
    """Fold one masked score block ``s`` ([rows, n]) and its values ``v``
    ([n, d]) into the running (m, l, acc) scratch state.  ``m``/``l`` are
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


def _slot_mask(s, first, pos, *, window, ring):
    """Validity of the token slots ``first, first + 1, ...`` (the columns of
    ``s``, in table order) against absolute position ``pos`` — the decode
    masking contract (see kernels/README.md): causal ``idx <= pos`` when
    ``window == 0``, else the ring rule recovering each slot's absolute
    position from the ring layout."""
    idx = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
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


def _fold_page(s, v, first, pos, m_ref, l_ref, acc_ref, *, window=0,
               ring=0, row_ok=None):
    """Mask the scores ``s`` [rows, n] of token slots ``first ..`` at
    per-row positions ``pos`` (and live rows ``row_ok``) and fold them with
    values ``v`` into the online-softmax state."""
    valid = _slot_mask(s, first, pos, window=window, ring=ring)
    if row_ok is not None:
        valid = valid & row_ok
    _online_softmax_update(jnp.where(valid, s, NEG_INF), v, m_ref, l_ref,
                           acc_ref)


def live_pages(last, page_size, n_pages):
    """Table columns a row walks: those up to the page of its last query
    position ``last``, at most the table width and at least one (an idle
    row attends its null page)."""
    return jnp.clip(last // page_size + 1, 1, n_pages)


def _vmem_bytes(shape, dtype):
    """VMEM bytes of an array tiled on its last two dims: (8, 128) 32-bit
    tiles, packed to 16 / 32 sublanes for 16 / 8-bit dtypes."""
    size = jnp.dtype(dtype).itemsize
    *lead, rows, cols = shape
    sub = 8 * (4 // size)
    return (math.prod(lead) * -(-rows // sub) * sub * -(-cols // 128) * 128
            * size)


def pages_per_block(page_size, n_pages, page_blocks):
    """Pages per step of a decode walk: ``BLOCK_TOKENS`` worth, at most the
    table width, halved until the double-buffered page blocks fit
    ``BLOCK_VMEM_BYTES``.  ``page_blocks`` lists the (shape, dtype) of one
    page's block of every pool the walk fetches: K and V (and their int8
    scales) for GQA, the latent ``ckv`` and ``krope`` (and their scales)
    for MLA — one rule for every family."""
    per_page = 2 * sum(_vmem_bytes(shape, dt) for shape, dt in page_blocks)

    def fits(ppb):
        return ppb * per_page <= BLOCK_VMEM_BYTES
    ppb = max(1, min(n_pages, BLOCK_TOKENS // page_size))
    while ppb > 1 and not fits(ppb):
        ppb //= 2
    return ppb


def walk_schedule(tables, last, page_size, ppb):
    """The decode walk's steps, from the page table and each row's last
    query position ``last`` [B]: ``(sched, pages, n_steps)``.  Rows come in
    order, each with ``ceil(live_pages / ppb)`` blocks of ``ppb`` pages
    (``n_blk`` blocks span the table); step ``s < n_steps`` attends block
    ``sched[s] % n_blk`` of row ``sched[s] // n_blk``, and its page slot
    ``j`` holds physical page ``pages[sched[s] * ppb + j]``.  A slot past
    the row's extent keeps the page it held one step before — the previous
    block's, so the pipeline fetches nothing for it — or, in a row's first
    block, the null page 0.  Vector ops only (a gather on the TPU goes
    index by index): ``sched`` has room for every block of every row, and
    entries past ``n_steps`` are never read."""
    B, n_pages = tables.shape
    n_blk = -(-n_pages // ppb)
    live = live_pages(last, page_size, n_pages)                       # [B]
    blocks = -(-live // ppb)
    ends = jnp.cumsum(blocks)
    s = jnp.arange(B * n_blk, dtype=jnp.int32)[:, None]
    # step s lies in the first row that ends after it; each row before
    # that one skips its n_blk - blocks dead blocks
    sched = s[:, 0] + jnp.sum(jnp.where(ends <= s, n_blk - blocks, 0),
                              axis=1)
    padded = jnp.pad(tables, ((0, 0), (0, n_blk * ppb - n_pages)))
    prev = jnp.pad(padded[:, :-ppb], ((0, 0), (ppb, 0)))
    cols = jnp.arange(n_blk * ppb, dtype=jnp.int32)
    pages = jnp.where(cols < live[:, None], padded, prev)
    return sched, pages.reshape(-1), ends[-1]


def _walk_kernel(sched_ref, pages_ref, pos_ref, *refs, page_size: int,
                 n_pages: int, ppb: int, scale: float, softcap: float,
                 window: int, quantized: bool, G: int, verify: bool):
    """Body shared by GQA decode and verify (``verify``: a fourth scalar
    operand ``n_q``): one step per live block of ``ppb`` pages (module
    docstring, "The decode walk")."""
    del pages_ref          # read by the index maps only
    nq_ref, refs = (refs[0], refs[1:]) if verify else (None, refs)
    q_ref, refs = refs[0], refs[1:]
    n_ops = 4 if quantized else 2
    pages = [refs[a * ppb:(a + 1) * ppb] for a in range(n_ops)]
    o_ref, m_scr, l_scr, acc_scr = refs[n_ops * ppb:]
    k_refs, v_refs = pages[:2]
    ks_refs, vs_refs = pages[2:] if quantized else ([None] * ppb,) * 2
    ps, n_blk = page_size, -(-n_pages // ppb)
    s = pl.program_id(0)
    r, blk = sched_ref[s] // n_blk, sched_ref[s] % n_blk
    last = pos_ref[r] if nq_ref is None else pos_ref[r] + nq_ref[r] - 1
    live = live_pages(last, ps, n_pages)

    @pl.when(blk == 0)
    def _():
        _init(m_scr, l_scr, acc_scr)

    pos = pos_ref[r]
    if nq_ref is None:
        row_ok = None
    else:
        # row j*G + g is query j of head group g, at absolute position
        # pos + j — the decode mask evaluated per row
        qi = jax.lax.broadcasted_iota(jnp.int32, (q_ref.shape[2], 1), 0) // G
        pos, row_ok = pos + qi, qi < nq_ref[r]
    first = blk * ppb * ps
    for kh in range(q_ref.shape[1]):       # each KV head of the block
        # the block's pages, in table order: slot t is token first + t
        k = jnp.concatenate([page_head(k_refs[j], kh, ks_refs[j])
                             for j in range(ppb)], axis=0)        # [T, D]
        v = jnp.concatenate([page_head(v_refs[j], kh, vs_refs[j])
                             for j in range(ppb)], axis=0)
        # scale after the dot, the reference ordering, so the two backends'
        # fp32 scores round identically
        sc = jax.lax.dot_general(q_ref[0, kh].astype(jnp.float32), k,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        if softcap:
            sc = softcap * jnp.tanh(sc / softcap)
        # slots past the extent hold a carried or null page: masked
        ok = first + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1) \
            < live * ps
        _fold_page(sc, v, first, pos, m_scr.at[kh], l_scr.at[kh],
                   acc_scr.at[kh], window=window, ring=n_pages * ps,
                   row_ok=ok if row_ok is None else ok & row_ok)

    @pl.when(blk == (live + ppb - 1) // ppb - 1)
    def _():
        for kh in range(q_ref.shape[1]):
            o_ref[0, kh] = _normalized(l_scr.at[kh], acc_scr.at[kh]).astype(
                o_ref.dtype)


def _walk_call(q, k_pages, v_pages, tables, pos, n_q, k_scale, v_scale, *,
               G, interpret, **kw):
    """``pallas_call`` of the decode walk: q [B, K, rows, D] (rows = G for
    decode, Q*G for verify with ``n_q``); each pool (and int8 scale) is
    passed once per page slot of a block, its index map reading the slot's
    page from the schedule."""
    B, K, rows, D = q.shape
    ps, n_pages = k_pages.shape[1], tables.shape[1]
    quantized = k_scale is not None
    blocks = [((ps, K, D), k_pages.dtype)] * 2
    if quantized:
        blocks += [((ps, K), k_scale.dtype)] * 2
    ppb = pages_per_block(ps, n_pages, blocks)
    n_blk = -(-n_pages // ppb)
    sched, pages, n_steps = walk_schedule(
        tables, pos if n_q is None else pos + n_q - 1, ps, ppb)
    prefetch = [sched, pages, pos] + ([] if n_q is None else [n_q])
    pools = [k_pages, v_pages] + ([k_scale, v_scale] if quantized else [])

    def page_spec(pool, j):
        block = (1,) + pool.shape[1:]
        return pl.BlockSpec(block, lambda s, sched_ref, pages_ref, *_: (
            pages_ref[sched_ref[s] * ppb + j],) + (0,) * (len(block) - 1))

    q_spec = pl.BlockSpec((1, K, rows, D), lambda s, sched_ref, *_: (
        sched_ref[s] // n_blk, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(n_steps,),
        in_specs=[q_spec] + [page_spec(p, j) for p in pools
                             for j in range(ppb)],
        out_specs=q_spec,
        scratch_shapes=_online_scratch((K, rows), D),
    )
    return pl.pallas_call(
        functools.partial(_walk_kernel, page_size=ps, n_pages=n_pages,
                          ppb=ppb, quantized=quantized, G=G,
                          verify=n_q is not None, **kw),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # a row's blocks run in order, carrying the online-softmax state
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, q, *[p for p in pools for _ in range(ppb)])


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
    kernel dequantizes in-register after the page DMA.  One grid step per
    live block of ``pages_per_block`` pages; each page is fetched once and
    every KV head attends it."""
    return _walk_call(q, k_pages, v_pages, tables, pos, None, k_scale,
                      v_scale, G=q.shape[2], scale=scale, softcap=softcap,
                      window=window, interpret=interpret)


def paged_verify_fwd(q, k_pages, v_pages, tables, pos, n_q, *, scale: float,
                     softcap: float = 0.0, window: int = 0,
                     k_scale=None, v_scale=None, interpret: bool = False):
    """Small-q speculative verify: q [B, K, Q, G, D] — per row the last
    emitted token plus its draft, padded to Q; pos [B] base positions; n_q
    [B] live query counts (1 + draft length).  Same page-table / ring /
    int8-scale contract and the same walk as ``paged_decode_fwd``, the
    extent reaching the last live query's page; each block is fetched once
    per row with all Q queries' masks evaluated against it.  Returns
    [B, K, Q, G, D]; dead query rows (j >= n_q) are exact zeros."""
    B, K, Q, G, D = q.shape
    # the (Q, G) query rows flatten outside the kernel: an in-kernel
    # [Q, G, D] -> [Q*G, D] reshape is a shape cast Mosaic refuses for G % 8
    o = _walk_call(q.reshape(B, K, Q * G, D), k_pages, v_pages, tables, pos,
                   n_q, k_scale, v_scale, G=G, scale=scale, softcap=softcap,
                   window=window, interpret=interpret)
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


def _mla_walk_kernel(sched_ref, pages_ref, pos_ref, *refs, page_size: int,
                     n_pages: int, ppb: int, scale: float, quantized: bool,
                     H: int, verify: bool):
    """Absorbed-latent decode and verify (``verify``: a fourth scalar
    operand ``n_q``) on the decode walk: one step per live block of ``ppb``
    latent pages.  Scores ``q_eff·ckv + q_rope·krope`` of every query row
    against the block's tokens in table order, scaled after the dots, folded
    into the online softmax with the latent ``ckv`` as values."""
    del pages_ref          # read by the index maps only
    nq_ref, refs = (refs[0], refs[1:]) if verify else (None, refs)
    qe_ref, qr_ref, refs = refs[0], refs[1], refs[2:]
    n_ops = 4 if quantized else 2
    pages = [refs[a * ppb:(a + 1) * ppb] for a in range(n_ops)]
    o_ref, m_scr, l_scr, acc_scr = refs[n_ops * ppb:]
    ckv_refs, kr_refs = pages[:2]
    cs_refs, rs_refs = pages[2:] if quantized else ([None] * ppb,) * 2
    ps, n_blk = page_size, -(-n_pages // ppb)
    s = pl.program_id(0)
    r, blk = sched_ref[s] // n_blk, sched_ref[s] % n_blk
    last = pos_ref[r] if nq_ref is None else pos_ref[r] + nq_ref[r] - 1
    live = live_pages(last, ps, n_pages)

    @pl.when(blk == 0)
    def _():
        _init(m_scr, l_scr, acc_scr)

    pos = pos_ref[r]
    row_ok = None
    if nq_ref is not None:
        # row j*H + h is query j of head h, at absolute position pos + j
        qi = jax.lax.broadcasted_iota(jnp.int32, (qe_ref.shape[1], 1), 0) // H
        pos, row_ok = pos + qi, qi < nq_ref[r]
    block = [_mla_page(ckv_refs[j], kr_refs[j], cs_refs[j], rs_refs[j])
             for j in range(ppb)]
    ckv = jnp.concatenate([c for c, _ in block], axis=0)          # [T, L]
    kr = jnp.concatenate([k for _, k in block], axis=0)           # [T, R]
    qe = qe_ref[0].astype(jnp.float32)                            # [rows, L]
    sc = jax.lax.dot_general(qe, ckv, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    sc = sc + jax.lax.dot_general(qr_ref[0].astype(jnp.float32), kr,
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    first = blk * ppb * ps
    # slots past the extent hold a carried or null page: masked
    ok = first + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1) < live * ps
    # context accumulates in latent space: acc += p @ ckv  -> [rows, L]
    _fold_page(sc * scale, ckv, first, pos, m_scr, l_scr, acc_scr,
               row_ok=ok if row_ok is None else ok & row_ok)

    @pl.when(blk == (live + ppb - 1) // ppb - 1)
    def _():
        o_ref[0] = _normalized(l_scr, acc_scr).astype(o_ref.dtype)


def scale_view(s):
    """[P, ps] latent scales as [P, ps, 1]: a page's block then equals the
    array's last two dims, which the (8, 128) tiling rule requires."""
    return s.reshape(s.shape[:2] + (1,))


def mla_pages_per_block(ckv_pages, krope_pages, n_pages, quantized):
    """``pages_per_block`` with the latent page shapes."""
    ps = ckv_pages.shape[1]
    blocks = [((ps, ckv_pages.shape[2]), ckv_pages.dtype),
              ((ps, krope_pages.shape[2]), krope_pages.dtype)]
    if quantized:
        blocks += [((ps, 1), jnp.bfloat16)] * 2
    return pages_per_block(ps, n_pages, blocks)


def _mla_walk_call(q_eff, q_rope, ckv_pages, krope_pages, tables, pos, n_q,
                   ckv_scale, krope_scale, *, H, scale, interpret):
    """``pallas_call`` of the latent walk: q_eff [B, rows, L], q_rope
    [B, rows, R] (rows = H for decode, Q*H for verify with ``n_q``); each
    latent pool (and int8 scale view) is passed once per page slot of a
    block, its index map reading the slot's page from the schedule."""
    B, rows, L = q_eff.shape
    R = q_rope.shape[-1]
    ps, n_pages = ckv_pages.shape[1], tables.shape[1]
    quantized = ckv_scale is not None
    ppb = mla_pages_per_block(ckv_pages, krope_pages, n_pages, quantized)
    n_blk = -(-n_pages // ppb)
    sched, pages, n_steps = walk_schedule(
        tables, pos if n_q is None else pos + n_q - 1, ps, ppb)
    prefetch = [sched, pages, pos] + ([] if n_q is None else [n_q])
    pools = [ckv_pages, krope_pages]
    if quantized:
        pools += [scale_view(ckv_scale), scale_view(krope_scale)]

    def page_spec(pool, j):
        return pl.BlockSpec((1,) + pool.shape[1:],
                            lambda s, sched_ref, pages_ref, *_: (
                                pages_ref[sched_ref[s] * ppb + j], 0, 0))

    def row_spec(width):
        return pl.BlockSpec((1, rows, width), lambda s, sched_ref, *_: (
            sched_ref[s] // n_blk, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(n_steps,),
        in_specs=[row_spec(L), row_spec(R)] + [page_spec(p, j) for p in pools
                                               for j in range(ppb)],
        out_specs=row_spec(L),
        scratch_shapes=_online_scratch((rows,), L),
    )
    return pl.pallas_call(
        functools.partial(_mla_walk_kernel, page_size=ps, n_pages=n_pages,
                          ppb=ppb, scale=scale, quantized=quantized, H=H,
                          verify=n_q is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_eff.shape, q_eff.dtype),
        # a row's blocks run in order, carrying the online-softmax state
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, q_eff, q_rope, *[p for p in pools for _ in range(ppb)])


def mla_paged_decode_fwd(q_eff, q_rope, ckv_pages, krope_pages, tables, pos,
                         *, scale: float, ckv_scale=None, krope_scale=None,
                         interpret: bool = False):
    """q_eff: [B, H, L] (w_uk-absorbed queries); q_rope: [B, H, R];
    ckv_pages: [P, ps, L]; krope_pages: [P, ps, R]; tables: [B, n_pages];
    pos: [B].  Returns the latent context [B, H, L].  ``ckv_scale``/
    ``krope_scale``: [P, ps] bf16 per-token absmax scales when the latent
    pages are int8-quantized.  One grid step per live block of
    ``pages_per_block`` latent pages (the GQA walk's schedule)."""
    return _mla_walk_call(q_eff, q_rope, ckv_pages, krope_pages, tables, pos,
                          None, ckv_scale, krope_scale, H=q_eff.shape[1],
                          scale=scale, interpret=interpret)


def mla_paged_verify_fwd(q_eff, q_rope, ckv_pages, krope_pages, tables, pos,
                         n_q, *, scale: float, ckv_scale=None,
                         krope_scale=None, interpret: bool = False):
    """Small-q absorbed-latent verify: q_eff [B, Q, H, L] / q_rope
    [B, Q, H, R] against the latent pages, with pos/n_q as in
    ``paged_verify_fwd`` and the same walk as ``mla_paged_decode_fwd``, the
    extent reaching the last live query's page.  Returns the latent context
    [B, Q, H, L]; dead query rows are exact zeros."""
    B, Q, H, L = q_eff.shape
    R = q_rope.shape[-1]
    # query rows flatten outside the kernel (no in-kernel shape cast)
    ctx = _mla_walk_call(q_eff.reshape(B, Q * H, L),
                         q_rope.reshape(B, Q * H, R), ckv_pages, krope_pages,
                         tables, pos, n_q, ckv_scale, krope_scale, H=H,
                         scale=scale, interpret=interpret)
    return ctx.reshape(B, Q, H, L)
