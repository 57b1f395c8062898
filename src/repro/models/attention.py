"""Attention: GQA / MQA / MHA with RoPE, causal + sliding-window masks, KV cache.

The training/prefill path is a *chunked* (query-blocked) attention: a ``lax.scan``
over query blocks keeps the live score tensor at ``[B, H, q_block, S]`` instead of
``[B, H, S, S]`` — this is what makes the 32k-prefill cells compile with sane
``memory_analysis`` numbers, and it is the XLA analogue of the Pallas flash kernel
(``repro.kernels.flash_attention``) that is the TPU target.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .layers import apply_rope, rope_freqs
from .params import ParamDef

NEG_INF = -1e30


# ------------------------------------------------------------------ param defs

def attn_defs(cfg: ArchConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    hd = cfg.head_dim_
    h, k = cfg.n_heads_padded, cfg.n_kv_heads
    defs = {
        "wq": ParamDef((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamDef((d, k, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((d, k, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((h, hd), ("heads", "head_dim"), init="zeros")
        defs["bk"] = ParamDef((k, hd), ("kv_heads", "head_dim"), init="zeros")
        defs["bv"] = ParamDef((k, hd), ("kv_heads", "head_dim"), init="zeros")
    return defs


def qkv(cfg: ArchConfig, p, x):
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return q, k, v


# --------------------------------------------------------- chunked core attention

def chunked_attention(
    q: jax.Array,            # [B, Sq, H, D]
    k: jax.Array,            # [B, Sk, K, D]
    v: jax.Array,            # [B, Sk, K, D]
    *,
    causal: bool = True,
    window: int = 0,
    q_block: int = 512,
    softcap: float = 0.0,
    q_offset=0,              # absolute position of q[0] relative to k[0];
                             # int (static) or [B] int32 (per-row, traced)
    scale: Optional[float] = None,   # default D ** -0.5
    unroll: bool = False,
) -> jax.Array:
    B, Sq, H, D = q.shape
    K = k.shape[2]
    G = H // K
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    q_block = min(q_block, Sq)
    # pad Sq to a multiple of q_block
    pad = (-Sq) % q_block
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nb = q.shape[1] // q_block
    qb = q.reshape(B, nb, q_block, K, G, D)
    qb = jnp.moveaxis(qb, 1, 0)                      # [nb, B, q_block, K, G, D]
    kpos = jnp.arange(k.shape[1])
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(-1)[:, None]    # [B or 1, 1]

    def block(carry, inp):
        qi, bidx = inp
        qpos = qoff + bidx * q_block + jnp.arange(q_block)[None, :]  # [B or 1, q]
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, k, preferred_element_type=jnp.float32)
        s = s * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        mask = jnp.ones((qoff.shape[0], q_block, k.shape[1]), bool)
        if causal:
            mask &= kpos[None, None, :] <= qpos[:, :, None]
        if window:
            mask &= kpos[None, None, :] > qpos[:, :, None] - window
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        a = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bkgqs,bskd->bqkgd", a, v)
        return carry, o

    # flash-style recompute: without this the q-block scan stacks every block's
    # fp32 softmax residuals for backward ([nb, B, H, q, S] — tens of GB)
    _, out = jax.lax.scan(jax.checkpoint(block), None, (qb, jnp.arange(nb)),
                          unroll=unroll)
    out = jnp.moveaxis(out, 0, 1).reshape(B, nb * q_block, H, v.shape[-1])
    if pad:
        out = out[:, :Sq]
    return out


def ring_chunk_attention(
    q: jax.Array,            # [B, T, H, D] roped chunk queries
    k: jax.Array,            # [B, T, K, D] fresh roped chunk keys
    v: jax.Array,            # [B, T, K, D]
    k_ring: jax.Array,       # [B, n, K, D] gathered page ring BEFORE the
    v_ring: jax.Array,       #   chunk's writes (positions < start)
    start: jax.Array,        # [B] absolute position of q[:, 0]
    n_live: jax.Array,       # [B] real (non-padding) chunk tokens
    *,
    window: int,
    softcap: float = 0.0,
    q_block: int = 512,
    unroll: bool = False,
) -> jax.Array:
    """Sliding-window attend for a *chunk* of prefill at offset ``start``.

    A chunk's queries need keys from earlier chunks, which live only in the
    page ring.  The ring is gathered before this chunk's scatter (writing
    first would recycle slots still holding in-window keys of the earliest
    queries), so ring slot ``s`` holds the latest position ``< start``
    congruent to ``s`` mod the ring length; each slot's absolute position is
    recovered from that layout and masked to the window, and the chunk's own
    keys are attended fresh with the causal+window rule.  At ``start == 0``
    the ring part is fully masked and this reduces (token-exactly — masked
    entries are exact softmax zeros) to the fresh-only attend the unchunked
    windowed prefill always used."""
    B, T, H, D = q.shape
    K = k.shape[2]
    n = k_ring.shape[1]                               # ring length in tokens
    scale = 1.0 / math.sqrt(D)
    q_block = min(q_block, T)
    pad = (-T) % q_block
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nb = q.shape[1] // q_block
    qb = q.reshape(B, nb, q_block, K, H // K, D)
    qb = jnp.moveaxis(qb, 1, 0)                      # [nb, B, q_block, K, G, D]
    kc = jnp.concatenate([k_ring, k], axis=1)        # [B, n + T, K, D]
    vc = jnp.concatenate([v_ring, v], axis=1)
    start = jnp.asarray(start, jnp.int32).reshape(-1)
    # ring-slot absolute positions, recovered relative to the last position
    # written before this chunk (start - 1); start == 0 -> all negative
    last = (start - 1)[:, None]                                   # [B, 1]
    idx = jnp.arange(n)[None, :]
    k_abs = last - ((last % n - idx) % n)                         # [B, n]
    fresh_abs = start[:, None] + jnp.arange(T)[None, :]           # [B, T]
    fresh_live = jnp.arange(T)[None, :] < n_live[:, None]         # [B, T]

    def block(carry, inp):
        qi, bidx = inp
        qpos = start[:, None] + bidx * q_block \
            + jnp.arange(q_block)[None, :]                        # [B, q]
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, kc,
                       preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        vr = (k_abs[:, None, :] >= 0) \
            & (k_abs[:, None, :] > qpos[:, :, None] - window)     # [B, q, n]
        vf = (fresh_abs[:, None, :] <= qpos[:, :, None]) \
            & (fresh_abs[:, None, :] > qpos[:, :, None] - window) \
            & fresh_live[:, None, :]                              # [B, q, T]
        mask = jnp.concatenate([vr, vf], axis=2)                  # [B, q, n+T]
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        a = jax.nn.softmax(s, axis=-1).astype(vc.dtype)
        o = jnp.einsum("bkgqs,bskd->bqkgd", a, vc)
        return carry, o

    _, out = jax.lax.scan(jax.checkpoint(block), None, (qb, jnp.arange(nb)),
                          unroll=unroll)
    out = jnp.moveaxis(out, 0, 1).reshape(B, nb * q_block, H, v.shape[-1])
    if pad:
        out = out[:, :T]
    return out


def full_attention_block(cfg: ArchConfig, p, x, freqs, *, causal=True, window=0,
                         positions=None, q_block=512, unroll=False):
    """Self-attention over a full sequence (train / prefill)."""
    B, S, _ = x.shape
    q, k, v = qkv(cfg, p, x)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if freqs is not None:
        q = apply_rope(q, positions, freqs)
        k = apply_rope(k, positions, freqs)
    o = chunked_attention(q, k, v, causal=causal, window=window, q_block=q_block,
                          softcap=cfg.attn_logit_softcap, unroll=unroll)
    return jnp.einsum("bshe,hed->bsd", o, p["wo"])


def cross_attention_block(cfg: ArchConfig, p, x, enc_out, q_block=512, unroll=False):
    """Decoder cross-attention (no rope, no mask)."""
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    k = jnp.einsum("bsd,dhe->bshe", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", enc_out, p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    o = chunked_attention(q, k, v, causal=False, q_block=q_block, unroll=unroll)
    return jnp.einsum("bshe,hed->bsd", o, p["wo"])


# ------------------------------------------------------------------- KV cache

def cache_defs(cfg: ArchConfig, batch: int, max_len: int, window: int = 0):
    """Abstract defs for one layer's KV cache. Ring buffer when window > 0."""
    hd = cfg.head_dim_
    L = min(window, max_len) if window else max_len
    return {
        "k": ParamDef((batch, L, cfg.n_kv_heads, hd), ("batch", "seq", "kv_heads", "head_dim"), init="zeros"),
        "v": ParamDef((batch, L, cfg.n_kv_heads, hd), ("batch", "seq", "kv_heads", "head_dim"), init="zeros"),
    }


def paged_cache_defs(cfg: ArchConfig, num_pages: int, page_size: int,
                     kv_dtype: str = "bf16"):
    """One layer's share of the paged KV pool: [P, page_size, W] per tensor,
    each token slot one lane-dense row of ``W = page_width(K * D)`` lanes
    (``pack_heads``: KV head h at lanes [h * D, (h + 1) * D)).

    Unlike ``cache_defs`` there is no batch dim — requests own disjoint page
    sets and a per-request page table maps logical pages to physical ones.

    ``kv_dtype == "int8"`` stores absmax-quantized int8 payloads plus
    per-token-slot-per-kv-head bf16 scale leaves (``k_scale``/``v_scale``,
    [P, page_size, K]) that share the payload's page axis — a physical page
    id addresses payload and scales together, so refcounting, radix sharing
    and COW forks need no separate scale accounting."""
    K = cfg.n_kv_heads
    width = page_width(K * cfg.head_dim_)
    payload_dt = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    defs = {
        "k": ParamDef((num_pages, page_size, width), (None, "seq", None),
                      dtype=payload_dt, init="zeros"),
        "v": ParamDef((num_pages, page_size, width), (None, "seq", None),
                      dtype=payload_dt, init="zeros"),
    }
    if kv_dtype == "int8":
        defs["k_scale"] = ParamDef((num_pages, page_size, K),
                                   (None, "seq", "kv_heads"),
                                   dtype=jnp.bfloat16, init="zeros")
        defs["v_scale"] = ParamDef((num_pages, page_size, K),
                                   (None, "seq", "kv_heads"),
                                   dtype=jnp.bfloat16, init="zeros")
    return defs


# ------------------------------------------------------ pool page layout
#
# Every paged pool leaf is one layer-stacked array [L, P, page_size, W] with
# W a multiple of 128 lanes (kernels/README.md, "Pool layout").  XLA keeps
# such an array row-major, the layout the Pallas kernels read, so the layer
# loop carries it and writes each step's token slots in place; the kernels
# take the whole stack and the layer index.

LANES = 128


def page_width(features: int) -> int:
    """Lanes of one token slot: ``features`` rounded up to whole 128-lane
    rows (the padding lanes hold zeros)."""
    return -(-features // LANES) * LANES


def pack_heads(x: jax.Array, width: int) -> jax.Array:
    """Per-head K or V [..., K, D] as lane-dense token slots [..., width]:
    head h at lanes [h * D, (h + 1) * D), zeros past K * D."""
    flat = x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))
    return jnp.pad(flat, [(0, 0)] * (flat.ndim - 1)
                   + [(0, width - flat.shape[-1])])


def head_view(slots: jax.Array, K: int, D: int) -> jax.Array:
    """Lane-dense token slots [..., W] as per-head [..., K, D]."""
    return slots[..., :K * D].reshape(slots.shape[:-1] + (K, D))


def write_slots(cache, layer, meta, new):
    """Scatter a step's new token slots into layer ``layer`` of the pool, in
    place on the carried stack: ``new`` maps leaf names to values shaped
    ``meta["write_page"].shape + slot``; every other leaf passes through."""
    wp, wo = meta["write_page"], meta["write_off"]
    out = dict(cache)
    for name, val in new.items():
        out[name] = cache[name].at[layer, wp, wo].set(
            val.astype(cache[name].dtype))
    return out


def kv_slots(cache, k, v):
    """The new token slots of per-head ``k``/``v`` [..., K, D]: lane-dense
    payloads, absmax-quantized with their scale leaves when the pool is
    int8."""
    W = cache["k"].shape[-1]
    if "k_scale" not in cache:
        return {"k": pack_heads(k, W), "v": pack_heads(v, W)}
    kq, ks = quantize_int8(k)
    vq, vs = quantize_int8(v)
    return {"k": pack_heads(kq, W), "v": pack_heads(vq, W), "k_scale": ks,
            "v_scale": vs}


def kv_scales(cache):
    """The scale leaves an attend core takes ({} for a bf16 pool)."""
    return ({"k_scale": cache["k_scale"], "v_scale": cache["v_scale"]}
            if "k_scale" in cache else {})


# ------------------------------------------------- int8 KV quantization
#
# The one quantize/dequant rounding contract every path shares (see
# kernels/README.md): absmax is taken in fp32 over the feature axis per
# (token slot, kv head); the stored scale is ``bf16(absmax / 127)`` (one
# round-to-nearest-even); the payload quantizes against the *stored* scale —
# ``int8(clip(round(x / f32(s)), -127, 127))`` — so the round-trip error is
# bounded by the stored scale regardless of its precision; a zero-absmax
# slice stores (q=0, s=0).  Dequant is ``f32(q) * f32(s)`` everywhere: the
# XLA reference gather, the Pallas kernel bodies, and the tests.

def quantize_int8(x: jax.Array):
    """Absmax-quantize ``x`` over its last axis.  Returns
    (q int8 [..., D], s bfloat16 [...])."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    s = (amax / 127.0).astype(jnp.bfloat16)
    sf = s.astype(jnp.float32)
    # zero-scale slices (all-zero input, or absmax underflowing bf16) store
    # q = 0; the safe denominator keeps the division finite either way
    safe = jnp.where(sf > 0.0, sf, 1.0)[..., None]
    q = jnp.clip(jnp.round(xf / safe), -127.0, 127.0)
    q = jnp.where(sf[..., None] > 0.0, q, 0.0).astype(jnp.int8)
    return q, s


def dequant_int8(q: jax.Array, s: jax.Array) -> jax.Array:
    """Invert ``quantize_int8``: fp32 payload * fp32 scale, broadcast over
    the feature axis.  q: [..., D] int8; s: [...] bf16.  Returns fp32."""
    return q.astype(jnp.float32) * s.astype(jnp.float32)[..., None]


# --------------------------------------------- shared paged-cache helpers
#
# One copy of the page-gather / write-targeting / masking arithmetic that the
# vanilla, sliding-window, and MLA paged blocks used to hand-roll separately.
# The reference attention backend (models.attn_backend) is built from these.

def gather_pages(pages: jax.Array, layer, tables: jax.Array) -> jax.Array:
    """Materialize the logical per-request view of one layer of a paged
    pool.

    pages: [L, P, ps, ...]; layer: int32 scalar; tables: [B, n] int32
    physical page ids.  Returns [B, n * ps, ...] — request b's pages of
    layer ``layer`` concatenated in table order."""
    B, n = tables.shape
    return pages[layer, tables].reshape((B, n * pages.shape[2])
                                        + pages.shape[3:])


def decode_valid_mask(pos: jax.Array, n: int, *, window: int = 0) -> jax.Array:
    """[B, n] validity of a gathered view at one-token decode.

    ``window == 0``: plain absolute-causal ``idx <= pos``.  ``window > 0``:
    ``n`` is the ring length — each slot's absolute position is recovered
    from the ring layout and masked to the window, the same rule as the
    contiguous ring buffer of ``decode_attention_block``."""
    idx = jnp.arange(n)
    if not window:
        return idx[None, :] <= pos[:, None]
    k_abs = pos[:, None] - (((pos % n)[:, None] - idx[None, :]) % n)
    return (k_abs >= 0) & (k_abs <= pos[:, None]) \
        & (k_abs > pos[:, None] - window)


def verify_valid_mask(pos: jax.Array, n_q: jax.Array, Q: int, n: int, *,
                      window: int = 0) -> jax.Array:
    """[B, Q, n] validity of a gathered view at a small-q verify step.

    Query j of row b sits at absolute position ``pos[b] + j``; its row of the
    mask is ``decode_valid_mask`` evaluated at that position (absolute-causal,
    or ring-recovered for ``window > 0`` with ring length ``n``).  Dead query
    rows (``j >= n_q[b]``) are all-False."""
    qpos = pos[:, None] + jnp.arange(Q)[None, :]                  # [B, Q]
    live = jnp.arange(Q)[None, :] < n_q[:, None]
    idx = jnp.arange(n)
    if not window:
        valid = idx[None, None, :] <= qpos[:, :, None]
    else:
        k_abs = qpos[:, :, None] \
            - (((qpos % n)[:, :, None] - idx[None, None, :]) % n)
        valid = (k_abs >= 0) & (k_abs <= qpos[:, :, None]) \
            & (k_abs > qpos[:, :, None] - window)
    return valid & live[:, :, None]


def decode_qkv(cfg: ArchConfig, p, x, pos, freqs):
    """Project + rope one decode token.  x: [B, d]; pos: [B].  Returns
    (q [B, H, D], k [B, K, D], v [B, K, D])."""
    x1 = x[:, None, :]
    q = jnp.einsum("bsd,dhe->bshe", x1, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x1, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x1, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if freqs is not None:
        q = apply_rope(q, pos[:, None], freqs)
        k = apply_rope(k, pos[:, None], freqs)
    return q[:, 0], k[:, 0], v[:, 0]


def masked_token_attend(q, kg, vg, valid, *, scale: float,
                        softcap: float = 0.0):
    """The one-token GQA attend every reference decode path shares.

    q: [B, H, D]; kg, vg: [B, S, K, D] (contiguous logical view); valid:
    [B, S] bool.  fp32 scores, masked softmax, and an fp32
    probability-weighted sum — the one rounding point is the cast back to
    cache dtype at the block output, which is exactly where the fused Pallas
    decode kernel rounds its fp32 accumulator, so the two backends agree to
    an output ulp.  Returns [B, H, D]."""
    B, H, D = q.shape
    K = kg.shape[2]
    qg = q.reshape(B, K, H // K, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, kg,
                   preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", a, vg,
                   preferred_element_type=jnp.float32)
    return o.astype(vg.dtype).reshape(B, H, D)


def masked_multi_token_attend(q, kg, vg, valid, *, scale: float,
                              softcap: float = 0.0):
    """``masked_token_attend`` with a small query axis (speculative verify).

    q: [B, Q, H, D]; kg, vg: [B, S, K, D]; valid: [B, Q, S] per-query masks.
    Each query row runs the exact per-row ops of the one-token attend (fp32
    scores, masked softmax, fp32 PV sum, single output cast), so ``Q == 1``
    reproduces it bit-for-bit.  Rows whose mask is all-False (dead / padded
    queries) return exact zeros — matching the fused kernel's zero-init
    accumulator — so backends agree on every row, live or dead.  Returns
    [B, Q, H, D]."""
    B, Q, H, D = q.shape
    K = kg.shape[2]
    qg = q.reshape(B, Q, K, H // K, D)
    s = jnp.einsum("bqkgd,bskd->bqkgs", qg, kg,
                   preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    any_valid = jnp.any(valid, axis=-1)                           # [B, Q]
    a = jnp.where(any_valid[:, :, None, None, None], a, 0.0)
    o = jnp.einsum("bqkgs,bskd->bqkgd", a, vg,
                   preferred_element_type=jnp.float32)
    return o.astype(vg.dtype).reshape(B, Q, H, D)


# --------------------------------------------------- paged attention blocks
#
# Family framing shared by every backend: QKV + RoPE, page-table scatter,
# output projection.  The attend itself is delegated to ``backend`` (see
# models.attn_backend) — reference gather+attend or the fused Pallas kernel.

def paged_prefill_attention_block(cfg: ArchConfig, p, x, cache, layer, meta,
                                  freqs, backend, *, q_block=512,
                                  unroll=False):
    """Multi-token (chunk) prefill step against the paged KV pool.

    x: [B, T, d] chunk activations; cache: the layer-stacked pool
    ({"k","v": [L, P, ps, W]}, plus int8 scale leaves), of which this block
    is layer ``layer``; meta: the flat per-step prefill metadata from
    ``attn_backend.prefill_meta`` — page-table rows, per-row chunk offsets
    (``start``: absolute position of x[:, 0]), live counts, and the
    precomputed physical (page, offset) write target of every chunk position
    (padding and ring-aged-out positions routed to the null page), derived
    once by the engine instead of per layer.

    Vanilla layers attend to the gathered (post-write) pages with absolute
    causal masking, so a prefix written by an earlier request (radix cache
    hit) or an earlier chunk of this request is read exactly as if this call
    had prefilled it itself.  Sliding-window layers attend the chunk's fresh
    K/V plus the page *ring* as read before the chunk's scatter
    (``ring_chunk_attention``); the attend core is delegated to ``backend``
    (reference gather+attend or the fused ragged-prefill kernel).  Returns
    (out [B, T, d], new_cache)."""
    B, T, _ = x.shape
    tables, start, n_live = meta["tables"], meta["start"], meta["n_live"]
    q, k, v = qkv(cfg, p, x)
    positions = start[:, None] + jnp.arange(T)[None, :]              # [B, T]
    if freqs is not None:
        q = apply_rope(q, positions, freqs)
        k = apply_rope(k, positions, freqs)
    new = kv_slots(cache, k, v)
    kw = dict(kv_heads=cfg.n_kv_heads, softcap=cfg.attn_logit_softcap,
              q_block=q_block, unroll=unroll)
    window = cfg.sliding_window
    if window:
        # the ring must be read *before* the chunk's writes recycle slots
        # still holding in-window keys of this chunk's earliest queries; its
        # modulus is the full table width the engine passes (>= window_pages;
        # may carry slack pages for speculation).  The fresh chunk K/V ride
        # in unquantized — only resident pages are int8
        o = backend.prefill_attend(
            q, k, v, cache["k"], cache["v"], layer, tables, start, n_live,
            window=window, **kw, **kv_scales(cache))
        cache = write_slots(cache, layer, meta, new)
    else:
        cache = write_slots(cache, layer, meta, new)
        o = backend.prefill_attend(
            q, k, v, cache["k"], cache["v"], layer, tables, start, n_live,
            window=0, **kw, **kv_scales(cache))
    return jnp.einsum("bshe,hed->bsd", o, p["wo"]), cache


def paged_decode_attention_block(cfg: ArchConfig, p, x, cache, layer, meta,
                                 freqs, backend):
    """One-token decode step against the paged KV pool.

    x: [B, d] slot activations; cache: the layer-stacked pool (shared by all
    slots), of which this block is layer ``layer``; meta: the flat per-step
    metadata from ``attn_backend.decode_meta`` — page-table rows, absolute
    positions, and the precomputed physical (page, offset) write target of
    the new token (ring-aware for sliding-window layers, recycling the page
    that just aged out of the window).  The attend reads the pages through
    ``backend`` with positions > pos masked (window layers: masked by
    absolute position recovered from the ring layout), so stale data in
    partially-filled or recycled pages is softmax-zero.  Returns
    (out [B, d], new_cache)."""
    pos = meta["pos"]
    q, k, v = decode_qkv(cfg, p, x, pos, freqs)
    cache = write_slots(cache, layer, meta, kv_slots(cache, k, v))
    o = backend.decode_attend(q, cache["k"], cache["v"], layer,
                              meta["tables"], pos, kv_heads=cfg.n_kv_heads,
                              scale=1.0 / math.sqrt(cfg.head_dim_),
                              softcap=cfg.attn_logit_softcap,
                              window=cfg.sliding_window, **kv_scales(cache))
    return jnp.einsum("bhe,hed->bd", o, p["wo"]), cache


def paged_verify_attention_block(cfg: ArchConfig, p, x, cache, layer, meta,
                                 freqs, backend):
    """Small-q speculative verify step against the paged KV pool.

    x: [B, Q, d] — per slot the last emitted token plus its draft, padded to
    the fixed width Q; meta: the flat metadata from
    ``attn_backend.verify_meta``.  Write-all-then-attend: every query token's
    K/V scatters into its page first (dead rows to the null page), then each
    query attends the post-write pool under the per-query causal mask
    ``token_pos <= pos + j`` (ring rule for windowed families) and
    ``j < n_q`` — so a rejected draft's K/V is invisible to every query that
    survives the accept decision and gets overwritten by the next step's
    writes at the same positions.  Per token the projections, rope, scatter
    and attend are the exact per-row ops of the decode block, which is what
    keeps accepted tokens bit-identical to the non-speculative stream.
    Returns (out [B, Q, d], new_cache)."""
    pos, Q = meta["pos"], x.shape[1]
    q, k, v = qkv(cfg, p, x)
    positions = pos[:, None] + jnp.arange(Q)[None, :]             # [B, Q]
    if freqs is not None:
        q = apply_rope(q, positions, freqs)
        k = apply_rope(k, positions, freqs)
    cache = write_slots(cache, layer, meta, kv_slots(cache, k, v))
    o = backend.verify_attend(q, cache["k"], cache["v"], layer,
                              meta["tables"], pos, meta["n_q"],
                              kv_heads=cfg.n_kv_heads,
                              scale=1.0 / math.sqrt(cfg.head_dim_),
                              softcap=cfg.attn_logit_softcap,
                              window=cfg.sliding_window, **kv_scales(cache))
    return jnp.einsum("bqhe,hed->bqd", o, p["wo"]), cache


def decode_attention_block(cfg: ArchConfig, p, x, cache, pos, freqs, *, window=0):
    """One-token decode step.  x: [B, d]; pos: [B] absolute positions; cache ring-
    buffered when window > 0.  Returns (out [B, d], new_cache)."""
    B = x.shape[0]
    q, k, v = decode_qkv(cfg, p, x, pos, freqs)
    L = cache["k"].shape[1]
    slot = (pos % L) if window else pos
    b = jnp.arange(B)
    ck = cache["k"].at[b, slot].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[b, slot].set(v.astype(cache["v"].dtype))
    # the contiguous ring masks to its own length L (entries older than L are
    # overwritten), matching the paged ring rule with ring == window == L
    valid = decode_valid_mask(pos, L, window=L if window else 0)
    o = masked_token_attend(q, ck, cv, valid,
                            scale=1.0 / math.sqrt(cfg.head_dim_),
                            softcap=cfg.attn_logit_softcap)
    out = jnp.einsum("bhe,hed->bd", o, p["wo"])
    return out, {"k": ck, "v": cv}
