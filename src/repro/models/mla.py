"""Multi-head Latent Attention (DeepSeek-V2).

Train/prefill materialize per-head K/V from the rank-``kv_lora`` joint compression;
decode uses the *absorbed* formulation so the per-token cache is only
``kv_lora + rope_head_dim`` floats (512 + 64 for the 236B config) — this is what makes
the decode_32k cell fit, and is the TPU-native analogue of the paper-era concern of
shipping the full weight matrix to every mapper (here: shipping the full KV to every
chip) being the bottleneck.

Every path — full, paged prefill, decode, verify — scales its scores by
``mla_scale(cfg)``: ``(nope + rope) ** -0.5``, times YaRN's
``mscale(rope_factor, yarn_mscale_all_dim) ** 2`` when the rotary is
yarn-scaled (``layers.softmax_scale``), and the query and latent norms
use the configuration's ``norm_eps``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .attention import (NEG_INF, chunked_attention, dequant_int8,
                        gather_pages, page_width, quantize_int8, write_slots)
from .layers import apply_rope, rmsnorm, softmax_scale
from .params import ParamDef


def mla_defs(cfg: ArchConfig):
    d, h = cfg.d_model, cfg.n_heads
    qk = cfg.nope_head_dim + cfg.rope_head_dim
    defs = {
        "wkv_a": ParamDef((d, cfg.kv_lora_rank + cfg.rope_head_dim), ("embed", "lora")),
        "kv_norm": ParamDef((cfg.kv_lora_rank,), ("lora",), init="ones"),
        "wkv_b": ParamDef((cfg.kv_lora_rank, h, cfg.nope_head_dim + cfg.v_head_dim),
                          ("lora", "heads", "head_dim")),
        "wo": ParamDef((h, cfg.v_head_dim, d), ("heads", "head_dim", "embed")),
    }
    if cfg.q_lora_rank:
        defs["wq_a"] = ParamDef((d, cfg.q_lora_rank), ("embed", "lora"))
        defs["q_norm"] = ParamDef((cfg.q_lora_rank,), ("lora",), init="ones")
        defs["wq_b"] = ParamDef((cfg.q_lora_rank, h, qk), ("lora", "heads", "head_dim"))
    else:
        defs["wq"] = ParamDef((d, h, qk), ("embed", "heads", "head_dim"))
    return defs


def mla_scale(cfg: ArchConfig) -> float:
    """The softmax scale of every MLA path: ``(nope + rope) ** -0.5``, times
    YaRN's ``mscale**2`` when the rotary is yarn-scaled."""
    return softmax_scale(cfg, cfg.nope_head_dim + cfg.rope_head_dim)


def latent_norm(cfg: ArchConfig, p, ckv_full):
    """The normed rank-``kv_lora`` latent of ``x @ wkv_a``."""
    return rmsnorm(ckv_full[..., :cfg.kv_lora_rank], p["kv_norm"],
                   cfg.norm_eps)


def _queries(cfg: ArchConfig, p, x):
    if cfg.q_lora_rank:
        cq = rmsnorm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsl,lhe->bshe", cq, p["wq_b"])
    else:
        q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    return q  # [B, S, H, nope+rope]


def mla_full_block(cfg: ArchConfig, p, x, freqs, *, positions=None, q_block=512, unroll=False):
    """Training / prefill MLA self-attention (materialized K/V)."""
    B, S, _ = x.shape
    nope, rope_d, vd = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q = _queries(cfg, p, x)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, freqs)

    ckv_full = x @ p["wkv_a"]
    ckv = latent_norm(cfg, p, ckv_full)
    k_rope = ckv_full[..., cfg.kv_lora_rank:][:, :, None, :]       # [B,S,1,rope]
    k_rope = apply_rope(k_rope, positions, freqs)

    kv = jnp.einsum("bsl,lhe->bshe", ckv, p["wkv_b"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:-1] + (rope_d,))], -1)
    qq = jnp.concatenate([q_nope, q_rope], -1)
    o = chunked_attention(qq, k, v, causal=True, q_block=q_block,
                          scale=mla_scale(cfg), unroll=unroll)
    return jnp.einsum("bshe,hed->bsd", o, p["wo"])


def mla_cache_defs(cfg: ArchConfig, batch: int, max_len: int):
    return {
        "ckv": ParamDef((batch, max_len, cfg.kv_lora_rank), ("batch", "seq", "lora"), init="zeros"),
        "krope": ParamDef((batch, max_len, cfg.rope_head_dim), ("batch", "seq", None), init="zeros"),
    }


def mla_paged_cache_defs(cfg: ArchConfig, num_pages: int, page_size: int,
                         kv_dtype: str = "bf16"):
    """One layer's share of the paged latent pool: the absorbed cache payload
    per token slot in one lane-dense ``latent`` row — the rank-``kv_lora``
    latent ``ckv`` at lanes [0, kv_lora), the roped rope-head key ``krope``
    after it, zeros up to ``page_width(kv_lora + rope)`` (``latent_slots``).

    ``kv_dtype == "int8"`` quantizes both payloads per token slot (the
    latent has one shared "kv head", so the scale leaves ``ckv_scale`` /
    ``krope_scale`` are [P, page_size] bf16), sharing the page axis exactly
    as the vanilla KV defs do."""
    width = page_width(cfg.kv_lora_rank + cfg.rope_head_dim)
    defs = {"latent": ParamDef(
        (num_pages, page_size, width), (None, "seq", None),
        dtype=jnp.int8 if kv_dtype == "int8" else jnp.bfloat16,
        init="zeros")}
    if kv_dtype == "int8":
        defs["ckv_scale"] = ParamDef((num_pages, page_size), (None, "seq"),
                                     dtype=jnp.bfloat16, init="zeros")
        defs["krope_scale"] = ParamDef((num_pages, page_size), (None, "seq"),
                                       dtype=jnp.bfloat16, init="zeros")
    return defs


def latent_slots(cache, ckv, krope):
    """The new token slots of latent ``ckv`` [..., L] and roped ``krope``
    [..., R]: one lane-dense row each, quantized with their scale leaves
    when the pool is int8."""
    scales = {}
    if "ckv_scale" in cache:
        ckv, cs = quantize_int8(ckv)
        krope, rs = quantize_int8(krope)
        scales = {"ckv_scale": cs, "krope_scale": rs}
    dt = cache["latent"].dtype
    row = jnp.concatenate([ckv.astype(dt), krope.astype(dt)], -1)
    pad = cache["latent"].shape[-1] - row.shape[-1]
    row = jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, pad)])
    return {"latent": row, **scales}


def latent_view(slots, L: int, R: int):
    """Lane-dense latent slots [..., W] as (ckv [..., L], krope [..., R])."""
    return slots[..., :L], slots[..., L:L + R]


def latent_scales(cache):
    """The scale leaves a latent attend core takes ({} for a bf16 pool)."""
    return ({"ckv_scale": cache["ckv_scale"],
             "krope_scale": cache["krope_scale"]}
            if "ckv_scale" in cache else {})


def mla_paged_prefill_block(cfg: ArchConfig, p, x, cache, layer, meta, freqs,
                            backend, *, q_block=512, unroll=False):
    """Multi-token MLA chunk prefill, straight into layer ``layer`` of the
    latent pages.

    Mirrors ``paged_prefill_attention_block``: the chunk's latent is written
    token-granularly through the page table (``meta`` carries the
    precomputed write targets; padding rows go to the null page), then the
    attend against the *whole* logical sequence — cached/earlier-chunk
    prefix pages plus the fresh chunk — is delegated to
    ``backend.mla_prefill_attend``, whose contract is the materialized-K
    formulation of ``mla_full_block`` (per-head K/V rebuilt from the
    post-write latent pages with ``wkv_b``)."""
    B, T, _ = x.shape
    nope = cfg.nope_head_dim
    tables, start, n_live = meta["tables"], meta["start"], meta["n_live"]
    positions = start[:, None] + jnp.arange(T)[None, :]              # [B, T]
    q = _queries(cfg, p, x)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, freqs)

    ckv_full = x @ p["wkv_a"]
    ckv = latent_norm(cfg, p, ckv_full)
    krope = apply_rope(ckv_full[..., cfg.kv_lora_rank:][:, :, None, :],
                       positions, freqs)[:, :, 0, :]
    cache = write_slots(cache, layer, meta, latent_slots(cache, ckv, krope))

    qq = jnp.concatenate([q_nope, q_rope], -1)
    o = backend.mla_prefill_attend(qq, cache["latent"], layer, p["wkv_b"],
                                   tables, start, n_live, nope=nope,
                                   scale=mla_scale(cfg), q_block=q_block,
                                   unroll=unroll, **latent_scales(cache))
    return jnp.einsum("bshe,hed->bsd", o, p["wo"]), cache


def gather_latent(pages, layer, tables, L: int, R: int, ckv_scale=None,
                  krope_scale=None):
    """The logical (ckv [B, S, L], krope [B, S, R]) view of one layer of the
    latent pool, dequantized to fp32 through the gathered scale pages when
    the pool is int8."""
    ccg, crg = latent_view(gather_pages(pages, layer, tables), L, R)
    if ckv_scale is not None:
        ccg = dequant_int8(ccg, gather_pages(ckv_scale, layer, tables))
        crg = dequant_int8(crg, gather_pages(krope_scale, layer, tables))
    return ccg, crg


def mla_materialized_prefill_attend(q, pages, layer, wkv_b, tables, start,
                                    n_live, *, nope: int,
                                    scale: float = None, q_block: int = 512,
                                    unroll: bool = False, ckv_scale=None,
                                    krope_scale=None):
    """The reference MLA prefill attend: gather layer ``layer`` of the
    (post-write) latent pages, materialize per-head K/V from them with
    ``wkv_b`` exactly as ``mla_full_block`` does — so a cached prefix or an
    earlier chunk is read as if this call had prefilled it itself — and run
    the chunked XLA attend.  q: [B, T, H, nope+rope] (rope part already
    roped).  int8 pages arrive with their per-token-slot scale pools
    (``ckv_scale`` / ``krope_scale``) and are dequantized to fp32 after the
    gather.  Returns the attended values [B, T, H, v_head_dim]."""
    rope_d = q.shape[-1] - nope
    ccg, crg = gather_latent(pages, layer, tables, wkv_b.shape[0], rope_d,
                             ckv_scale, krope_scale)
    kv = jnp.einsum("bsl,lhe->bshe", ccg, wkv_b)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(crg[:, :, None, :],
                                  k_nope.shape[:-1] + (rope_d,))], -1)
    return chunked_attention(q, k, v, causal=True, q_block=q_block,
                             q_offset=start, scale=scale, unroll=unroll)


def mla_paged_decode_block(cfg: ArchConfig, p, x, cache, layer, meta, freqs,
                           backend):
    """Absorbed one-token decode against layer ``layer`` of the latent pages
    (the paged twin of ``mla_decode_block``).  ``meta`` is the flat per-step
    metadata from ``attn_backend.decode_meta``; the latent-space attend
    (scores against the ckv/krope lanes, context in rank-``kv_lora`` space)
    is delegated to ``backend.mla_decode_attend``."""
    nope = cfg.nope_head_dim
    pos = meta["pos"]

    q = _queries(cfg, p, x[:, None, :])[:, 0]                      # [B,H,·]
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope[:, None], pos[:, None], freqs)[:, 0]

    ckv_full = x @ p["wkv_a"]
    ckv_new = latent_norm(cfg, p, ckv_full)
    kr_new = apply_rope(ckv_full[..., None, cfg.kv_lora_rank:][:, None],
                        pos[:, None], freqs)[:, 0, 0]
    cache = write_slots(cache, layer, meta,
                        latent_slots(cache, ckv_new, kr_new))

    w_uk = p["wkv_b"][..., :nope]                                  # [L,H,nope]
    q_eff = jnp.einsum("bhn,lhn->bhl", q_nope, w_uk)
    ctx = backend.mla_decode_attend(q_eff, q_rope, cache["latent"], layer,
                                    meta["tables"], pos, scale=mla_scale(cfg),
                                    **latent_scales(cache))
    w_uv = p["wkv_b"][..., nope:]                                  # [L, H, v]
    o = jnp.einsum("bhl,lhv->bhv", ctx, w_uv)
    return jnp.einsum("bhv,hvd->bd", o, p["wo"]), cache


def mla_latent_attend(q_eff, q_rope, cc, cr, valid, *, scale: float):
    """The absorbed-latent attend every reference MLA decode path shares.

    q_eff: [B, H, L] (``w_uk``-absorbed); q_rope: [B, H, R]; cc: [B, S, L];
    cr: [B, S, R] (contiguous logical views); valid: [B, S] bool.  fp32
    scores and fp32 probability-weighted context, rounded to cache dtype
    only at the output — the same rounding point as the fused kernel.
    Returns the latent context [B, H, L]."""
    s = jnp.einsum("bhl,bsl->bhs", q_eff, cc,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bhr,bsr->bhs", q_rope, cr,
                       preferred_element_type=jnp.float32)
    s = s * scale
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhs,bsl->bhl", a, cc,
                     preferred_element_type=jnp.float32)
    return ctx.astype(cc.dtype)


def mla_latent_verify_attend(q_eff, q_rope, cc, cr, valid, *, scale: float):
    """``mla_latent_attend`` with a small query axis (speculative verify).

    q_eff: [B, Q, H, L]; q_rope: [B, Q, H, R]; valid: [B, Q, S] per-query
    masks (``attention.verify_valid_mask``).  Per query row the ops are the
    exact per-row ops of the one-token attend, so ``Q == 1`` reproduces it
    bit-for-bit; all-False rows (dead / padded queries) return exact zeros,
    matching the fused verify kernel's zero-init accumulator.  Returns the
    latent context [B, Q, H, L]."""
    s = jnp.einsum("bqhl,bsl->bqhs", q_eff, cc,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bqhr,bsr->bqhs", q_rope, cr,
                       preferred_element_type=jnp.float32)
    s = s * scale
    s = jnp.where(valid[:, :, None, :], s, NEG_INF)
    a = jax.nn.softmax(s, axis=-1)
    any_valid = jnp.any(valid, axis=-1)                            # [B, Q]
    a = jnp.where(any_valid[:, :, None, None], a, 0.0)
    ctx = jnp.einsum("bqhs,bsl->bqhl", a, cc,
                     preferred_element_type=jnp.float32)
    return ctx.astype(cc.dtype)


def mla_paged_verify_block(cfg: ArchConfig, p, x, cache, layer, meta, freqs,
                           backend):
    """Small-q speculative verify against layer ``layer`` of the latent pages
    (the verify twin of ``mla_paged_decode_block``).  x: [B, Q, d] — last
    emitted token plus draft, padded to Q; ``meta`` from
    ``attn_backend.verify_meta``.  Write-all-then-attend: every query's
    latent scatters first (dead rows to the null page), then the absorbed
    attend masks per query — see ``attention.paged_verify_attention_block``
    for the rollback contract.  Returns (out [B, Q, d], new_cache)."""
    Q = x.shape[1]
    nope = cfg.nope_head_dim
    pos = meta["pos"]
    positions = pos[:, None] + jnp.arange(Q)[None, :]              # [B, Q]

    q = _queries(cfg, p, x)                                        # [B,Q,H,·]
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, freqs)

    ckv_full = x @ p["wkv_a"]
    ckv_new = latent_norm(cfg, p, ckv_full)
    kr_new = apply_rope(ckv_full[..., cfg.kv_lora_rank:][:, :, None, :],
                        positions, freqs)[:, :, 0, :]
    cache = write_slots(cache, layer, meta,
                        latent_slots(cache, ckv_new, kr_new))

    w_uk = p["wkv_b"][..., :nope]                                  # [L,H,nope]
    q_eff = jnp.einsum("bqhn,lhn->bqhl", q_nope, w_uk)
    ctx = backend.mla_verify_attend(q_eff, q_rope, cache["latent"], layer,
                                    meta["tables"], pos, meta["n_q"],
                                    scale=mla_scale(cfg),
                                    **latent_scales(cache))
    w_uv = p["wkv_b"][..., nope:]                                  # [L, H, v]
    o = jnp.einsum("bqhl,lhv->bqhv", ctx, w_uv)
    return jnp.einsum("bqhv,hvd->bqd", o, p["wo"]), cache


def mla_decode_block(cfg: ArchConfig, p, x, cache, pos, freqs):
    """Absorbed one-token decode.  x: [B, d]."""
    B = x.shape[0]
    nope = cfg.nope_head_dim
    scale = mla_scale(cfg)

    q = _queries(cfg, p, x[:, None, :])[:, 0]                      # [B,H,nope+rope]
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope[:, None], pos[:, None], freqs)[:, 0]

    ckv_full = x @ p["wkv_a"]
    ckv_new = latent_norm(cfg, p, ckv_full)
    kr_new = apply_rope(ckv_full[..., None, cfg.kv_lora_rank:][:, None], pos[:, None], freqs)[:, 0, 0]

    b = jnp.arange(B)
    cc = cache["ckv"].at[b, pos].set(ckv_new.astype(cache["ckv"].dtype))
    cr = cache["krope"].at[b, pos].set(kr_new.astype(cache["krope"].dtype))

    # absorb W_uk into q:  q_eff[b,h,l] = sum_n q_nope[b,h,n] wkv_b[l,h,n]
    w_uk = p["wkv_b"][..., :nope]                                  # [L, H, nope]
    q_eff = jnp.einsum("bhn,lhn->bhl", q_nope, w_uk)
    valid = jnp.arange(cc.shape[1])[None, :] <= pos[:, None]
    ctx = mla_latent_attend(q_eff, q_rope, cc, cr, valid, scale=scale)
    w_uv = p["wkv_b"][..., nope:]                                  # [L, H, v]
    o = jnp.einsum("bhl,lhv->bhv", ctx, w_uv)
    out = jnp.einsum("bhv,hvd->bd", o, p["wo"])
    return out, {"ckv": cc, "krope": cr}
