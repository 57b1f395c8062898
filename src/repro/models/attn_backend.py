"""Attention-backend registry: one dispatch point for every paged path.

The serving hot loop is paged attention — prefill writes K/V (or MLA latent)
through per-request page tables, decode reads every live token back per step.
How that read happens is a *backend* choice, orthogonal to the cache family:

* ``reference`` — the XLA gather+attend formulation (``pool[tables]``
  materializes the logical view in HBM, then dense masked attention) — the
  parity oracle every other backend is verified against.  Its decode attends
  keep the probability-weighted sum in fp32 and round to cache dtype only at
  the block output, the same single rounding point as the fused kernel's
  fp32 accumulator, so backends agree to an output ulp and greedy decode
  stays token-exact across them.
* ``pallas`` — the fused kernels: ``repro.kernels.paged_attention`` for
  decode and ``repro.kernels.ragged_prefill`` for chunk prefill.  In both,
  the page table rides into the kernel as a scalar-prefetch operand and the
  BlockSpec index maps walk it directly, so the gather never materializes.

A backend implements four *attend cores* — ``decode_attend`` (vanilla GQA +
sliding-window rings), ``mla_decode_attend`` (absorbed-latent),
``prefill_attend`` (ragged multi-token chunks against the paged pool), and
``mla_prefill_attend`` (materialized-K chunks against the latent pages) —
while the family framing (QKV projection, RoPE, page-table scatter, output
projection) is shared code in ``models.attention`` / ``models.mla`` that
every backend reuses.  Model code routes exclusively through
``backend.paged_prefill`` / ``backend.paged_decode``; future backends (GPU,
speculative verify) plug in by registering a class and overriding the cores
they fuse.

Selection is threaded from ``ServeConfig.attn_backend`` (``auto`` |
``reference`` | ``pallas``) through ``launch/serve.py --attn-backend`` and the
engine's jitted-step cache; ``auto`` picks the fused kernel exactly when jax
has a TPU to compile it for.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from . import attention, mla
from ..kernels.paged_attention import (mla_paged_attention_decode,
                                       mla_paged_attention_verify,
                                       paged_attention_decode,
                                       paged_attention_verify)
from ..kernels.ragged_prefill import (mla_ragged_prefill_attend,
                                      ragged_prefill_attend)

# ---------------------------------------------------------------- registry

_REGISTRY: Dict[str, "AttentionBackend"] = {}


def register_backend(cls):
    """Class decorator: instantiate and register under ``cls.name``."""
    _REGISTRY[cls.name] = cls()
    return cls


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve_backend(name: str) -> str:
    """Resolve a ``ServeConfig.attn_backend`` knob to a concrete backend name.

    ``auto`` picks the fused kernel exactly when jax has a TPU to compile it
    for; elsewhere the XLA reference path is faster than an interpreted
    kernel (parity tests opt into interpret-mode pallas explicitly)."""
    if name == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "reference"
    if name not in _REGISTRY:
        raise ValueError(f"unknown attention backend {name!r}; "
                         f"available: {available_backends()}")
    if name == "pallas" and jax.default_backend() not in ("tpu", "cpu"):
        # fail at config time with a clear message instead of deep inside a
        # Mosaic lowering attempt (the kernels are TPU-targeted; CPU runs
        # them in interpret mode, other backends have no lowering)
        raise ValueError(
            "attn_backend='pallas' requires a TPU (compiled) or CPU "
            f"(interpret mode); jax backend is {jax.default_backend()!r}")
    return name


def get_backend(name: str) -> "AttentionBackend":
    return _REGISTRY[resolve_backend(name)]


# ----------------------------------------------------- flat decode metadata

def decode_meta(cfg: ArchConfig, page_size: int, tables, pos):
    """Flat per-step decode metadata, computed once instead of re-derived by
    every layer's block inside the scan: the page-table rows, per-row
    absolute positions, and the physical (page, offset) write target of the
    step's new token — ring-aware for sliding-window families.  Works on
    numpy (engine host path) and jnp (traced) arrays alike; values feed the
    jitted ``decode_paged`` step as one pytree."""
    B = tables.shape[0]
    col = pos // page_size
    if cfg.sliding_window:
        # ring modulus contract: the ring IS the table width the engine
        # passes (>= window_pages; the pool may add slack pages, e.g. for
        # speculative verify rollback) — write targets and every attend
        # core's recovered-position mask use the same modulus
        col = col % tables.shape[1]
    xp = jnp if isinstance(tables, jax.Array) else np
    # live paged rows always have col < table width; the clamp covers rows
    # whose table is a null placeholder (state-slot families, idle slots)
    col = xp.minimum(col, tables.shape[1] - 1)
    return {"tables": tables, "pos": pos,
            "write_page": tables[xp.arange(B), col],
            "write_off": pos % page_size}


# ---------------------------------------------------- flat prefill metadata

def prefill_meta(cfg: ArchConfig, page_size: int, tables, slots, start,
                 n_tail, T: int):
    """Flat per-step prefill metadata, the prefill twin of ``decode_meta``:
    page-table rows, state-slot rows, each row's chunk offset (``start``,
    absolute position of the chunk's first token) and live token count, and
    the precomputed physical (page, offset) write target of every chunk
    position — shared by all layers instead of re-derived per block.

    ``T`` is the chunk's *text* width (the prefill bucket); the write-target
    arrays cover the hidden width ``cfg.n_image_tokens + T`` (vlm prepends
    its image prefix).  Padding rows/positions and, for sliding-window
    families, positions that age out of the ring before the chunk ends are
    routed to the reserved null page.  Works on numpy (engine host path) and
    jnp arrays alike; the jitted ``prefill_paged`` step consumes it as one
    pytree, so step shapes are keyed by (bucket, padded rows) — with
    chunking, by the chunk budget — never by individual prompt lengths."""
    xp = jnp if isinstance(tables, jax.Array) else np
    B = tables.shape[0]
    Th = cfg.n_image_tokens + T
    positions = start[:, None] + xp.arange(Th)[None, :]           # [B, Th]
    n_live = n_tail + cfg.n_image_tokens
    live = xp.arange(Th)[None, :] < n_live[:, None]
    col = positions // page_size
    if cfg.sliding_window:
        # ring modulus = table width (see decode_meta ring contract)
        R = tables.shape[1]
        live = live & (positions >= (start + n_live)[:, None]
                       - R * page_size)
        col = col % R
    col = xp.minimum(col, tables.shape[1] - 1)
    page = tables[xp.arange(B)[:, None], col]
    return {"tables": tables, "slots": slots, "start": start,
            "n_tail": n_tail, "n_live": n_live,
            "write_page": xp.where(live, page, 0),
            "write_off": positions % page_size}


# ---------------------------------------------------- flat verify metadata

def verify_meta(cfg: ArchConfig, page_size: int, tables, pos, n_q, Q: int):
    """Flat metadata for a small-q speculative *verify* step.

    Row ``b`` carries ``n_q[b]`` live queries (the last emitted token plus
    its draft) at absolute positions ``pos[b] .. pos[b] + n_q[b] - 1``; the
    step is padded to the fixed width ``Q = speculate_tokens + 1``.  Write
    targets follow the decode ring contract (modulus = table width); dead
    query rows (``j >= n_q[b]``) are routed to the reserved null page so
    their garbage K/V never lands in an owned page.  Works on numpy (engine
    host path) and jnp arrays alike."""
    xp = jnp if isinstance(tables, jax.Array) else np
    B = tables.shape[0]
    positions = pos[:, None] + xp.arange(Q)[None, :]              # [B, Q]
    live = xp.arange(Q)[None, :] < n_q[:, None]
    col = positions // page_size
    if cfg.sliding_window:
        col = col % tables.shape[1]
    col = xp.minimum(col, tables.shape[1] - 1)
    page = tables[xp.arange(B)[:, None], col]
    return {"tables": tables, "pos": pos, "n_q": n_q,
            "write_page": xp.where(live, page, 0),
            "write_off": positions % page_size}


# ----------------------------------------------------------- backend classes

class AttentionBackend:
    """Family routing (shared) + attend cores (the extension point)."""

    name = "abstract"

    # -------- public entry points: the only paged-attention call sites

    def paged_prefill(self, cfg: ArchConfig, p, x, cache, meta, freqs, *,
                      q_block: int = 512, unroll: bool = False):
        """Multi-token chunk prefill at an offset into the paged pool.
        ``meta`` is the flat per-step metadata from ``prefill_meta``.  Routes
        by cache family (MLA latent / sliding-window ring / vanilla KV);
        returns (out [B, T, d], new_cache)."""
        if cfg.use_mla:
            return mla.mla_paged_prefill_block(
                cfg, p, x, cache, meta, freqs, backend=self,
                q_block=q_block, unroll=unroll)
        return attention.paged_prefill_attention_block(
            cfg, p, x, cache, meta, freqs, backend=self,
            q_block=q_block, unroll=unroll)

    def paged_decode(self, cfg: ArchConfig, p, x, cache, meta, freqs):
        """One-token decode against the paged pool.  ``meta`` is the flat
        per-step metadata from ``decode_meta``; returns (out [B, d],
        new_cache)."""
        if cfg.use_mla:
            return mla.mla_paged_decode_block(cfg, p, x, cache, meta, freqs,
                                              backend=self)
        return attention.paged_decode_attention_block(cfg, p, x, cache, meta,
                                                      freqs, backend=self)

    def paged_verify(self, cfg: ArchConfig, p, x, cache, meta, freqs):
        """Small-q speculative verify against the paged pool: ``x`` is
        [B, Q, d] (last emitted token + draft, padded to Q), ``meta`` is the
        flat metadata from ``verify_meta``.  All Q tokens' K/V scatter into
        their pages first, then every query attends the post-write pool
        under the per-query causal mask — rejected drafts stay invisible to
        surviving queries and are overwritten by the next step's writes.
        Returns (out [B, Q, d], new_cache)."""
        if cfg.use_mla:
            return mla.mla_paged_verify_block(cfg, p, x, cache, meta, freqs,
                                              backend=self)
        return attention.paged_verify_attention_block(cfg, p, x, cache, meta,
                                                      freqs, backend=self)

    # -------- attend cores (override to fuse)
    #
    # Every core takes optional scale pools (``k_scale``/``v_scale``
    # [P, ps, K] bf16, or ``ckv_scale``/``krope_scale`` [P, ps] for MLA).
    # ``None`` (the default) means the payload pools hold bf16 values and
    # the core behaves exactly as before; non-None means the payloads are
    # int8 and must be dequantized ``f32(q) * f32(s)`` before use.

    def decode_attend(self, q, k_pages, v_pages, tables, pos, *, scale: float,
                      softcap: float = 0.0, window: int = 0,
                      k_scale=None, v_scale=None):
        """q: [B, H, D]; pools [P, ps, K, D]; tables [B, n] (ring when
        ``window > 0``); pos [B].  Returns [B, H, D]."""
        raise NotImplementedError

    def mla_decode_attend(self, q_eff, q_rope, ckv_pages, krope_pages, tables,
                          pos, *, scale: float, ckv_scale=None,
                          krope_scale=None):
        """Absorbed-latent scores + latent context: q_eff [B, H, L] /
        q_rope [B, H, R] against [P, ps, L] / [P, ps, R] pages.  Returns the
        latent context [B, H, L]."""
        raise NotImplementedError

    def verify_attend(self, q, k_pages, v_pages, tables, pos, n_q, *,
                      scale: float, softcap: float = 0.0, window: int = 0,
                      k_scale=None, v_scale=None):
        """Small-q verify attend: q [B, Q, H, D] (query j of row b sits at
        absolute position ``pos[b] + j``) against the *post-write* pool.
        Mask: token position <= pos + j (ring-recovered when ``window > 0``)
        and j < n_q[b]; dead query rows return exact zeros on every backend.
        Returns [B, Q, H, D]."""
        raise NotImplementedError

    def mla_verify_attend(self, q_eff, q_rope, ckv_pages, krope_pages,
                          tables, pos, n_q, *, scale: float, ckv_scale=None,
                          krope_scale=None):
        """Small-q absorbed-latent verify attend: q_eff [B, Q, H, L] /
        q_rope [B, Q, H, R] against the post-write latent pages, masked as
        ``verify_attend``.  Returns the latent context [B, Q, H, L]."""
        raise NotImplementedError

    def prefill_attend(self, q, k, v, k_pages, v_pages, tables, start, n_live,
                       *, window: int = 0, softcap: float = 0.0,
                       q_block: int = 512, unroll: bool = False,
                       k_scale=None, v_scale=None):
        """Ragged multi-token prefill attend against the paged pool.

        q: [B, T, H, D] roped chunk queries at per-row offsets ``start``;
        n_live: [B] real chunk tokens.  ``window == 0``: the chunk's K/V are
        already resident — ``k_pages``/``v_pages`` are the *post-write* pool
        and ``k``/``v`` are unused.  ``window > 0``: ``k_pages``/``v_pages``
        are the *pre-write* page ring (``tables`` truncated to the ring
        horizon) and ``k``/``v`` [B, T, K, D] carry the chunk's fresh roped
        K/V (always unquantized — only resident pages are int8).  Returns
        [B, T, H, D_v]."""
        raise NotImplementedError

    def mla_prefill_attend(self, q, ckv_pages, krope_pages, wkv_b, tables,
                           start, n_live, *, nope: int, scale: float = None,
                           q_block: int = 512, unroll: bool = False,
                           ckv_scale=None, krope_scale=None):
        """Ragged MLA prefill attend: materialized-K semantics against the
        post-write latent pages (see ``mla.mla_materialized_prefill_attend``,
        the reference formulation).  q: [B, T, H, nope+rope]; returns
        [B, T, H, v_head_dim]."""
        raise NotImplementedError


def _gather_dequant(pages, scale_pages, tables):
    """Materialize the logical fp32 view of an int8 pool: gather payload and
    scale pages through the same table, dequant ``f32(q) * f32(s)``."""
    g = attention.gather_pages(pages, tables)
    return attention.dequant_int8(g, attention.gather_pages(scale_pages,
                                                            tables))


@register_backend
class ReferenceBackend(AttentionBackend):
    """Gather+attend via XLA — the parity oracle.

    int8 pools are dequantized to fp32 right after the gather, then run
    through the unchanged fp32 attend pipeline; the only added rounding
    point vs bf16 is the quantize/dequant round-trip itself, and the output
    is cast back to the query dtype — the same single output rounding the
    fused kernels keep."""

    name = "reference"

    def decode_attend(self, q, k_pages, v_pages, tables, pos, *, scale: float,
                      softcap: float = 0.0, window: int = 0,
                      k_scale=None, v_scale=None):
        if k_scale is not None:
            kg = _gather_dequant(k_pages, k_scale, tables)
            vg = _gather_dequant(v_pages, v_scale, tables)
        else:
            kg = attention.gather_pages(k_pages, tables)
            vg = attention.gather_pages(v_pages, tables)
        valid = attention.decode_valid_mask(pos, kg.shape[1], window=window)
        o = attention.masked_token_attend(q, kg, vg, valid, scale=scale,
                                          softcap=softcap)
        return o.astype(q.dtype)

    def mla_decode_attend(self, q_eff, q_rope, ckv_pages, krope_pages, tables,
                          pos, *, scale: float, ckv_scale=None,
                          krope_scale=None):
        if ckv_scale is not None:
            ccg = _gather_dequant(ckv_pages, ckv_scale, tables)
            crg = _gather_dequant(krope_pages, krope_scale, tables)
        else:
            ccg = attention.gather_pages(ckv_pages, tables)
            crg = attention.gather_pages(krope_pages, tables)
        valid = attention.decode_valid_mask(pos, ccg.shape[1])
        ctx = mla.mla_latent_attend(q_eff, q_rope, ccg, crg, valid,
                                    scale=scale)
        return ctx.astype(q_eff.dtype)

    def verify_attend(self, q, k_pages, v_pages, tables, pos, n_q, *,
                      scale: float, softcap: float = 0.0, window: int = 0,
                      k_scale=None, v_scale=None):
        if k_scale is not None:
            kg = _gather_dequant(k_pages, k_scale, tables)
            vg = _gather_dequant(v_pages, v_scale, tables)
        else:
            kg = attention.gather_pages(k_pages, tables)
            vg = attention.gather_pages(v_pages, tables)
        valid = attention.verify_valid_mask(pos, n_q, q.shape[1],
                                            kg.shape[1], window=window)
        o = attention.masked_multi_token_attend(q, kg, vg, valid,
                                                scale=scale, softcap=softcap)
        return o.astype(q.dtype)

    def mla_verify_attend(self, q_eff, q_rope, ckv_pages, krope_pages,
                          tables, pos, n_q, *, scale: float, ckv_scale=None,
                          krope_scale=None):
        if ckv_scale is not None:
            ccg = _gather_dequant(ckv_pages, ckv_scale, tables)
            crg = _gather_dequant(krope_pages, krope_scale, tables)
        else:
            ccg = attention.gather_pages(ckv_pages, tables)
            crg = attention.gather_pages(krope_pages, tables)
        valid = attention.verify_valid_mask(pos, n_q, q_eff.shape[1],
                                            ccg.shape[1])
        ctx = mla.mla_latent_verify_attend(q_eff, q_rope, ccg, crg, valid,
                                           scale=scale)
        return ctx.astype(q_eff.dtype)

    def prefill_attend(self, q, k, v, k_pages, v_pages, tables, start, n_live,
                       *, window: int = 0, softcap: float = 0.0,
                       q_block: int = 512, unroll: bool = False,
                       k_scale=None, v_scale=None):
        if window == 0:
            if k_scale is not None:
                kg = _gather_dequant(k_pages, k_scale, tables)
                vg = _gather_dequant(v_pages, v_scale, tables)
            else:
                kg = attention.gather_pages(k_pages, tables)
                vg = attention.gather_pages(v_pages, tables)
            o = attention.chunked_attention(
                q, kg, vg, causal=True, q_block=q_block, softcap=softcap,
                q_offset=start, unroll=unroll)
            return o.astype(q.dtype)
        if k_scale is not None:
            kr = _gather_dequant(k_pages, k_scale, tables)
            vr = _gather_dequant(v_pages, v_scale, tables)
            # the fresh chunk K/V stay unquantized; promote to fp32 so the
            # ring concat and the probability cast are fp32 end to end
            k, v = k.astype(jnp.float32), v.astype(jnp.float32)
        else:
            kr = attention.gather_pages(k_pages, tables)
            vr = attention.gather_pages(v_pages, tables)
        o = attention.ring_chunk_attention(
            q, k, v, kr, vr, start, n_live,
            window=window, softcap=softcap, q_block=q_block, unroll=unroll)
        return o.astype(q.dtype)

    def mla_prefill_attend(self, q, ckv_pages, krope_pages, wkv_b, tables,
                           start, n_live, *, nope: int, scale: float = None,
                           q_block: int = 512, unroll: bool = False,
                           ckv_scale=None, krope_scale=None):
        o = mla.mla_materialized_prefill_attend(
            q, ckv_pages, krope_pages, wkv_b, tables, start, n_live,
            nope=nope, scale=scale, q_block=q_block, unroll=unroll,
            ckv_scale=ckv_scale, krope_scale=krope_scale)
        return o.astype(q.dtype)


@register_backend
class PallasBackend(ReferenceBackend):
    """Fused paged attention (``repro.kernels.paged_attention`` decode +
    ``repro.kernels.ragged_prefill`` chunk prefill); interpret mode on CPU,
    Mosaic on TPU."""

    name = "pallas"

    def decode_attend(self, q, k_pages, v_pages, tables, pos, *, scale: float,
                      softcap: float = 0.0, window: int = 0,
                      k_scale=None, v_scale=None):
        return paged_attention_decode(q, k_pages, v_pages, tables, pos,
                                      scale=scale, softcap=softcap,
                                      window=window, k_scale=k_scale,
                                      v_scale=v_scale)

    def mla_decode_attend(self, q_eff, q_rope, ckv_pages, krope_pages, tables,
                          pos, *, scale: float, ckv_scale=None,
                          krope_scale=None):
        return mla_paged_attention_decode(q_eff, q_rope, ckv_pages,
                                          krope_pages, tables, pos,
                                          scale=scale, ckv_scale=ckv_scale,
                                          krope_scale=krope_scale)

    def verify_attend(self, q, k_pages, v_pages, tables, pos, n_q, *,
                      scale: float, softcap: float = 0.0, window: int = 0,
                      k_scale=None, v_scale=None):
        return paged_attention_verify(q, k_pages, v_pages, tables, pos, n_q,
                                      scale=scale, softcap=softcap,
                                      window=window, k_scale=k_scale,
                                      v_scale=v_scale)

    def mla_verify_attend(self, q_eff, q_rope, ckv_pages, krope_pages,
                          tables, pos, n_q, *, scale: float, ckv_scale=None,
                          krope_scale=None):
        return mla_paged_attention_verify(q_eff, q_rope, ckv_pages,
                                          krope_pages, tables, pos, n_q,
                                          scale=scale, ckv_scale=ckv_scale,
                                          krope_scale=krope_scale)

    def prefill_attend(self, q, k, v, k_pages, v_pages, tables, start, n_live,
                       *, window: int = 0, softcap: float = 0.0,
                       q_block: int = 512, unroll: bool = False,
                       k_scale=None, v_scale=None):
        return ragged_prefill_attend(q, k, v, k_pages, v_pages, tables,
                                     start, n_live, window=window,
                                     softcap=softcap, k_scale=k_scale,
                                     v_scale=v_scale)

    def mla_prefill_attend(self, q, ckv_pages, krope_pages, wkv_b, tables,
                           start, n_live, *, nope: int, scale: float = None,
                           q_block: int = 512, unroll: bool = False,
                           ckv_scale=None, krope_scale=None):
        return mla_ragged_prefill_attend(q, ckv_pages, krope_pages, wkv_b,
                                         tables, start, n_live, nope=nope,
                                         scale=scale, ckv_scale=ckv_scale,
                                         krope_scale=krope_scale)
