"""Jit'd public wrappers for the fused ragged paged-prefill kernels.

On CPU (this container, CI) the kernel bodies execute in interpret mode; on
TPU the same ``pallas_call`` lowers to Mosaic.  The wrappers accept the
model-layout tensors (``q: [B, T, H, D]``, pools ``[P, ps, K, D]`` /
``[P, ps, L]``) and handle the kernel's grouped-query / head-major layouts,
q-block padding, and per-row int32 metadata; see
``src/repro/kernels/README.md`` for the full ragged-prefill contract
(per-row (start, n_live) metadata, masking rules, pre- vs post-write pool
semantics, numerics).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from .. import default_interpret
from .kernel import (fit_q_block, mla_ragged_prefill_fwd, ragged_prefill_fwd,
                     windowed_ragged_prefill_fwd)


def _pad_q(q, q_blk):
    """Pad the token axis (axis 2 of [B, K/H, T, ...]) to a q_blk multiple.
    Padding rows attend causally-valid garbage and are sliced off."""
    T = q.shape[2]
    pad = (-T) % q_blk
    if pad:
        q = jnp.pad(q, [(0, 0), (0, 0), (0, pad)]
                    + [(0, 0)] * (q.ndim - 3))
    return q, T


@partial(jax.jit, static_argnames=("window", "softcap", "q_blk", "interpret"))
def ragged_prefill_attend(q, k_new, v_new, k_pages, v_pages, tables, start,
                          n_live, *, window: int = 0, softcap: float = 0.0,
                          q_blk: int = 128, k_scale=None, v_scale=None,
                          interpret: bool = None):
    """Ragged chunk-prefill attend against the paged KV pool.

    q: [B, T, H, D] roped chunk queries at per-row offsets ``start`` [B];
    n_live: [B] real chunk tokens.  ``window == 0``: ``k_pages``/``v_pages``
    [P, ps, K, D] are the *post-write* pool (the chunk's K/V are already
    resident; ``k_new``/``v_new`` are ignored).  ``window > 0``: the pool is
    *pre-write*, ``tables`` [B, n_ring] is the page ring, and
    ``k_new``/``v_new`` [B, T, K, D] carry the chunk's fresh roped K/V (T
    must be a page multiple).  Returns [B, T, H, D].  ``k_scale``/
    ``v_scale``: [P, ps, K] bf16 absmax scales when the pool is int8; the
    windowed path's fresh K/V stay at model dtype (only resident ring pages
    are quantized)."""
    B, T, H, D = q.shape
    K = k_pages.shape[2]
    assert H % K == 0, (H, K)
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, T, K, H // K, D).transpose(0, 2, 1, 3, 4)
    n_keys = tables.shape[1] * k_pages.shape[1]
    if window:
        n_keys += k_new.shape[1]        # the fresh chunk's keys ride along
    blk = fit_q_block(T, H // K, n_keys, q_blk)
    qg, T0 = _pad_q(qg, blk)
    tables = jnp.asarray(tables, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    n_live = jnp.asarray(n_live, jnp.int32)
    if window == 0:
        o = ragged_prefill_fwd(qg, k_pages, v_pages, tables, start, n_live,
                               scale=scale, softcap=softcap, q_blk=blk,
                               k_scale=k_scale, v_scale=v_scale,
                               interpret=default_interpret(interpret))
    else:
        # never round the fresh chunk to the pool dtype: under int8 the pool
        # is quantized but the chunk attends at model precision
        new_dt = k_new.dtype if k_scale is not None else k_pages.dtype
        kn = jnp.asarray(k_new, new_dt)
        vn = jnp.asarray(v_new, new_dt)
        o = windowed_ragged_prefill_fwd(
            qg, kn, vn, k_pages, v_pages, tables, start, n_live,
            window=window, scale=scale, softcap=softcap, q_blk=blk,
            k_scale=k_scale, v_scale=v_scale,
            interpret=default_interpret(interpret))
    return o[:, :, :T0].transpose(0, 2, 1, 3, 4).reshape(B, T0, H, D)


@partial(jax.jit, static_argnames=("nope", "scale", "q_blk", "interpret"))
def mla_ragged_prefill_attend(q, ckv_pages, krope_pages, wkv_b, tables, start,
                              n_live, *, nope: int, scale: float = None,
                              q_blk: int = 512, ckv_scale=None,
                              krope_scale=None, interpret: bool = None):
    """Ragged MLA chunk-prefill attend against the post-write latent pages.

    q: [B, T, H, nope+rope] (rope part already roped); ckv_pages:
    [P, ps, L]; krope_pages: [P, ps, R]; wkv_b: [L, H, nope + v_head_dim];
    tables: [B, n_pages].  Per-head K/V are materialized a block of pages at
    a time inside the kernel (``ckv @ w_uk`` ++ krope, ``ckv @ w_uv``) with
    the reference einsum's rounding.  ``scale`` defaults to
    ``(nope + rope) ** -0.5``.  A query block spans the whole chunk up to
    ``q_blk`` tokens: each block re-materializes the K/V it walks, so one
    block a chunk row does that work once.  Returns [B, T, H, v_head_dim].
    ``ckv_scale``/``krope_scale``: [P, ps] bf16 scales when the latent pages
    are int8."""
    B, T, H, E = q.shape
    scale = 1.0 / math.sqrt(E) if scale is None else scale
    qg = q.transpose(0, 2, 1, 3)                       # [B, H, T, E]
    blk = min(q_blk, -(-T // 16) * 16)
    qg, T0 = _pad_q(qg, blk)
    w = wkv_b.transpose(1, 0, 2)                       # [H, L, nope+vd]
    w_uk, w_uv = w[..., :nope], w[..., nope:]
    o = mla_ragged_prefill_fwd(
        qg, ckv_pages, krope_pages, w_uk, w_uv,
        jnp.asarray(tables, jnp.int32), jnp.asarray(start, jnp.int32),
        jnp.asarray(n_live, jnp.int32), scale=scale, q_blk=blk,
        ckv_scale=ckv_scale, krope_scale=krope_scale,
        interpret=default_interpret(interpret))
    return o[:, :, :T0].transpose(0, 2, 1, 3)
