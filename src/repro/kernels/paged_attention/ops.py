"""Jit'd public wrappers for the fused paged-attention decode kernels.

On CPU (this container, CI) the kernel bodies execute in interpret mode; on
TPU the same ``pallas_call`` lowers to Mosaic.  The wrappers accept the
model-layout tensors (``q: [B, H, D]``, layer-stacked lane-dense pools
``[L, P, ps, W]`` and the index of the layer to read) and handle the
kernel's grouped-query ``[B, K, G, D]`` layout; see
``src/repro/kernels/README.md`` for the full backend contract (pool layout,
page-table layout, masking rules, null-page semantics).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import default_interpret
from .kernel import (mla_paged_decode_fwd, mla_paged_verify_fwd,
                     paged_decode_fwd, paged_verify_fwd)


@partial(jax.jit, static_argnames=("kv_heads", "scale", "softcap", "window",
                                   "interpret"))
def paged_attention_decode(q, k_pages, v_pages, layer, tables, pos, *,
                           kv_heads: int, scale: float, softcap: float = 0.0,
                           window: int = 0, k_scale=None, v_scale=None,
                           interpret: bool = None):
    """One-token GQA decode against layer ``layer`` of the paged KV pool.

    q: [B, H, D] (the step's roped queries, new token already written to its
    page); k_pages/v_pages: [L, P, ps, W], each token slot ``kv_heads``
    heads of D lanes (head h at lanes [h * D, (h + 1) * D)) with H %
    kv_heads == 0; tables: [B, n_pages] int32 physical page ids (a ring of
    ``n_pages`` pages when ``window > 0``); pos: [B] int32 absolute
    positions.  Returns [B, H, D].  When the pool is int8,
    ``k_scale``/``v_scale`` carry the [L, P, ps, K] bf16 absmax scales and
    the kernel dequantizes in-register.
    """
    B, H, D = q.shape
    K = kv_heads
    assert H % K == 0 and K * D <= k_pages.shape[-1], (H, K, D)
    qg = q.reshape(B, K, H // K, D)
    o = paged_decode_fwd(qg, k_pages, v_pages, layer,
                         jnp.asarray(tables, jnp.int32),
                         jnp.asarray(pos, jnp.int32), scale=scale,
                         softcap=softcap, window=window,
                         k_scale=k_scale, v_scale=v_scale,
                         interpret=default_interpret(interpret))
    return o.reshape(B, H, D)


@partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_paged_attention_decode(q_eff, q_rope, pages, layer, tables, pos, *,
                               scale: float, ckv_scale=None,
                               krope_scale=None, interpret: bool = None):
    """One-token absorbed-latent MLA decode against layer ``layer`` of the
    latent pages.

    q_eff: [B, H, L] (``w_uk``-absorbed queries); q_rope: [B, H, R] (roped);
    pages: [Lyr, P, ps, W] lane-dense latent pages (ckv at lanes [0, L),
    krope at [L, L + R)); tables: [B, n_pages]; pos: [B].  Returns the
    latent context [B, H, L] — the caller up-projects it with ``w_uv``.
    ``ckv_scale``/``krope_scale``: [Lyr, P, ps] bf16 scales when the latent
    pages are int8-quantized."""
    return mla_paged_decode_fwd(q_eff, q_rope, pages, layer,
                                jnp.asarray(tables, jnp.int32),
                                jnp.asarray(pos, jnp.int32), scale=scale,
                                ckv_scale=ckv_scale, krope_scale=krope_scale,
                                interpret=default_interpret(interpret))


@partial(jax.jit, static_argnames=("kv_heads", "scale", "softcap", "window",
                                   "interpret"))
def paged_attention_verify(q, k_pages, v_pages, layer, tables, pos, n_q, *,
                           kv_heads: int, scale: float, softcap: float = 0.0,
                           window: int = 0, k_scale=None, v_scale=None,
                           interpret: bool = None):
    """Small-q GQA verify against the paged KV pool (speculative decoding).

    q: [B, Q, H, D] — per row the last emitted token plus its draft, roped
    at positions ``pos + j`` and already written to their pages; pos: [B]
    base positions; n_q: [B] live query counts (1 + draft length).  Pool /
    layer / table / ring / int8-scale layout as ``paged_attention_decode``.
    Returns [B, Q, H, D]; dead query rows (j >= n_q) are exact zeros."""
    B, Q, H, D = q.shape
    K = kv_heads
    assert H % K == 0 and K * D <= k_pages.shape[-1], (H, K, D)
    qg = q.reshape(B, Q, K, H // K, D).transpose(0, 2, 1, 3, 4)
    o = paged_verify_fwd(qg, k_pages, v_pages, layer,
                         jnp.asarray(tables, jnp.int32),
                         jnp.asarray(pos, jnp.int32),
                         jnp.asarray(n_q, jnp.int32), scale=scale,
                         softcap=softcap, window=window,
                         k_scale=k_scale, v_scale=v_scale,
                         interpret=default_interpret(interpret))
    return o.transpose(0, 2, 1, 3, 4).reshape(B, Q, H, D)


@partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_paged_attention_verify(q_eff, q_rope, pages, layer, tables, pos, n_q,
                               *, scale: float, ckv_scale=None,
                               krope_scale=None, interpret: bool = None):
    """Small-q absorbed-latent MLA verify against the latent pages.

    q_eff: [B, Q, H, L]; q_rope: [B, Q, H, R]; pages, layer and pos/n_q as
    in ``mla_paged_attention_decode`` / ``paged_attention_verify``.
    Returns the latent context [B, Q, H, L] (dead query rows exact zeros) —
    the caller up-projects with ``w_uv``."""
    return mla_paged_verify_fwd(q_eff, q_rope, pages, layer,
                                jnp.asarray(tables, jnp.int32),
                                jnp.asarray(pos, jnp.int32),
                                jnp.asarray(n_q, jnp.int32), scale=scale,
                                ckv_scale=ckv_scale,
                                krope_scale=krope_scale,
                                interpret=default_interpret(interpret))
