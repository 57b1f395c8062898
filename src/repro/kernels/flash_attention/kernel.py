"""Causal GQA flash attention — Pallas TPU kernel.

TPU-native adaptation (see DESIGN.md §7): instead of a CUDA warp-level softmax,
the kernel tiles Q into ``block_q`` x ``head_dim`` VMEM blocks (MXU-aligned,
multiples of 128 recommended), streams K/V in ``block_k`` tiles along the
innermost ("arbitrary") grid dimension, and keeps the online-softmax state
(running max ``m``, normalizer ``l``, accumulator ``acc``) in fp32 VMEM scratch
across the K sweep.  GQA is expressed in the BlockSpec index maps: the K/V
block index maps divide the query-head index by the group size, so no KV
replication is materialized.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int, causal: bool,
                  seq_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    # with causal masking, blocks strictly above the diagonal contribute nothing
    q_lo = qi * block_q
    k_lo = ki * block_k
    run = (not causal) or (k_lo <= q_lo + block_q - 1)

    @pl.when(k_lo <= q_lo + block_q - 1 if causal else True)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)                  # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq, bk]
        if causal:
            qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows (m_new == -inf)
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m[:, None])
        p = jnp.where(jnp.isfinite(m_new)[:, None], p, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-20)[:, None]).astype(
            o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False) -> jax.Array:
    """q: [B, H, S, D]; k, v: [B, K, S, D] with H % K == 0. Returns [B, H, S, D]."""
    B, H, S, D = q.shape
    K = k.shape[1]
    assert H % K == 0, (H, K)
    G = H // K
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    nq, nk = S // block_q, S // block_k
    scale = 1.0 / math.sqrt(D)

    grid = (B, H, nq, nk)
    kernel = functools.partial(_flash_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, causal=causal, seq_len=S)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
