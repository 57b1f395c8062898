"""Fused GEMM + bias + sigmoid — the RBM CD hot loop as a Pallas TPU kernel.

The paper's mapper spends its time in ``sigmoid(v @ W + b)`` (positive phase)
and the transposed GEMM of the negative phase.  On TPU the win is fusing the
bias+sigmoid epilogue into the blocked matmul so hidden probabilities never
round-trip to HBM in fp32: the kernel tiles (M, N, K) into MXU-aligned VMEM
blocks, accumulates in fp32 scratch over the K ("arbitrary") grid dimension,
and applies the epilogue on the last K step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _gemm_sigmoid_kernel(x_ref, w_ref, b_ref, o_ref, acc_scr):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        z = acc_scr[...] + b_ref[...].astype(jnp.float32)         # [1, bn]
        o_ref[...] = jax.nn.sigmoid(z).astype(o_ref.dtype)


def gemm_sigmoid_fwd(x: jax.Array, w: jax.Array, b: jax.Array, *,
                     block_m: int = 128, block_n: int = 128, block_k: int = 128,
                     interpret: bool = False) -> jax.Array:
    """sigmoid(x @ w + b).  x: [M, K]; w: [K, N]; b: [N]."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and b.shape == (N,)
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    pm, pn, pk = (-M) % block_m, (-N) % block_n, (-K) % block_k
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, pk), (0, pn)))
    if pn:
        b = jnp.pad(b, (0, pn))
    Mp, Kp = x.shape
    Np = w.shape[1]
    grid = (Mp // block_m, Np // block_n, Kp // block_k)
    out = pl.pallas_call(
        _gemm_sigmoid_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((block_k, block_n), lambda mi, ni, ki: (ki, ni)),
            # 2-D bias block: a 1-D block's layout does not match XLA's
            pl.BlockSpec((1, block_n), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, b.reshape(1, Np))
    return out[:M, :N]
