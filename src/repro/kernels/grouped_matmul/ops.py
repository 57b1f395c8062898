"""Jit'd wrapper of the grouped expert matmul.

``lhs`` holds one row per (token, expert) pair, sorted by expert; the rows
of expert ``e`` are the ``group_sizes[e]`` after those of the experts
before it, and rows past ``sum(group_sizes)`` belong to no expert.  Each
row is multiplied by its expert's ``rhs[e]``: the Pallas kernel is
Megablox's ``gmm`` (``jax.experimental.pallas.ops.tpu.megablox``), whose
grid visits only the row tiles the groups cover and fetches an expert's
weights once per tile it covers, so work and weight bytes follow the live
pairs and the experts they touch.  Rows past the groups are left unwritten:
the caller masks them.  On CPU the kernel runs in interpret mode.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

from .. import default_interpret

ROW_TILE = 64          # pair rows per tile: a decode step's few rows per
                       # expert, a prefill chunk's tens
K_TILE, N_TILE = 1024, 768


def _tile(dim: int, target: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``target``; ``dim`` itself when there is none (a small test width)."""
    for t in range(min(target, dim) // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def row_padding(n_rows: int) -> int:
    """Rows to append so the pair rows fill whole row tiles."""
    return -n_rows % ROW_TILE


@partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def expert_matmul(lhs, rhs, group_sizes, *, out_dtype=None,
                  interpret: bool = None):
    """lhs: [M, K] pair rows sorted by expert (M a ``ROW_TILE`` multiple);
    rhs: [E, K, N]; group_sizes: [E] int32.  Returns [M, N] in
    ``out_dtype`` (default ``lhs.dtype``), fp32 accumulation."""
    K, N = rhs.shape[1], rhs.shape[2]
    return gmm(lhs, rhs, group_sizes.astype(jnp.int32),
               preferred_element_type=out_dtype or lhs.dtype,
               tiling=(ROW_TILE, _tile(K, K_TILE), _tile(N, N_TILE)),
               interpret=default_interpret(interpret))
