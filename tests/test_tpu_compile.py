"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e chip.

Interpret mode (every other kernel test) runs a kernel body on the CPU and
cannot see what the TPU compiler refuses: block shapes off the (8, 128)
tiling, shape casts Mosaic has no layout for, scratch beyond the scoped VMEM
limit.  Here each kernel is lowered and compiled for one chip of a described
``v5e:2x2`` topology, at the published widths of the model whose serving
path runs it — nothing executes, so no chip is needed.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and pytest-xdist workers import
every test file.
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.grouped_matmul import expert_matmul
from repro.kernels.paged_attention import (mla_paged_attention_decode,
                                           mla_paged_attention_verify,
                                           paged_attention_decode,
                                           paged_attention_verify)
from repro.kernels.ragged_prefill import (mla_ragged_prefill_attend,
                                          ragged_prefill_attend)
from repro.kernels.ragged_prefill.kernel import fit_q_block
from repro.kernels.rbm_cd import gemm_sigmoid

BF16, I8, I32, F32 = jnp.bfloat16, jnp.int8, jnp.int32, jnp.float32

PS = 16          # serving page size
B = 8            # decode slots
CHUNK = 256      # prefill chunk tokens
WIDEST = 32768   # widest prefill table the kernels compile for (tokens)


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        # the compiler would otherwise write its logs under the temp dir
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's executables cannot be read back from the
        # persistent cache; keep these compiles out of it
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)


def _compile(one_chip, fn, *shapes, **kwargs):
    """Lower ``fn`` at ``shapes`` ((shape, dtype) pairs; ``None`` passes
    through) for the described chip and compile; the kernel must reach the
    program as a Mosaic custom call."""
    args = [None if s is None else
            jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            for s in shapes]
    kw = {k: (jax.ShapeDtypeStruct(v[0], v[1], sharding=one_chip)
              if isinstance(v, tuple) else v) for k, v in kwargs.items()}
    compiled = fn.lower(*args, interpret=False, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _pool(n_pages, K, D, dtype):
    return ((n_pages, PS, K, D), dtype)


# published widths: qwen2-0.5b (H=14, K=2, D=64) at an 8 x 1,024-token pool;
# starcoder2-7b (H=36, K=4, D=128, 4,096-token window -> 257-page ring);
# deepseek-v2 latent (H=128, L=512, R=64, nope=128, v=128)
QWEN = dict(H=14, K=2, D=64, n_pages=64)
SC2 = dict(H=36, K=4, D=128, n_pages=4096 // PS + 1, window=4096)
DSV2 = dict(H=128, L=512, R=64, nope=128, vd=128, n_pages=64)


def _n_phys(n_pages):
    return B * n_pages + 1


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_decode_qwen2(one_chip, kv):
    H, K, D, n = QWEN["H"], QWEN["K"], QWEN["D"], QWEN["n_pages"]
    P = _n_phys(n)
    dt = I8 if kv == "int8" else BF16
    scales = ({"k_scale": ((P, PS, K), BF16), "v_scale": ((P, PS, K), BF16)}
              if kv == "int8" else {})
    _compile(one_chip, paged_attention_decode, ((B, H, D), BF16),
             _pool(P, K, D, dt), _pool(P, K, D, dt), ((B, n), I32),
             ((B,), I32), scale=1 / math.sqrt(D), **scales)


# the benchmark's decode programs (bench/configs): qwen2-0.5b's 64 slots
# and minitron-4b's 4 (H=24, K=8, D=128), each with 256-page tables over a
# pool of slots x 256 + 1 pages — the page blocks' VMEM at real widths
BENCH_DECODE = [
    pytest.param(dict(H=14, K=2, D=64, B=64, P=16385), id="qwen2-0.5b"),
    pytest.param(dict(H=24, K=8, D=128, B=4, P=1025), id="minitron-4b"),
]


@pytest.mark.parametrize("shape", BENCH_DECODE)
def test_decode_benchmark_shapes(one_chip, shape):
    H, K, D, rows, P = (shape[k] for k in ("H", "K", "D", "B", "P"))
    _compile(one_chip, paged_attention_decode, ((rows, H, D), BF16),
             _pool(P, K, D, BF16), _pool(P, K, D, BF16), ((rows, 256), I32),
             ((rows,), I32), scale=1 / math.sqrt(D))


def test_decode_windowed_starcoder2(one_chip):
    H, K, D, n = SC2["H"], SC2["K"], SC2["D"], SC2["n_pages"]
    P = _n_phys(n)
    _compile(one_chip, paged_attention_decode, ((B, H, D), BF16),
             _pool(P, K, D, BF16), _pool(P, K, D, BF16), ((B, n), I32),
             ((B,), I32), scale=1 / math.sqrt(D), window=SC2["window"])


def test_decode_mla_deepseek_v2(one_chip):
    H, L, R, n = DSV2["H"], DSV2["L"], DSV2["R"], DSV2["n_pages"]
    P = _n_phys(n)
    _compile(one_chip, mla_paged_attention_decode, ((B, H, L), BF16),
             ((B, H, R), BF16), ((P, PS, L), BF16), ((P, PS, R), BF16),
             ((B, n), I32), ((B,), I32), scale=1 / math.sqrt(192))


def test_verify_qwen2(one_chip):
    H, K, D, n = QWEN["H"], QWEN["K"], QWEN["D"], QWEN["n_pages"]
    P = _n_phys(n)
    _compile(one_chip, paged_attention_verify, ((B, 5, H, D), BF16),
             _pool(P, K, D, BF16), _pool(P, K, D, BF16), ((B, n), I32),
             ((B,), I32), ((B,), I32), scale=1 / math.sqrt(D))


def test_verify_mla_deepseek_v2(one_chip):
    H, L, R, n = DSV2["H"], DSV2["L"], DSV2["R"], DSV2["n_pages"]
    P = _n_phys(n)
    _compile(one_chip, mla_paged_attention_verify, ((B, 5, H, L), BF16),
             ((B, 5, H, R), BF16), ((P, PS, L), BF16), ((P, PS, R), BF16),
             ((B, n), I32), ((B,), I32), ((B,), I32),
             scale=1 / math.sqrt(192))


# the deepseek-v2 cell (bench/configs/deepseek-v2-236b-serve.json): 28 slots
# of 16,384 tokens (1,024-page tables) over a pool of 28 x 1,024 + 1 pages,
# a 512-token prefill chunk against the whole table, and the held experts'
# grouped matmuls (20 experts, D=5120, F=1536) at a decode step's pairs
# (28 rows x top-6, padded to the row tile) and a prefill chunk's
DSV2_CELL = dict(B=28, n_pages=1024, P=28 * 1024 + 1)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_decode_mla_deepseek_v2_cell(one_chip, kv):
    H, L, R = DSV2["H"], DSV2["L"], DSV2["R"]
    rows, n, P = DSV2_CELL["B"], DSV2_CELL["n_pages"], DSV2_CELL["P"]
    dt = I8 if kv == "int8" else BF16
    scales = ({"ckv_scale": ((P, PS), BF16), "krope_scale": ((P, PS), BF16)}
              if kv == "int8" else {})
    _compile(one_chip, mla_paged_attention_decode, ((rows, H, L), BF16),
             ((rows, H, R), BF16), ((P, PS, L), dt), ((P, PS, R), dt),
             ((rows, n), I32), ((rows,), I32), scale=0.1147, **scales)


def test_verify_mla_deepseek_v2_cell(one_chip):
    H, L, R = DSV2["H"], DSV2["L"], DSV2["R"]
    rows, n, P = DSV2_CELL["B"], DSV2_CELL["n_pages"], DSV2_CELL["P"]
    _compile(one_chip, mla_paged_attention_verify, ((rows, 2, H, L), BF16),
             ((rows, 2, H, R), BF16), ((P, PS, L), BF16), ((P, PS, R), BF16),
             ((rows, n), I32), ((rows,), I32), ((rows,), I32), scale=0.1147)


def test_prefill_mla_deepseek_v2_cell(one_chip):
    H, L, R = DSV2["H"], DSV2["L"], DSV2["R"]
    n, P = DSV2_CELL["n_pages"], DSV2_CELL["P"]
    _compile(one_chip, mla_ragged_prefill_attend,
             ((1, 512, H, DSV2["nope"] + R), BF16), ((P, PS, L), BF16),
             ((P, PS, R), BF16), ((L, H, DSV2["nope"] + DSV2["vd"]), BF16),
             ((1, n), I32), ((1,), I32), ((1,), I32), nope=DSV2["nope"],
             scale=0.1147)


@pytest.mark.parametrize("rows", [192, 3072])
@pytest.mark.parametrize("k,n,out", [(5120, 1536, BF16), (1536, 5120, F32)])
def test_grouped_matmul_deepseek_v2_experts(one_chip, rows, k, n, out):
    _compile(one_chip, expert_matmul, ((rows, k), BF16), ((20, k, n), BF16),
             ((20,), I32), out_dtype=out)


def test_prefill_qwen2(one_chip):
    H, K, D, n = QWEN["H"], QWEN["K"], QWEN["D"], QWEN["n_pages"]
    P = _n_phys(n)
    _compile(one_chip, ragged_prefill_attend, ((B, CHUNK, H, D), BF16),
             None, None, _pool(P, K, D, BF16), _pool(P, K, D, BF16),
             ((B, n), I32), ((B,), I32), ((B,), I32))


def test_prefill_windowed_starcoder2(one_chip):
    H, K, D, n = SC2["H"], SC2["K"], SC2["D"], SC2["n_pages"]
    P = _n_phys(n)
    _compile(one_chip, ragged_prefill_attend, ((B, CHUNK, H, D), BF16),
             ((B, CHUNK, K, D), BF16), ((B, CHUNK, K, D), BF16),
             _pool(P, K, D, BF16), _pool(P, K, D, BF16), ((B, n), I32),
             ((B,), I32), ((B,), I32), window=SC2["window"])


def test_prefill_mla_deepseek_v2(one_chip):
    H, L, R, n = DSV2["H"], DSV2["L"], DSV2["R"], DSV2["n_pages"]
    P = _n_phys(n)
    _compile(one_chip, mla_ragged_prefill_attend,
             ((B, CHUNK, H, DSV2["nope"] + R), BF16), ((P, PS, L), BF16),
             ((P, PS, R), BF16), ((L, H, DSV2["nope"] + DSV2["vd"]), BF16),
             ((B, n), I32), ((B,), I32), ((B,), I32), nope=DSV2["nope"])


def test_rbm_cd_mnist(one_chip):
    # the paper's first RBM layer: 784 visible units, 1,000 hidden
    _compile(one_chip, gemm_sigmoid, ((128, 784), F32), ((784, 1000), F32),
             ((1000,), F32))


def test_prefill_widest_table(one_chip):
    # qwen2 at the widest table the score-scratch budget admits: 32,768
    # tokens (2,048 pages), where the query block shrinks to 16 tokens
    H, K, D = QWEN["H"], QWEN["K"], QWEN["D"]
    n = WIDEST // PS
    assert fit_q_block(CHUNK, H // K, WIDEST) == 16
    _compile(one_chip, ragged_prefill_attend, ((1, CHUNK, H, D), BF16),
             None, None, _pool(n + 1, K, D, BF16), _pool(n + 1, K, D, BF16),
             ((1, n), I32), ((1,), I32), ((1,), I32))


def test_prefill_table_past_budget_is_refused():
    assert fit_q_block(CHUNK, 7, 1024) == 128
    with pytest.raises(ValueError, match="score scratch"):
        fit_q_block(CHUNK, 7, WIDEST + PS)
