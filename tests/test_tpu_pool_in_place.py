"""The serving steps keep the paged KV pool in place.

The layer-stacked pool is carried through the layer loop and each layer
writes its new token slots into it in place; the Pallas kernels read it
where it lies (``kernels/README.md``, "Pool layout").  A pool stored in any
other layout, or passed through the loop as scan input and output, costs
every step whole-pool copies, relayouts and slices.  Here the whole
``decode_paged`` and ``prefill_paged`` steps of two benchmark
configurations are compiled for one chip of a described ``v5e:2x2``
topology, with the Mosaic kernels — nothing executes, so no chip is needed —
and two things are checked:

* XLA's temporaries stay far below the pool (a whole-pool copy or relayout
  needs a pool-sized temporary);
* outside the Pallas custom calls, no op outputs a pool-sized array except
  the in-place token scatters and the loop's own plumbing.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library.
"""
from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ServeConfig, get_arch
from repro.models.attn_backend import decode_meta, prefill_meta
from repro.models.params import abstract_tree
from repro.models.registry import build_model

CHUNK = 512

# (name, config, slots, max_len, temp bound): qwen2-0.5b's chat cell at 64
# slots of 4,096 tokens (3.2 GB pool), and the DeepSeek-V2 cell — the dense
# layer and 4 MoE layers, 20 held experts, 28 slots of 16,384 tokens
# (2.9 GB latent pool)
CELLS = {
    "qwen2-0.5b": (lambda: get_arch("qwen2-0.5b"), 64, 4096, 0.3e9),
    "deepseek-v2": (lambda: dataclasses.replace(
        get_arch("deepseek-v2-236b"), n_layers=5, n_experts_held=20,
        first_expert_held=0), 28, 16384, 0.6e9),
}

# ops that may output a pool-sized array: the step's parameters, the layer
# loop's tuple plumbing, bitcasts (no data moves) and the Pallas kernels
PLUMBING = {"parameter", "get-tuple-element", "tuple", "while", "bitcast",
            "custom-call"}
# a fusion may, when its root updates its pool operand in place
IN_PLACE = {"scatter", "dynamic-update-slice"}


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # the kernels lower to Mosaic (the CPU backend picks interpret mode)
        import repro.kernels.paged_attention.ops as pa
        import repro.kernels.ragged_prefill.ops as rp
        for mod in (pa, rp):
            mp.setattr(mod, "default_interpret",
                       lambda i: False if i is None else i)
        # a described chip's executables cannot be read back from the
        # persistent cache; keep these compiles out of it
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)


def _compile(one_chip, name, program):
    """The engine's jitted step (pool donated), compiled at the cell's
    shapes with every row idle.  Returns (compiled, pool pages)."""
    from repro.serving.engine import _paged_steps
    make, slots, max_len, _ = CELLS[name]
    cfg = dataclasses.replace(make(), remat="none")
    scfg = ServeConfig(page_size=16, max_slots=slots, max_len=max_len,
                       prefill_chunk_tokens=CHUNK, attn_backend="pallas")
    ps, width = scfg.page_size, scfg.pages_per_request
    n_pages = slots * width + 1
    model = build_model(cfg)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def meta(d):
        return {k: sds(jnp.asarray(v)) for k, v in d.items()}

    params = jax.tree.map(sds, abstract_tree(model.param_defs()))
    kv = jax.tree.map(sds, abstract_tree(model.paged_cache_defs(n_pages,
                                                                ps)))
    prefill, _, decode, *_ = _paged_steps(cfg, None, "pallas")
    zeros = np.zeros((slots,), np.int32)
    if program == "decode":
        args = (params, kv, {}, meta(decode_meta(
            cfg, ps, np.zeros((slots, width), np.int32), zeros)),
            sds(jnp.zeros((slots,), jnp.int32)))
        return decode.lower(*args).compile(), n_pages
    one = np.zeros((1,), np.int32)
    args = (params, kv, {}, meta(prefill_meta(
        cfg, ps, np.zeros((1, width), np.int32), one, one, one, CHUNK)),
        sds(jnp.zeros((1, CHUNK), jnp.int32)), {})
    return prefill.lower(*args).compile(), n_pages


def _instructions(hlo: str):
    """(computation, name, result shape, opcode, calls) of each instruction
    of an HLO module's text."""
    comp = None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$", line)
        if not m:
            continue
        rest = m.group(2)
        if rest.startswith("("):          # a tuple shape: up to its ")"
            depth = 0
            for i, ch in enumerate(rest):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    break
            shape, rest = rest[:i + 1], rest[i + 2:]
        else:
            shape, _, rest = rest.partition(" ")
        calls = re.search(r"calls=%([\w.\-]+)", rest)
        yield (comp, m.group(1), shape, rest.split("(", 1)[0],
               calls.group(1) if calls else None)


def _pool_sized_ops(hlo: str, n_pages: int):
    """Ops outside the fusion bodies whose (array) output has the pool's
    page axis, as (name, opcode, the fusion root's opcode or None)."""
    insts = list(_instructions(hlo))
    fused = {c for *_, c in insts if c}
    last = {}                      # each computation's last (root) op
    for comp, _, _, op, _ in insts:
        last[comp] = op
    for comp, name, shape, op, calls in insts:
        if comp in fused or shape.startswith("("):
            continue
        dims = re.match(r"^\w+\[([\d,]*)\]", shape)
        if dims and str(n_pages) in dims.group(1).split(","):
            yield name, op, last.get(calls)


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_step_keeps_pool_in_place(one_chip, cell, program):
    compiled, n_pages = _compile(one_chip, cell, program)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < CELLS[cell][3], (
        f"{cell} {program}: {temp} B of temporaries, over the "
        f"{CELLS[cell][3]:.3g} B bound (a whole-pool copy needs more)")
    moved = [(name, op, root)
             for name, op, root in _pool_sized_ops(compiled.as_text(),
                                                    n_pages)
             if op not in PLUMBING | IN_PLACE
             and not (op == "fusion" and root in IN_PLACE)]
    assert not moved, f"{cell} {program}: pool-sized ops {moved}"
