"""Size a serving configuration by compiling its steps for a described
v5e, with no chip.

    JAX_PLATFORMS=cpu python bench/aot_size.py minitron-4b-serve 2 4 8 16

Compiles the engine's decode step and its largest prefill step (one row,
the chunk bucket, the full table) at each ``max_slots`` given, with the
configuration's own ``ServeConfig`` otherwise, and prints XLA's
``memory_analysis`` per program: the bytes of arguments (weights and the
KV pool among them), temporaries and unaliased outputs.  One JSON line per
program; a program that does not fit is refused by the compiler, and the
refusal is printed too.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

HBM = 16e9


def _compiled_on_tpu():
    """Kernels lower to Mosaic here too (the CPU backend would pick
    interpret mode)."""
    import repro.kernels.paged_attention.ops as pa
    import repro.kernels.ragged_prefill.ops as rp
    for mod in (pa, rp):
        mod.default_interpret = lambda i: False if i is None else i


def _mem(compiled) -> dict:
    ma = compiled.memory_analysis()
    args, out = ma.argument_size_in_bytes, ma.output_size_in_bytes
    alias, temp = ma.alias_size_in_bytes, ma.temp_size_in_bytes
    return {"argument_bytes": args, "temp_bytes": temp,
            "output_bytes": out, "alias_bytes": alias,
            "peak_bytes": args + temp + out - alias}


def serve(conf, slots_list, topo):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding
    from repro.configs import ServeConfig, get_arch
    from repro.models.attn_backend import decode_meta, prefill_meta
    from repro.models.params import abstract_tree
    from repro.models.registry import build_model
    from repro.serving.engine import _paged_steps
    dev = SingleDeviceSharding(topo.devices[0])
    cfg = dataclasses.replace(get_arch(conf["arch"]), remat="none")
    model = build_model(cfg)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev)
    params = jax.tree.map(sds, abstract_tree(model.param_defs()))
    prefill, _, decode, *_ = _paged_steps(cfg, None, "pallas")
    for slots in slots_list:
        scfg = ServeConfig(**dict(conf["serve"], max_slots=slots))
        ps, tw = scfg.page_size, scfg.pages_per_request
        kv = jax.tree.map(sds, abstract_tree(model.paged_cache_defs(
            slots * tw + 1, ps, scfg.kv_dtype)))
        arr = lambda d: {k: sds(jnp.asarray(v)) for k, v in d.items()}
        progs = {
            "decode": (decode, (params, kv, {}, arr(decode_meta(
                cfg, ps, np.zeros((slots, tw), np.int32),
                np.zeros((slots,), np.int32))),
                sds(jnp.zeros((slots,), jnp.int32)))),
            "prefill": (prefill, (params, kv, {}, arr(prefill_meta(
                cfg, ps, np.zeros((1, tw), np.int32),
                np.zeros((1,), np.int32), np.zeros((1,), np.int32),
                np.zeros((1,), np.int32), scfg.chunk_tokens)),
                sds(jnp.zeros((1, scfg.chunk_tokens), jnp.int32)), {}))}
        for name, (fn, args) in progs.items():
            row = {"config": conf["name"], "max_slots": slots,
                   "program": name}
            try:
                row.update(_mem(fn.lower(*args).compile()))
                row["fits"] = row["peak_bytes"] <= HBM
            except Exception as e:          # the compiler's refusal
                row.update(fits=False, error=str(e).splitlines()[0][:300])
            print(json.dumps(row), flush=True)


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    name, sizes = argv[0], [int(x) for x in argv[1:]]
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        conf = json.load(f)
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    _compiled_on_tpu()
    serve(conf, sizes, topo)


if __name__ == "__main__":
    main()
