"""Parameter-definition machinery.

Models declare their parameters as a pytree of ``ParamDef`` leaves (shape, dtype,
logical sharding axes, init rule).  The same tree serves three consumers:

* ``init_tree``      -> real arrays (smoke tests, examples)
* ``abstract_tree``  -> ShapeDtypeStructs with shardings (dry-run: zero allocation)
* ``specs_tree``     -> NamedShardings (jit in/out_shardings)
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from . import shardings


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    dtype: Any = jnp.bfloat16
    init: str = "kernel"      # kernel | embed | zeros | ones | const:<v>

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def _is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _init_leaf(path, d: ParamDef, key) -> jax.Array:
    # a stable digest of the leaf's path (not the per-process salted
    # ``hash``), so every process draws the same weights from one seed
    path_id = zlib.crc32(jax.tree_util.keystr(path).encode()) % (2**31)
    leaf_key = jax.random.fold_in(key, path_id)
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    if d.init.startswith("const:"):
        return jnp.full(d.shape, float(d.init.split(":")[1]), d.dtype)
    if d.init == "embed":
        scale = 0.02
    else:  # kernel: variance scaling on fan-in (all dims but last)
        fan_in = max(1, math.prod(d.shape[:-1]))
        scale = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(leaf_key, d.shape, jnp.float32) * scale).astype(d.dtype)


def init_tree(defs, key) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda p, d: _init_leaf(p, d, key), defs, is_leaf=_is_def
    )


def abstract_tree(defs, mesh: Optional[Mesh] = None) -> Any:
    def mk(d: ParamDef):
        if mesh is None:
            return jax.ShapeDtypeStruct(d.shape, d.dtype)
        sh = NamedSharding(mesh, shardings.resolve(d.logical, d.shape, mesh))
        return jax.ShapeDtypeStruct(d.shape, d.dtype, sharding=sh)
    return jax.tree.map(mk, defs, is_leaf=_is_def)


def specs_tree(defs, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda d: NamedSharding(mesh, shardings.resolve(d.logical, d.shape, mesh)),
        defs,
        is_leaf=_is_def,
    )


def stack_defs(d: ParamDef, n: int) -> ParamDef:
    """Stack a per-layer def into a scan-friendly [n, ...] def."""
    return ParamDef((n,) + d.shape, ("layers",) + d.logical, d.dtype, d.init)


def stack_tree(defs, n: int) -> Any:
    return jax.tree.map(lambda d: stack_defs(d, n), defs, is_leaf=_is_def)


def param_bytes(defs) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=_is_def)
    return sum(math.prod(d.shape) * jnp.dtype(d.dtype).itemsize for d in leaves)


def sharded_bytes(defs, mesh: Mesh) -> int:
    """Per-device bytes of a defs tree under its resolved shardings."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    total = 0
    for d in jax.tree.leaves(defs, is_leaf=_is_def):
        spec = shardings.resolve(d.logical, d.shape, mesh)
        shards = 1
        for entry in spec:
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                shards *= sizes[a]
        total += math.prod(d.shape) * jnp.dtype(d.dtype).itemsize // shards
    return total


def param_count(defs) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=_is_def)
    return sum(math.prod(d.shape) for d in leaves)
