"""Divisibility-aware sharding rules.

Rather than hand-wiring a PartitionSpec per tensor per arch, each module asks for a
spec via *logical axes* (e.g. ``("embed", "heads")``); the resolver maps logical axes
to mesh axes and silently drops any assignment that does not divide evenly (e.g.
qwen2's 14 heads over a 16-way model axis -> replicated heads, sharded elsewhere).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# logical axis -> preferred mesh axis (in priority order)
LOGICAL_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("dp",),            # dp is the compound data axis (pod+data)
    "seq": (),
    "seq_sp": ("model",),
    "embed": (),                 # d_model is replicated by default (TP on other dims)
    "embed_tp": ("model",),      # d_model sharded (used as fallback / ZeRO dim)
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    "ff": ("model",),
    "experts": ("model",),
    "lora": (),
    "state": (),
    "rnn": ("model",),
    "conv": (),
    "layers": (),
    "zero": ("data",),           # optimizer-state sharding dim (ZeRO-1)
}


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The compound data-parallel axes present in this mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def resolve(
    logical: Sequence[Optional[str]],
    shape: Sequence[int],
    mesh: Mesh,
    *,
    used: Optional[set] = None,
) -> P:
    """Map logical axes to a PartitionSpec, dropping non-dividing assignments.

    Each mesh axis is used at most once per tensor.
    """
    sizes = axis_sizes(mesh)
    taken = set() if used is None else used
    out = []
    for ax, dim in zip(logical, shape):
        assigned = None
        if ax is not None:
            candidates = LOGICAL_RULES.get(ax, ())
            for cand in candidates:
                if cand == "dp":
                    dps = dp_axes(mesh)
                    total = 1
                    for a in dps:
                        total *= sizes[a]
                    if dps and dim % total == 0 and not (set(dps) & taken):
                        assigned = dps if len(dps) > 1 else dps[0]
                        taken.update(dps)
                        break
                elif cand in sizes and dim % sizes[cand] == 0 and cand not in taken:
                    assigned = cand
                    taken.add(cand)
                    break
        out.append(assigned)
    return P(*out)


def named(mesh: Mesh, logical: Sequence[Optional[str]], shape: Sequence[int]) -> NamedSharding:
    return NamedSharding(mesh, resolve(logical, shape, mesh))


def constrain(x, mesh: Mesh, logical: Sequence[Optional[str]]):
    """Apply a with_sharding_constraint using logical axes (inside jit)."""
    spec = resolve(logical, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def tree_specs(defs, mesh: Mesh):
    """defs: pytree of (shape, dtype, logical) -> pytree of NamedSharding."""
    return jax.tree.map(
        lambda d: NamedSharding(mesh, resolve(d[2], d[0], mesh)),
        defs,
        is_leaf=lambda d: isinstance(d, tuple) and len(d) == 3 and isinstance(d[0], tuple),
    )
