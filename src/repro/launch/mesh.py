"""Production mesh factory (TPU v5e pods).

Single pod: (data=16, model=16) = 256 chips.  Multi-pod adds a leading ``pod``
axis: (pod=2, data=16, model=16) = 512 chips.  A function (not a module-level
constant) so importing never touches jax device state.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 1):
    """Small mesh for tests/examples on whatever devices exist."""
    axes, shape = [], []
    if pod > 1:
        axes.append("pod"); shape.append(pod)
    axes.append("data"); shape.append(data)
    if model > 1:
        axes.append("model"); shape.append(model)
    return _make_mesh(shape, axes)


# TPU v5e hardware constants (per chip)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link
