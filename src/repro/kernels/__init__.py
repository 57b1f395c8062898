# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Shared Pallas-TPU helper: the interpret-mode rule.

Every kernel package in this tree (``flash_attention``, ``rbm_cd``,
``paged_attention``, ``ragged_prefill``, ``grouped_matmul``) follows the same shape: ``kernel.py`` holds the
``pallas_call`` body (``grouped_matmul``'s is Megablox's ``gmm``), ``ops.py`` the jit'd public wrapper.  The wrappers
share one backend rule, hosted here: on CPU (this container, CI) the kernel
body executes in Pallas interpret mode — bit-accurate to the TPU lowering's
semantics — and on TPU the same call lowers to Mosaic.
"""
from __future__ import annotations

from typing import Optional

import jax


def on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def default_interpret(interpret: Optional[bool]) -> bool:
    """The one interpret-mode rule every kernel wrapper applies: an explicit
    caller choice wins; otherwise interpret exactly when jax has no TPU/GPU
    backend to compile for."""
    return on_cpu() if interpret is None else interpret
