"""The closed loop of ``closed_backlog`` (its rounds, order and first-round
stagger), driven by the latent-attention MoE family's serving driver."""
from __future__ import annotations

from bench.generators.closed_backlog import (  # noqa: F401
    ORDER, WAIT_FIRST, prompt_lengths, streams)

DRIVER = "serve_mla_moe"
