"""Grouped expert matmul (Pallas TPU, interpret on CPU): the dropless MoE
layer's one device op over the live (token, expert) pairs."""
from .ops import expert_matmul

__all__ = ["expert_matmul"]
