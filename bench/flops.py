"""Operations and bytes the traffic needs, from the dense family's shapes.

Every count here is of the work a request needs at its own live lengths:
never the engine's table width, the pages it sweeps, its padded rows or
recomputed activations.  A later change that skips dead work raises the
shares computed from these counts; it cannot push them past 100%.

``m`` is a configuration mapping with the published keys (``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``intermediate_size``, ``vocab_size``, ``mlp_gated``).
"""
from __future__ import annotations

ACT_BYTES = 2          # bf16 activations
KV_BYTES = 2           # bf16 KV cache


def dims(m):
    d = m["hidden_size"]
    H = m["num_attention_heads"]
    K = m["num_key_value_heads"]
    D = m.get("head_dim") or d // H
    return d, H, K, D, m["num_hidden_layers"]


def layer_matmul_params(m) -> int:
    """Weights one token multiplies through in one layer (q, k, v, o, MLP)."""
    d, H, K, D, _ = dims(m)
    ff = m["intermediate_size"]
    attn = d * H * D + 2 * d * K * D + H * D * d
    mlp = (3 if m["mlp_gated"] else 2) * d * ff
    return attn + mlp


def head_flops(m) -> int:
    """LM head for one token's logits."""
    return 2 * m["hidden_size"] * m["vocab_size"]


def attn_flops(m, start: int, n: int) -> int:
    """Causal attention (scores and values) of ``n`` queries at positions
    ``start .. start+n-1``, each over its own ``pos+1`` keys, all layers."""
    _, H, _, D, L = dims(m)
    keys = n * start + n * (n + 1) // 2
    return 4 * H * D * keys * L


def attn_bytes(m, start: int, n: int) -> int:
    """Bytes causal attention must move for that chunk: K and V of the
    ``start+n`` keys once, plus queries in and outputs out, all layers."""
    _, H, K, D, L = dims(m)
    return L * ((start + n) * 2 * K * D * KV_BYTES + 2 * n * H * D * ACT_BYTES)


def model_flops(m, start: int, n: int, logits: int) -> int:
    """Forward FLOPs of ``n`` tokens at ``start``: matmuls at 2 x params,
    attention at the actual context, and the LM head for ``logits`` tokens."""
    L = m["num_hidden_layers"]
    return (2 * layer_matmul_params(m) * L * n + attn_flops(m, start, n)
            + head_flops(m) * logits)


def decode_flops(m, ctx: int) -> int:
    """One decoded token whose query sits at position ``ctx - 1``."""
    return model_flops(m, ctx - 1, 1, 1)


def decode_attn(m, ctx: int):
    """(FLOPs, bytes) of one decoded token's attention over ``ctx`` keys."""
    return attn_flops(m, ctx - 1, 1), attn_bytes(m, ctx - 1, 1)


def roofline_s(flops: float, nbytes: float, pk: dict):
    """Least time on the chip and which bound sets it."""
    tc = flops / pk["bf16_flops"]
    tm = nbytes / pk["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
