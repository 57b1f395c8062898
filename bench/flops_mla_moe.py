"""Operations and bytes the traffic needs, from the latent-attention MoE
family's shapes (DeepSeek-V2's published keys in ``m``).

As in ``bench/flops.py``, every count is of the work a request needs at its
own live lengths and of the expert pairs it routes to this chip's experts:
never the engine's table width, padded rows, padding tokens, a grouped
matmul's row tiles or recomputed activations.  So no share computed from
them can pass 100%.

Latent attention is counted as the program runs it: absorbed at decode (a
query's scores against the rank-``kv_lora_rank`` latent and the rope key,
its context in latent space), materialized at prefill (per-head K and V
from the latent, once per key).  The projections cost the same either way:
``wkv_b``'s columns multiply every token once, as ``w_uk``/``w_uv`` on the
query side at decode and on the key side at prefill.  The held experts'
work comes from the engine's counts of the pairs it routed to them
(``moe_held`` on each step span), their weight bytes from the experts
those pairs touched (``moe_touched``).
"""
from __future__ import annotations

from bench.flops import head_flops
from bench.records import ENGINE_PID

W_BYTES = 2            # bf16 weights
ACT_BYTES = 2          # bf16 activations
KV_BYTES = 2           # bf16 latent cache


def layers(m):
    """(leading dense layers, MoE layers)."""
    k = m["first_k_dense_replace"]
    return k, m["num_hidden_layers"] - k


def attn_params(m) -> int:
    """Weights one token multiplies through in one layer's latent
    attention: the query's two projections, the latent's, ``wkv_b`` and
    the output projection."""
    d, H = m["hidden_size"], m["num_attention_heads"]
    nope, R = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    L, ql, vd = m["kv_lora_rank"], m["q_lora_rank"], m["v_head_dim"]
    return (d * ql + ql * H * (nope + R) + d * (L + R)
            + L * H * (nope + vd) + H * vd * d)


def expert_params(m) -> int:
    """Weights of one routed expert (gate, up, down)."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def token_flops(m) -> int:
    """FLOPs of one token through every layer, outside attention's context
    and the routed experts: projections, the dense MLP and the shared
    experts.  The router (0.3% of a token's work) is left out, so the
    count stays below the work done."""
    d = m["hidden_size"]
    dense, moe = layers(m)
    shared = 3 * d * m["moe_intermediate_size"] * m["n_shared_experts"]
    return 2 * (attn_params(m) * (dense + moe)
                + 3 * d * m["intermediate_size"] * dense + shared * moe)


def decode_attn(m, ctx: int):
    """(FLOPs, bytes) of one decoded token's absorbed latent attention over
    ``ctx`` keys, all layers: scores against latent and rope key, context
    in latent space; the latent pages read once, the query in and the
    latent context out."""
    H, L, R = m["num_attention_heads"], m["kv_lora_rank"], \
        m["qk_rope_head_dim"]
    n = m["num_hidden_layers"]
    f = n * ctx * H * 2 * ((L + R) + L)
    b = n * (ctx * (L + R) * KV_BYTES + H * (2 * L + R) * ACT_BYTES)
    return f, b


def prefill_attn_flops(m, start: int, n: int) -> int:
    """Materialized attention of ``n`` queries at ``start ..``, each over
    its own causal keys: scores over ``nope + rope``, values of
    ``v_head_dim``, all layers."""
    H = m["num_attention_heads"]
    E = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    keys = n * start + n * (n + 1) // 2
    return 2 * H * (E + m["v_head_dim"]) * keys * m["num_hidden_layers"]


def expert_work(m, pairs: int, touched: int):
    """(FLOPs, bytes) of the held experts' grouped matmuls: 6·D·F per pair;
    the touched experts' weights once, each pair's activation in and out."""
    d = m["hidden_size"]
    f = 2 * expert_params(m) * pairs
    b = touched * expert_params(m) * W_BYTES + pairs * 2 * d * ACT_BYTES
    return f, b


def step_args(rec, kinds):
    """Args of the engine steps of these kinds that started in the traced
    window (the ``moe_*`` counts ride on them)."""
    tr = rec["tracer"]
    out = []
    for ev in tr.events:
        if ev.get("pid") == ENGINE_PID and ev.get("tid") == 0 \
                and ev.get("ph") == "X" and ev["name"] in kinds:
            s = tr.t0 + ev["ts"] * 1e-6
            if rec["t0"] <= s < rec["t1"]:
                out.append(ev.get("args", {}))
    return out


def moe_counts(rec, kinds):
    """(held pairs, held experts touched) summed over the window's steps of
    these kinds; None when the program's steps carry no such counts."""
    args = [a for a in step_args(rec, kinds) if "moe_held" in a]
    if not args:
        return None
    return (sum(a["moe_held"] for a in args),
            sum(a["moe_touched"] for a in args))


def model_flops(m, start: int, n: int, logits: int) -> int:
    """Prefill FLOPs of ``n`` tokens at ``start`` outside the routed
    experts, with the LM head for ``logits`` tokens."""
    return (token_flops(m) * n + prefill_attn_flops(m, start, n)
            + head_flops(m) * logits)


def decode_flops(m, ctx: int) -> int:
    """One decoded token over ``ctx`` keys, outside the routed experts."""
    return token_flops(m) + decode_attn(m, ctx)[0] + head_flops(m)
