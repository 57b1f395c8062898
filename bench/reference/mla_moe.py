"""Plain float32 forward of DeepSeek-V2 (latent attention, group-limited
mixture of experts), in ``jax.numpy``.

It imports nothing of the program.  It follows the published description
(arXiv:2405.04434 and the model's ``modeling_deepseek.py``) of the
configuration mapping ``m``: RMSNorm at ``rms_norm_eps`` (the block norms,
the query and latent norms, the final norm); multi-head latent attention
with the query through its rank-``q_lora_rank`` compression, the keys and
values materialized per head from the rank-``kv_lora_rank`` latent
(``wkv_b``), a decoupled ``qk_rope_head_dim`` rotary key shared by all
heads, YaRN rotary frequencies (``rope_scaling``: interpolated below the
``beta_fast``/``beta_slow`` correction range, extrapolated above it, a
linear ramp between) and the softmax scale ``(nope + rope) ** -0.5``
times ``mscale(factor, mscale_all_dim) ** 2``; the first
``first_k_dense_replace`` layers with a SiLU-gated MLP of
``intermediate_size``; then MoE layers: softmax scores over all
``routed_experts_per_layer`` experts, the ``topk_group`` of ``n_group``
groups with the highest best score kept, the top ``num_experts_per_tok``
scores as gate weights, unnormalized and times ``routed_scaling_factor``
(``norm_topk_prob`` false), SiLU-gated experts of
``moe_intermediate_size``, plus ``n_shared_experts`` shared experts as one
MLP that every token runs; the untied LM head over the published
vocabulary.  It runs under ``default_matmul_precision("highest")``, one
layer at a time inside a ``lax.scan``, heads in groups and experts one at a
time, so that little is widened to float32 at once.

Departures, each a fixed relabelling of random weights or the
deployment's cut, none a change of the mathematics:

- the rotary pairs the two halves of the ``qk_rope_head_dim`` lanes
  (rotate-half); the published code pairs interleaved lanes and
  de-interleaves them before rotating, a fixed permutation of the rope
  columns of ``wq_b`` and ``wkv_a``;
- the expert share: ``deployment`` names the experts this chip holds
  (``held_experts_first``, ``n_routed_experts`` of them).  Routing is over
  all ``routed_experts_per_layer`` experts, and only the pairs whose expert
  is held add their part; what the absent chips' experts would add is left
  out, as in the program;
- the cos/sin of the rotary are unscaled: ``mscale / mscale_all_dim`` is 1
  for this model (0.707 / 0.707).

``mode="int8"`` is the control: every matmul takes int8 operands (weights
per output channel, activations per token, attention queries and keys per
token and head, values per channel, probabilities per query row, the
router's too; symmetric absmax), the precision below the bf16 the
configuration states.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense import _mm, _q8

SEQ_MULTIPLE = 512     # sequences pad to this many tokens (fewer compiles)
Q_BLOCK = 128          # query rows of one score block
HEAD_GROUP = 16        # heads whose K/V are materialized at once
TOKEN_BLOCK = 512      # tokens of one dense-MLP block
VOCAB_BLOCK = 12800    # logits columns of one head block
ROW_BLOCK = 256        # output positions of one head block


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(m) -> np.ndarray:
    """The published YaRN frequencies (``DeepseekV2YarnRotaryEmbedding``)."""
    dim, base = m["qk_rope_head_dim"], m["rope_theta"]
    rs = m["rope_scaling"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / rs["factor"]

    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def softmax_scale(m) -> float:
    rs = m["rope_scaling"]
    s = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    f = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return s * f * f


def _rms(m, x, scale):
    y = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + m["rms_norm_eps"])
    return y * scale.astype(jnp.float32)


def _rope(x, pos, inv):
    """x [S, h, R]: rotate the halves by position."""
    half = x.shape[-1] // 2
    ang = pos[:, None].astype(jnp.float32) * inv             # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mlp(mode, h, gate, up, down):
    g = _mm(h, gate, "sd,df->sf", mode, 0)
    u = _mm(h, up, "sd,df->sf", mode, 0)
    return _mm(jax.nn.silu(g) * u, down, "sf,fd->sd", mode, 0)


def _blocked(fn, x, block):
    """``fn`` over token blocks of x [S, D] (S a ``block`` multiple)."""
    S = x.shape[0]
    y = jax.lax.map(fn, x.reshape(S // block, block, x.shape[-1]))
    return y.reshape(S, -1)


def _attention(m, mode, S, h, p):
    H = m["num_attention_heads"]
    nope, R = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    L, vd = m["kv_lora_rank"], m["v_head_dim"]
    G = min(HEAD_GROUP, H)
    pos = jnp.arange(S)
    inv = jnp.asarray(inv_freq(m))
    scale = softmax_scale(m)
    cq = _rms(m, _mm(h, p["wq_a"], "sd,dl->sl", mode, 0), p["q_norm"])
    kv_a = _mm(h, p["wkv_a"], "sd,dl->sl", mode, 0)
    ckv = _rms(m, kv_a[:, :L], p["kv_norm"])
    k_pe = _rope(kv_a[:, None, L:], pos, inv)                 # [S, 1, R]

    def group(_, w):
        wq, wkv = w                                           # [l, G, e]
        q = _mm(cq, wq, "sl,lhe->she", mode, 0)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, inv)],
                            -1)                               # [S, G, E]
        kv = _mm(ckv, wkv, "sl,lhe->she", mode, 0)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (S, G, R))], -1)
        v = kv[..., nope:]
        if mode == "int8":        # keys over features, values over tokens
            k, v = _q8(k, -1), _q8(v, 0)

        def qblock(_, i):
            qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
            if mode == "int8":
                qb = _q8(qb, -1)
            sc = jnp.einsum("qhe,she->hqs", qb, k) * scale
            rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
            sc = jnp.where(pos[None, None, :] <= rows[None, :, None], sc,
                           -jnp.inf)
            a = jax.nn.softmax(sc, axis=-1)
            if mode == "int8":
                a = _q8(a, -1)
            return None, jnp.einsum("hqs,shv->qhv", a, v)

        _, o = jax.lax.scan(qblock, None, jnp.arange(S // Q_BLOCK))
        return None, o.reshape(S, G, vd)

    n_hg = H // G
    split = lambda w: jnp.moveaxis(
        w.reshape(w.shape[0], n_hg, G, w.shape[-1]), 1, 0)
    _, o = jax.lax.scan(group, None, (split(p["wq_b"]), split(p["wkv_b"])))
    o = jnp.moveaxis(o, 0, 1).reshape(S, H, vd)               # [S, H, vd]
    return _mm(o, p["wo"], "she,hed->sd", mode, (0, 1))


def _route(m, dep, mode, h, router):
    """Gate weights and expert ids [S, k] over all the layer's experts."""
    n, k = m["n_group"], m["num_experts_per_tok"]
    s = jax.nn.softmax(_mm(h, router, "sd,de->se", mode, 0), axis=-1)
    E = dep["routed_experts_per_layer"]
    best = s.reshape(-1, n, E // n).max(-1)                   # [S, n_group]
    groups = jax.lax.top_k(best, m["topk_group"])[1]
    keep = jnp.zeros(best.shape, bool).at[
        jnp.arange(best.shape[0])[:, None], groups].set(True)
    keep = jnp.repeat(keep, E // n, axis=1)
    w, idx = jax.lax.top_k(jnp.where(keep, s, 0.0), k)
    if m["norm_topk_prob"]:
        return w / w.sum(-1, keepdims=True), idx
    return w * m["routed_scaling_factor"], idx


def _moe(m, dep, mode, h, p):
    w, idx = _route(m, dep, mode, h, p["router"])
    first = dep["held_experts_first"]

    def expert(acc, e):
        i, gate, up, down = e
        we = jnp.where(idx == first + i, w, 0.0).sum(-1)      # [S]
        return acc + _mlp(mode, h, gate, up, down) * we[:, None], None

    held = jnp.arange(p["gate"].shape[0])
    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (held, p["gate"], p["up"], p["down"]))
    return out + _mlp(mode, h, p["shared_gate"], p["shared_up"],
                      p["shared_down"])


def _layer(m, dep, mode, S, x, p, moe: bool):
    x = x + _attention(m, mode, S, _rms(m, x, p["ln1"]["scale"]), p["attn"])
    h = _rms(m, x, p["ln2"]["scale"])
    if moe:
        return x + _moe(m, dep, mode, h, p["moe"])
    mlp = p["mlp"]
    return x + _blocked(lambda hb: _mlp(mode, hb, mlp["gate"], mlp["up"],
                                        mlp["down"]), h, TOKEN_BLOCK)


@functools.lru_cache(maxsize=None)
def _hidden(mjson: str, djson: str, mode: str, S: int):
    m, dep = json.loads(mjson), json.loads(djson)

    def f(params, tokens, out_pos):
        with jax.default_matmul_precision("highest"):
            x = params["embed"]["tok"][tokens].astype(jnp.float32)
            for stack, moe in (("dense_blocks", False), ("blocks", True)):
                x, _ = jax.lax.scan(
                    lambda x, p: (_layer(m, dep, mode, S, x, p, moe), None),
                    x, params[stack])
            return _rms(m, x[out_pos], params["final_norm"]["scale"])
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _head(V: int, mode: str):
    """(row max, logits at ``picks``, row argmax) of h @ w[:, :V], over
    vocabulary blocks."""
    def f(h, w, picks):
        with jax.default_matmul_precision("highest"):
            n_blk = -(-V // VOCAB_BLOCK)
            w = jnp.pad(w[:, :V], ((0, 0), (0, n_blk * VOCAB_BLOCK - V)))
            w = jnp.moveaxis(w.reshape(w.shape[0], n_blk, VOCAB_BLOCK), 1, 0)

            def blk(carry, wi):
                best, at, arg = carry
                i, wb = wi
                lg = _mm(h, wb, "sd,dv->sv", mode, 0)
                col = i * VOCAB_BLOCK + jnp.arange(VOCAB_BLOCK)
                lg = jnp.where(col[None, :] < V, lg, -jnp.inf)
                bm, ba = lg.max(-1), i * VOCAB_BLOCK + lg.argmax(-1)
                at = at + jnp.where(col[None, :] == picks[:, None], lg,
                                    0.0).sum(-1)
                return (jnp.maximum(best, bm), at,
                        jnp.where(bm > best, ba, arg)), None

            n = h.shape[0]
            init = (jnp.full((n,), -jnp.inf), jnp.zeros((n,)),
                    jnp.zeros((n,), jnp.int32))
            (best, at, arg), _ = jax.lax.scan(
                blk, init, (jnp.arange(n_blk), w))
            return best, at, arg
    return jax.jit(f)


def hidden(m, dep, params, tokens, out_pos, mode="f32"):
    """Final-normed float32 hidden states at ``out_pos`` of one sequence,
    padded at its end to a multiple of ``SEQ_MULTIPLE`` (causal masking
    keeps the padding from every real position)."""
    tokens = np.asarray(tokens, np.int32)
    S = -(-len(tokens) // SEQ_MULTIPLE) * SEQ_MULTIPLE
    padded = np.zeros(S, np.int32)
    padded[:len(tokens)] = tokens
    f = _hidden(json.dumps(m, sort_keys=True), json.dumps(dep, sort_keys=True),
                mode, S)
    return f(params, jnp.asarray(padded), jnp.asarray(out_pos, jnp.int32))


def served_gaps(m, dep, params, prompt, served, control=False):
    """Per served token: how far its reference logit lies below the
    reference's best.  With ``control``, the same gap for the token that
    the int8 control puts first at each position.  One forward per
    sequence; the head runs ``ROW_BLOCK`` positions at a time (the last
    block padded by repeating its final position)."""
    seq = list(prompt) + list(served[:-1])
    P, n = len(prompt), len(served)
    rows = -(-n // ROW_BLOCK) * ROW_BLOCK
    idx = np.minimum(np.arange(rows), n - 1)
    pos = P - 1 + idx
    V = m["vocab_size"]
    w = params["embed"]["lm_head"]
    h = hidden(m, dep, params, seq, pos)
    hc = hidden(m, dep, params, seq, pos, mode="int8") if control else None
    picks = np.asarray(served, np.int32)[idx]
    gaps = []
    for b in range(0, rows, ROW_BLOCK):
        sl = slice(b, b + ROW_BLOCK)
        pick = jnp.asarray(picks[sl])
        if control:
            pick = _head(V, "int8")(hc[sl], w, pick)[2]
        best, at, _ = _head(V, "f32")(h[sl], w, pick)
        gaps.append(np.asarray(best - at))
    return np.concatenate(gaps)[:n]
