"""Plain float32 forward of the dense decoder family, in ``jax.numpy``.

It imports nothing of the program.  It follows the published description
of the configuration mapping ``m``: RMSNorm or LayerNorm at the stated
epsilon, rotary embedding on the first ``partial_rotary_factor`` of each
head (rotate-half form, base ``rope_theta``), grouped-query attention with
causal masking, a SiLU-gated or squared-ReLU MLP, and the LM head over the
published vocabulary only.  It runs under ``default_matmul_precision
("highest")``, one layer at a time inside a ``lax.scan`` so that only one
layer's weights are widened to float32 at once.

``mode="int8"`` is the control: every matmul takes int8 operands
(weights per output channel, activations per token, and in attention
queries and keys per token and head, values per channel, probabilities
per query row; symmetric absmax), the precision below the bf16 the
configuration states.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s).clip(-127, 127) * s


def _mm(x, w, spec, mode, w_in_axes):
    """einsum with float32 operands, or int8-rounded ones for the control.
    ``w_in_axes`` are the weight's contracted axes (its input channels)."""
    w = w.astype(jnp.float32)
    if mode == "int8":
        x = _q8(x, -1)
        w = _q8(w, w_in_axes)
    return jnp.einsum(spec, x, w)


def _norm(m, p, x):
    eps = m.get("rms_norm_eps", m.get("norm_eps"))
    if m["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        y = (x - mu) / jnp.sqrt(var + eps)
        return (y * p["scale"].astype(jnp.float32)
                + p["bias"].astype(jnp.float32))
    y = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)
    return y * p["scale"].astype(jnp.float32)


def _rope(x, pos, m):
    D = x.shape[-1]
    rot = int(D * m.get("partial_rotary_factor", 1.0))
    half = rot // 2
    inv = 1.0 / (m["rope_theta"] ** (np.arange(half, dtype=np.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * inv           # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _layer(m, mode, S, x, p):
    H, K = m["num_attention_heads"], m["num_key_value_heads"]
    G = H // K
    pos = jnp.arange(S)
    h = _norm(m, p["ln1"], x)
    q = _mm(h, p["attn"]["wq"], "sd,dhe->she", mode, 0)
    k = _mm(h, p["attn"]["wk"], "sd,dhe->she", mode, 0)
    v = _mm(h, p["attn"]["wv"], "sd,dhe->she", mode, 0)
    if "bq" in p["attn"]:
        q = q + p["attn"]["bq"].astype(jnp.float32)
        k = k + p["attn"]["bk"].astype(jnp.float32)
        v = v + p["attn"]["bv"].astype(jnp.float32)
    q, k = _rope(q, pos, m), _rope(k, pos, m)
    D = q.shape[-1]
    q = q.reshape(S, K, G, D)
    if mode == "int8":            # keys over features, values over tokens
        k, v = _q8(k, -1), _q8(v, 0)
    outs = []
    for s0 in range(0, S, Q_BLOCK):               # query blocks bound memory
        qb = q[s0:s0 + Q_BLOCK]
        if mode == "int8":
            qb = _q8(qb, -1)
        sc = jnp.einsum("qkgd,skd->kgqs", qb, k) / math.sqrt(D)
        mask = pos[None, :] <= (s0 + jnp.arange(qb.shape[0]))[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        a = jax.nn.softmax(sc, axis=-1)
        if mode == "int8":
            a = _q8(a, -1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", a, v))
    o = jnp.concatenate(outs, 0).reshape(S, H, D)
    x = x + _mm(o, p["attn"]["wo"], "she,hed->sd", mode, (0, 1))
    h = _norm(m, p["ln2"], x)
    up = _mm(h, p["mlp"]["up"], "sd,df->sf", mode, 0)
    if m["mlp_gated"]:
        g = _mm(h, p["mlp"]["gate"], "sd,df->sf", mode, 0)
        a = jax.nn.silu(g) * up
    else:
        a = jnp.square(jax.nn.relu(up))
    return x + _mm(a, p["mlp"]["down"], "sf,fd->sd", mode, 0)


@functools.lru_cache(maxsize=None)
def _forward(mjson: str, mode: str, S: int):
    m = json.loads(mjson)
    V = m["vocab_size"]

    def f(params, tokens, out_pos):
        with jax.default_matmul_precision("highest"):
            x = params["embed"]["tok"][tokens].astype(jnp.float32)
            x, _ = jax.lax.scan(
                lambda x, p: (_layer(m, mode, S, x, p), None), x,
                params["blocks"])
            h = _norm(m, params["final_norm"], x[out_pos])
            if m["tie_word_embeddings"]:
                w = params["embed"]["tok"][:V].T
            else:
                w = params["embed"]["lm_head"][:, :V]
            return _mm(h, w, "sd,dv->sv", mode, 0)
    return jax.jit(f)


def logits(m, params, tokens, out_pos, mode="f32"):
    """Float32 logits [len(out_pos), vocab] of one sequence at ``out_pos``.
    The sequence is padded at its end to a multiple of ``Q_BLOCK``, which
    causal masking keeps from every real position."""
    tokens = np.asarray(tokens, np.int32)
    S = -(-len(tokens) // Q_BLOCK) * Q_BLOCK
    padded = np.zeros(S, np.int32)
    padded[:len(tokens)] = tokens
    f = _forward(json.dumps(m, sort_keys=True), mode, S)
    return f(params, jnp.asarray(padded), jnp.asarray(out_pos, jnp.int32))


def served_gaps(m, params, prompt, served, control=False, block=256):
    """Per served token: how far its reference logit lies below the
    reference's best.  With ``control``, the same gap for the token that
    the int8 control puts first at each position.  Positions are taken in
    blocks of ``block`` (the last one padded by repeating its final
    position, so every block has one shape) to keep the vocabulary-wide
    logits small."""
    seq = list(prompt) + list(served[:-1])
    P = len(prompt)
    gaps = []
    for b0 in range(0, len(served), block):
        n = min(block, len(served) - b0)
        idx = np.minimum(np.arange(b0, b0 + block), b0 + n - 1)
        pos = P - 1 + idx
        ref = logits(m, params, seq, pos)
        if control:
            pick = jnp.argmax(logits(m, params, seq, pos, mode="int8"), -1)
        else:
            pick = jnp.asarray(np.asarray(served)[idx], jnp.int32)
        got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        gaps.append(np.asarray(ref.max(-1) - got)[:n])
    return np.concatenate(gaps)
