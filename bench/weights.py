"""Seeded weights of the dense family, made on the device in one jitted call.

The benchmark makes the weights itself, in the type they are served in
(bf16), and hands the same tree to the system under test and to the plain
reference; nothing the program makes is read back as a weight.  The tree
follows the program's parameter layout (stacked ``[L, ...]`` blocks; the
vocabulary padded to a multiple of 256), which ``check_layout`` compares
with the program's own shapes before any run.  Rows of the padded
vocabulary are zero, as when a published checkpoint is loaded into the
padded table.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one wider than 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def padded_vocab(m) -> int:
    return -(-m["vocab_size"] // 256) * 256


def _norm(m, d):
    if m["norm"] == "layernorm":
        return {"scale": ("norm", (d,)), "bias": ("bias", (d,))}
    return {"scale": ("norm", (d,))}


def layout(m):
    """{path: (kind, shape)} of every leaf, as a nested dict."""
    d, L = m["hidden_size"], m["num_hidden_layers"]
    H, K = m["num_attention_heads"], m["num_key_value_heads"]
    D = m.get("head_dim") or d // H
    ff, V = m["intermediate_size"], padded_vocab(m)
    attn = {"wq": ("kernel", (L, d, H, D)), "wk": ("kernel", (L, d, K, D)),
            "wv": ("kernel", (L, d, K, D)), "wo": ("kernel", (L, H, D, d))}
    if m["attention_bias"]:
        attn.update(bq=("bias", (L, H, D)), bk=("bias", (L, K, D)),
                    bv=("bias", (L, K, D)))
    mlp = {"up": ("kernel", (L, d, ff)), "down": ("kernel", (L, ff, d))}
    if m["mlp_gated"]:
        mlp["gate"] = ("kernel", (L, d, ff))
    stack = lambda t: {k: (kind, (L,) + s) for k, (kind, s) in t.items()}
    embed = {"tok": ("embed", (V, d))}
    if not m["tie_word_embeddings"]:
        embed["lm_head"] = ("head", (d, V))
    return {"embed": embed, "final_norm": _norm(m, d),
            "blocks": {"ln1": stack(_norm(m, d)), "attn": attn,
                       "ln2": stack(_norm(m, d)), "mlp": mlp}}


def _leaf(key, kind, shape, vocab):
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "kernel":        # fan-in: every dim but the layer and the last
        fan = 1
        for s in shape[1:-1]:
            fan *= s
        x = x / fan ** 0.5
    elif kind == "norm":
        x = 1.0 + 0.1 * x
    elif kind == "bias":
        x = 0.1 * x
    elif kind == "embed":
        x = 0.02 * x
        x = jnp.where(jnp.arange(shape[0])[:, None] < vocab, x, 0.0)
    elif kind == "head":
        x = x / shape[0] ** 0.5
        x = jnp.where(jnp.arange(shape[1])[None, :] < vocab, x, 0.0)
    return x.astype(jnp.bfloat16)


def make(m, seed: int, sharding=None):
    """The whole tree, on the device, in one jitted call."""
    spec = layout(m)

    def build(key):
        def go(path, leaf):
            kind, shape = leaf
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) % 2**31)
            return _leaf(k, kind, shape, m["vocab_size"])
        return {a: {b: (go(f"{a}/{b}", v) if isinstance(v, tuple) else
                        {c: go(f"{a}/{b}/{c}", w) for c, w in v.items()})
                    for b, v in t.items()} for a, t in spec.items()}

    return jax.jit(build, out_shardings=sharding)(seed_key(seed))


def check_layout(m, program_tree) -> None:
    """Raise if the program expects another tree than ``layout`` makes."""
    mine = jax.tree.map(lambda kv: tuple(kv[1]), layout(m),
                        is_leaf=lambda x: isinstance(x, tuple) and
                        isinstance(x[0], str))
    theirs = jax.tree.map(lambda a: tuple(a.shape), program_tree)
    if mine != theirs:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"bench   {mine}\nprogram {theirs}")
