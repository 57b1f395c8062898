"""Seeded weights of the latent-attention MoE family (DeepSeek-V2), made on
the device in one jitted call, as ``bench/weights.py`` makes the dense
family's.

The tree follows the program's parameter layout: stacked
``dense_blocks`` (the leading dense layers) and ``blocks`` (the MoE
layers), latent attention's projections, the router over every routed
expert of the layer (float32, as the program keeps it), and the weights of
the experts this chip holds (``n_routed_experts`` of the configuration,
the deployment's share).  ``check_layout`` compares it with the program's
own shapes and types before any run.  Each kernel is scaled by the fan-in
of the axes it contracts.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from bench.weights import _leaf, padded_vocab, seed_key


def layout(m, dep):
    """{path: (kind, shape, contracted axes, dtype)} as a nested dict."""
    d, H = m["hidden_size"], m["num_attention_heads"]
    nope, R = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    L, ql, vd = m["kv_lora_rank"], m["q_lora_rank"], m["v_head_dim"]
    F, Fs = m["moe_intermediate_size"], \
        m["moe_intermediate_size"] * m["n_shared_experts"]
    E, V = m["n_routed_experts"], padded_vocab(m)
    bf, f32 = jnp.bfloat16, jnp.float32

    def kernel(*shape, axes=(0,), dtype=bf):
        return ("kernel", shape, axes, dtype)

    def norm(n):
        return {"scale": ("norm", (n,), (), bf)}

    attn = {"wq_a": kernel(d, ql), "q_norm": ("norm", (ql,), (), bf),
            "wq_b": kernel(ql, H, nope + R),
            "wkv_a": kernel(d, L + R), "kv_norm": ("norm", (L,), (), bf),
            "wkv_b": kernel(L, H, nope + vd), "wo": kernel(H, vd, d,
                                                          axes=(0, 1))}

    def block(ffn):
        return {"ln1": norm(d), "attn": attn, "ln2": norm(d), **ffn}

    dense = {"mlp": {"gate": kernel(d, m["intermediate_size"]),
                     "up": kernel(d, m["intermediate_size"]),
                     "down": kernel(m["intermediate_size"], d)}}
    moe = {"moe": {"router": kernel(d, dep["routed_experts_per_layer"],
                                    dtype=f32),
                   "gate": kernel(E, d, F, axes=(1,)),
                   "up": kernel(E, d, F, axes=(1,)),
                   "down": kernel(E, F, d, axes=(1,)),
                   "shared_gate": kernel(d, Fs), "shared_up": kernel(d, Fs),
                   "shared_down": kernel(Fs, d)}}

    def stack(tree, n):
        if isinstance(tree, tuple):
            kind, shape, axes, dt = tree
            return (kind, (n,) + shape, tuple(a + 1 for a in axes), dt)
        return {k: stack(v, n) for k, v in tree.items()}

    k = m["first_k_dense_replace"]
    return {"embed": {"tok": ("embed", (V, d), (), bf),
                      "lm_head": ("head", (d, V), (), bf)},
            "final_norm": norm(d),
            "dense_blocks": stack(block(dense), k),
            "blocks": stack(block(moe), m["num_hidden_layers"] - k)}


def _is_leaf(x):
    return isinstance(x, tuple) and isinstance(x[0], str)


def make(m, dep, seed: int):
    """The whole tree, on the device, in one jitted call."""
    spec = layout(m, dep)

    def build(key):
        def go(path, leaf):
            kind, shape, axes, dt = leaf
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) % 2**31)
            if kind != "kernel":
                return _leaf(k, kind, shape, m["vocab_size"])
            fan = 1
            for a in axes:
                fan *= shape[a]
            x = jax.random.normal(k, shape, jnp.float32) / fan ** 0.5
            return x.astype(dt)
        paths = jax.tree_util.tree_flatten_with_path(spec, is_leaf=_is_leaf)
        leaves = [go("/".join(str(p.key) for p in path), leaf)
                  for path, leaf in paths[0]]
        return jax.tree_util.tree_unflatten(paths[1], leaves)

    return jax.jit(build)(seed_key(seed))


def check_layout(m, dep, program_tree) -> None:
    """Raise if the program expects another tree than ``layout`` makes."""
    mine = jax.tree.map(lambda leaf: (tuple(leaf[1]), jnp.dtype(leaf[3])),
                        layout(m, dep), is_leaf=_is_leaf)
    theirs = jax.tree.map(lambda a: (tuple(a.shape), jnp.dtype(a.dtype)),
                          program_tree)
    if mine != theirs:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"bench   {mine}\nprogram {theirs}")
