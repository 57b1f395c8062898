"""Mixture-of-Experts: the serving layer (dropless, over the held experts) and
the training layer (token-choice top-k with per-expert capacity).

Routing (``route``) is shared: fp32 softmax scores over every routed expert
the router has outputs for; with ``n_group > 1`` the published
``group_limited_greedy`` gate (DeepSeek-V2) first keeps the ``topk_group``
expert groups whose best score is highest and zeroes the rest; then the
top-k scores are the gate weights, renormalized to sum to one
(``norm_topk_prob``) or else scaled by ``routed_scaling_factor``.

``moe_serve_apply`` is every serving step's layer (prefill, decode and
verify of all MoE families; DeepSeek-V2 and DBRX in the registry).  It is
told which experts it holds (``first_expert_held`` and ``experts_held``:
its share of an expert-parallel deployment, all of them by default),
routes each live token over all ``n_experts``, and computes only the
(token, expert) pairs whose expert is held, as one grouped matmul over the
live pairs sorted by expert (``kernels/grouped_matmul``).  No pair is
dropped, so a token's output never depends on its neighbours.  The shared
experts run for every token.  What the absent experts would add is left
out — no exchange, and nothing stands in for it: the held experts' part
plus the shared experts is what goes on to the next layer.

``moe_apply`` is the training path (``transformer.DecoderLM.loss``).
Dispatch is *gather-based*: for each (group, expert) the top-C tokens by gate
probability are gathered into a dense ``[G, E, C, D]`` buffer (C = capacity), run
through the expert matmuls, weighted by their gate, and scatter-added back.  This
keeps dispatch cost at gather/scatter (≈0 FLOPs) instead of the classic
``[tokens, E, C]`` one-hot einsum, whose FLOPs would dwarf the expert matmuls at
160 experts.  Experts are sharded over the ``model`` mesh axis (EP); the gathered
buffer is sharding-constrained so XLA materializes the EP all-to-all around the
expert matmuls.  Tokens over capacity are dropped (lowest gate first), per the
standard capacity-factor contract.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, round_up
from ..kernels.grouped_matmul import expert_matmul
from ..kernels.grouped_matmul.ops import row_padding
from . import shardings
from .layers import act_fn
from .params import ParamDef


def moe_defs(cfg: ArchConfig):
    """The router scores all ``n_experts``; expert weights are the held
    share's (``experts_held``)."""
    d, f = cfg.d_model, cfg.d_ff_expert
    e = cfg.experts_held
    defs = {
        "router": ParamDef((d, cfg.n_experts), ("embed", None),
                           dtype=jnp.float32),
        "up": ParamDef((e, d, f), ("experts", "embed", "ff")),
        "down": ParamDef((e, f, d), ("experts", "ff", "embed")),
    }
    if cfg.mlp_gated:
        defs["gate"] = ParamDef((e, d, f), ("experts", "embed", "ff"))
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        defs["shared_up"] = ParamDef((d, fs), ("embed", "ff"))
        defs["shared_down"] = ParamDef((fs, d), ("ff", "embed"))
        if cfg.mlp_gated:
            defs["shared_gate"] = ParamDef((d, fs), ("embed", "ff"))
    return defs


def capacity(cfg: ArchConfig, tokens_per_group: int) -> int:
    c = math.ceil(tokens_per_group * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return min(tokens_per_group, max(8, round_up(c, 8)))


def moe_apply(cfg: ArchConfig, p, x, *,
              mesh=None) -> Tuple[jax.Array, jax.Array]:
    """The training layer.  x: [G, S, D] (groups route independently).
    Returns (out, aux_loss)."""
    G, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = capacity(cfg, S)
    act = act_fn(cfg.act)

    assert cfg.experts_held == E, "the training layer holds every expert"
    top_p, top_i = route(cfg, p["router"], x.reshape(G * S, D))
    top_p, top_i = top_p.reshape(G, S, K), top_i.reshape(G, S, K)
    probs = jax.nn.softmax(x.astype(jnp.float32) @ p["router"], axis=-1)

    # dense gate map via scatter-add (no [G,S,K,E] one-hot materialized)
    gg = jnp.arange(G)[:, None, None]
    ss = jnp.arange(S)[None, :, None]
    gates = jnp.zeros((G, S, E), jnp.float32).at[gg, ss, top_i].add(top_p)

    # per-expert top-C tokens by gate (capacity with lowest-gate dropping)
    scores = jnp.swapaxes(gates, 1, 2)                             # [G,E,S]
    vals, idx = jax.lax.top_k(scores, C)                           # [G,E,C]
    keep = (vals > 0.0)

    xe = jax.vmap(lambda xg, ig: xg[ig])(x, idx)                   # [G,E,C,D]
    if mesh is not None:
        xe = shardings.constrain(xe, mesh, ("batch", "experts", None, None))
    if cfg.mlp_gated:
        h = act(jnp.einsum("gecd,edf->gecf", xe, p["gate"])) * jnp.einsum(
            "gecd,edf->gecf", xe, p["up"])
    else:
        h = act(jnp.einsum("gecd,edf->gecf", xe, p["up"]))
    ye = jnp.einsum("gecf,efd->gecd", h, p["down"])
    ye = ye * (vals * keep)[..., None].astype(ye.dtype)
    if mesh is not None:
        ye = shardings.constrain(ye, mesh, ("batch", "experts", None, None))

    def scatter_g(idx_g, ye_g):
        return jnp.zeros((S, D), ye.dtype).at[idx_g.reshape(-1)].add(
            ye_g.reshape(-1, D), mode="drop")
    out = jax.vmap(scatter_g)(idx, ye)                             # [G,S,D]
    if mesh is not None:
        out = shardings.constrain(out, mesh, ("batch", None, None))

    if cfg.n_shared_experts:
        out = out + shared_experts(cfg, p, x)

    # switch-style load-balance auxiliary loss
    frac = jnp.mean(gates > 0.0, axis=(0, 1)).astype(jnp.float32)  # fraction routed
    mean_p = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac * mean_p)
    return out, aux




def route(cfg: ArchConfig, router, x):
    """Gate weights and expert ids ([N, top_k] each) of tokens x [N, D] over
    all ``n_experts`` (module docstring)."""
    scores = jax.nn.softmax(x.astype(jnp.float32) @ router, axis=-1)
    if cfg.n_group > 1:
        N, E = scores.shape
        best = jnp.max(scores.reshape(N, cfg.n_group, E // cfg.n_group), -1)
        _, groups = jax.lax.top_k(best, cfg.topk_group)        # [N, topk_group]
        keep = jnp.zeros((N, cfg.n_group), bool).at[
            jnp.arange(N)[:, None], groups].set(True)
        scores = jnp.where(jnp.repeat(keep, E // cfg.n_group, axis=1),
                           scores, 0.0)
    w, idx = jax.lax.top_k(scores, cfg.top_k)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, -1, keepdims=True)
    else:
        w = w * cfg.routed_scaling_factor
    return w, idx


def shared_experts(cfg: ArchConfig, p, x):
    act = act_fn(cfg.act)
    if cfg.mlp_gated:
        hs = act(x @ p["shared_gate"]) * (x @ p["shared_up"])
    else:
        hs = act(x @ p["shared_up"])
    return hs @ p["shared_down"]


def moe_serve_apply(cfg: ArchConfig, p, x, live):
    """The dropless expert-share layer (module docstring).  x: [N, D];
    live: [N] bool (padding rows and idle slots route nothing).  Returns
    (out [N, D], held_pairs, experts_touched): the live (token, expert)
    pairs whose expert is held here, and how many held experts they touch.
    """
    N, D = x.shape
    K, E = cfg.top_k, cfg.experts_held
    act = act_fn(cfg.act)
    w, idx = route(cfg, p["router"], x)
    local = idx - cfg.first_expert_held
    held = (local >= 0) & (local < E) & live[:, None]              # [N, K]
    sizes = jnp.sum(held[..., None] & (local[..., None] == jnp.arange(E)),
                    axis=(0, 1), dtype=jnp.int32)                  # [E]
    # pairs sorted by held expert, the rest after them; stable, so a
    # token's pairs keep their gate order within an expert's rows
    order = jnp.argsort(jnp.where(held, local, E).reshape(-1), stable=True)
    rows = jnp.pad(order // K, (0, row_padding(N * K)))
    xs = x[rows]
    if cfg.mlp_gated:
        h = act(expert_matmul(xs, p["gate"], sizes)) \
            * expert_matmul(xs, p["up"], sizes)
    else:
        h = act(expert_matmul(xs, p["up"], sizes))
    y = expert_matmul(h, p["down"], sizes, out_dtype=jnp.float32)
    # back to (token, gate) order; unheld pairs read rows nobody wrote
    y = y[jnp.argsort(order)].reshape(N, K, D)
    wk = jnp.where(held, w, 0.0)
    out = jnp.zeros((N, D), jnp.float32)
    for k in range(K):                          # a fixed order per token
        out = out + jnp.where(held[:, k, None], y[:, k], 0.0) * wk[:, k, None]
    out = out.astype(x.dtype)
    if cfg.n_shared_experts:
        out = out + shared_experts(cfg, p, x)
    return out, jnp.sum(held, dtype=jnp.int32), jnp.sum(sizes > 0,
                                                         dtype=jnp.int32)
