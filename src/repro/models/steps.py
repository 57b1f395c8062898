"""Train / serve step factories.

Two engines build the same training step (see DESIGN.md §2):
  * ``pjit``      — sharding-constraint formulation; XLA schedules/overlaps the
    gradient collectives.  The dry-run/roofline substrate.
  * ``mapreduce`` — the paper-faithful explicit map/combine/reduce via
    ``shard_map`` with selectable reduce mode (allreduce | hierarchical |
    compressed int8+EF).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig, ShapeConfig
from ..core.mapreduce import mapreduce_value_and_grad
from ..optim import OptConfig, apply_updates, init_opt_state, opt_state_defs
from . import shardings
from .params import abstract_tree, init_tree, specs_tree
from .registry import build_model, input_defs


# ------------------------------------------------------------- train steps

def make_train_step(cfg: ArchConfig, mesh: Optional[Mesh], opt_cfg: OptConfig,
                    *, engine: str = "pjit", reduce_mode: str = "allreduce",
                    n_micro: int = 1):
    """Returns ``step(params, opt_state, batch) -> (params, opt_state, metrics)``
    (un-jitted; caller jits with the sharding trees from ``train_shardings``)."""
    model = build_model(cfg)

    def loss_fn(params, batch):
        return model.loss(params, batch, mesh)

    if engine == "pjit":
        def step(params, opt_state, batch):
            if n_micro > 1:
                def to_micro(x):
                    return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
                mb = jax.tree.map(to_micro, batch)

                def acc(carry, m):
                    gsum, lsum = carry
                    (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params, m)
                    return (jax.tree.map(lambda a, b: a + b.astype(a.dtype), gsum, g),
                            lsum + l), None
                g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (gsum, lsum), _ = jax.lax.scan(acc, (g0, jnp.zeros((), jnp.float32)), mb)
                grads = jax.tree.map(lambda g: g / n_micro, gsum)
                loss = lsum / n_micro
                aux = {}
            else:
                (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, batch)
            params, opt_state, om = apply_updates(params, grads, opt_state, opt_cfg)
            return params, opt_state, {"loss": loss, **om}
        return step

    assert engine == "mapreduce", engine
    # inside shard_map the data axes are Manual: global sharding constraints
    # would reference a mismatched mesh, so the model runs constraint-free and
    # the engine's in_specs/psum carry the distribution
    def loss_fn_manual(params, batch):
        return model.loss(params, batch, None)

    mr = mapreduce_value_and_grad(loss_fn_manual, mesh, reduce_mode=reduce_mode,
                                  n_micro=n_micro)

    def step(params, opt_state, batch):
        err = opt_state.get("comp_err") if isinstance(opt_state, dict) else None
        loss, grads, new_err, aux = mr(params, batch, err)
        inner = {k: v for k, v in opt_state.items() if k != "comp_err"}
        params, inner, om = apply_updates(params, grads, inner, opt_cfg)
        if new_err is not None:
            inner["comp_err"] = new_err
        return params, inner, {"loss": loss, **om}
    return step


def train_shardings(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh,
                    opt_cfg: OptConfig):
    """(params, opt_state, batch) NamedSharding trees for jit in/out_shardings."""
    model = build_model(cfg)
    pdefs = model.param_defs()
    odefs = opt_state_defs(pdefs, opt_cfg)
    bdefs = input_defs(cfg, shape)
    return (specs_tree(pdefs, mesh), specs_tree(odefs, mesh),
            specs_tree(bdefs, mesh))


def abstract_train_args(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh,
                        opt_cfg: OptConfig):
    """ShapeDtypeStructs (with shardings) for lower() — zero allocation."""
    model = build_model(cfg)
    pdefs = model.param_defs()
    odefs = opt_state_defs(pdefs, opt_cfg)
    bdefs = input_defs(cfg, shape)
    return (abstract_tree(pdefs, mesh), abstract_tree(odefs, mesh),
            abstract_tree(bdefs, mesh))


# ------------------------------------------------------------- serve steps

def make_serve_step(cfg: ArchConfig, mesh: Optional[Mesh], kind: str,
                    attn_backend: str = "reference"):
    """kind='decode': step(params, cache, tokens) -> (next_tokens, cache)
       kind='prefill': step(params, batch) -> (logits, cache)
       kind='prefill_at': step(params, batch, last_idx) -> (logits, cache)
         (logits read at per-row position ``last_idx`` — bucketed prompts)
       kind='decode_paged': step(params, kv, state, meta, tokens)
         -> (next_tokens, ok, new_kv, new_state) — slot-indexed continuous-
         batching decode against the paged pool and/or state-slot pool
         (see repro.serving; {} stands in for an absent pool).  ``meta`` is
         the flat per-step metadata pytree from ``attn_backend.decode_meta``
         (page-table rows, positions, precomputed write targets).  ``ok`` is
         a per-row bool: True iff every logit in that row is finite — the
         engine's NaN/inf quarantine guard, computed in-jit so the argmax
         result never has to leave the device alongside raw logits.
       kind='verify_paged': step(params, kv, state, meta, tokens)
         -> (next_tokens [B, Q], ok [B], new_kv, new_state) — small-q
         speculative verify: ``tokens`` is [B, Q] (last emitted token +
         draft per slot) and ``meta`` comes from ``attn_backend.verify_meta``;
         row j of the output is the greedy next token after position pos + j,
         from which the engine computes the accepted draft prefix.  ``ok``
         reduces finiteness over both the Q and vocab axes.
       kind='prefill_paged': step(params, kv, state, meta, tokens, extras)
         -> (logits, new_kv, new_state) — batched chunk prefill straight
         into the pools.  ``meta`` is the flat per-step metadata pytree from
         ``attn_backend.prefill_meta`` (page tables, slot rows, per-row
         chunk offsets + live counts, precomputed write targets): positions
         < start are read from already-resident pages — radix prefix-cache
         hits and earlier chunks alike — recurrent/cross state is scattered
         into the slot rows, and ``extras`` carries frontend inputs
         (frames / image_embeds).

       ``attn_backend`` selects the paged-attention backend the paged kinds
       route through (``reference`` gather+attend | ``pallas`` fused decode
       kernel).

       For MoE configurations the decode and prefill kinds also carry each
       MoE layer's held pairs and held experts touched
       (``DecoderLM._paged_layers``) in the outputs the engine already
       reads: appended to ``next_tokens`` after the B rows, and as one row
       after the B logits rows (``pack_counts``)."""
    model = build_model(cfg, attn_backend)
    moe = {"moe_stats": True} if cfg.is_moe else {}
    # each step is named after its kind, so the compiled module
    # (``jit_decode_paged``, ...) says which step ran or recompiled
    if kind == "decode":
        def decode(params, cache, tokens):
            logits, cache = model.decode(params, cache, tokens, mesh)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, cache
        return decode
    if kind == "decode_paged":
        def decode_paged(params, kv, state, meta, tokens):
            logits, kv, state, *stats = model.decode_paged(
                params, kv, state, meta, tokens, mesh, **moe)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            ok = jnp.isfinite(logits).all(axis=-1)
            if stats:
                nxt = jnp.concatenate([nxt, stats[0].reshape(-1)])
            return nxt, ok, kv, state
        return decode_paged
    if kind == "verify_paged":
        def verify_paged(params, kv, state, meta, tokens):
            logits, kv, state = model.verify_paged(params, kv, state, meta,
                                                   tokens, mesh)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            ok = jnp.isfinite(logits).all(axis=(-2, -1))
            return nxt, ok, kv, state
        return verify_paged
    if kind == "prefill_paged":
        def prefill_paged(params, kv, state, meta, tokens, extras):
            logits, kv, state, *stats = model.prefill_paged(
                params, kv, state, meta, tokens, extras, mesh, **moe)
            if stats:
                logits = jnp.concatenate(
                    [logits, pack_counts(stats[0], logits.shape[-1],
                                         logits.dtype)])
            return logits, kv, state
        return prefill_paged
    if kind == "prefill_paged_cont":
        # continuation chunks of a long prompt: pure page work — enc-dec
        # skips the encoder and reads its pinned cross K/V from the slots
        def prefill_paged_cont(params, kv, state, meta, tokens, extras):
            return model.prefill_paged(params, kv, state, meta, tokens,
                                       extras, mesh, continuation=True)
        return prefill_paged_cont
    if kind == "prefill_at":
        def prefill_at(params, batch, last_idx):
            return model.prefill(params, batch, mesh, logits_idx=last_idx)
        return prefill_at
    assert kind == "prefill", kind

    def prefill(params, batch):
        return model.prefill(params, batch, mesh)
    return prefill


COUNT_DIGITS = 3      # base-256 digits of a packed count: below 2**24


def pack_counts(counts, width: int, dtype):
    """Non-negative int counts as one [1, width] row of ``dtype``: each
    count's base-256 digits, least significant first — digits below 256
    are exact in any float type, bf16 included."""
    flat = counts.reshape(-1).astype(jnp.int32)
    digits = jnp.stack([(flat >> (8 * i)) & 255
                        for i in range(COUNT_DIGITS)], -1).reshape(-1)
    return jnp.zeros((1, width), dtype).at[0, :digits.size].set(
        digits.astype(dtype))


def unpack_counts(row, n: int):
    """The ``n`` counts ``pack_counts`` wrote in ``row`` (host numpy)."""
    digits = np.asarray(row[:n * COUNT_DIGITS], np.float64).astype(
        np.int64).reshape(n, COUNT_DIGITS)
    return digits @ (256 ** np.arange(COUNT_DIGITS))


def abstract_serve_args(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh):
    model = build_model(cfg)
    pdefs = model.param_defs()
    if shape.kind == "decode":
        cdefs = model.cache_defs(shape.global_batch, shape.seq_len)
        bdefs = input_defs(cfg, shape)
        return (abstract_tree(pdefs, mesh), abstract_tree(cdefs, mesh),
                abstract_tree(bdefs, mesh)["tokens"])
    bdefs = input_defs(cfg, shape)
    return (abstract_tree(pdefs, mesh), abstract_tree(bdefs, mesh))


def serve_shardings(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh):
    model = build_model(cfg)
    pdefs = model.param_defs()
    if shape.kind == "decode":
        cdefs = model.cache_defs(shape.global_batch, shape.seq_len)
        bdefs = input_defs(cfg, shape)
        return (specs_tree(pdefs, mesh), specs_tree(cdefs, mesh),
                specs_tree(bdefs, mesh)["tokens"])
    bdefs = input_defs(cfg, shape)
    return (specs_tree(pdefs, mesh), specs_tree(bdefs, mesh))
