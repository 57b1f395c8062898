"""Shared neural layers: norms, activations, MLPs, RoPE, embeddings.

Norms take their epsilon from the configuration (``norm_eps``).  Rotary
frequencies are ``rope_theta`` powers, or with ``rope_scaling == "yarn"``
the YaRN blend (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``): each
frequency is interpolated (divided by ``rope_factor``) below the
correction range that ``yarn_beta_fast`` / ``yarn_beta_slow`` rotations
over ``rope_original_max_pos`` bound, kept as is above it, and blended by
a linear ramp inside it, at every position.  Attention then scales its
softmax by ``yarn_mscale(rope_factor, yarn_mscale_all_dim)**2``
(``softmax_scale``); the cos/sin scale ``mscale / mscale_all_dim`` must be
1, as it is for DeepSeek-V2, and anything else is refused.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .params import ParamDef

# ----------------------------------------------------------------------------- norms

def norm_defs(cfg: ArchConfig, d: int):
    if cfg.norm == "layernorm":
        return {"scale": ParamDef((d,), ("embed",), init="ones"),
                "bias": ParamDef((d,), ("embed",), init="zeros")}
    return {"scale": ParamDef((d,), ("embed",), init="ones")}


def apply_norm(cfg: ArchConfig, p, x):
    eps = cfg.norm_eps
    if cfg.norm_fp32:
        xf = x.astype(jnp.float32)
        if cfg.norm == "layernorm":
            mu = jnp.mean(xf, -1, keepdims=True)
            var = jnp.var(xf, -1, keepdims=True)
            y = (xf - mu) * jax.lax.rsqrt(var + eps)
            y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
        else:  # rmsnorm
            ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
            y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
        return y.astype(x.dtype)
    # bf16 elementwise path: only the variance statistics are fp32, so the
    # backward activation tensors (and their TP all-reduces) stay bf16
    if cfg.norm == "layernorm":
        mu = jnp.mean(x.astype(jnp.float32), -1, keepdims=True)
        var = jnp.var(x.astype(jnp.float32), -1, keepdims=True)
        inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
        return (x - mu.astype(x.dtype)) * inv * p["scale"] + p["bias"]
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps).astype(x.dtype) * p["scale"]


def rmsnorm(x, scale, eps: float = 1e-5):
    """RMSNorm of a sub-layer's own vector (MLA's latent and query norms
    pass the configuration's ``norm_eps``; SSM's gated norm keeps 1e-5)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)

# ----------------------------------------------------------------- activations

def act_fn(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "relu": jax.nn.relu,
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    }[name]

# ------------------------------------------------------------------------ MLP

def mlp_defs(cfg: ArchConfig, d: int, ff: int):
    defs = {"down": ParamDef((ff, d), ("ff", "embed"))}
    if cfg.mlp_gated:
        defs["gate"] = ParamDef((d, ff), ("embed", "ff"))
        defs["up"] = ParamDef((d, ff), ("embed", "ff"))
    else:
        defs["up"] = ParamDef((d, ff), ("embed", "ff"))
        if cfg.qkv_bias:  # starcoder2-style biased MLP
            defs["up_b"] = ParamDef((ff,), ("ff",), init="zeros")
            defs["down_b"] = ParamDef((d,), ("embed",), init="zeros")
    return defs


def apply_mlp(cfg: ArchConfig, p, x):
    act = act_fn(cfg.act)
    if cfg.mlp_gated:
        h = act(x @ p["gate"]) * (x @ p["up"])
    else:
        h = x @ p["up"]
        if "up_b" in p:
            h = h + p["up_b"]
        h = act(h)
    y = h @ p["down"]
    if "down_b" in p:
        y = y + p["down_b"]
    return y

# ----------------------------------------------------------------------- RoPE

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor ``0.1 * mscale * ln(factor) + 1``
    (1 for no extension)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(cfg: ArchConfig, dim: int):
    """Rotary dims (of ``dim // 2``) between which YaRN's ramp runs: those
    that turn ``yarn_beta_fast`` and ``yarn_beta_slow`` times over the
    original context, floored and ceiled, clipped to the dims."""
    def at(rot):
        return (dim * math.log(cfg.rope_original_max_pos / (rot * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))
    low = math.floor(at(cfg.yarn_beta_fast))
    high = math.ceil(at(cfg.yarn_beta_slow))
    return max(low, 0), min(high, dim - 1)


def softmax_scale(cfg: ArchConfig, qk_dim: int) -> float:
    """``qk_dim ** -0.5``, times YaRN's ``mscale(mscale_all_dim)**2``."""
    scale = 1.0 / math.sqrt(qk_dim)
    if cfg.rope_scaling == "yarn" and cfg.yarn_mscale_all_dim:
        m = yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim)
        scale = scale * m * m
    return scale


def rope_freqs(cfg: ArchConfig, head_dim: int) -> jax.Array:
    half = head_dim // 2
    extra = 1.0 / (cfg.rope_theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if cfg.rope_scaling != "yarn":
        return extra
    if yarn_mscale(cfg.rope_factor, cfg.yarn_mscale) != yarn_mscale(
            cfg.rope_factor, cfg.yarn_mscale_all_dim):
        raise NotImplementedError("yarn with a cos/sin scale other than 1")
    low, high = yarn_correction_range(cfg, head_dim)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / (high - low),
                    0.0, 1.0)
    keep = 1.0 - ramp                      # 1: extrapolate, 0: interpolate
    return extra / cfg.rope_factor * (1.0 - keep) + extra * keep


def apply_rope(x: jax.Array, positions: jax.Array, freqs: jax.Array) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: [..., seq] (broadcastable)."""
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, half]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return y.astype(x.dtype)

# ------------------------------------------------------------------ embeddings

def embed_defs(cfg: ArchConfig):
    v, d = cfg.vocab_padded, cfg.d_model
    defs = {"tok": ParamDef((v, d), ("vocab", "embed"), init="embed")}
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((d, v), ("embed", "vocab"))
    return defs


def embed_tokens(p, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["tok"], tokens, axis=0)


def lm_logits(cfg: ArchConfig, p, x: jax.Array) -> jax.Array:
    w = p["tok"].T if cfg.tie_embeddings else p["lm_head"]
    return x @ w
