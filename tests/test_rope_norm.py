"""Configuration-derived numerics: YaRN rotary and softmax scale against
their closed forms, and the defaults every other family keeps."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch
from repro.models.layers import (apply_norm, rope_freqs, softmax_scale,
                                 yarn_correction_range, yarn_mscale)
from repro.models.mla import mla_scale
from repro.models.moe import route

DSV2 = get_arch("deepseek-v2-236b")


def _published_yarn(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepseekV2YarnRotaryEmbedding's inv_freq, in float64."""
    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return inter * (1 - mask) + extra * mask, (low, high)


@pytest.mark.parametrize("orig", [4096, 64])
def test_yarn_frequencies_match_closed_form(orig):
    cfg = dataclasses.replace(DSV2, rope_original_max_pos=orig)
    want, rng = _published_yarn(64, 1e4, 40.0, orig, 32.0, 1.0)
    got = np.asarray(rope_freqs(cfg, 64), np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert yarn_correction_range(cfg, 64) == rng
    if orig == 4096:
        assert rng == (10, 23)          # DeepSeek-V2's ramp
        # past the ramp the frequencies are interpolated by the factor
        np.testing.assert_allclose(got[-1], want[-1], rtol=1e-6)
        assert got[-1] < 1.0 / 1e4 ** (62 / 64) / 39


def test_yarn_softmax_scale():
    m = 0.1 * 0.707 * math.log(40) + 1.0
    assert abs(yarn_mscale(40, 0.707) - m) < 1e-12
    assert abs(m * m - 1.590) < 1e-3
    np.testing.assert_allclose(mla_scale(DSV2), 192 ** -0.5 * m * m,
                               rtol=1e-12)
    assert softmax_scale(dataclasses.replace(DSV2, rope_scaling=""),
                         192) == 1.0 / math.sqrt(192)


def test_yarn_with_scaled_cos_sin_is_refused():
    cfg = dataclasses.replace(DSV2, yarn_mscale=1.0)
    with pytest.raises(NotImplementedError):
        rope_freqs(cfg, 64)


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_family_defaults_keep_their_numbers(name):
    """Every family but DeepSeek-V2 keeps plain rotary powers, the 1e-5
    norm epsilon, 1/sqrt(d) attention and the renormalized top-k gate, bit
    for bit."""
    cfg = ARCHS[name]
    if name == "deepseek-v2-236b":
        assert (cfg.norm_eps, cfg.rope_scaling, cfg.n_group) == \
            (1e-6, "yarn", 8)
        return
    assert cfg.norm_eps == 1e-5 and cfg.rope_scaling == ""
    if cfg.family != "ssm":
        hd = cfg.head_dim_
        half = hd // 2
        plain = 1.0 / (cfg.rope_theta
                       ** (jnp.arange(half, dtype=jnp.float32) / half))
        np.testing.assert_array_equal(np.asarray(rope_freqs(cfg, hd)),
                                      np.asarray(plain))
        assert softmax_scale(cfg, hd) == 1.0 / math.sqrt(hd)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16), jnp.float32)
    p = {"scale": jnp.ones((16,)), "bias": jnp.zeros((16,))}
    if cfg.norm == "rmsnorm":
        ms = jnp.mean(jnp.square(x), -1, keepdims=True)
        want = x * jax.lax.rsqrt(ms + 1e-5)
        np.testing.assert_array_equal(np.asarray(apply_norm(cfg, p, x)),
                                      np.asarray(want))
    if cfg.is_moe:
        assert (cfg.n_group, cfg.norm_topk_prob, cfg.routed_scaling_factor,
                cfg.experts_held) == (1, True, 1.0, cfg.n_experts)
        small = dataclasses.replace(cfg, n_experts=8, top_k=2)
        router = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
        w, idx = route(small, router, x)
        probs = jax.nn.softmax(x @ router, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, 2)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(top_i))
        np.testing.assert_array_equal(
            np.asarray(w), np.asarray(top_p / jnp.sum(top_p, -1,
                                                       keepdims=True)))
