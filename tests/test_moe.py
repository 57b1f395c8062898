"""MoE routing invariants + equivalence with a dense (no-capacity) reference."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models.moe import (capacity, moe_apply, moe_defs, moe_serve_apply,
                              route, shared_experts)
from repro.models.params import init_tree


def _cfg(**kw):
    base = reduced(ARCHS["dbrx-132b"])
    return dataclasses.replace(base, **kw)


def _dense_ref(cfg, p, x):
    """Every token through its top-k experts, no capacity limit."""
    G, S, D = x.shape
    logits = (x.astype(jnp.float32) @ p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    out = jnp.zeros(x.shape, jnp.float32)
    act = jax.nn.silu
    for e in range(cfg.n_experts):
        h = act(x @ p["gate"][e]) * (x @ p["up"][e])
        ye = (h @ p["down"][e]).astype(jnp.float32)
        w = jnp.sum(jnp.where(top_i == e, top_p, 0.0), -1)
        out = out + ye * w[..., None]
    return out


def test_moe_matches_dense_when_capacity_ample():
    cfg = _cfg(capacity_factor=8.0)   # capacity >> load: nothing dropped
    key = jax.random.PRNGKey(0)
    p = init_tree(moe_defs(cfg), key)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 32, cfg.d_model))
    out, aux = moe_apply(cfg, p, x)
    ref = _dense_ref(cfg, p, x)
    if cfg.n_shared_experts:
        pytest.skip("reference covers routed experts only")
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=2e-3, rtol=2e-2)


def test_capacity_drops_lowest_gate_tokens():
    cfg = _cfg(capacity_factor=0.25)
    key = jax.random.PRNGKey(1)
    p = init_tree(moe_defs(cfg), key)
    x = jax.random.normal(jax.random.fold_in(key, 2), (1, 64, cfg.d_model),
                          jnp.float32)
    out, aux = moe_apply(cfg, p, x)
    assert out.shape == x.shape
    assert jnp.all(jnp.isfinite(out))
    # with tight capacity some tokens must receive zero routed output
    norms = jnp.linalg.norm(out[0], axis=-1)
    assert float(jnp.min(norms)) < float(jnp.max(norms))


def test_capacity_formula():
    cfg = _cfg(capacity_factor=1.0)
    c = capacity(cfg, 4096)
    # top_k * tokens / n_experts, rounded up to 8
    assert c >= 4096 * cfg.top_k / cfg.n_experts
    assert c % 8 == 0 or c == 4096


def test_aux_loss_balanced_router_is_minimal():
    """A perfectly uniform router gives aux ~= top_k; an unbalanced one more."""
    cfg = _cfg()
    key = jax.random.PRNGKey(3)
    p = init_tree(moe_defs(cfg), key)
    # uniform logits -> balanced: aux == top_k exactly
    p["router"] = jnp.zeros_like(p["router"])
    x = jnp.ones((2, 64, cfg.d_model), jnp.float32)
    _, aux_bal = moe_apply(cfg, p, x)
    assert abs(float(aux_bal) - cfg.top_k) < 0.5
    # heavily biased router (x constant positive -> expert 0 always wins)
    p["router"] = p["router"].at[:, 0].set(1.0)
    _, aux_skew = moe_apply(cfg, p, x)
    assert float(aux_skew) > float(aux_bal)


def test_deepseek_shared_experts_path():
    cfg = reduced(ARCHS["deepseek-v2-236b"])
    key = jax.random.PRNGKey(4)
    p = init_tree(moe_defs(cfg), key)
    x = jax.random.normal(key, (2, 16, cfg.d_model), jnp.bfloat16)
    out, aux = moe_apply(cfg, p, x)
    assert out.shape == x.shape
    assert jnp.all(jnp.isfinite(out.astype(jnp.float32)))


# ------------------------------------------- the serving layer (dropless)

def _share_cfg(**kw):
    """A small DeepSeek-V2-shaped MoE: 16 routed experts in 8 groups of 2,
    top-3 groups, top-4 experts, unnormalized x 16, 2 shared experts."""
    base = reduced(ARCHS["deepseek-v2-236b"])
    return dataclasses.replace(base, **dict(dict(
        n_experts=16, top_k=4, n_group=8, topk_group=3, d_ff_expert=32,
        n_shared_experts=2, norm_topk_prob=False, routed_scaling_factor=16.0),
        **kw))


def _f32_params(cfg, seed):
    p = init_tree(moe_defs(cfg), jax.random.PRNGKey(seed))
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


def _np_route(cfg, router, x):
    """numpy transcription of the published group_limited_greedy gate."""
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    N, E = s.shape
    if cfg.n_group > 1:
        best = s.reshape(N, cfg.n_group, -1).max(-1)
        top = np.argsort(-best, -1)[:, :cfg.topk_group]
        mask = np.zeros_like(best)
        np.put_along_axis(mask, top, 1.0, -1)
        s = np.where(np.repeat(mask, E // cfg.n_group, -1) > 0, s, 0.0)
    idx = np.argsort(-s, -1)[:, :cfg.top_k]
    w = np.take_along_axis(s, idx, -1)
    if cfg.norm_topk_prob:
        return w / w.sum(-1, keepdims=True), idx
    return w * cfg.routed_scaling_factor, idx


@pytest.mark.parametrize("kw", [
    {},                                                  # deepseek-v2 gate
    dict(n_group=1, topk_group=1, norm_topk_prob=True,   # plain top-k
         routed_scaling_factor=1.0),
])
def test_route_matches_published_gate(kw):
    cfg = _share_cfg(**kw)
    p = _f32_params(cfg, 5)
    x = jax.random.normal(jax.random.PRNGKey(6), (64, cfg.d_model))
    w, idx = route(cfg, p["router"], x)
    w_np, idx_np = _np_route(cfg, p["router"], x)
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(idx_np, -1))
    order = np.argsort(np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, -1),
        np.take_along_axis(w_np, np.argsort(idx_np, -1), -1), rtol=1e-5)
    if not cfg.norm_topk_prob:       # unnormalized: sums stay below 16
        assert float(jnp.max(jnp.sum(w, -1))) < cfg.routed_scaling_factor


def _dense_layer(cfg, p, x):
    """The whole layer in plain jax.numpy: every routed pair through its
    expert, then the shared experts."""
    w, idx = _np_route(cfg, p["router"], x)
    act = jax.nn.silu
    out = np.zeros(x.shape, np.float64)
    for e in range(cfg.n_experts):
        y = (act(x @ p["gate"][e]) * (x @ p["up"][e])) @ p["down"][e]
        out += np.asarray(y, np.float64) * np.where(idx == e, w, 0).sum(-1,
                                                                      keepdims=True)
    hs = act(x @ p["shared_gate"]) * (x @ p["shared_up"])
    return out + np.asarray(hs @ p["shared_down"], np.float64)


def test_expert_shares_add_up_to_the_whole_layer():
    """Each of 8 chips holds one routing group (2 experts); the parts its
    held experts give, plus the shared experts counted once, add up to the
    uncut layer."""
    cfg = _share_cfg()
    p = _f32_params(cfg, 7)
    x = jax.random.normal(jax.random.PRNGKey(8), (48, cfg.d_model))
    live = jnp.ones((48,), bool)
    shared = np.asarray(shared_experts(cfg, p, x), np.float64)
    total, pairs = shared.copy(), 0
    per = cfg.n_experts // 8
    for g in range(8):
        cg = dataclasses.replace(cfg, n_experts_held=per,
                                 first_expert_held=g * per)
        pg = dict(p, **{k: p[k][g * per:(g + 1) * per]
                        for k in ("gate", "up", "down")})
        out, held, touched = moe_serve_apply(cg, pg, x, live)
        total += np.asarray(out, np.float64) - shared
        pairs += int(held)
        assert int(touched) <= per
    assert pairs == 48 * cfg.top_k                 # every pair, once
    whole, held, _ = moe_serve_apply(cfg, p, x, live)
    ref = _dense_layer(cfg, p, x)
    np.testing.assert_allclose(total, ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(whole, np.float64), ref,
                               rtol=2e-4, atol=2e-4)
    assert int(held) == 48 * cfg.top_k


def test_serving_layer_drops_no_token():
    """A token's output is the same alone or batched with 64 others that
    all route to the same (hot) expert; no capacity, nothing dropped."""
    cfg = _share_cfg(n_group=1, topk_group=1)
    p = _f32_params(cfg, 9)
    p["router"] = p["router"].at[:, 3].add(4.0)        # expert 3 is hot
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(10), (65, cfg.d_model)))
    _, idx = route(cfg, p["router"], x)
    assert bool(jnp.all(jnp.any(idx == 3, -1)))       # all 65 route to it
    live = jnp.ones((65,), bool)
    batched, held, _ = moe_serve_apply(cfg, p, x, live)
    alone, _, _ = moe_serve_apply(cfg, p, x[:1], live[:1])
    np.testing.assert_array_equal(np.asarray(batched[0]), np.asarray(alone[0]))
    assert int(held) == 65 * cfg.top_k


def test_serving_layer_skips_dead_tokens():
    cfg = _share_cfg()
    p = _f32_params(cfg, 11)
    x = jax.random.normal(jax.random.PRNGKey(12), (8, cfg.d_model))
    live = jnp.arange(8) < 5
    out, held, _ = moe_serve_apply(cfg, p, x, live)
    full, _, _ = moe_serve_apply(cfg, p, x[:5], live[:5])
    assert int(held) == 5 * cfg.top_k
    np.testing.assert_array_equal(np.asarray(out[:5]), np.asarray(full))
    # a dead token gets the shared experts only
    np.testing.assert_array_equal(np.asarray(out[5:]),
                                  np.asarray(shared_experts(cfg, p, x[5:])))
