"""Validate the loop-aware HLO cost model against XLA's own cost analysis on a
fully-unrolled program (where XLA's numbers are trustworthy), and check the
trip-count multiplication against it on the scanned version of the same fn."""
import jax
import jax.numpy as jnp

from repro.launch import hlo_cost


def _xla_flops(compiled) -> float:
    return compiled.cost_analysis()["flops"]


def _mlp_scan(unroll):
    def f(w, x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=6, unroll=unroll)
        return jnp.sum(y)
    return f


def test_matches_xla_on_unrolled():
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    c = jax.jit(_mlp_scan(True)).lower(w, x).compile()
    ref = _xla_flops(c)
    mine = hlo_cost.module_cost(c.as_text())
    assert 0.8 <= mine.flops / ref <= 1.3, (mine.flops, ref)


def test_scan_trip_count_accounted():
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    unrolled = jax.jit(_mlp_scan(True)).lower(w, x).compile()
    scanned = jax.jit(_mlp_scan(False)).lower(w, x).compile()
    ref = _xla_flops(unrolled)
    mine = hlo_cost.module_cost(scanned.as_text())
    # XLA's own analysis of the scanned program is ~6x off; ours must not be
    assert 0.8 <= mine.flops / ref <= 1.3, (mine.flops, ref)
    blind = _xla_flops(scanned)
    assert blind < 0.5 * ref     # documents why the custom walker exists


def test_grad_scan_counted():
    def f(w, x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=4)
        return jnp.sum(y * y)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    g_scan = jax.jit(jax.grad(f)).lower(w, x).compile()
    def f_u(w, x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=4, unroll=True)
        return jnp.sum(y * y)
    g_unr = jax.jit(jax.grad(f_u)).lower(w, x).compile()
    ref = _xla_flops(g_unr)
    mine = hlo_cost.module_cost(g_scan.as_text())
    assert 0.7 <= mine.flops / ref <= 1.5, (mine.flops, ref)
