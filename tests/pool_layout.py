"""The serving pool layout, built from per-head test data.

The attend cores read one layer of a layer-stacked lane-dense pool
``[L, P, ps, W]`` (``kernels/README.md``, "Pool layout").  The parity tests
draw per-head K/V ``[P, ps, K, D]`` (latent ``ckv [P, ps, L]`` and
``krope [P, ps, R]``, int8 scales ``[P, ps(, K)]``) and place them here as
layer ``LAYER`` of a ``STACK``-layer pool whose other layers hold other
values, so a core that reads the wrong layer fails its comparison.
"""
import jax.numpy as jnp
import numpy as np

from repro.models.attention import pack_heads, page_width

LAYER, STACK = 1, 3


def stack_layer(x):
    """``x`` [P, ...] as layer ``LAYER`` of a ``[STACK, P, ...]`` stack."""
    x = jnp.asarray(x)
    rng = np.random.RandomState(x.size % 997)
    if jnp.issubdtype(x.dtype, jnp.integer):
        other = rng.randint(-127, 128, size=(STACK,) + x.shape)
    else:
        other = np.abs(rng.randn(STACK, *x.shape))
    return jnp.asarray(other, x.dtype).at[LAYER].set(x)


def gqa_pool(x):
    """Per-head K or V ``[P, ps, K, D]`` as a stacked lane-dense pool."""
    return stack_layer(pack_heads(jnp.asarray(x),
                                  page_width(x.shape[-2] * x.shape[-1])))


def latent_pool(ckv, krope):
    """Latent ``ckv`` [P, ps, L] and ``krope`` [P, ps, R] (one dtype) as a
    stacked lane-dense latent pool."""
    row = jnp.concatenate([jnp.asarray(ckv), jnp.asarray(krope)], -1)
    pad = page_width(row.shape[-1]) - row.shape[-1]
    return stack_layer(jnp.pad(row, ((0, 0), (0, 0), (0, pad))))
