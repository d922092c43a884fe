"""Pure-jnp oracles for the gradient-coding kernels.

The underscored ``_*_math`` forms are unjitted (they inline cleanly into
an enclosing jit / shard_map trace — the training hot path); the
``*_ref`` names wrap them in jax.jit for standalone benchmark/test use.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _encode_math(b_code: jax.Array, g: jax.Array) -> jax.Array:
    """C = B_code @ G with fp32 accumulation (matches kernel numerics)."""
    return jax.lax.dot_general(
        b_code.astype(g.dtype), g, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(g.dtype)


def _decode_math(a: jax.Array, c: jax.Array) -> jax.Array:
    """y = a @ C with fp32 accumulation."""
    return jax.lax.dot_general(
        a.astype(c.dtype)[None, :], c, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[0].astype(c.dtype)


def _encode_decode_math(a: jax.Array, row: jax.Array,
                        g: jax.Array) -> jax.Array:
    """y = Σ_k a · row[k] · g[k] — encode and decode weight in one
    weighted sum over the (K, *shape) stack, float32 accumulation."""
    f32 = jnp.float32
    coef = jnp.reshape(a, ()).astype(f32) * row.astype(f32)
    return jnp.tensordot(coef, g.astype(f32), axes=1,
                         precision=jax.lax.Precision.HIGHEST).astype(g.dtype)


encode_ref = jax.jit(_encode_math)
decode_ref = jax.jit(_decode_math)
encode_decode_ref = jax.jit(_encode_decode_math)
