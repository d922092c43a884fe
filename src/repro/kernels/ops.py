"""Public kernel entry points.

On TPU these dispatch to the Pallas kernels; elsewhere (this container
is CPU) they run the kernels in interpret mode when ``interpret=True``
is requested (tests do this to validate the kernel bodies) and otherwise
fall back to the jnp oracle — same math, no per-call interpret overhead
in the hot training loop.  The oracle forms used off-TPU are the
unjitted ``ref._*_math`` bodies, so they inline into whatever jit /
shard_map trace the caller is already under.
"""
from __future__ import annotations

import jax

from . import ref
from .gc_decode import decode_pallas
from .gc_encode import encode_pallas
from .gc_fused import encode_decode_pallas

__all__ = ["encode", "decode", "encode_decode", "on_tpu"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def encode(b_code: jax.Array, g: jax.Array, *, tile_d: int = 512,
           force_pallas: bool = False) -> jax.Array:
    """Coded blocks C = B_code @ G.  b_code: (NB, K), g: (K, D)."""
    if on_tpu():
        return encode_pallas(b_code, g, tile_d=tile_d)
    if force_pallas:
        return encode_pallas(b_code, g, tile_d=tile_d, interpret=True)
    return ref._encode_math(b_code, g)


def decode(a: jax.Array, c: jax.Array, *, tile_d: int = 512,
           force_pallas: bool = False) -> jax.Array:
    """Decoded gradient y = a @ C.  a: (N,), c: (N, D)."""
    if on_tpu():
        return decode_pallas(a, c, tile_d=tile_d)
    if force_pallas:
        return decode_pallas(a, c, tile_d=tile_d, interpret=True)
    return ref._decode_math(a, c)


def encode_decode(a: jax.Array, row: jax.Array, g: jax.Array, *,
                  force_pallas: bool = False) -> jax.Array:
    """Fused coded combine y = Σ_k a · row[k] · g[k] — encode and decode
    weight folded into one streaming pass over the per-shard stack.
    a: scalar or (1,), row: (K,), g: (K, *shape) -> (*shape)."""
    if on_tpu():
        return encode_decode_pallas(a, row, g)
    if force_pallas:
        return encode_decode_pallas(a, row, g, interpret=True)
    return ref._encode_decode_math(a, row, g)
