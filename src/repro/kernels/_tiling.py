"""Shared tiling helpers for the gradient-coding Pallas kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def mask_tail_lanes(x, d: int, tile_d: int):
    """Zero-select the lanes of tile ``pl.program_id(0)`` that fall past
    column ``d`` (the true array width).

    Call inside a kernel whose grid tiles the last axis by ``tile_d``.
    Out-of-bounds lanes read NaN in interpret mode / garbage on
    hardware, so this must be a ``where`` select — a multiply by a mask
    would keep the NaNs.  The select runs in float32 on a ``(1, tile_d)``
    lane row: Mosaic cannot sublane-broadcast a mask onto a packed bf16
    tile with fewer rows than its packing (K = 2 or 4); the round trip
    through float32 is exact.
    """
    col0 = pl.program_id(0) * tile_d
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, x.shape[-1]), 1)
    return jnp.where(cols < d, x.astype(jnp.float32), 0.0).astype(x.dtype)
