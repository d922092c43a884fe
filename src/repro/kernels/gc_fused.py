"""Pallas TPU kernel: FUSED gradient-coding encode + decode-weight.

The coded training hot path wants, per leaf, this worker's
decode-weighted coded block

    y = Σ_k c_k · g[k]        c = a · row : (K,)  coding row with the decode
                                                  weight (and 1/N) folded in
                              g           : (K, *shape) per-shard gradients

Computing ``encode`` then ``decode-scale`` as two ops costs two HBM
passes (write C, read C, write a*C); folding the decode weight into the
coding row turns the whole combine into ONE weighted sum over the
per-shard stack — a single streaming pass over g.

The kernel reads the stack in the layout the backward writes it: each
shard's gradient is one K-major slab, laid out as the device lays out
the leaf by default (``leaf_order``: a TPU keeps e.g. an (L, D, H, 64)
attention weight with D minor).  Transposed into that order (a
bitcast), the stack is one of two views:

  * ``(K, M, S, C)`` for a leaf of rank ≥ 2 — C the minor dim, S the one
    before, M the product of the rest (merging major dims keeps the
    (sublane, lane) tiles).  The sum runs over the block's leading axis.
  * ``(1, K, C)`` for a 0- or 1-D leaf, whose (K, C) stack is tiled with
    K on the sublanes.  The sum runs over the block's sublane axis.

Had the kernel asked for another order, XLA would either copy the stack
into it or have the backward's loop write each slab strided into it.
Blocks are filled from the minor dim outwards up to ``_BLOCK_BYTES``
(``fused_block``), so each grid step moves enough bytes that the
per-step cost does not bound the kernel.  The K coefficients sit in
SMEM; the weighted sum of a block's K slices runs on the VPU with
float32 accumulation and is cast to g's dtype.

The stack and the result are pinned to HBM.  Left to itself, XLA's
memory-space assignment keeps each result in VMEM (for the optimizer's
gradient-norm pass, then evicts it to HBM by an async copy) and
prefetches some small stacks into VMEM; the kernel's time then covers
only part of the (K+1) HBM passes that ``perf/flops.py:combine_bytes``
counts, and ``combine_roofline`` reads over 100%.  Pinned, the kernel
moves every byte of those passes itself; on a TPU v5e the pin moved
the training step's p90 by -2.3% to +0.5% against XLA's placement
(gc-lm-110m at K=1 and K=8, whisper-base at K=8).  The pin goes once
that count leaves out operands and results that XLA keeps in VMEM.

Ragged blocks need no mask: an output element reads only the K stack
elements at its own position, so the garbage a ragged block reads past
the array's end reaches only output elements that are themselves past
the end, and Pallas drops those on write-back.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout
from jax.experimental.pallas import tpu as pltpu

LANE = 128
_BLOCK_BYTES = 2 << 20  # tile-padded VMEM bytes of one block of g


def _device():
    return jax.devices()[0]


def leaf_order(shape: tuple[int, ...], dtype) -> tuple[int, ...]:
    """Dims of a leaf, major to minor, as the device lays out such an
    array by default; row-major where the device reports no layout."""
    dev = _device()
    try:
        order = Layout.from_pjrt_layout(dev.client.get_default_layout(
            np.dtype(dtype), shape, dev)).major_to_minor
    except Exception:  # a backend without default layouts
        return tuple(range(len(shape)))
    return tuple(order)


def stack_view(k: int, shape) -> tuple[tuple[int, ...], int]:
    """(view, K's axis in it) of a (K, *shape) stack whose leaf dims are
    in the device's order: ``(K, M, S, C)``, or ``(1, K, C)`` for a 0-
    or 1-D leaf."""
    if len(shape) <= 1:
        return (1, k, math.prod(shape)), 1
    *major, s, c = shape
    return (k, math.prod(major), s, c), 0


def block_bytes(block, itemsize: int) -> int:
    """VMEM bytes of a block, its last two dims padded to whole tiles."""
    sub = 8 * 4 // itemsize  # sublanes of a tile: 8 of 32-bit, 16 of 16-bit
    *major, s, c = block
    return (math.prod(major) * pl.cdiv(s, sub) * sub * pl.cdiv(c, LANE)
            * LANE * itemsize)


def fused_block(view, k_axis: int, itemsize: int) -> tuple[int, ...]:
    """Block of ``view``, one rule: K whole, then from the minor dim
    outwards each dim whole while the block fits ``_BLOCK_BYTES``; the
    first dim that does not fit takes the most whole tiles of it that
    do, and the dims outside it one tile.  A tile is 128 lanes of the
    minor dim, a sublane tile of rows of the output block's second-minor
    dim (S, or M where K is on the sublanes), 1 of any other dim; a
    block dim below its array dim is a multiple of its tile, as Mosaic
    requires."""
    n = len(view)
    rows = 0 if k_axis == n - 2 else n - 2
    tile = [1] * n
    tile[-1] = LANE
    tile[rows] = 8 * 4 // itemsize
    block = [1] * n
    block[k_axis] = view[k_axis]
    block[rows] = min(view[rows], tile[rows])
    for d in range(n - 1, -1, -1):
        if d == k_axis:
            continue
        block[d] = view[d]
        if block_bytes(block, itemsize) <= _BLOCK_BYTES:
            continue
        block[d] = tile[d]
        fit = max(1, _BLOCK_BYTES // block_bytes(block, itemsize))
        block[d] = min(view[d], fit * tile[d])
        break
    return tuple(block)


def fused_plan(k: int, shape, dtype):
    """(leaf order, view, K's axis in the view, block) of the kernel's
    call on a (K, *shape) stack."""
    order = leaf_order(tuple(shape), dtype)
    view, k_axis = stack_view(k, [shape[d] for d in order])
    return order, view, k_axis, fused_block(view, k_axis,
                                            np.dtype(dtype).itemsize)


def _weighted_sum_kernel(c_ref, g_ref, out_ref, *, k_axis: int):
    def term(i):
        return c_ref[i] * g_ref[(slice(None),) * k_axis + (i,)].astype(
            jnp.float32)

    acc = term(0)
    for i in range(1, g_ref.shape[k_axis]):
        acc = acc + term(i)
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def encode_decode_pallas(a: jax.Array, row: jax.Array, g: jax.Array, *,
                         interpret: bool = False) -> jax.Array:
    """y = Σ_k a · row[k] · g[k] in one HBM pass.

    a: decode weight, scalar or (1,); row: (K,) coding row;
    g: (K, *shape) per-shard stack -> (*shape), in g's dtype.
    """
    k, shape = g.shape[0], g.shape[1:]
    assert row.shape == (k,), (row.shape, g.shape)
    f32 = jnp.float32
    coef = jnp.reshape(a, ()).astype(f32) * row.astype(f32)   # (K,)
    order, view, k_axis, block = fused_plan(k, shape, g.dtype)
    g = jnp.transpose(g, (0,) + tuple(1 + d for d in order))  # a bitcast
    keep = [d for d in range(len(view)) if d != k_axis]
    drop_k = lambda t: tuple(t[d] for d in keep)

    def g_index(*i):
        return i[:k_axis] + (0,) + i[k_axis:]

    operand = g.reshape(view)
    out_shape = jax.ShapeDtypeStruct(drop_k(view), g.dtype)
    if not interpret:  # the interpreter has no memory spaces
        operand = pltpu.with_memory_space_constraint(operand, pltpu.HBM)
        out_shape = pltpu.HBM(out_shape.shape, out_shape.dtype)
    out = pl.pallas_call(
        functools.partial(_weighted_sum_kernel, k_axis=k_axis),
        grid=tuple(pl.cdiv(v, b) for v, b in zip(drop_k(view),
                                                 drop_k(block))),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),          # coefficients
            pl.BlockSpec(block, g_index),
        ],
        out_specs=pl.BlockSpec(drop_k(block), lambda *i: i),
        out_shape=out_shape,
        interpret=interpret,
    )(coef, operand)
    out = out.reshape(g.shape[1:])              # the leaf, device order
    return jnp.transpose(out, tuple(int(d) for d in np.argsort(order)))
