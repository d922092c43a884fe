"""Initial weights of a cell, made on the device from the run's seed.

The benchmark makes the weights, not the program, so that its reference
can start from the same values without taking anything the program
made.  The tree's structure (the names and shapes of the leaves) is the
program's parameter layout; the values come from one jitted call:

  * weight matrices: truncated normal, std 1/sqrt(fan_in) (fan_in is the
    input width: the first axis of a q/k/v projection, else every axis
    but the last), and 0.02 for the token embedding;
  * norm scales: 1 + 0.1 N(0, 1) for a layer norm (one that has a bias),
    0.1 N(0, 1) for an RMS norm (stored as scale - 1);
  * biases: 0.02 N(0, 1); cross-attention gates: 0.5 + 0.1 N(0, 1), so
    that the encoder's gradient is not zero at the start.

A leading axis of a stacked layer run (a leaf under ``stack``) is a
layer count, not a fan-in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perf.traffic import key_of

_QKV = ("wq", "wk", "wv")


def _names(path) -> tuple:
    return tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _leaf(key, names, shape, dtype, layer_norm: bool):
    name = names[-1]
    per_layer = shape[1:] if "stack" in names else shape
    if name == "scale":
        val = (1.0 if layer_norm else 0.0) + 0.1 * jax.random.normal(
            key, shape, jnp.float32)
    elif name in ("bias", "bq", "bk", "bv"):
        val = 0.02 * jax.random.normal(key, shape, jnp.float32)
    elif name == "gate":
        val = 0.5 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:
        if name == "tok":
            std = 0.02
        else:
            fan_in = per_layer[0] if name in _QKV else math.prod(per_layer[:-1])
            std = 1.0 / math.sqrt(max(fan_in, 1))
        val = std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                                jnp.float32)
    return val.astype(dtype)


def make_params(shapes, seed: int, sharding=None):
    """Parameter tree shaped like ``shapes`` (ShapeDtypeStructs), from
    ``seed``, on the device(s) of ``sharding`` (default device if None)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_names(p) for p, _ in flat]
    present = set(names)
    layer_norm = [n[-1] == "scale" and n[:-1] + ("bias",) in present
                  for n in names]

    def make(key):
        leaves = [_leaf(jax.random.fold_in(key, i), n, s.shape, s.dtype, ln)
                  for i, ((_, s), n, ln) in enumerate(zip(flat, names,
                                                          layer_norm))]
        return jax.tree.unflatten(treedef, leaves)

    out = None if sharding is None else jax.tree.map(lambda _: sharding,
                                                     shapes)
    return jax.jit(make, out_shardings=out)(key_of(seed, 0))
