"""Inputs of a coded-training cell, made from the run's seed.

One general generator reads every traffic file.  Shard ``j`` of step
``t`` is a pure function of ``(seed, t, j)``, so any worker, and the
reference, can make any shard on its own.  Every seed gives the same
sizes; only the token values and frame values differ.

Whisper-style cells also need frame embeddings (the stubbed audio
front end's output).  They come from a fixed pool made on the device
in one jitted call during set-up; utterance ``r`` of shard ``j`` at
step ``t`` is pool row ``((t * N + j) * rows + r) % pool``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class ShardTokens:
    """Random access to token shards: ``shard(step, j, n)`` -> (rows, S+1).

    The ``shard`` signature is the one the program's
    ``coded_worker_batches`` asks of its data source.
    """

    def __init__(self, seed: int, vocab: int, rows: int, seq_len: int):
        self.seed, self.vocab, self.rows, self.seq_len = (
            int(seed), int(vocab), int(rows), int(seq_len))

    def shard(self, step: int, shard_idx: int, n_shards: int) -> np.ndarray:
        del n_shards  # a shard's rows do not depend on the worker count
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, int(step), int(shard_idx)]))
        return rng.integers(0, self.vocab, size=(self.rows, self.seq_len + 1),
                            dtype=np.int32)


def frame_ids(step: int, shard_idx, n_workers: int, rows: int,
              pool: int) -> np.ndarray:
    """Pool rows of shard(s) ``shard_idx`` at ``step``: (..., rows)."""
    j = np.asarray(shard_idx, np.int64)
    base = (int(step) * n_workers + j) * rows
    return ((base[..., None] + np.arange(rows)) % pool).astype(np.int32)


def key_of(seed: int, salt: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)
    return jax.random.fold_in(key, salt)


def make_frame_pool(seed: int, pool: int, n_frames: int, d_model: int,
                    std: float, sharding=None) -> jax.Array:
    """(pool, n_frames, d_model) float32 frame embeddings on the device."""
    key = key_of(seed, 1)

    def make(k):
        return std * jax.random.normal(k, (pool, n_frames, d_model),
                                       jnp.float32)

    return jax.jit(make, out_shardings=sharding)(key)
