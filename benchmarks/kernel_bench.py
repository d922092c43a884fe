"""Kernel microbenchmark: gc_encode / gc_decode / gc_fused us-per-call
and effective GB/s on this host (jnp oracle path — the TPU path is the
Pallas kernel, validated in interpret mode by the test suite).

``gc_fused`` is the encode⊙decode single-pass combine the flat training
pipeline runs (kernels/gc_fused): the weighted sum of a (K, D) per-shard
stack, as for a 1-D leaf; comparing its row against gc_encode +
gc_decode at the same shape shows what the fusion saves.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref


def _bench(fn, *args, iters: int = 20) -> float:
    jax.block_until_ready(fn(*args))  # one warmup/compile call
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run(verbose: bool = True, smoke: bool = False) -> list:
    rng = np.random.default_rng(0)
    rows = []
    shapes = [(4, 1 << 16, jnp.float32)] if smoke else \
        [(4, 1 << 20, jnp.float32), (8, 1 << 22, jnp.float32),
         (4, 1 << 22, jnp.bfloat16)]
    for k, d, dt in shapes:
        g = jnp.asarray(rng.standard_normal((k, d)), dt)
        b = jnp.asarray(rng.standard_normal((1, k)), dt)
        a = jnp.asarray(rng.standard_normal(k), dt)
        row = jnp.asarray(rng.standard_normal(k), jnp.float32)
        a1 = jnp.float32(rng.standard_normal())
        nbytes = g.size * g.dtype.itemsize
        for name, t in (
            ("gc_encode", _bench(ref.encode_ref, b, g)),
            ("gc_decode", _bench(ref.decode_ref, a, g)),
            ("gc_fused", _bench(ref.encode_decode_ref, a1, row, g)),
        ):
            rows.append({"kernel": name, "k": k, "d": d,
                         "dtype": str(dt.__name__), "us": t * 1e6,
                         "gbps": nbytes / t / 1e9})
    if verbose:
        for r in rows:
            print(f"{r['kernel']},K={r['k']},D={r['d']},{r['dtype']},"
                  f"{r['us']:.1f}us,{r['gbps']:.1f}GB/s")
    return rows


def main(smoke: bool = False) -> list:
    rows = run(smoke=smoke)
    print("kernel_bench: OK")
    return rows


if __name__ == "__main__":
    main()
