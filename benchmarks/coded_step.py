"""Coded-step combine benchmark: tree vs flat fused pipeline.

Times the coded gradient COMBINE (encode + decode-weighted mean over
workers — ``repro.train.coded.combine_grads``) on synthetic per-shard
gradients with the real gc-lm-110m leaf structure, for both pipelines:

  * ``tree`` — the legacy per-leaf loop: lax.map over workers, per-leaf
    encode tensordot, per-leaf decode-weight scale, per-leaf sum, 1/N.
  * ``flat`` — the fused pipeline: per leaf ONE weighted sum
    ``Σ (dec_w ⊙ rows / N) · g`` streaming the whole (N*K, *shape)
    shard stack once (kernels/gc_fused math; ``Plan.flat_layout``
    supplies the leaf -> level binding).

Effective GB/s is the mandatory traffic N*K*D*4 bytes (every pipeline
must read every per-shard gradient once) over wall time; the flat
pipeline's win is everything it does NOT do beyond that read.

The non-smoke run sizes the model to the full gc-lm-110m config and
ASSERTS the flat pipeline is >= MIN_SPEEDUP_FULL faster on this host —
the repo's perf-trajectory gate.  ``--smoke`` (CI) runs a tiny reduced
shape and asserts flat is at worst SMOKE_SLACK x tree (a regression
guard, not a throughput claim — tiny shapes are dispatch-bound).
Both emit machine-readable ``BENCH_coded_step.json``.
"""
from __future__ import annotations

import json
import os
import platform

import jax
import jax.numpy as jnp
import numpy as np

from .kernel_bench import _bench

#: non-smoke gate: flat must beat tree by at least this factor
MIN_SPEEDUP_FULL = 1.3
#: smoke gate: flat may never be slower than tree by more than this
SMOKE_SLACK = 1.15

JSON_DEFAULT = "BENCH_coded_step.json"


def _synthetic_grads(shapes, n_workers: int, k: int, seed: int = 0):
    """(N, K, *shape) fp32 leaves — float32 draws (standard_normal would
    be fp64 and dominate setup time at 110M params)."""
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.random((n_workers, k) + s, dtype=np.float32) - 0.5)
            for s in shapes]


def run(smoke: bool = False, verbose: bool = True, seed: int = 0,
        json_path: str = JSON_DEFAULT) -> dict:
    from repro.configs import get_config
    from repro.core import Plan, ShiftedExponential
    from repro.train.coded import combine_grads
    from repro.train.state import init_train_state

    cfg = get_config("gc-lm-110m")
    if smoke:
        cfg = cfg.reduced(n_layers=2, d_model=128)
    # abstract init: leaf structure without materializing weights
    shape_tree = jax.eval_shape(
        lambda: init_train_state(cfg, jax.random.PRNGKey(0))[0].params)
    env = ShiftedExponential(mu=1e-3, t0=50.0)
    n_workers = 4
    plan = Plan.build(shape_tree, env, n_workers, scheme="xf", s_cap=1)
    layout = plan.flat_layout
    k = plan.k_shards
    shapes = layout.leaf_shapes
    d_total = layout.total_elems
    leaves = _synthetic_grads(shapes, n_workers, k, seed)
    treedef = jax.tree.structure(shape_tree)
    grads = jax.tree.unflatten(treedef, leaves)
    # one realized straggler: decode weights renormalize the survivors
    times = np.ones(n_workers)
    times[-1] = 1e6
    dec_w = jnp.asarray(plan.decode_weights(times), jnp.float32)

    fns = {
        p: jax.jit(lambda g, d, p=p: combine_grads(plan, g, d, pipeline=p))
        for p in ("tree", "flat")
    }
    iters = 10 if smoke else 4
    nbytes = n_workers * k * d_total * 4
    out = {
        "bench": "coded_step",
        "smoke": bool(smoke),
        "config": cfg.name,
        "n_workers": n_workers,
        "k_shards": k,
        "n_levels": layout.n_levels,
        "n_leaves": layout.n_leaves,
        "params": d_total,
        "bytes_per_step": nbytes,
        "iters": iters,
        "host": {
            "backend": jax.default_backend(),
            "platform": platform.platform(),
            "cpu_count": os.cpu_count(),
        },
    }
    for name, fn in fns.items():
        t = _bench(fn, grads, dec_w, iters=iters)
        out[name] = {"seconds": t, "gbps": nbytes / t / 1e9}
        if verbose:
            print(f"{name:4s}: {t * 1e3:8.1f} ms/step   "
                  f"{out[name]['gbps']:6.2f} GB/s effective")
    out["speedup"] = out["tree"]["seconds"] / out["flat"]["seconds"]
    # exactness rides along: the two pipelines must agree bitwise-close
    gt = fns["tree"](grads, dec_w)
    gf = fns["flat"](grads, dec_w)
    err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), gt, gf)))
    out["max_abs_err"] = err
    if verbose:
        print(f"speedup: flat {out['speedup']:.2f}x tree   "
              f"(max |flat - tree| = {err:.2e})")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
        if verbose:
            print(f"wrote {json_path}")
    assert err < 1e-4, f"flat/tree combine disagree: {err}"
    if smoke:
        assert out["flat"]["seconds"] <= SMOKE_SLACK * out["tree"]["seconds"], (
            f"PERF REGRESSION: flat combine {out['flat']['seconds']:.4f}s is "
            f">{SMOKE_SLACK}x slower than tree {out['tree']['seconds']:.4f}s")
    else:
        assert out["speedup"] >= MIN_SPEEDUP_FULL, (
            f"PERF REGRESSION: flat speedup {out['speedup']:.2f}x < "
            f"{MIN_SPEEDUP_FULL}x at {cfg.name} scale")
    return out


def main(smoke: bool = False, json_path: str = None) -> dict:
    """Smoke runs skip the default JSON file so CI never clobbers the
    committed full-scale ``BENCH_coded_step.json`` (the runner's
    ``--json`` captures the smoke rows instead)."""
    if json_path is None:
        json_path = "" if smoke else JSON_DEFAULT
    out = run(smoke=smoke, json_path=json_path)
    print("coded_step: OK")
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    main(smoke=args.smoke, json_path=args.json)
