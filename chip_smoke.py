"""Chip smoke test: coded training of gc-lm-110m at its published widths.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # the coded spmd path on a 4-chip host

One chip: builds the state, plan and jitted coded step through the
training launcher (``repro.launch.train``) in sim mode, asserts the
fused Pallas combine kernel is in the compiled step, takes a few
training steps, and checks on the chip that the coded gradient equals
the uncoded mean gradient for every straggler count 0..s_max.

``--chips 4``: runs only the coded spmd path over a ``data=4, model=1``
mesh — psum and psum_scatter, each with fp32 and bf16 ``grad_dtype``,
against the uncoded gradient on the same mesh for every straggler
count — then a few launcher steps.

Every failure raises.  Without a TPU the script exits non-zero before
printing a result.  The last line of stdout is one JSON object naming
the device; everything else is printed before it.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.data.pipeline import coded_worker_batches  # noqa: E402
from repro.dist.sharding import make_rules, use_mesh  # noqa: E402
from repro.launch.train import (coded_setup, parse_args, setup,  # noqa: E402
                                use_compile_cache)
from repro.train.coded import make_coded_grad_fn, uncoded_grad_fn  # noqa: E402
from repro.train.state import init_train_state  # noqa: E402

# One chip, sim mode: all N workers' K = s_max+1 per-shard gradients are
# stacked on one device, (N, K, *params) fp32.  Compiled for a described
# v5e, N=3 (xf: s_max=2) needs 11.71 GiB at this batch; N=4 needs 15.39.
ONE_CHIP_ARGS = ["--arch", "gc-lm-110m", "--scheme", "xf", "--workers", "3",
                 "--global-batch", "6", "--seq", "256", "--steps", "4"]
FOUR_CHIP_ARGS = ["--arch", "gc-lm-110m", "--scheme", "xf", "--workers", "4",
                  "--data-par", "4", "--model-par", "1",
                  "--global-batch", "8", "--seq", "256", "--steps", "3"]

# Relative L2 error of the coded gradient against the uncoded one, over
# the whole gradient tree.  A float32 matmul on the TPU runs at its
# default precision as one bfloat16 pass (unit roundoff 2**-9 ~ 2e-3);
# the coded and uncoded programs round the 12-layer backward and the
# decode-weighted sums independently, so allow ~10 roundoffs.  A bf16
# grad_dtype also rounds each rank's contribution and the reduction to
# bfloat16 (2**-8): allow 5e-2.  A missing or doubled shard moves the
# error by ~1/N, far above both.
TOL_FP32 = 2e-2
TOL_BF16 = 5e-2


@jax.jit
def _rel_err(g, ref):
    num = sum(jnp.sum((a.astype(jnp.float32) - b) ** 2)
              for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref)))
    den = sum(jnp.sum(b ** 2) for b in jax.tree.leaves(ref))
    return jnp.sqrt(num / den)


def _straggler_dec_w(plan, u):
    """Decode weights with the first ``u`` workers as stragglers."""
    times = np.ones(plan.n_workers)
    times[:u] = 1e6
    return jnp.asarray(plan.decode_weights(times), jnp.float32)


def _check_parity(label, grad_fn, params, wb, plan, g_ref, tol):
    for u in range(plan.s_max + 1):
        err = float(_rel_err(grad_fn(params, wb, _straggler_dec_w(plan, u)),
                             g_ref))
        print(f"parity {label} stragglers={u}: rel_err {err!r} (tol {tol})",
              flush=True)
        if not err <= tol:
            raise AssertionError(f"coded != uncoded gradient ({label}, "
                                 f"{u} stragglers): {err} > {tol}")


def _uncoded_ref(cfg, params, data, step, n):
    shards = jnp.asarray(np.stack([data.shard(step, i, n) for i in range(n)]))
    grad_fn = jax.jit(uncoded_grad_fn(cfg, n))
    return grad_fn(params, shards)


def _train(step, state, data, sim, n, s_max, steps):
    """``steps`` launcher steps; returns the state and the step seconds."""
    times = []
    for i in range(steps):
        wb = jnp.asarray(coded_worker_batches(data, i, n, s_max))
        dec_w = jnp.asarray(sim.step()[0], jnp.float32)
        t0 = time.perf_counter()
        state, metrics = step(state, wb, dec_w)
        jax.block_until_ready(state)
        times.append(time.perf_counter() - t0)
        loss = float(metrics["loss"])
        print(f"step {i} loss {loss!r} step_s {times[-1]!r}", flush=True)
        if not math.isfinite(loss):
            raise AssertionError(f"non-finite loss at step {i}: {loss}")
    return state, times


def one_chip():
    args = parse_args(ONE_CHIP_ARGS)
    cfg, mesh, env, cfg_t, data = setup(args)
    n = args.workers
    with use_mesh(mesh, make_rules(cfg)):
        state, _ = init_train_state(cfg, jax.random.PRNGKey(0))
        plan, mode, step_for = coded_setup(args, cfg, cfg_t, mesh, env,
                                           state.params)
        sim = plan.simulator(env)
        print(f"setting: workers={n} scheme={args.scheme} s_max={plan.s_max} "
              f"levels={len(plan.used_levels)} mode={mode} "
              f"global_batch={args.global_batch} seq={args.seq}", flush=True)

        wb = jnp.asarray(coded_worker_batches(data, 0, n, plan.s_max))
        dec_w = jnp.asarray(plan.full_decode_weights(), jnp.float32)
        t0 = time.perf_counter()
        compiled = step_for(plan).lower(state, wb, dec_w).compile()
        print(f"compile_s {time.perf_counter() - t0!r}", flush=True)
        if "tpu_custom_call" not in compiled.as_text():
            raise AssertionError("the Pallas combine kernel is not in the "
                                 "compiled coded step")
        mem = compiled.memory_analysis()
        print(f"step program bytes: temp {mem.temp_size_in_bytes} "
              f"args {mem.argument_size_in_bytes} "
              f"outputs {mem.output_size_in_bytes}", flush=True)

        state, times = _train(compiled, state, data, sim, n, plan.s_max,
                              args.steps)
        print(f"step_s median {float(np.median(times[1:]))!r}", flush=True)
        stats = jax.devices()[0].memory_stats() or {}
        print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}",
              flush=True)

        g_ref = _uncoded_ref(cfg, state.params, data, 0, n)
        coded = jax.jit(make_coded_grad_fn(cfg, plan, mode=mode))
        _check_parity("sim fp32", coded, state.params, wb, plan, g_ref,
                      TOL_FP32)


def four_chips():
    args = parse_args(FOUR_CHIP_ARGS)
    cfg, mesh, env, cfg_t, data = setup(args)
    n = args.workers
    with use_mesh(mesh, make_rules(cfg)):
        state, _ = init_train_state(cfg, jax.random.PRNGKey(0))
        plan, mode, step_for = coded_setup(args, cfg, cfg_t, mesh, env,
                                           state.params)
        if mode != "spmd":
            raise AssertionError(f"expected the spmd path, got {mode}")
        print(f"setting: mesh {dict(mesh.shape)} workers={n} "
              f"scheme={args.scheme} s_max={plan.s_max} mode={mode}",
              flush=True)
        wb = jnp.asarray(coded_worker_batches(data, 0, n, plan.s_max))
        g_ref = _uncoded_ref(cfg, state.params, data, 0, n)
        variants = {
            f"{reduce_mode} {dtype_name}": (jax.jit(make_coded_grad_fn(
                cfg, plan, mesh=mesh, mode=mode, reduce_mode=reduce_mode,
                grad_dtype=grad_dtype)), tol)
            for reduce_mode in ("psum", "psum_scatter")
            for dtype_name, grad_dtype, tol in (("fp32", None, TOL_FP32),
                                                ("bf16", jnp.bfloat16,
                                                 TOL_BF16))}
        for label, (fn, tol) in variants.items():
            _check_parity(label, fn, state.params, wb, plan, g_ref, tol)
        del g_ref
        # the launcher's jitted step, compiled on its first call (and again
        # on the second, when the state comes back with new shardings)
        _train(step_for(plan), state, data, plan.simulator(env), n,
               plan.s_max, args.steps)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = ap.parse_args().chips
    use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found {dev.platform}")
    if len(jax.devices()) < chips:
        raise SystemExit(f"--chips {chips} needs {chips} devices; JAX found "
                         f"{len(jax.devices())}")
    print(f"device {dev.device_kind} x{len(jax.devices())}, jax "
          f"{jax.__version__}", flush=True)
    if chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
