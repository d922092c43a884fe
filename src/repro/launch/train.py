"""Production training launcher.

  PYTHONPATH=src python -m repro.launch.train \
      --arch gemma-2b [--reduced] --steps 100 --workers 4 \
      --scheme xf --data-par 1 --model-par 1 [--coded/--uncoded] \
      [--env cluster_env.json]

Builds a (data, model) mesh over the available devices, initializes the
TrainState with the config's sharding rules, and runs either the coded
trainer (paper technique; straggler realizations simulated host-side)
or the plain pjit baseline.  On a TPU slice the same entry point scales
to the production meshes in launch/mesh.py.

Each step is a ``StepTraceAnnotation("train")`` holding host spans
(``batch_build``, ``straggler_draw``, ``dispatch``, ``wait``,
``metrics_sync``, ``ckpt_save``, ``replan``); a profiler session
(``jax.profiler.start_server`` or ``trace``) puts them on the device
trace's clock (docs/PERF.md, "Profiling a run").

The straggler environment is ``Env.iid(ShiftedExponential(mu), N)`` by
default; ``--env`` loads a full worker-population model (heterogeneous
per-worker distributions, degradations, traces) from an
``Env.to_dict()`` JSON file, so a production launch plans its partition
for the cluster it actually runs on.
"""
from __future__ import annotations

import argparse
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager, CkptConfig, CodedSpec
from repro.configs import get_config
from repro.core import Env, Plan, ShiftedExponential
from repro.data.pipeline import DataConfig, SyntheticTokens, coded_worker_batches
from repro.dist.sharding import make_rules, use_mesh
from repro.launch.mesh import make_local_mesh
from repro.models.params import count_params
from repro.train.state import init_train_state
from repro.train.trainer import TrainConfig, make_coded_train_step, make_train_step

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> None:
    """Keep JAX's persistent compile cache at ``<repo>/.jax_cache``.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing.  The path is fixed: it is part of the cache key.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_ROOT / ".jax_cache"))


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gc-lm-110m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--scheme", "--solver", dest="scheme", default="xf",
                    help="any name from repro.core.available_schemes(), or "
                         "'auto' to search the launch space (repro.tune)")
    ap.add_argument("--autotune", action="store_true",
                    help="shorthand for --scheme auto")
    ap.add_argument("--hbm-gb", type=float, default=0.0,
                    help="per-worker HBM cap in GiB for the autotuner "
                         "(0: uncapped); implies --autotune")
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mu", type=float, default=1e-3)
    ap.add_argument("--env", default="",
                    help="JSON file with an Env.to_dict() worker-population "
                         "model (overrides --mu/--workers defaults)")
    ap.add_argument("--adapt", action="store_true",
                    help="adaptive re-planning: monitor realized per-worker "
                         "completion times, re-solve + hot-swap the plan on "
                         "drift (docs/ADAPTIVE.md)")
    ap.add_argument("--adapt-window", type=int, default=128,
                    help="sliding-window rounds for the runtime monitor")
    ap.add_argument("--uncoded", action="store_true")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint every N steps (0: once, after training "
                         "ends); resumes from the newest intact checkpoint "
                         "under --ckpt on startup")
    ap.add_argument("--ckpt-coded", type=int, default=0, metavar="S",
                    help="erasure-code checkpoints across the workers with S "
                         "parity shards (any workers-S survivors restore "
                         "bit-exactly; 0: monolithic npz)")
    return ap.parse_args(argv)


def setup(args):
    """(cfg, mesh, env, cfg_t, data) from the CLI args; ``--env`` also
    sets ``args.workers`` to the loaded population's size."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = cfg.replace(max_seq=max(args.seq * 2, 512))
    mesh = make_local_mesh(args.data_par, args.model_par)
    if args.env:
        with open(args.env) as f:
            env = Env.from_dict(json.load(f))
        args.workers = env.n_workers
    else:
        env = Env.iid(ShiftedExponential(mu=args.mu, t0=50.0), args.workers)
    cfg_t = TrainConfig(lr=args.lr, warmup=max(args.steps // 10, 5),
                        total_steps=args.steps)
    data = SyntheticTokens(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                      global_batch=args.global_batch))
    return cfg, mesh, env, cfg_t, data


def coded_setup(args, cfg, cfg_t, mesh, env, params):
    """The coded loop's plan, mode and step factory.

    Returns ``(plan, mode, step_for)``: ``step_for(plan)`` is the jitted
    coded step ``(state, worker_batches, dec_w) -> (state, metrics)``,
    compiled once per partition.  Mode is ``spmd`` when every worker
    has its own data rank, else ``sim`` (all workers on one device).
    """
    reduce_mode, grad_dtype, pipeline = "psum", None, "auto"
    if args.autotune or args.hbm_gb or args.scheme == "auto":
        from repro.tune import MemBudget, autotune

        budget = (MemBudget.from_gb(args.hbm_gb)
                  if args.hbm_gb else None)
        res = autotune(cfg, env, budget,
                       global_batch=args.global_batch,
                       seq_len=args.seq)
        plan, best = res.plan, res.best
        reduce_mode, pipeline = best.reduce_mode, best.pipeline
        grad_dtype = jnp.bfloat16 if best.grad_dtype == "bf16" else None
        print(f"autotune: {len(res.report.candidates)} admissible, "
              f"{len(res.report.pruned)} pruned "
              f"(budget {budget or 'uncapped'})")
        print(res.report.table())
        print(f"selected {best.label()}")
    else:
        plan = Plan.build(params, env, scheme=args.scheme)
    mode = "spmd" if args.data_par == args.workers else "sim"
    step_mesh = mesh if mode == "spmd" else None
    step_cache = {}

    def step_for(p):
        key = p.partition_key()
        if key not in step_cache:
            step_cache[key] = jax.jit(make_coded_train_step(
                cfg, cfg_t, p, mesh=step_mesh, mode=mode,
                reduce_mode=reduce_mode, grad_dtype=grad_dtype,
                pipeline=pipeline))
        return step_cache[key]

    return plan, mode, step_for


def main():
    args = parse_args()
    use_compile_cache()
    cfg, mesh, env, cfg_t, data = setup(args)

    manager = None
    if args.ckpt:
        spec = CodedSpec(n_shards=args.workers, parity=args.ckpt_coded) \
            if args.ckpt_coded else None
        manager = CheckpointManager(CkptConfig(
            dir=args.ckpt, every=args.ckpt_every, coded=spec))

    with use_mesh(mesh, make_rules(cfg)):
        state, axes = init_train_state(cfg, jax.random.PRNGKey(0))
        print(f"{cfg.name}: {count_params(state.params)/1e6:.1f}M params, "
              f"mesh {dict(mesh.shape)}, coded={not args.uncoded}")
        if manager is not None:
            restored = manager.restore_latest(state)
            if restored is not None:
                state, resumed = restored
                print(f"resumed from checkpoint step {resumed} "
                      f"under {args.ckpt}")
        span = jax.profiler.TraceAnnotation
        if args.uncoded:
            step = jax.jit(make_train_step(cfg, cfg_t))
            i = int(state.step)
            while i < args.steps:
                with jax.profiler.StepTraceAnnotation("train", step_num=i):
                    with span("batch_build"):
                        batch = {"tokens": jnp.asarray(data.batch(i))}
                    with span("dispatch"):
                        state, metrics = step(state, batch)
                    with span("wait"):
                        jax.block_until_ready((state, metrics))
                    with span("metrics_sync"):
                        done, loss = int(state.step), float(metrics["loss"])
                    if manager is not None:
                        with span("ckpt_save"):
                            manager.maybe_save(done, state)
                if i % 10 == 0 or i == args.steps - 1:
                    print(f"step {i:4d} loss {loss:.4f}")
                i = done
        else:
            plan, mode, step_for = coded_setup(args, cfg, cfg_t, mesh, env,
                                               state.params)
            sim = plan.simulator(env)
            step = step_for(plan)
            controller = None
            if args.adapt:
                from repro.adapt import AdaptConfig, AdaptiveController

                controller = AdaptiveController(
                    AdaptConfig(window=args.adapt_window), plan, state.params)
            print(f"plan x={plan.x.tolist()} s_max={plan.s_max} mode={mode} "
                  f"adapt={bool(controller)}")
            i = int(state.step)
            while i < args.steps:
                with jax.profiler.StepTraceAnnotation("train", step_num=i):
                    with span("batch_build"):
                        wb = jnp.asarray(coded_worker_batches(
                            data, i, args.workers, plan.s_max))
                    with span("straggler_draw"):
                        dec_w, rec = sim.step()
                    with span("dispatch"):
                        state, metrics = step(state, wb, dec_w)
                    with span("wait"):
                        jax.block_until_ready((state, metrics))
                    with span("metrics_sync"):
                        done, loss = int(state.step), float(metrics["loss"])
                    if manager is not None:
                        with span("ckpt_save"):
                            manager.maybe_save(done, state,
                                               extra={"plan": plan.to_dict()})
                    if controller is not None:
                        with span("replan"):
                            new_plan = controller.observe(rec["times"])
                            if new_plan is not None:
                                plan, sim.plan = new_plan, new_plan
                                step = step_for(new_plan)
                                gain = controller.swaps[-1].predicted_gain
                                print(f"step {i:4d} plan swap -> "
                                      f"x={plan.x.tolist()} (predicted gain "
                                      f"{gain:.1%})")
                if i % 10 == 0 or i == args.steps - 1:
                    print(f"step {i:4d} loss {loss:.4f} "
                          f"tau_c {rec['tau_coded']:.3g} "
                          f"tau_u {rec['tau_uncoded']:.3g}")
                i = done
            print("ledger:", json.dumps(sim.summary()))
            if controller is not None:
                print(f"adaptive: {len(controller.swaps)} plan swap(s), "
                      f"{controller.checks} drift check(s)")
    if manager is not None and manager.last_saved != int(state.step):
        extra = {} if args.uncoded else {"plan": plan.to_dict()}
        print("saved:", manager.save(int(state.step), state, extra=extra))


if __name__ == "__main__":
    main()
