"""Training steps + the Trainer driver.

``make_train_step``       — standard pjit step (uncoded baseline): GSPMD
                            aggregates gradients from the sharded batch.
``make_coded_train_step`` — the paper's step: coded per-shard gradients,
                            decode-weighted reduction, then AdamW.  The
                            decode weights (straggler realization) are a
                            per-step *input*, sampled host-side by
                            ``plan.simulator(dist)``, so one compiled
                            step serves every realization.
``Trainer``               — loop: data, straggler sim, runtime ledger,
                            checkpointing, metrics.

The step's ``monitor_forward`` and ``optimizer`` named scopes, with those
of ``repro.train.coded``, name each device op's layer in a profiler
trace; ``Trainer.run`` marks each step (``StepTraceAnnotation("train")``)
and its host work (``TraceAnnotation`` spans) on the same clock.  See
docs/PERF.md, "Profiling a run".
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Env, Plan
from repro.data.pipeline import DataConfig, SyntheticTokens, coded_worker_batches
from repro.models.model import train_loss
from repro.optim.optim import adamw_update, clip_by_global_norm, cosine_schedule
from .coded import make_coded_grad_fn
from .state import TrainState, init_train_state


@dataclass
class TrainConfig:
    lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.01
    clip_norm: float = 1.0
    b1: float = 0.9
    b2: float = 0.95


@jax.named_scope("optimizer")
def _apply_update(cfg_t: TrainConfig, state: TrainState, grads, metrics):
    lr = cosine_schedule(state.step, cfg_t.lr, cfg_t.warmup, cfg_t.total_steps)
    grads, gnorm = clip_by_global_norm(grads, cfg_t.clip_norm)
    params, opt = adamw_update(grads, state.opt, state.params, lr,
                               b1=cfg_t.b1, b2=cfg_t.b2,
                               weight_decay=cfg_t.weight_decay)
    metrics = dict(metrics, grad_norm=gnorm, lr=lr)
    return TrainState(params=params, opt=opt, step=state.step + 1), metrics


def make_train_step(cfg, cfg_t: TrainConfig) -> Callable:
    """Uncoded pjit step: (state, batch) -> (state, metrics)."""

    def step(state: TrainState, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: train_loss(cfg, p, batch), has_aux=True
        )(state.params)
        return _apply_update(cfg_t, state, grads, metrics)

    return step


def make_coded_train_step(cfg, cfg_t: TrainConfig, plan: Plan, *,
                          mesh=None, mode: str = "sim", reduce_mode: str = "psum",
                          grad_dtype=None, param_shapes=None,
                          param_axes=None, pipeline: str = "auto") -> Callable:
    """Coded step: (state, worker_batches, dec_w) -> (state, metrics).

    worker_batches: (N, K, rows, S+1); dec_w: (n_used, N) from
    ``plan.simulator(...).step()`` — zeros drop the realized stragglers, Tandon
    decode weights rescale the survivors, psum makes it exact.
    reduce_mode/grad_dtype/pipeline: see make_coded_grad_fn ('auto'
    takes the fused flat pipeline whenever the plan carries a
    ``FlatLayout``, i.e. it was built from a parameter pytree).
    """
    grad_fn = make_coded_grad_fn(cfg, plan, mesh=mesh, mode=mode,
                                 reduce_mode=reduce_mode, grad_dtype=grad_dtype,
                                 param_shapes=param_shapes, param_axes=param_axes,
                                 pipeline=pipeline)

    def step(state: TrainState, worker_batches, dec_w, worker_aux=None):
        grads = grad_fn(state.params, worker_batches, dec_w, worker_aux)
        # monitoring loss on shard 0 (cheap; the grads are what matter)
        with jax.named_scope("monitor_forward"):
            mon = {"tokens": worker_batches[0, 0]}
            if worker_aux is not None:
                mon["aux_inputs"] = worker_aux[0, 0]
            loss, metrics = train_loss(cfg, state.params, mon)
        return _apply_update(cfg_t, state, grads, metrics)

    return step


class Trainer:
    """End-to-end coded-training driver (used by examples/train_lm.py).

    ``env`` is the worker population the run is planned and simulated
    against: an ``Env`` (``n_workers`` then optional — the env knows its
    size) or a bare ``StragglerDistribution`` (coerced to
    ``Env.iid(dist, n_workers)``, the pre-Env behavior unchanged).

    ``scheme="auto"`` searches the joint launch space with
    ``repro.tune.autotune`` (optionally under a ``budget=MemBudget``):
    the winning candidate sets the plan AND any step knob the caller
    left at its open default — ``pipeline`` ('auto'), ``reduce_mode``
    ('psum'), ``grad_dtype`` (None) — and the search record lands on
    ``self.tune_report`` (docs/AUTOTUNE.md).

    ``adapt`` is an optional ``repro.adapt.AdaptConfig``: the trainer
    then feeds every round's realized per-worker completion times into
    an ``AdaptiveController`` and hot-swaps the plan (``swap_plan``)
    when drift makes re-planning pay — optimizer state, RNG stream, and
    step count untouched; see docs/ADAPTIVE.md.

    ``wave`` is an optional ``repro.train.wave.WaveConfig``: ``run``
    then executes rounds on the wave-pipelined (async) schedule instead
    of the barrier loop — staleness 0 is bit-identical to the barrier,
    staleness k overlaps up to k rounds; see docs/ASYNC.md.  Composes
    with ``adapt`` (swaps quiesce in-flight waves first).

    ``ckpt`` is an optional ``repro.checkpoint.CkptConfig``: the trainer
    then checkpoints every ``ckpt.every`` steps at step boundaries
    (erasure-coded across the workers when ``ckpt.coded`` is set),
    resumes from the newest intact checkpoint on construction
    (``ckpt.resume``), and arms the worker-death recovery path: a
    ``DeathWatch`` tripwire over the realized round times triggers
    forced re-plan + restore-from-survivors in one motion, recorded as
    a ``RecoveryEvent`` in ``self.recoveries``; see docs/CHECKPOINT.md.
    """

    def __init__(self, cfg, cfg_t: TrainConfig, env, *, n_workers: int = None,
                 scheme: str = None, global_batch: int = 32, seed: int = 0,
                 mesh=None, mode: str = "sim", data_kind: str = "zipf",
                 solver: str = None, pipeline: str = "auto", adapt=None,
                 wave=None, ckpt=None, budget=None, reduce_mode: str = "psum",
                 grad_dtype: str = None):
        if scheme is None:
            scheme = solver if solver is not None else "xf"  # `solver` is the legacy kw
        if n_workers is None:
            if isinstance(env, Env):
                n_workers = env.n_workers
            elif isinstance(env, (list, tuple)):
                n_workers = len(env)   # per-worker dists pin their own size
            else:
                n_workers = 8          # bare distribution: legacy default
        env = Env.coerce(env, n_workers)
        self.cfg, self.cfg_t = cfg, cfg_t
        self.env = self.dist = env  # `dist` is the legacy attribute name
        self.n_workers = n_workers
        self.mesh, self.mode, self.pipeline = mesh, mode, pipeline
        self.reduce_mode, self.grad_dtype = reduce_mode, grad_dtype
        self.tune_report = None
        key = jax.random.PRNGKey(seed)
        self.state, self.axes = init_train_state(cfg, key)
        if scheme == "auto":
            # model-aware search: the winner sets the plan AND the step
            # knobs (pipeline/reduce_mode/grad_dtype) the user left open
            from repro.tune import autotune

            res = autotune(cfg, env, budget, global_batch=global_batch,
                           seq_len=min(cfg.max_seq, 512), seed=seed)
            self.plan = res.plan
            self.tune_report = res.report
            best = res.best
            if pipeline == "auto":
                self.pipeline = best.pipeline
            if reduce_mode == "psum":       # the open default
                self.reduce_mode = best.reduce_mode
            if grad_dtype is None:
                self.grad_dtype = best.grad_dtype
        elif budget is not None:
            raise ValueError("budget= requires scheme='auto'")
        else:
            self.plan = Plan.build(self.state.params, env,
                                   scheme=scheme, rng=seed)
        self.sim = self.plan.simulator(env, seed=seed)
        self.data = SyntheticTokens(DataConfig(
            vocab=cfg.vocab, seq_len=min(cfg.max_seq, 512),
            global_batch=global_batch, seed=seed, kind=data_kind))
        #: compiled coded steps keyed by (partition, pipeline,
        #: reduce_mode, grad_dtype) — a swap back to a previously-seen
        #: partition reuses the compiled step.
        self._step_cache: dict = {}
        self.step_fn = self._step_fn_for(self.plan)
        self.controller = None
        if adapt is not None:
            from repro.adapt import AdaptiveController

            self.controller = AdaptiveController(adapt, self.plan,
                                                 self.state.params)
        self.history: list[dict] = []
        self.recoveries: list = []
        self.manager = self.deathwatch = None
        if ckpt is not None:
            from repro.adapt.monitor import DeathWatch
            from repro.checkpoint.manager import CheckpointManager

            self.manager = CheckpointManager(ckpt)
            if n_workers >= 2:
                self.deathwatch = DeathWatch(n_workers)
            if ckpt.resume:
                restored = self.manager.restore_latest(self.state)
                if restored is not None:
                    self.state = restored[0]
        self.wave = None
        if wave is not None:
            from .wave import WaveRunner

            self.wave = WaveRunner(self, wave)

    # ------------------------------------------------------------- hot swap
    def _step_fn_for(self, plan: Plan):
        key = (plan.partition_key(), self.pipeline, self.reduce_mode,
               self.grad_dtype)
        fn = self._step_cache.get(key)
        if fn is None:
            gd = (jnp.bfloat16 if self.grad_dtype == "bf16"
                  else None if self.grad_dtype in (None, "fp32")
                  else self.grad_dtype)
            fn = jax.jit(make_coded_train_step(
                self.cfg, self.cfg_t, plan, mesh=self.mesh, mode=self.mode,
                reduce_mode=self.reduce_mode, grad_dtype=gd,
                pipeline=self.pipeline))
            self._step_cache[key] = fn
        return fn

    def swap_plan(self, plan: Plan) -> None:
        """Hot-swap the coding plan at a step boundary (the swap epoch).

        Non-invasive by construction: optimizer state, data stream, RNG
        stream, and step count are untouched — only the plan the next
        step codes against changes.  The straggler simulator keeps its
        env/rng/ledger and just prices future rounds with the new plan;
        the compiled coded step comes from a per-(partition, pipeline,
        reduce_mode, grad_dtype) cache, so swapping back to a previous
        plan is free (tested bit-identical in tests/test_adaptive.py).
        """
        if plan.n_workers != self.n_workers:
            raise ValueError(f"plan has {plan.n_workers} workers, trainer "
                             f"runs {self.n_workers}")
        self.plan = plan
        self.sim.plan = plan
        if self.controller is not None and self.controller.plan is not plan:
            # manual swap (not controller-initiated): re-baseline the
            # re-planner too, or its pricing and slow-drift reference
            # would keep comparing against the plan no longer running.
            self.controller.plan = plan
            self.controller.monitor.reset()
        self.step_fn = self._step_fn_for(plan)

    # ------------------------------------------------------------- recovery
    def recover_from_deaths(self, newly_dead, log_fn=None):
        """Worker-death recovery in one motion: forced re-plan (routes
        future work off the dead workers) + erasure-coded restore from
        the surviving shards (rewinds to the last checkpoint — the dead
        workers' shards are gone, but any ``N - s`` survivors rebuild
        the exact state).  Returns the ``RecoveryEvent``, or ``None``
        when there is no checkpoint to restore from (training continues
        on gradient-level redundancy alone).

        The data stream is keyed by ``state.step``, so the rewound
        steps replay deterministically under the new plan.
        """
        from repro.adapt.controller import RecoveryEvent

        dead = tuple(sorted(self.deathwatch.dead)) \
            if self.deathwatch is not None else tuple(sorted(newly_dead))
        detected_at = int(self.state.step)
        swap = None
        if self.controller is not None:
            new_plan = self.controller.replan_now()
            if new_plan is not None:
                swap = self.controller.swaps[-1]
                self.swap_plan(new_plan)
        if self.manager is None or self.manager.latest() is None:
            if log_fn:
                log_fn(f"step {detected_at:5d}  worker death {list(newly_dead)}"
                       " — no checkpoint to restore; continuing on redundancy")
            return None
        self.state, ckpt_step = self.manager.restore_from_survivors(
            self.state, missing=dead)
        ev = RecoveryEvent(step=detected_at, dead_workers=dead,
                           ckpt_step=ckpt_step, swap=swap)
        self.recoveries.append(ev)
        if log_fn:
            log_fn(f"step {detected_at:5d}  worker death {list(newly_dead)} -> "
                   f"re-plan{' + swap' if swap else ' skipped'}, coded restore "
                   f"from survivors @ step {ckpt_step}")
        return ev

    def _after_round(self, rec, metrics, log_every, log_fn) -> int:
        """Feed the round's realized times to the controller (swapping the
        plan on drift) and the death watch (recovering from deaths);
        returns the step to run next, which a recovery rewinds."""
        if self.controller is not None:
            new_plan = self.controller.observe(rec["times"])
            if new_plan is not None:
                self.swap_plan(new_plan)
                metrics["plan_swap"] = 1
                if log_every:
                    log_fn(f"step {metrics['step']:5d}  plan swap -> "
                           f"x={new_plan.x.tolist()} (predicted gain "
                           f"{self.controller.swaps[-1].predicted_gain:.1%})")
        if self.deathwatch is not None:
            newly = self.deathwatch.observe(rec["times"])
            if newly:
                ev = self.recover_from_deaths(
                    newly, log_fn if log_every else None)
                if ev is not None:
                    metrics["recovery"] = 1
                    metrics["recovery_ckpt_step"] = ev.ckpt_step
                    return int(self.state.step)
        return metrics["step"]

    def run(self, n_steps: int, log_every: int = 10, log_fn=print):
        if self.wave is not None:
            return self.wave.run(n_steps, log_every, log_fn)
        span = jax.profiler.TraceAnnotation
        step = int(self.state.step)
        for i in range(n_steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                with span("batch_build"):
                    wb = jnp.asarray(coded_worker_batches(
                        self.data, step, self.n_workers, self.plan.s_max))
                with span("straggler_draw"):
                    dec_w, rec = self.sim.step()
                with span("dispatch"):
                    self.state, metrics = self.step_fn(self.state, wb, dec_w)
                with span("wait"):
                    jax.block_until_ready((self.state, metrics))
                with span("metrics_sync"):
                    metrics = {k: float(v) for k, v in metrics.items()}
                    step = int(self.state.step)
                metrics.update(step=step, tau_coded=rec["tau_coded"],
                               tau_uncoded=rec["tau_uncoded"])
                if self.controller is not None or self.deathwatch is not None:
                    with span("replan"):
                        step = self._after_round(rec, metrics, log_every,
                                                 log_fn)
                if self.manager is not None:
                    with span("ckpt_save"):
                        self.manager.maybe_save(
                            step, self.state,
                            extra={"plan": self.plan.to_dict()})
            self.history.append(metrics)
            if log_every and (i % log_every == 0 or i == n_steps - 1):
                log_fn(f"step {metrics['step']:5d}  loss {metrics['loss']:.4f}  "
                       f"tau_coded {metrics['tau_coded']:.3g}  "
                       f"tau_uncoded {metrics['tau_uncoded']:.3g}")
        return self.state, self.sim.summary()
