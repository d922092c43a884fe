"""Wave-pipelined (async) coded training loop.

The barrier ``Trainer`` serializes every round: wait for the
(N - s_b)-th delivery of every block, decode, apply the optimizer
update, broadcast, start the next round.  The event simulator
(``repro.sim.cluster``, ``wave=True``) shows what that leaves on the
table: round t+1's low-redundancy head can run while round t's slow
high-redundancy tail — and the master's serialized decode + optimizer
update — are still in flight.

This module is the live counterpart.  ``WaveRunner`` executes the
simulator's schedule as the loop's contract:

1. draw the segment's per-round straggler times exactly like the
   barrier loop does (same ``Env``/rng stream, same degradation
   factors), and run ``ClusterSim`` (level-form schedule, ``wave=True``,
   the configured ``staleness``) over them;
2. normalize the run into a ``WaveTrace`` — dispatch / decode / update
   events with per-round parameter versions and per-level
   first-(N - s) deliverer sets;
3. execute the events in trace order: ``dispatch`` freezes the round's
   parameter snapshot and starts the per-shard gradients, ``decode``
   triggers that level's fused combine the instant its block decodes
   (``repro.train.coded.combine_level`` math), ``update`` assembles the
   decoded mean gradient and applies AdamW.

Staleness semantics (docs/ASYNC.md):

* ``staleness=0`` is the barrier contract — the trace degenerates to
  strict dispatch -> decodes -> update sequences, and the runner calls
  the *same compiled barrier step* the synchronous ``Trainer`` caches,
  so an n-step run is bit-identical to ``Trainer.run`` (params,
  optimizer state, and rng stream; asserted in
  tests/test_wave_loop.py).
* ``staleness=k`` bounds the overlap: round r's gradients are computed
  on the newest parameters applied when round r dispatched, which the
  engine guarantees include at least round r-1-k's update.  The
  realized event order is the simulator's, exactly (differential test).

Hot-swap quiesce: when the adaptive controller accepts a re-plan
mid-wave, rounds already dispatched under the old plan drain to their
updates (their events keep executing; no new round dispatches), the
swap binds at the quiescent boundary, and the next segment re-traces
under the new plan.  Raw straggler draws for undispatched rounds are
requeued, so the time stream stays aligned with the round index.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import coded_worker_batches

__all__ = ["WaveConfig", "WaveRunner"]


@dataclass(frozen=True)
class WaveConfig:
    """Knobs of the wave-pipelined training loop (docs/ASYNC.md).

    Latency/cost fields are absolute simulated-time units — the same
    axis as ``ClusterSim`` latencies and ``plan.tau``.  Express them as
    fractions of the plan's mean barrier round (e.g.
    ``0.25 * plan.simulate(steps=50).summary()["mean_tau_coded"]``).
    """

    #: rounds of bounded parameter staleness: 0 = barrier semantics
    #: (bit-identical to the synchronous Trainer), k = round r may
    #: dispatch once round r-1-k's update is applied.  None = unbounded.
    staleness: Optional[int] = 1
    #: master-side serialized decode + optimizer-update time per round
    #: (the cost the wave overlaps and the barrier pays serially).
    update_cost: float = 0.0
    #: master -> worker broadcast latency per dependency.
    broadcast_latency: float = 0.0
    #: worker -> master delivery latency per block completion.
    comm_delay: float = 0.0
    #: workers skip blocks the master already decoded (jump ahead).
    cancel_decoded: bool = False
    #: keep per-segment WaveTraces + executed-event logs on the runner
    #: (the differential-test surface; cheap — host-side tuples).
    record: bool = True

    def __post_init__(self):
        if self.staleness is not None and int(self.staleness) < 0:
            raise ValueError("staleness must be >= 0 (or None = unbounded)")
        if min(self.update_cost, self.broadcast_latency, self.comm_delay) < 0:
            raise ValueError("latencies/update_cost must be >= 0")

    def cluster_config(self):
        from repro.sim import ClusterConfig

        return ClusterConfig(
            wave=True, staleness=self.staleness, update_cost=self.update_cost,
            broadcast_latency=self.broadcast_latency,
            comm_delay=self.comm_delay, cancel_decoded=self.cancel_decoded)


class _Round:
    """In-flight state of one dispatched round."""

    __slots__ = ("index", "version", "wb", "snap", "grads", "dec_w",
                 "combined", "times", "decoded")

    def __init__(self, index: int, version: int, wb, snap, times):
        self.index = index          # absolute round index (data key offset)
        self.version = version      # segment-relative params version
        self.wb = wb                # (N, K, rows, S+1) worker batches
        self.snap = snap            # params snapshot at dispatch
        self.grads = None           # per-shard grad stack (staged path)
        self.dec_w = None           # (n_used, N) float64, filled per decode
        self.combined = {}          # leaf id -> decoded grad (staged path)
        self.times = times          # (N,) effective draw for the ledger
        self.decoded = 0            # decode events seen


class WaveRunner:
    """Executes ``Trainer`` rounds on the wave schedule.

    Constructed by ``Trainer(..., wave=WaveConfig(...))``; drive it via
    ``Trainer.run`` (which delegates here).  Compiled stages live in
    the trainer's per-(partition, pipeline) step cache, so plan
    hot-swaps back to a seen partition recompile nothing.
    """

    def __init__(self, trainer, cfg_w: WaveConfig):
        self.tr = trainer
        self.cfg_w = cfg_w
        if trainer.env.has_deaths():
            raise ValueError("the live wave loop prices WorkerDeath only "
                             "through the event simulator; drop death "
                             "faults from the env (degradations are fine)")
        #: per-segment WaveTrace / executed-event log (tests, debugging)
        self.traces: list = []
        self.executed: list = []
        #: absolute round index where each accepted re-plan bound
        self.swap_rounds: list = []
        #: raw (undegraded) draws carried across a quiesce boundary so
        #: the env sample stream stays aligned with the round index
        self._raw_queue: list = []

    # -------------------------------------------------------- compiled stages
    def _cached(self, key, build):
        cache = self.tr._step_cache
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = jax.jit(build())
        return fn

    def _stage_key(self, plan, stage):
        return (plan.partition_key(), self.tr.pipeline, "wave", stage)

    def _shard_fn(self, plan):
        """Per-shard gradient stack: (params, worker_batches) ->
        pytree with leaves (N, K, *shape)."""
        from .coded import _per_shard_grads

        cfg, n = self.tr.cfg, plan.n_workers

        def build():
            def fn(params, worker_batches):
                def worker(i):
                    return _per_shard_grads(cfg, params, worker_batches[i])

                return jax.lax.map(worker, jnp.arange(n))

            return fn

        return self._cached(self._stage_key(plan, "shards"), build)

    def _level_fn(self, plan, li):
        """Fused per-level combine: (grad stack, dec_w row) ->
        {leaf id: decoded mean grad} — triggered at that level's decode
        event, before higher levels land."""
        from .coded import _fused_level_leaves

        layout, n = plan.flat_layout, plan.n_workers
        b_rows = jnp.asarray(plan.b_rows, jnp.float32)

        def build():
            def fn(grads_stacked, dec_w_row):
                leaves, _ = jax.tree.flatten(grads_stacked)
                return _fused_level_leaves(layout, leaves, b_rows, dec_w_row,
                                           li, n, None)

            return fn

        return self._cached(self._stage_key(plan, ("level", li)), build)

    def _update_fn(self, plan):
        """(state, shard-0 tokens, flat grad leaves) -> (state, metrics):
        monitoring loss + AdamW, identical math to the barrier step."""
        from repro.models.model import train_loss
        from .trainer import _apply_update

        cfg, cfg_t = self.tr.cfg, self.tr.cfg_t
        treedef = jax.tree.structure(self.tr.state.params)

        def build():
            def fn(state, tokens0, grad_leaves):
                grads = jax.tree.unflatten(treedef, grad_leaves)
                loss, metrics = train_loss(cfg, state.params,
                                           {"tokens": tokens0})
                return _apply_update(cfg_t, state, grads, metrics)

            return fn

        return self._cached(self._stage_key(plan, "update"), build)

    def _deferred_fn(self, plan):
        """Whole-round stale step for the spmd / tree pipelines:
        (state, snapshot params, worker_batches, dec_w) -> (state,
        metrics).  Gradients come from the dispatch-time snapshot, the
        update applies to the current state; the per-level collective
        schedule stays round-granular (docs/ASYNC.md)."""
        from repro.models.model import train_loss
        from .coded import make_coded_grad_fn
        from .trainer import _apply_update

        tr = self.tr

        def build():
            grad_fn = make_coded_grad_fn(tr.cfg, plan, mesh=tr.mesh,
                                         mode=tr.mode, pipeline=tr.pipeline)

            def fn(state, grad_params, worker_batches, dec_w):
                grads = grad_fn(grad_params, worker_batches, dec_w)
                loss, metrics = train_loss(tr.cfg, state.params,
                                           {"tokens": worker_batches[0, 0]})
                return _apply_update(tr.cfg_t, state, grads, metrics)

            return fn

        return self._cached(self._stage_key(plan, "deferred"), build)

    def _strategy(self, plan) -> str:
        """How rounds execute: 'barrier' (staleness 0: the cached
        synchronous step, bit-identical), 'staged' (sim-mode flat
        pipeline: per-level combines fire at decode events), 'deferred'
        (spmd / tree: whole-round stale step at the update event)."""
        if self.cfg_w.staleness == 0:
            return "barrier"
        from .coded import _resolve_pipeline

        if self.tr.mode == "sim" and _resolve_pipeline(self.tr.pipeline,
                                                       plan) == "flat":
            return "staged"
        return "deferred"

    # ------------------------------------------------------------ the loop
    def run(self, n_steps: int, log_every: int = 10, log_fn=print):
        done = 0
        while done < n_steps:
            done += self._run_segment(n_steps - done, log_every, log_fn)
        return self.tr.state, self.tr.sim.summary()

    def _draw_segment(self, env, rounds: int, ledger_base: int):
        """Per-round draws, identical stream to the barrier loop's
        ``PlanSimulator.step`` (one (N,) sample per round, degradation
        factors by absolute round index).  Quiesce leftovers are
        consumed before fresh samples."""
        n = self.tr.n_workers
        raw = []
        while self._raw_queue and len(raw) < rounds:
            raw.append(self._raw_queue.pop(0))
        for _ in range(rounds - len(raw)):
            raw.append(np.asarray(env.sample(self.tr.sim.rng, (n,)),
                                  np.float64))
        eff = np.stack([r * env.degradation_factors(ledger_base + i)
                        for i, r in enumerate(raw)])
        return raw, eff

    def _run_segment(self, max_rounds: int, log_every, log_fn) -> int:
        from repro.sim import ClusterSim, schedule_from_plan_levels

        tr, cfg_w = self.tr, self.cfg_w
        plan, env, sim_cost = tr.plan, tr.sim.env, tr.sim.cost
        ledger_base = len(tr.sim.ledger)
        data_base = int(tr.state.step)
        raw, eff = self._draw_segment(env, max_rounds, ledger_base)

        sched = schedule_from_plan_levels(plan)
        res = ClusterSim(sched, eff, tr.n_workers, cost=sim_cost,
                         config=cfg_w.cluster_config()).run(max_rounds)
        trace = res.wave_trace()
        log = [] if cfg_w.record else None
        if cfg_w.record:
            self.traces.append(trace)
            self.executed.append(log)

        strategy = self._strategy(plan)
        n_used = len(plan.used_levels)
        rounds: dict[int, _Round] = {}   # segment-relative index -> state
        pending_swap = None              # plan accepted, waiting to bind
        last_dispatched = -1
        unc_scale = sim_cost.scale(plan.n_workers)

        for ev in trace.events:
            if ev.kind == "dispatch":
                if pending_swap is not None:
                    continue             # quiesce: no new round dispatches
                # the engine's version bookkeeping and the live state
                # must agree on how many updates the snapshot has seen
                assert int(tr.state.step) - data_base == ev.version + 1, \
                    (ev, int(tr.state.step), data_base)
                wb = coded_worker_batches(tr.data, data_base + ev.round,
                                          tr.n_workers, plan.s_max)
                rd = _Round(data_base + ev.round, ev.version, wb,
                            tr.state.params, eff[ev.round])
                rd.dec_w = np.zeros((n_used, tr.n_workers))
                if strategy == "staged":
                    rd.grads = self._shard_fn(plan)(rd.snap, jnp.asarray(wb))
                rounds[ev.round] = rd
                last_dispatched = ev.round

            elif ev.kind == "decode":
                rd = rounds.get(ev.round)
                if rd is None:
                    continue             # round skipped by quiesce
                deliverers = np.asarray(ev.workers, np.int64)
                s = int(plan.used_levels[ev.pos])
                rd.dec_w[ev.pos] = plan.codes.decode(s, deliverers)
                if strategy == "staged":
                    row = jnp.asarray(rd.dec_w[ev.pos], jnp.float32)
                    rd.combined.update(
                        self._level_fn(plan, ev.pos)(rd.grads, row))
                rd.decoded += 1

            elif ev.kind == "update":
                rd = rounds.pop(ev.round, None)
                if rd is None:
                    continue             # round skipped by quiesce
                assert rd.decoded == n_used, (ev, rd.decoded, n_used)
                dec_w = np.asarray(rd.dec_w, np.float32)
                wb_j = jnp.asarray(rd.wb)
                if strategy == "barrier":
                    # the synchronous Trainer's own compiled step — the
                    # staleness-0 bit-identity guarantee
                    tr.state, metrics = tr.step_fn(tr.state, wb_j, dec_w)
                elif strategy == "staged":
                    leaves = [rd.combined[j]
                              for j in range(plan.flat_layout.n_leaves)]
                    tr.state, metrics = self._update_fn(plan)(
                        tr.state, wb_j[0, 0], leaves)
                else:
                    tr.state, metrics = self._deferred_fn(plan)(
                        tr.state, rd.snap, wb_j, dec_w)
                metrics = {k: float(v) for k, v in metrics.items()}
                rec = {"times": rd.times,
                       "tau_coded": plan.tau(rd.times, sim_cost),
                       "tau_uncoded": float(unc_scale * rd.times.max()
                                            * plan.total_units)}
                tr.sim.ledger.append(rec)
                metrics.update(step=int(tr.state.step),
                               tau_coded=rec["tau_coded"],
                               tau_uncoded=rec["tau_uncoded"],
                               staleness=(ev.round - 1) - rd.version)
                if tr.controller is not None:
                    new_plan = tr.controller.observe(
                        rec["times"], replan_ok=pending_swap is None)
                    if new_plan is not None:
                        pending_swap = new_plan
                        metrics["plan_swap"] = 1
                        if log_every:
                            log_fn(f"step {metrics['step']:5d}  plan swap "
                                   "accepted; quiescing in-flight waves")
                tr.history.append(metrics)
                if log_every and (ev.round % log_every == 0
                                  or ev.round == max_rounds - 1):
                    log_fn(f"step {metrics['step']:5d}  "
                           f"loss {metrics['loss']:.4f}  "
                           f"tau_coded {metrics['tau_coded']:.3g}  "
                           f"tau_uncoded {metrics['tau_uncoded']:.3g}")

            if log is not None:
                log.append(ev)

        if pending_swap is None:
            return max_rounds
        executed = last_dispatched + 1
        self._raw_queue.extend(raw[executed:])
        self.swap_rounds.append(data_base + executed)
        tr.swap_plan(pending_swap)
        if log_every:
            log_fn(f"step {int(tr.state.step):5d}  wave quiesced after "
                   f"round {data_base + executed - 1}; plan swap -> "
                   f"x={pending_swap.x.tolist()}")
        return executed
