"""One run of one coded-training cell: set-up, the timed window, the check.

The system under test is the program's coded training step,
``repro.train.trainer.make_coded_train_step`` in ``spmd`` mode on the
cell's ``(data, model)`` mesh, fed as the training launcher feeds it
(``repro.launch.train``): per step the host builds the workers' batches
with the program's cyclic allocation (``coded_worker_batches``), draws
the straggler realization's decode weights from the plan's simulator,
calls the step and waits for it.

A cell whose mesh has one data rank per worker runs the whole coded
cluster.  A cell on one chip with ``workers`` > 1 runs rank 0's share of
that cluster: the same spmd program on a 1 x 1 mesh, given rank 0's
batches; ``axis_index`` is 0 and the level collectives are over one
device, so the chip does exactly one worker's work.

Set-up (``setup_s``): weights from the seed, the plan, the jitted step
through JAX's persistent compilation cache, and the three checked steps
(which also absorb a recompile when the state comes back from the first
step in other shardings).  The window then runs whole steps until
``seconds`` have passed.  Nothing may compile inside it.
"""
from __future__ import annotations

import gc
import shutil
import statistics
import sys
import time
from contextlib import contextmanager
from dataclasses import fields
from pathlib import Path
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from perf import check, flops, peaks, scopes, trace_reduce
from perf.spec import ROOT, Cell
from perf.traffic import ShardTokens, frame_ids, make_frame_pool
from perf.weights import make_params

#: a traced window is capped at this many seconds (trace size and the
#: time to read it back)
TRACE_SECONDS = 8.0
TRACE_DIR = ROOT / ".perf_trace"

SPANS = ("batch_build", "straggler_draw", "dispatch", "wait")

#: no cell shards the model or runs the per-leaf ``tree`` pipeline yet;
#: a traffic file gains these keys when one does
MODEL_PAR = 1
PIPELINE = "auto"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_chips(n: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"this benchmark runs on a TPU; JAX found "
                            f"{devs[0].platform}")
    if len(devs) < n:
        raise NoAccelerator(f"the cell needs {n} chips; JAX found {len(devs)}")
    return devs[:n]


def model_config(cell: Cell):
    """The program's ModelConfig for the cell, sizes from its config file."""
    from repro.configs import get_config
    from repro.configs.base import EncoderSpec, ModelConfig

    model = cell.config["model"]
    names = {f.name for f in fields(ModelConfig)}
    kw = {k: v for k, v in model.items() if k in names}
    if "encoder_layers" in model:
        kw["encoder"] = EncoderSpec(n_layers=model["encoder_layers"],
                                    n_frames=model["encoder_frames"])
    kw["max_seq"] = max(2 * cell.traffic["seq_len"], 512)
    preset = get_config(cell.config["arch"])
    if kw.get("n_layers", preset.n_layers) != preset.n_layers:
        kw["layers"] = preset.layers[: kw["n_layers"]]
    return preset.replace(**kw)


class Program:
    """The coded training step of one cell, with its state and feed."""

    def __init__(self, cell: Cell, seed: int, devices):
        from repro.core import Env, Plan, ShiftedExponential
        from repro.dist.sharding import make_rules, use_mesh
        from repro.train.state import abstract_train_state
        from repro.train.trainer import TrainConfig, make_coded_train_step

        t = self.traffic = cell.traffic
        self.cell = cell
        self.cfg = model_config(cell)
        self.n = int(t["workers"])
        dp, mp = int(t["data_par"]), MODEL_PAR
        if dp not in (1, self.n):
            raise ValueError(f"data_par {dp} must be 1 (rank 0's share) or "
                             f"the worker count {self.n}")
        if t["env"]["dist"] != "shifted_exponential":
            raise ValueError(f"unknown straggler distribution {t['env']}")
        self.ranks = dp
        self.mesh = jax.sharding.Mesh(np.asarray(devices[:dp * mp]).reshape(
            dp, mp), ("data", "model"))
        self.mesh_ctx = lambda: use_mesh(self.mesh, make_rules(self.cfg))
        self.replicated = NamedSharding(self.mesh, P())
        self.batch_sharding = NamedSharding(self.mesh, P("data"))
        self.env = Env.iid(ShiftedExponential(mu=t["env"]["mu"],
                                              t0=t["env"]["t0"]), self.n)
        o = t["optimizer"]
        self.cfg_t = TrainConfig(
            lr=o["lr"], warmup=o["warmup"], total_steps=o["total_steps"],
            weight_decay=o["weight_decay"], clip_norm=o["clip_norm"],
            b1=o["b1"], b2=o["b2"])
        grad_dtype = {"float32": None, "bfloat16": jnp.bfloat16}[
            t["grad_dtype"]]
        with self.mesh_ctx():
            self.param_shapes = abstract_train_state(self.cfg)[0].params
            self.plan = Plan.build(self.param_shapes, self.env,
                                   scheme=t["scheme"], s_cap=t.get("s_cap"))
            self.step_fn = jax.jit(make_coded_train_step(
                self.cfg, self.cfg_t, self.plan, mesh=self.mesh, mode="spmd",
                reduce_mode=t["reduce_mode"], grad_dtype=grad_dtype,
                pipeline=PIPELINE))
        self.k = self.plan.k_shards
        if t.get("frames"):
            self._gather = jax.jit(lambda pool, ids: pool[ids],
                                   out_shardings=self.batch_sharding)
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """Fresh weights, optimizer state, inputs and straggler draws from
        ``seed``; the compiled step is kept."""
        from repro.optim.optim import adamw_init
        from repro.train.state import TrainState

        t = self.traffic
        self.seed = int(seed)
        self.state = None
        with self.mesh_ctx():
            params = make_params(self.param_shapes, self.seed, self.replicated)
            opt = jax.jit(adamw_init, out_shardings=self.replicated)(params)
            self.state = TrainState(params=params, opt=opt, step=jax.device_put(
                jnp.zeros((), jnp.int32), self.replicated))
        self.sim = self.plan.simulator(self.env, seed=self.seed)
        self.check_rng = np.random.default_rng([self.seed, 1])
        self.tokens = ShardTokens(self.seed, self.cfg.vocab,
                                  t["rows_per_shard"], t["seq_len"])
        self.pool = None
        if t.get("frames"):
            self.pool = make_frame_pool(
                self.seed, t["frames"]["pool"], self.cfg.encoder.n_frames,
                self.cfg.d_model, t["frames"]["std"], self.replicated)
        self.step_no = 0

    # ------------------------------------------------------------- feed
    def feed(self, step: int):
        """(worker batches, worker aux or None) for ``step``, on device."""
        from repro.data.pipeline import coded_worker_batches

        wb = coded_worker_batches(self.tokens, step, self.n,
                                  self.plan.s_max)[: self.ranks]
        wb = jax.device_put(wb, self.batch_sharding)
        aux = None
        if self.pool is not None:
            shard = (np.arange(self.ranks)[:, None] + np.arange(self.k)) % self.n
            ids = frame_ids(step, shard, self.n, self.traffic["rows_per_shard"],
                            self.pool.shape[0])
            aux = self._gather(self.pool, ids)
        return wb, aux

    def checked_decode_weights(self) -> np.ndarray:
        """A straggler realization drawn from the env in which worker 0
        arrives first, so that rank 0's work counts at every level (in a
        random draw a one-rank cell's contribution is zero at each level
        where rank 0 straggles, and the check would see nothing)."""
        times = self.env.sample(self.check_rng, (self.n,))
        times[0] = 0.0
        return np.asarray(self.plan.decode_weights(times), np.float32)

    def call(self, step_fn, state, wb, dec_w, aux):
        if aux is None:
            return step_fn(state, wb, dec_w)
        return step_fn(state, wb, dec_w, aux)

    def useful_tokens_per_step(self) -> int:
        """Unique target tokens per step that this process's ranks own."""
        return self.ranks * self.traffic["rows_per_shard"] * self.traffic[
            "seq_len"]

    def step_inputs(self) -> check.StepInputs:
        return check.StepInputs(
            model=self.cell.config["model"], traffic=self.traffic,
            seed=self.seed, param_shapes=self.param_shapes, n_workers=self.n,
            k_shards=self.k, ranks=self.ranks,
            b_rows=np.asarray(self.plan.b_rows, np.float64),
            leaf_level=np.asarray(self.plan.level_index()))


@contextmanager
def span(name: str):
    with jax.profiler.TraceAnnotation(name):
        yield


def run_checked_steps(prog: Program, step_fn, n_steps: int):
    """Drive the step from the seed through its first ``n_steps`` steps,
    keeping the readings the reference is compared on."""
    readings = check.Readings()
    inputs = prog.step_inputs()
    state0 = prog.state
    state = state0
    b1 = prog.traffic["optimizer"]["b1"]
    for i in range(n_steps):
        wb, aux = prog.feed(i)
        dec_w = prog.checked_decode_weights()
        inputs.dec_w.append(dec_w)
        state, metrics = prog.call(step_fn, state, wb, dec_w, aux)
        readings.losses.append(float(metrics["loss"]))
        if i == 0:
            readings.grad_norms = np.asarray(check.leaf_norms(
                state.opt["m"])) / (1.0 - b1)
    readings.change_norms = np.asarray(check.change_norms(state.params,
                                                          state0.params))
    prog.state = state
    prog.step_no = n_steps
    return readings, inputs


class GcPauses:
    """Python garbage-collector pauses, for the log (``gc.callbacks``)."""

    def __init__(self):
        self.pauses, self._t0 = [], 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))


def window(prog: Program, step_fn, seconds: float):
    """Whole steps until ``seconds`` have passed.  Returns the per-step
    wall times and the number of steps whose loss was not finite; raises
    if anything was traced or compiled in the window."""
    times, losses, compiles = [], [], []

    def on_event(event: str, duration: float, **_):
        if event.startswith(("/jax/core/compile", "/jax/compilation_cache")):
            compiles.append(event)

    state = prog.state
    jax.monitoring.register_event_duration_secs_listener(on_event)
    # What set-up left behind (traced programs, the plan) is never
    # garbage; freezing it keeps a full collection from scanning it in
    # the window.  The steps' own garbage is still collected.
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    t0 = t_prev = time.perf_counter()
    while True:
        i = prog.step_no
        with span("batch_build"):
            wb, aux = prog.feed(i)
        with span("straggler_draw"):
            dec_w, _ = prog.sim.step()
        with span("dispatch"):
            state, metrics = prog.call(step_fn, state, wb, dec_w, aux)
        with span("wait"):
            jax.block_until_ready((state, metrics))
        now = time.perf_counter()
        times.append(now - t_prev)
        losses.append(metrics["loss"])
        t_prev = now
        prog.step_no += 1
        if now - t0 >= seconds:
            break
    jax.monitoring.unregister_event_duration_listener(on_event)
    gc.callbacks.remove(pauses)
    gc.unfreeze()
    if compiles:
        raise RuntimeError(f"{len(compiles)} compile/trace events inside the "
                           f"window: {sorted(set(compiles))}")
    prog.state = state
    bad = int(np.sum(~np.isfinite(np.asarray(jax.device_get(losses)))))
    slow = sorted(range(len(times)), key=lambda i: -times[i])[:5]
    gen2 = [p for g, p in pauses.pauses if g == 2]
    log(f"window: {len(times)} steps; slowest (step, s) "
        f"{[(i, round(times[i], 4)) for i in slow]}; gc pauses "
        f"{len(pauses.pauses)} totalling {sum(p for _, p in pauses.pauses):.4f}"
        f" s, {len(gen2)} full ({max(gen2, default=0.0):.4f} s at most)")
    return times, bad


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def compiled_step(prog: Program):
    """The step the window ran, compiled again (a compile-cache hit)."""
    wb, aux = prog.feed(prog.step_no)
    dec_w = prog.plan.full_decode_weights().astype(np.float32)
    args = (prog.state, wb, dec_w) + (() if aux is None else (aux,))
    return prog.step_fn.lower(*args).compile()


def compiled_bytes(compiled) -> Optional[int]:
    """Bytes the compiled step holds on one chip: its arguments, the
    outputs not aliased to them, its temporaries and its code, as the
    compiler reports them.  (``memory_stats``' peak does not see a TPU
    program's temporaries.)"""
    m = compiled.memory_analysis()
    if m is None:
        return None
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes
               + m.generated_code_size_in_bytes)


def _trace_window(prog, step_fn, seconds, trace_dir: Path):
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    with jax.profiler.trace(str(trace_dir)):
        t0 = time.perf_counter()
        times, bad = window(prog, step_fn, seconds)
        traced_s = time.perf_counter() - t0
    return times, bad, traced_s


def layer_record(cell: Cell, ev: dict, hlo: str, *, k_shards: int,
                 ranks: int, chips: int, kind: str, steps: int,
                 window_s: float, traced_s: float, memory_peak_bytes: int,
                 compiled_bytes: Optional[int]) -> dict:
    """What each per-layer metric's reader gets, from a traced window's
    events (``trace_reduce.events_of``) and the step's HLO text.

    The cell's ``config`` and ``traffic`` files as loaded, ``k_shards``
    and ``ranks`` (per process), ``chips``, the device's ``peak``;
    ``step_flops`` (``flops.step_flops``, by the configuration's own
    ``forward_flops`` where its reference defines one) and
    ``combine_bytes``; the window's host readings as given; ``trace``,
    ``trace_reduce.reduce_events``' structural classes; ``scopes``,
    ``scope_s``, ``unscoped_s`` and ``mixed_s`` of
    ``scopes.scope_times``; and ``ops``, every op of the step that ran,
    ``[name, class, op_name, seconds per chip]``, which
    ``scopes.part_s`` sums by any component of the ``op_name`` path.
    All times are per chip over the whole window.
    """
    sc = scopes.scope_times(ev, hlo, chips, SPANS)
    return {
        "cell": cell.name, "config": cell.config, "traffic": cell.traffic,
        "k_shards": k_shards, "ranks": ranks, "chips": chips,
        "peak": peaks.peak(kind),
        "step_flops": flops.step_flops(cell.config["model"], cell.traffic,
                                       k_shards, ranks, cell.reference),
        "combine_bytes": flops.combine_bytes(cell.config["params"],
                                             k_shards, ranks),
        "steps": steps, "window_s": window_s, "traced_s": traced_s,
        "memory_peak_bytes": memory_peak_bytes,
        "compiled_bytes": compiled_bytes,
        "trace": trace_reduce.reduce_events(ev, hlo, chips, SPANS),
        "scopes": {k: sc[k] for k in ("scope_s", "unscoped_s", "mixed_s")},
        "ops": sc["ops"],
    }


def layer_metrics(cell: Cell, record: dict) -> dict:
    """The cell's per-layer metrics, each by its own reader
    (``perf/metrics/<name>.py``); one whose reader finds nothing to read
    is left out."""
    metrics = {}
    for m in cell.per_layer:
        value = cell.metric_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             step_hook: Optional[Callable] = None,
             dump: Optional[Path] = None) -> dict:
    """One run of ``cell``; returns the result line's object.

    ``step_hook`` wraps the compiled step (tests plant faults with it);
    ``dump`` keeps the raw trace and the step's HLO text there.
    """
    if require_tpu:
        devices = require_chips(cell.chips)
    else:
        devices = jax.devices()[: cell.chips]
    prog = Program(cell, seed, devices)
    step_fn = prog.step_fn if step_hook is None else step_hook(prog.step_fn)
    n_checked = int(cell.traffic["checked_steps"])
    with prog.mesh_ctx():
        prog_readings, inputs = run_checked_steps(prog, step_fn, n_checked)
        setup_s = time.perf_counter() - t_start
        log(f"setup_s {setup_s!r} (cell {cell.name}, seed {seed}, "
            f"{prog.k} shards per rank, levels "
            f"{[int(s) for s in prog.plan.used_levels]})")
        if trace:
            seconds = min(seconds, TRACE_SECONDS)
            times, bad, traced_s = _trace_window(
                prog, step_fn, seconds, TRACE_DIR / cell.name)
        else:
            times, bad = window(prog, step_fn, seconds)
        peak = memory_peak(devices)
        hlo, held = None, None
        if trace:
            compiled = compiled_step(prog)
            hlo, held = compiled.as_text(), compiled_bytes(compiled)
            del compiled
    steps = len(times)
    window_s = float(sum(times))
    tokens = prog.useful_tokens_per_step()
    kind = devices[0].device_kind
    platform = devices[0].platform
    del prog.state, step_fn
    prog.step_fn = None
    gc.collect()

    # ---- correctness, after the window and with the program's state freed
    values = check.gaps(prog_readings, check.reference_readings(
        inputs, cell.reference))
    if prog.ranks < prog.n:
        values["decode_residual"] = check.decode_residual(inputs)
    correct, checks = check.judge(values, cell.limits["limits"])
    correct = correct and bad == 0

    result = {"correct": correct, "attempted": steps, "failed": bad}
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak}
    if not trace:
        result["metrics"] = {
            "tokens_per_s": {"value": tokens * steps / window_s / len(devices),
                             "unit": "tokens/s"},
            "step_ms_p90": {"value": 1e3 * (statistics.quantiles(
                times, n=10)[-1] if steps > 1 else times[0]), "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        result["metrics"] = {m["name"]: result["metrics"][m["name"]]
                             for m in cell.end_to_end}
    else:
        trace_dir = TRACE_DIR / cell.name
        ev = trace_reduce.load_events(trace_dir, SPANS)
        if dump is not None:
            dump.mkdir(parents=True, exist_ok=True)
            (dump / f"{cell.name}.hlo.txt").write_text(hlo)
            shutil.copytree(trace_dir, dump / f"{cell.name}.trace",
                            dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
        record = layer_record(
            cell, ev, hlo, k_shards=prog.k, ranks=prog.ranks,
            chips=len(devices), kind=kind, steps=steps, window_s=window_s,
            traced_s=traced_s, memory_peak_bytes=peak, compiled_bytes=held)
        result["metrics"] = layer_metrics(cell, record)
        reduced = record["trace"]
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["idle_by_span"]}
    result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():   # the last lines on standard error
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result
