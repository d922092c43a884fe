"""Readings that a cell's correctness limits are set from.

    python3 -m perf.calibrate --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 [--out readings.jsonl]

Not run by the benchmark.  In one process on the chip it builds the
cell's program once, then for each seed drives the three checked steps
exactly as a benchmark run does and compares them with the reference at
the configuration's stated precision (the lower readings).  On the
control seeds it also reads:

  * each control (``control`` in ``perf/workloads/<cell>.json``): the
    reference put in the program's place with its activations and matmul
    operands in a lower type (``{"act": ..., "operand": ...}``, e.g.
    bfloat16 for a float32 configuration);
  * each training fault the cell can have, planted in the reference put
    in the program's place: ``half_batch`` (half of the rows, or of the
    shards, left out, the mean over the rest) and, where the cell holds
    every rank, ``no_exchange`` (no collective: rank 0's part alone);
    ``unchanged`` (a step that returns its state) reads 1 by the
    measure and needs no run.

Each reading is one JSON line: seed, what was read, and the compared
numbers.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def numerics(spec: dict):
    import jax.numpy as jnp

    from perf import reference as R

    return R.Numerics(act=getattr(jnp, spec["act"]),
                      operand=getattr(jnp, spec["operand"]),
                      precision=None)


def read_cell(cell, seeds, control_seeds, emit, require_tpu=True) -> None:
    import jax

    from perf import check
    from perf.bench import Program, require_chips, run_checked_steps

    devices = require_chips(cell.chips) if require_tpu else jax.devices()[
        : cell.chips]
    n_checked = int(cell.traffic["checked_steps"])
    prog = Program(cell, seeds[0], devices)
    for seed in sorted(set(seeds) | set(control_seeds)):
        prog.reseed(seed)
        with prog.mesh_ctx():
            readings, inputs = run_checked_steps(prog, prog.step_fn, n_checked)
        prog.state = None
        ref = check.reference_readings(inputs, cell.reference)
        if prog.ranks < prog.n:
            res = check.decode_residual(inputs)
        else:
            res = None
        if seed in seeds:
            emit(seed, "program", dict(check.gaps(readings, ref),
                                       decode_residual=res))
        if seed not in control_seeds:
            continue
        for ctl_spec in cell.limits["control"]:
            ctl = check.reference_readings(inputs, cell.reference,
                                           nm=numerics(ctl_spec))
            emit(seed, "control " + json.dumps(ctl_spec), check.gaps(ctl, ref))
        faults = ["half_batch"] + (["no_exchange"] if prog.ranks == prog.n
                                   else [])
        for fault in faults:
            bad = check.reference_readings(inputs, cell.reference,
                                           fault=fault)
            emit(seed, fault, check.gaps(bad, ref))
        still = check.Readings(losses=readings.losses,
                               grad_norms=0 * readings.grad_norms,
                               change_norms=0 * readings.change_norms)
        emit(seed, "unchanged", check.gaps(still, ref))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from repro.launch.train import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from perf.spec import load_cell

    cell = load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    out = open(args.out, "a") if args.out else None

    def emit(seed, what, values):
        line = json.dumps({"cell": cell.name, "seed": seed, "read": what,
                           "values": values,
                           "t": time.perf_counter() - T_START})
        print(line, flush=True)
        if out is not None:
            out.write(line + "\n")
            out.flush()

    try:
        read_cell(cell, seeds, ctl_seeds, emit)
    finally:
        if out is not None:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
