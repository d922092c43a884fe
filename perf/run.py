"""Run one benchmark cell once and print its result as the last line.

    python3 -m perf.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout (the directory holding BENCHMARK.json
and ``src/``).  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number the
correctness check compared, beside its limit (also printed as the last
lines of standard error).  With ``--trace 0`` the metrics are the
cell's end-to-end ones; with ``--trace 1`` its per-layer ones.

Exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for, or where the program cannot be imported.
JAX's persistent compilation cache lives at ``<checkout>/.jax_cache``
unless ``JAX_COMPILATION_CACHE_DIR`` names another directory.
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


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default="",
                    help="with --trace 1: keep the raw trace and the step's "
                         "HLO text under this directory")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from repro.launch.train import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from perf.bench import NoAccelerator, run_cell
    from perf.spec import load_cell

    cell = load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START,
                          dump=Path(args.dump) if args.dump else None)
    except NoAccelerator as e:
        print(f"perf.run: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
