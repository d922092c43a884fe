"""Find a cell's files by name.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``.  It names a
configuration and a traffic mix; each lives in files of its own:

  perf/configs/<config>.json    sizes of the model as run
  perf/configs/<config>.py      its plain float32 reference (``loss``)
  perf/traffic/<traffic>.json   the job: workers, scheme, batch, optimizer
  perf/workloads/<cell>.json    the cell's correctness limits and control
  perf/metrics/<metric>.py      one per-layer metric's reader (``read``)

Adding a configuration, cell or metric adds files and entries; no file
here changes.  ``base`` lets a test point the lookup at another tree.

What a new configuration may bring besides its sizes and reference:

  * ``forward_flops(model, seq_len)`` in its reference module: FLOPs of
    one forward pass of one row, which ``perf/flops.py:step_flops`` then
    uses in place of its dense formula (for ``mfu``, ``backward_roofline``
    and any reader of ``rec["step_flops"]``);
  * readers of its own layers.  A reader gets the record that
    ``perf/bench.py:layer_record`` builds: ``rec["config"]`` and
    ``rec["traffic"]`` (its files as loaded), ``k_shards``, ``ranks``,
    ``chips``, ``peak``; ``rec["trace"]``, the structural classes;
    ``rec["scopes"]``, time by the program's named scopes; and
    ``rec["ops"]``, every op of the step as ``[name, class, op_name,
    seconds per chip]``.  ``perf/scopes.py:part_s(rec, name)`` times any
    scope the program adds, nested where it may be (an expert layer
    under ``per_shard_grad``); a reader returns None where it finds
    nothing to read.

A Pallas kernel (``tpu_custom_call``) inside the per-shard backward is
classed ``backward``; only one outside it is the ``combine``
(``perf/trace_reduce.py:Hlo.classify``).
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Optional

PERF_DIR = Path(__file__).resolve().parent
ROOT = PERF_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import a file by path (its name may hold characters, such as '-',
    that an import statement cannot)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    name = "perf_file_" + "".join(c if c.isalnum() else "_" for c in
                                  str(path.relative_to(path.parents[1])))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of the benchmark with every file it names loaded."""

    name: str
    entry: dict           # the BENCHMARK.json workloads entry
    config: dict          # perf/configs/<config>.json
    traffic: dict         # perf/traffic/<traffic>.json
    limits: dict          # perf/workloads/<cell>.json
    reference: ModuleType  # perf/configs/<config>.py
    end_to_end: list      # BENCHMARK.json end_to_end entries this cell reports
    per_layer: list       # BENCHMARK.json per_layer entries this cell reports
    base: Path

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    def metric_reader(self, name: str) -> Callable[[dict], Optional[float]]:
        return load_module(self.base / "metrics" / f"{name}.py").read


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Optional[dict] = None,
              base: Path = PERF_DIR) -> Cell:
    """The cell called ``name``; ``bench`` defaults to the root
    ``BENCHMARK.json`` next to ``base``'s parent."""
    if bench is None:
        bench = load_json(base.parent / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(entries)}")
    entry = entries[name]
    cfg_name, traffic = entry["config"], entry["traffic"]
    return Cell(
        name=name, entry=entry,
        config=load_json(base / "configs" / f"{cfg_name}.json"),
        traffic=load_json(base / "traffic" / f"{traffic}.json"),
        limits=load_json(base / "workloads" / f"{name}.json"),
        reference=load_module(base / "configs" / f"{cfg_name}.py"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        base=base)
