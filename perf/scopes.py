"""Per-layer device time from the program's named scopes.

The program runs each layer of its coded step under a ``jax.named_scope``
(``SCOPES``), which the compiled HLO keeps in every instruction's
``op_name`` metadata.  Where ``perf/trace_reduce.py`` classes a device
event by the structure of the HLO (a Pallas kernel is the combine, the
per-shard loop is the backward), this module gives it the scope of its
instruction: the layer that did the work, whatever implements it.  It
reads the same window, the same step module and the same events, and
leaves control-flow instructions out, so the two reductions of one trace
sum to the same step time and can be set side by side.

A fusion takes the scope of its own ``op_name``; where its fused
computation holds instructions of more than one scope, its time also
counts in ``mixed_s``.  An instruction with no scope in its ``op_name``
(a copy the compiler inserted, a slice of the inputs) counts in
``unscoped_s``.  A trace of a program that has no scopes is all
``unscoped_s``.

``op_times`` lists each op with its ``op_name`` path, and ``part_s``
sums the ops under any component of that path: a scope the program
nests inside a layer (an expert layer under ``per_shard_grad``) is timed
by name, without an entry in ``SCOPES``.

    python3 -m perf.scopes --dump <dir> --cell <name>

reduces what ``python3 -m perf.run --workload <name> ... --trace 1 --dump
<dir>`` kept: per step, milliseconds by scope, by structural class and by
both, the unscoped instructions that take most time, and the clock check
(each run of the step program lies between its step's ``dispatch`` start
and ``wait`` end).
"""
from __future__ import annotations

import argparse
import glob
import json
import re
import sys
from pathlib import Path

from perf import trace_reduce

#: the program's named scopes, one per layer of the coded step (the
#: benchmark keeps its own copy and never imports the program)
SCOPES = ("per_shard_grad", "gc_pack", "gc_combine", "level_collective",
          "gc_unpack", "monitor_forward", "optimizer")
UNSCOPED = "unscoped"

_FUSION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+.*\sfusion\(.*"
                     r"calls=%?([\w.\-]+)", re.M)


def scope_of(op_name: str) -> str:
    """The first component of an ``op_name`` path that is a scope."""
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return UNSCOPED


class ScopedHlo(trace_reduce.Hlo):
    """``trace_reduce.Hlo`` with each instruction's scope."""

    def __init__(self, text: str):
        super().__init__(text)
        self.fused = dict(_FUSION.findall(text))   # fusion -> computation
        self.comp_scopes = {}                      # computation -> scopes
        self.comp_fusions = {}                     # computation -> fusions
        for name, (_, op_name, _, comp) in self.instr.items():
            if op_name:
                self.comp_scopes.setdefault(comp, set()).add(scope_of(op_name))
            if name in self.fused:
                self.comp_fusions.setdefault(comp, []).append(name)
        #: the scopes that some instruction of the module carries
        self.present = {s for found in self.comp_scopes.values()
                        for s in found} - {UNSCOPED}
        self._mixed = {}

    def mixed(self, name: str) -> bool:
        """Whether the fusion ``name`` fuses instructions of two scopes."""
        if name not in self._mixed:
            todo, seen, found = [self.fused.get(name)], set(), set()
            while todo:
                comp = todo.pop()
                if comp is None or comp in seen:
                    continue
                seen.add(comp)
                found |= self.comp_scopes.get(comp, set()) - {UNSCOPED}
                todo.extend(self.fused[f]
                            for f in self.comp_fusions.get(comp, ()))
            self._mixed[name] = len(found) > 1
        return self._mixed[name]


def op_times(ev: dict, hlo: ScopedHlo, n_chips: int, spans: tuple) -> list:
    """Every non-control instruction of the step program that ran in the
    window, as ``[name, class, op_name, seconds per chip]``, the longest
    first.  The window and classes are ``trace_reduce.reduce_events``'
    (see ``events_of``); the programs of the feed are left out."""
    lo, hi = trace_reduce.window_of(ev["host"], spans)
    planes = trace_reduce.planes_of(ev, n_chips)
    total = {}
    for plane in planes:
        for name, s, e, mod in ev["devices"][plane]:
            s, e = max(s, lo), min(e, hi)
            if e > s and mod == hlo.module:
                total[name] = total.get(name, 0.0) + (e - s)
    out = []
    for name, sec in total.items():
        cls = hlo.classify(name)
        if cls not in ("feed", "control"):
            info = hlo.instr.get(name)
            out.append([name, cls, info[1] if info else "", sec / len(planes)])
    return sorted(out, key=lambda op: (-op[3], op[0]))


def scope_times(ev: dict, hlo_text: str, n_chips: int, spans: tuple) -> dict:
    """Per-chip seconds of the step program's device ops by scope, over
    ``trace_reduce.reduce_events``' window and ops (see ``events_of``).

    ``ops`` is ``op_times``.  ``scope_s`` holds every scope the HLO
    carries (0.0 where none of its ops ran on its own); ``scope_s`` plus
    ``unscoped_s`` is the step program's non-control op time.
    ``mixed_s`` is the part of it spent in fusions of two scopes;
    ``cross_s`` splits it by structural class and scope;
    ``top_unscoped`` names the largest unscoped ops.
    """
    hlo = ScopedHlo(hlo_text)
    ops = op_times(ev, hlo, n_chips, spans)
    per_scope = dict.fromkeys(sorted(hlo.present, key=SCOPES.index), 0.0)
    cross, unscoped, mixed = {}, [], 0.0
    for name, cls, op_name, sec in ops:
        scope = scope_of(op_name)
        if scope == UNSCOPED:
            unscoped.append([name, sec])
        else:
            per_scope[scope] += sec
        row = cross.setdefault(cls, {})
        row[scope] = row.get(scope, 0.0) + sec
        if hlo.mixed(name):
            mixed += sec
    return {
        "scope_s": per_scope,
        "unscoped_s": sum(sec for _, sec in unscoped),
        "mixed_s": mixed,
        "cross_s": cross,
        "top_unscoped": unscoped[:10],
        "ops": ops,
    }


def part_s(rec: dict, name: str):
    """Per-chip seconds of the ops in ``rec["ops"]`` whose ``op_name``
    path has ``name`` as a component, wherever it is nested (a scope
    inside ``per_shard_grad`` counts here and in ``per_shard_grad``'s
    ``scope_s``); None where no op that ran carries it."""
    secs = [op[3] for op in rec["ops"] if name in op[2].split("/")]
    return sum(secs) if secs else None


def per_step_ms(rec: dict, *names: str):
    """Milliseconds per step and chip of the scopes ``names`` together,
    from ``rec["scopes"]["scope_s"]``; None where none of them took
    time."""
    sec = sum(rec["scopes"]["scope_s"].get(n, 0.0) for n in names)
    steps = rec["trace"]["steps"]
    return 1e3 * sec / steps if sec > 0 and steps else None


def module_runs(pd, module: str) -> dict:
    """{device plane: sorted (start_s, end_s) of each run of ``module``}
    from a ProfileData's ``XLA Modules`` lines."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                out[plane.name] = sorted(
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.name.split("(", 1)[0] == module)
    return out


def clock_check(runs: dict, host: list, start: str = "dispatch",
                end: str = "wait") -> dict:
    """Each chip's k-th run of the step program against step k's host
    spans: the run's start less the k-th ``start`` span's start
    (``lead``), and the k-th ``end`` span's end less the run's end
    (``tail``), in seconds.  On one clock both are >= 0, up to the
    resolution of spans and events.  ``matched`` is false where a chip
    ran the program another number of times than there were steps."""
    starts = sorted(s for n, s, e in host if n == start)
    ends = sorted(e for n, s, e in host if n == end)
    lead, tail = [], []
    for plane_runs in runs.values():
        for (s, e), t0, t1 in zip(plane_runs, starts, ends):
            lead.append(s - t0)
            tail.append(t1 - e)
    return {"steps": len(starts), "runs": len(lead),
            "matched": all(len(r) == len(starts) for r in runs.values()),
            "min_lead_s": min(lead, default=None),
            "min_tail_s": min(tail, default=None)}


def reduce_dump(dump: Path, cell: str, n_chips: int, spans: tuple) -> dict:
    """Per-step milliseconds by scope and class from a ``--dump`` dir."""
    import jax

    hlo_text = (dump / f"{cell}.hlo.txt").read_text()
    files = glob.glob(str(dump / f"{cell}.trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb for {cell} under {dump}, "
                         f"found {len(files)}")
    pd = jax.profiler.ProfileData.from_file(files[0])
    ev = trace_reduce.events_of(pd, spans)
    red = trace_reduce.reduce_events(ev, hlo_text, n_chips, spans)
    sc = scope_times(ev, hlo_text, n_chips, spans)
    module = re.search(r"^HloModule\s+([\w.\-]+)", hlo_text, re.M).group(1)
    per_step = 1e3 / max(red["steps"], 1)

    def ms(d):
        return {k: v * per_step for k, v in d.items()}

    step_ops = sum(v for c, v in red["class_s"].items() if c != "feed")
    return {
        "cell": cell, "steps": red["steps"], "window_s": red["window_s"],
        "step_ms": red["window_s"] * per_step,
        "class_ms": ms(red["class_s"]),
        "scope_ms": ms(sc["scope_s"]),
        "unscoped_ms": sc["unscoped_s"] * per_step,
        "mixed_ms": sc["mixed_s"] * per_step,
        "step_ops_ms": step_ops * per_step,
        "cross_ms": {c: ms(row) for c, row in sc["cross_s"].items()},
        "top_unscoped_ms": [[n, v * per_step] for n, v in sc["top_unscoped"]],
        "clock": clock_check(module_runs(pd, module), ev["host"]),
    }


def main(argv=None) -> int:
    from perf.bench import SPANS
    from perf.spec import load_cell

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dump", required=True)
    ap.add_argument("--cell", required=True)
    args = ap.parse_args(argv)
    chips = load_cell(args.cell).chips
    print(json.dumps(reduce_dump(Path(args.dump), args.cell, chips, SPANS)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
