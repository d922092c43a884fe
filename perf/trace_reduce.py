"""Reduce a profiler trace of the window to per-layer numbers.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
On a TPU each device plane ``/device:TPU:<n>`` has an ``XLA Modules``
line (one event per program run, named ``<module>(<id>)``) and an
``XLA Ops`` line (one event per executed instruction, named by the
instruction's HLO text, ``%name = shape opcode(...)``).  The host plane
holds the benchmark's own spans (``jax.profiler.TraceAnnotation``).

Each device event is classed by its instruction in the compiled step's
HLO text (kept from the run), without touching the program:

  * ``collective``: all-reduce, reduce-scatter, all-gather,
    collective-permute or all-to-all (their start/done halves too);
  * ``backward``: an instruction inside the per-shard loop (see ``Hlo``):
    the K shards' forward and backward passes and the stacking of their
    gradients, a Pallas kernel among them (an attention or expert kernel
    of the model is backward, not combine);
  * ``combine``: a Pallas kernel (``custom-call`` to ``tpu_custom_call``)
    outside the per-shard loop;
  * ``other``: the rest of the step: optimizer, monitoring forward, and
    the packing into and unpacking from the level buffers;
  * ``feed``: instructions of other programs (the benchmark's input
    gather), by the ``XLA Modules`` event they run in.

Control-flow instructions (``while``, ``conditional``, ``call``) span
their bodies and are left out of the sums; busy time is the union of all
intervals, so nesting is never counted twice.
"""
from __future__ import annotations

import bisect
import glob
import re
from pathlib import Path
from typing import Iterable

COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
               "collective-permute", "all-to-all")
CONTROL = ("while", "conditional", "call")

_COMP = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
_CALLED = re.compile(r"(?:calls|body|condition|to_apply|branch_computations)="
                     r"\{?((?:%?[\w.\-]+(?:,\s*)?)+)\}?")
_OPNAME = re.compile(r'op_name="([^"]*)"')
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)


class Hlo:
    """The instructions of a compiled module's HLO text, each classed.

    ``backward`` is every instruction that runs inside the per-shard
    loop: the computations reachable from the body of a top-level
    ``while`` whose instructions include an ``op_name`` under ``jvp(``
    (the ``lax.map`` over the K shards' ``value_and_grad``).  Where XLA
    has unrolled that loop (K = 1), instructions are classed by their
    own ``op_name`` instead.
    """

    def __init__(self, text: str):
        m = _MODULE.search(text)
        self.module = m.group(1) if m else ""
        self.instr = {}   # name -> (opcode, op_name, target, computation)
        calls = {}        # computation -> called computations
        entry, comp = None, None
        entry_whiles = []
        for line in text.splitlines():
            c = _COMP.match(line)
            if c:
                comp = c.group(2)
                calls[comp] = set()
                if c.group(1):
                    entry = comp
                continue
            i = _INSTR.match(line)
            if not i or comp is None:
                continue
            name, rest = i.group(1), i.group(2)
            op = _OPCODE.search(rest)
            opcode = op.group(1) if op else ""
            opn = _OPNAME.search(rest)
            tgt = _TARGET.search(rest)
            self.instr[name] = (opcode, opn.group(1) if opn else "",
                                tgt.group(1) if tgt else "", comp)
            for cm in _CALLED.finditer(rest):
                for callee in re.split(r",\s*", cm.group(1)):
                    calls[comp].add(callee.lstrip("%"))
            if comp == entry and opcode == "while":
                body = re.search(r"body=%?([\w.\-]+)", rest)
                if body:
                    entry_whiles.append(body.group(1))
        by_comp = {}
        for name, (_, opn, _, comp) in self.instr.items():
            by_comp.setdefault(comp, []).append(opn)
        self.backward_comps = set()
        for body in entry_whiles:
            reach, todo = set(), [body]
            while todo:
                cur = todo.pop()
                if cur in reach:
                    continue
                reach.add(cur)
                todo.extend(calls.get(cur, ()))
            if any("jvp(" in o for cmp in reach for o in by_comp.get(cmp, ())):
                self.backward_comps |= reach

    def classify(self, name: str) -> str:
        info = self.instr.get(name)
        if info is None:
            return "feed"
        opcode, op_name, target, comp = info
        if opcode in CONTROL:
            return "control"
        if any(opcode.startswith(c) for c in COLLECTIVES):
            return "collective"
        if comp in self.backward_comps or "jvp(" in op_name:
            return "backward"
        if opcode == "custom-call" and target == "tpu_custom_call":
            return "combine"
        return "other"


def union(intervals: Iterable[tuple]) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def overlap(a: list, b: list) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def complement(intervals: list, lo: float, hi: float) -> list:
    out, cur = [], lo
    for s, e in intervals:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _instr_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def events_of(pd, spans: tuple) -> dict:
    """Plain lists from a ProfileData: ``devices`` {plane name: [(instr
    name, start_s, end_s, module)]} and ``host`` [(span, start_s, end_s)].
    An op's module is the ``XLA Modules`` event it lies in."""
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [(e.start_ns, e.start_ns + e.duration_ns,
                             e.name.split("(", 1)[0]) for e in line.events]
                elif line.name == "XLA Ops":
                    ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
            mods.sort()
            starts = [m[0] for m in mods]
            out = []
            for name, s, e in ops:
                k = bisect.bisect_right(starts, s) - 1
                mod = mods[k][2] if k >= 0 and s <= mods[k][1] else ""
                out.append((_instr_name(name), s * 1e-9, e * 1e-9, mod))
            if out:
                devices[plane.name] = out
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        host.append((e.name, e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9))
    return {"devices": devices, "host": host}


def window_of(host: list, spans: tuple) -> tuple:
    """(start, end) of the window: the first ``spans[0]`` span's start to
    the last ``spans[-1]`` span's end."""
    if not host:
        raise ValueError("no host spans in the trace")
    return (min(s for n, s, e in host if n == spans[0]),
            max(e for n, s, e in host if n == spans[-1]))


def planes_of(ev: dict, n_chips: int) -> list:
    """The device planes of the cell's ``n_chips`` chips."""
    planes = sorted(ev["devices"])[:n_chips]
    if len(planes) < n_chips:
        raise ValueError(f"trace has {len(planes)} device planes, the cell "
                         f"uses {n_chips}")
    return planes


def reduce_events(ev: dict, hlo_text: str, n_chips: int,
                  spans: tuple) -> dict:
    """Per-step, per-chip numbers from plain events (see ``events_of``)."""
    hlo = Hlo(hlo_text)
    host = ev["host"]
    lo, hi = window_of(host, spans)
    steps = sum(1 for n, s, e in host if n == spans[0])
    span_iv = {name: union((s, e) for n, s, e in host if n == name)
               for name in spans}
    per_class, busy, exposed, idle_by_span = {}, 0.0, 0.0, {}
    op_time = {}
    planes = planes_of(ev, n_chips)
    for plane in planes:
        ivs, coll, comp = [], [], []
        for name, s, e, mod in ev["devices"][plane]:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            cls = hlo.classify(name) if mod == hlo.module else "feed"
            ivs.append((s, e))
            if cls == "control":
                continue
            per_class[cls] = per_class.get(cls, 0.0) + (e - s)
            key = f"{cls}:{name}"
            op_time[key] = op_time.get(key, 0.0) + (e - s)
            (coll if cls == "collective" else comp).append((s, e))
        merged = union(ivs)
        busy += sum(e - s for s, e in merged)
        coll_m = union(coll)
        exposed += sum(e - s for s, e in coll_m) - overlap(coll_m, union(comp))
        idle = complement(merged, lo, hi)
        covered = []
        for name in spans:
            part = overlap(idle, span_iv[name])
            idle_by_span[name] = idle_by_span.get(name, 0.0) + part
            covered.extend(span_iv[name])
        idle_tot = sum(e - s for s, e in idle)
        idle_by_span["between_spans"] = idle_by_span.get(
            "between_spans", 0.0) + idle_tot - overlap(idle, union(covered))
    k = float(len(planes))
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(((n, v / k) for n, v in idle_by_span.items()),
                  key=lambda kv: -kv[1])
    return {
        "steps": steps,
        "window_s": hi - lo,
        "busy_s": busy / k,
        "class_s": {c: v / k for c, v in per_class.items()},
        "collective_exposed_s": exposed / k,
        "top_ops": [[n, v / k] for n, v in top],
        "idle_by_span": [[n, v] for n, v in gaps[:10]],
    }


def load_events(trace_dir: Path, spans: tuple) -> dict:
    """``events_of`` the one ``.xplane.pb`` under ``trace_dir``."""
    import jax

    files = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(files)}")
    return events_of(jax.profiler.ProfileData.from_file(files[0]), spans)
