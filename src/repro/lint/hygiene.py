"""Repo-state hygiene checks (RH001-RH005).

These migrated from bash greps in ``scripts/check.sh`` so the lint
engine is the single owner of repo hygiene — one implementation, one
output format, no bash/python drift:

  * RH001 — tracked ``.pyc`` files (43 of them shipped before PR 3's
    cleanup; a tracked bytecode file silently shadows source edits).
  * RH002 — tracked bench/smoke JSON outside ``BENCH_*.json`` and the
    chip benchmark's ``BENCHMARK.json``: committed perf rows live in
    ``BENCH_*.json`` only; per-run dumps
    (``bench_smoke.json``, scratch output) belong in .gitignore — a
    tracked one silently goes stale and reads as current.
  * RH003 — the committed ``BENCH_async.json`` headline must stay at
    or above the wave benchmark's enforcement floor
    (``benchmarks/wave_step.py`` ``MIN_SPEEDUP_FULL``): a regenerated
    file below the gate should fail here, not ship.
  * RH004 — the committed ``BENCH_ckpt.json`` coded-checkpoint storage
    overhead must stay under the erasure-coding floor
    ``1.5 * (s/N + 1)`` bytes per payload byte (total stored / payload
    — the MDS ideal is ``s/N + 1``; the 1.5 headroom covers digit
    byte-packing and lane padding).  A coded checkpoint that costs
    replication-class storage defeats its own point and must not ship
    as the pinned number.
  * RH005 — the committed ``BENCH_autotune.json`` headline
    (``tuned_vs_default``) must stay at or above 1.0: the autotuner
    selecting a configuration slower than the hand-picked default
    (xf / flat / psum / fp32) is a selection bug, not a tuning result,
    and must not ship as the pinned number.
"""
from __future__ import annotations

import json
import re
import subprocess
from pathlib import Path
from typing import List, Optional

from .engine import Finding

__all__ = ["run_hygiene", "ASYNC_HEADLINE_FLOOR", "AUTOTUNE_HEADLINE_FLOOR",
           "ckpt_overhead_floor"]

#: keep in sync with benchmarks/wave_step.py MIN_SPEEDUP_FULL
ASYNC_HEADLINE_FLOOR = 1.2

#: keep in sync with benchmarks/autotune.py HEADLINE_FLOOR
AUTOTUNE_HEADLINE_FLOOR = 1.0


def ckpt_overhead_floor(n_shards: int, parity: int) -> float:
    """Max allowed coded-checkpoint bytes per payload byte: the MDS
    ideal ``s/N + 1`` with 1.5x headroom for digit packing + padding.
    Shared by RH004 and benchmarks/ckpt_recovery.py's own gate."""
    return 1.5 * (parity / n_shards + 1.0)

_BENCHISH = re.compile(r"(bench|smoke)", re.IGNORECASE)
_COMMITTED = re.compile(r"^(BENCH_[A-Za-z0-9_]+|BENCHMARK)\.json$")


def _repo_root(start: Optional[Path] = None) -> Path:
    p = (Path(start) if start else Path.cwd()).resolve()
    for cand in (p, *p.parents):
        if (cand / ".git").exists():
            return cand
    raise FileNotFoundError(f"repro.lint --hygiene: no .git above {p}")


def _tracked_files(root: Path) -> List[str]:
    out = subprocess.run(["git", "ls-files"], cwd=root, text=True,
                         capture_output=True, check=True)
    return [line for line in out.stdout.splitlines() if line]


def run_hygiene(root=None) -> List[Finding]:
    root = _repo_root(root)
    tracked = _tracked_files(root)
    findings: List[Finding] = []

    for f in tracked:
        if f.endswith(".pyc"):
            findings.append(Finding(
                "RH001", f, 0, 0,
                "tracked .pyc file — git rm --cached it (bytecode shadows "
                "source edits)"))

    for f in tracked:
        name = f.rsplit("/", 1)[-1]
        if f.endswith(".json") and _BENCHISH.search(f) \
                and not _COMMITTED.match(name):
            findings.append(Finding(
                "RH002", f, 0, 0,
                "tracked bench/smoke artifact outside BENCH_*.json / "
                "BENCHMARK.json — "
                "git rm --cached it (per-run dumps go stale silently)"))

    async_json = root / "BENCH_async.json"
    if "BENCH_async.json" in tracked:
        try:
            speedup = float(json.loads(async_json.read_text())["speedup"])
        except (OSError, KeyError, ValueError, json.JSONDecodeError) as e:
            findings.append(Finding(
                "RH003", "BENCH_async.json", 0, 0,
                f"unreadable committed async headline ({e}) — regenerate "
                "with benchmarks/wave_step.py"))
        else:
            if speedup < ASYNC_HEADLINE_FLOOR:
                findings.append(Finding(
                    "RH003", "BENCH_async.json", 0, 0,
                    f"committed async headline {speedup:.3f}x is below the "
                    f"{ASYNC_HEADLINE_FLOOR}x floor benchmarks/wave_step.py "
                    "enforces — a regression must not ship as the pinned "
                    "number"))

    ckpt_json = root / "BENCH_ckpt.json"
    if "BENCH_ckpt.json" in tracked:
        try:
            blob = json.loads(ckpt_json.read_text())
            n = int(blob["coded"]["n_shards"])
            s = int(blob["coded"]["parity"])
            overhead = float(blob["coded"]["bytes_per_payload_byte"])
        except (OSError, KeyError, TypeError, ValueError,
                json.JSONDecodeError) as e:
            findings.append(Finding(
                "RH004", "BENCH_ckpt.json", 0, 0,
                f"unreadable committed checkpoint headline ({e}) — "
                "regenerate with benchmarks/ckpt_recovery.py"))
        else:
            floor = ckpt_overhead_floor(n, s)
            if overhead > floor:
                findings.append(Finding(
                    "RH004", "BENCH_ckpt.json", 0, 0,
                    f"coded checkpoint stores {overhead:.3f} bytes per "
                    f"payload byte, above the 1.5*(s/N + 1) = {floor:.3f} "
                    f"floor for (N={n}, s={s}) — replication-class storage "
                    "defeats erasure coding and must not ship as the "
                    "pinned number"))

    tune_json = root / "BENCH_autotune.json"
    if "BENCH_autotune.json" in tracked:
        try:
            ratio = float(json.loads(
                tune_json.read_text())["tuned_vs_default"])
        except (OSError, KeyError, TypeError, ValueError,
                json.JSONDecodeError) as e:
            findings.append(Finding(
                "RH005", "BENCH_autotune.json", 0, 0,
                f"unreadable committed autotune headline ({e}) — "
                "regenerate with benchmarks/autotune.py"))
        else:
            if ratio < AUTOTUNE_HEADLINE_FLOOR:
                findings.append(Finding(
                    "RH005", "BENCH_autotune.json", 0, 0,
                    f"committed autotune headline {ratio:.3f}x is below "
                    f"the {AUTOTUNE_HEADLINE_FLOOR}x floor — the tuner "
                    "selected a configuration slower than the hand-picked "
                    "default, which is a selection bug and must not ship "
                    "as the pinned number"))
    return findings
