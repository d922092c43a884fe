"""Fixtures for the harness's own tests: a benchmark tree of tiny cells.

``tiny_tree`` copies ``perf/`` into a temporary directory and cuts every
configuration and traffic mix to a size the CPU runs in seconds, so the
tests drive the harness end to end (``require_tpu=False``) without a
chip.  Nothing here describes a TPU topology.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

from perf.spec import PERF_DIR, load_cell, load_json

TINY_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 2, "n_kv_heads": 2,
              "head_dim": 32, "d_ff": 128, "vocab": 256}
TINY_ENCODER = {"encoder_layers": 2, "encoder_frames": 48}
TINY_SEQ = {"lm": 32, "asr": 16}


def _write(path: Path, blob: dict) -> None:
    path.write_text(json.dumps(blob, indent=2) + "\n")


def make_tiny_tree(dest: Path) -> tuple[Path, dict]:
    """(perf dir, BENCHMARK dict) of a tiny copy of the benchmark."""
    base = dest / "perf"
    shutil.copytree(PERF_DIR, base, ignore=shutil.ignore_patterns(
        "__pycache__", "fixtures", "test_*.py"))
    for path in (base / "configs").glob("*.json"):
        cfg = load_json(path)
        cfg["model"].update(TINY_MODEL)
        if "encoder_layers" in cfg["model"]:
            cfg["model"].update(TINY_ENCODER)
        _write(path, cfg)
    for path in (base / "traffic").glob("*.json"):
        tr = load_json(path)
        tr["seq_len"] = TINY_SEQ[path.stem.split("-")[0]]
        _write(path, tr)
    bench = load_json(PERF_DIR.parent / "BENCHMARK.json")
    _write(dest / "BENCHMARK.json", bench)
    return base, bench


@pytest.fixture(scope="session")
def tiny_tree(tmp_path_factory):
    return make_tiny_tree(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(scope="session")
def tiny_cell(tiny_tree):
    base, bench = tiny_tree

    def get(name: str):
        return load_cell(name, bench=bench, base=base)

    return get
