"""A run whose timed step is broken underneath comes out not correct.

Each test skips the look for a chip and drives the rest of a run on a
tiny copy of a cell, with one training fault planted in the timed path:

  * ``unchanged``: the step returns the state it was given;
  * ``half_batch``: half of the rank's data is left out and the rest
    counted twice (rows where a shard has two or more, else every other
    shard replaced by its neighbour);
  * ``no_exchange``: the level collective across the data ranks is left
    out, in a tiny N=4 cell on ``data=4`` (four CPU devices, in a child
    process: the device count is fixed when JAX starts).
"""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from perf.bench import run_cell

REPO = Path(__file__).resolve().parents[1]


def _unchanged(step):
    def run(state, *args):
        return state, step(state, *args)[1]
    return run


@jax.jit
def _halve(wb, aux):
    rows = wb.shape[2]
    if rows >= 2:
        keep = rows // 2
        fold = lambda x: jnp.concatenate([x[:, :, :keep]] * 2, axis=2)  # noqa
        return fold(wb), None if aux is None else fold(aux)
    dup = lambda x: jnp.repeat(x[:, ::2], 2, axis=1)[:, : x.shape[1]]  # noqa
    return dup(wb), None if aux is None else dup(aux)


def _half_batch(step):
    def run(state, wb, dec_w, aux=None):
        wb, aux = _halve(wb, aux)
        return step(state, wb, dec_w) if aux is None else step(
            state, wb, dec_w, aux)
    return run


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
@pytest.mark.parametrize("name", ["gclm-n8-xf", "whisper-fp32-n8-xf"])
def test_a_planted_fault_is_not_correct(tiny_cell, name, fault):
    hook = {"unchanged": _unchanged, "half_batch": _half_batch}[fault]
    res = run_cell(tiny_cell(name), seed=2**31 + 5, seconds=0.2, trace=False,
                   t_start=time.perf_counter(), require_tpu=False,
                   step_hook=hook)
    assert not res["correct"], res["checks"]


_CHILD = r"""
import json, sys, time
from pathlib import Path
import jax
from perf.conftest import make_tiny_tree
from perf.spec import load_cell
from perf.bench import run_cell

base, bench = make_tiny_tree(Path(sys.argv[1]))
traffic = json.loads((base / "traffic" / "lm-n8-xf.json").read_text())
traffic.update(workers=4, data_par=4)
(base / "traffic" / "dp4.json").write_text(json.dumps(traffic))
limits = json.loads((base / "workloads" / "gclm-n8-xf.json").read_text())
limits["limits"].pop("decode_residual")
(base / "workloads" / "dp4-cell.json").write_text(json.dumps(limits))
bench["workloads"].append({"name": "dp4-cell", "config": "gc-lm-110m",
                           "traffic": "dp4", "chips": 4, "why": "test"})
out = {}
for fault in ("none", "no_exchange"):
    real = jax.lax.psum
    if fault == "no_exchange":
        jax.lax.psum = lambda x, axis_name, **kw: (
            x if axis_name == "data" else real(x, axis_name, **kw))
    try:
        cell = load_cell("dp4-cell", bench=bench, base=base)
        res = run_cell(cell, seed=77, seconds=0.2, trace=False,
                       t_start=time.perf_counter(), require_tpu=False)
    finally:
        jax.lax.psum = real
    out[fault] = [res["correct"], res["checks"], res["device"]["count"]]
print(json.dumps(out))
"""


def test_exchange_left_out_is_not_correct(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{REPO}{os.pathsep}{REPO / 'src'}")
    proc = subprocess.run([sys.executable, "-c", _CHILD, str(tmp_path)],
                          cwd=REPO, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["none"][2] == 4
    assert out["none"][0], out["none"][1]
    assert not out["no_exchange"][0], out["no_exchange"][1]
