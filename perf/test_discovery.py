"""A configuration, a traffic mix, a cell and a per-layer metric are added
by adding files and entries only: the harness finds each by its name.
And the window refuses to time a step that compiles."""
import json
import time

import jax
import jax.numpy as jnp
import pytest

from perf.bench import run_cell
from perf.spec import load_cell


def test_new_files_are_found_by_name_and_run(tiny_tree):
    base, bench = tiny_tree
    bench = json.loads(json.dumps(bench))
    cfg = json.loads((base / "configs" / "gc-lm-110m.json").read_text())
    cfg["model"]["d_ff"] = 96
    (base / "configs" / "toy-lm.json").write_text(json.dumps(cfg))
    (base / "configs" / "toy-lm.py").write_text(
        (base / "configs" / "gc-lm-110m.py").read_text())
    traffic = json.loads((base / "traffic" / "lm-n8-xf.json").read_text())
    traffic.update(workers=4, seq_len=24)
    (base / "traffic" / "toy-traffic.json").write_text(json.dumps(traffic))
    (base / "workloads" / "toy-cell.json").write_text(
        (base / "workloads" / "gclm-n8-xf.json").read_text())
    (base / "metrics" / "toy_share.py").write_text(
        "def read(rec):\n    return 100.0 * rec['steps'] / 4\n")
    bench["configs"].append({"name": "toy-lm", "source": "test",
                             "file": "perf/configs/toy-lm.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy-cell", "config": "toy-lm",
                               "traffic": "toy-traffic", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "toy_share", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "test", "moves": "tokens_per_s",
                               "workloads": ["toy-cell"]})

    cell = load_cell("toy-cell", bench=bench, base=base)
    assert cell.config["model"]["d_ff"] == 96
    assert cell.traffic["workers"] == 4
    assert "toy_share" in [m["name"] for m in cell.per_layer]
    assert cell.metric_reader("toy_share")({"steps": 2}) == 50.0
    # a cell that the metric does not list does not report it
    other = load_cell("gclm-n8-xf", bench=bench, base=base)
    assert "toy_share" not in [m["name"] for m in other.per_layer]

    res = run_cell(cell, seed=5, seconds=0.2, trace=False,
                   t_start=time.perf_counter(), require_tpu=False)
    assert res["correct"], res["checks"]
    assert list(res["metrics"]) == ["tokens_per_s", "step_ms_p90", "setup_s"]
    assert list(res)[-1] == "checks"


def test_a_compile_inside_the_window_is_an_error(tiny_cell):
    calls = []

    def compile_once_in_the_window(step):
        def run(state, *args):
            calls.append(1)
            if len(calls) == 5:    # steps 1-3 are set-up; 5 is in the window
                jax.jit(lambda x: x * 3)(jnp.ones(7)).block_until_ready()
            return step(state, *args)
        return run

    with pytest.raises(RuntimeError, match="inside the window"):
        run_cell(tiny_cell("gclm-n8-uniform"), seed=1, seconds=0.2,
                 trace=False, t_start=time.perf_counter(), require_tpu=False,
                 step_hook=compile_once_in_the_window)
