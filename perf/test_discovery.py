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


#: a step whose per-shard backward holds a part of the model under a scope
#: of its own (``toy_part``), which the harness's ``SCOPES`` do not name
TOY_HLO = """HloModule jit_step, entry_computation_layout={()}

%body (t: (f32[8])) -> (f32[8]) {
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, metadata={op_name="jit(step)/per_shard_grad/while/body/jvp(jit(train_loss))/toy_part/dot_general"}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, metadata={op_name="jit(step)/per_shard_grad/while/body/jvp(jit(train_loss))/mlp/dot_general"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %while.3 = (f32[8]{0}) while((f32[8]{0}) %t), condition=%cond, body=%body
  ROOT %custom-call.4 = f32[8]{0} custom-call(f32[8]{0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/gc_combine/pallas_call"}
}
"""

TOY_READER = '''from perf import scopes


def read(rec):
    sec = scopes.part_s(rec, "toy_part")
    if sec is None:
        return None
    m = rec["config"]["model"]
    flops = (2 * rec["traffic"]["seq_len"] * m["d_model"] * m["d_ff"]
             * rec["k_shards"] * rec["ranks"])
    return 100.0 * flops / (rec["peak"]["bf16_flops"] * sec
                            / rec["trace"]["steps"] * rec["chips"])
'''

TOY_COUNT = '''

def forward_flops(model, seq_len):
    return 11 * seq_len * model["d_model"]
'''


def test_a_configuration_brings_its_count_and_a_nested_scope_metric(
        tiny_tree):
    """A configuration whose reference defines ``forward_flops`` and a
    reader that times a scope nested in the backward (``scopes.part_s``)
    from the configuration's own sizes (``rec["config"]``) come in as new
    files and entries; every file of the harness stays as it is."""
    from perf.bench import SPANS, layer_metrics, layer_record
    from perf.spec import PERF_DIR

    base, bench = tiny_tree
    bench = json.loads(json.dumps(bench))
    (base / "configs" / "toy-moe.json").write_text(
        (base / "configs" / "gc-lm-110m.json").read_text())
    (base / "configs" / "toy-moe.py").write_text(
        (base / "configs" / "gc-lm-110m.py").read_text() + TOY_COUNT)
    (base / "workloads" / "toy-moe-cell.json").write_text(
        (base / "workloads" / "gclm-n8-xf.json").read_text())
    (base / "metrics" / "toy_part_roofline.py").write_text(TOY_READER)
    bench["configs"].append({"name": "toy-moe", "source": "test",
                             "file": "perf/configs/toy-moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy-moe-cell", "config": "toy-moe",
                               "traffic": "lm-n8-xf", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "toy_part_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "test", "moves": "tokens_per_s",
        "workloads": ["toy-moe-cell"]})
    for path in base.rglob("*.py"):
        if (PERF_DIR / path.relative_to(base)).is_file():
            assert path.read_text() == (
                PERF_DIR / path.relative_to(base)).read_text(), path

    cell = load_cell("toy-moe-cell", bench=bench, base=base)
    host, ops = [], []
    for t0 in (0.0, 1.0):
        host += [(n, t0 + 0.25 * i, t0 + 0.25 * (i + 1))
                 for i, n in enumerate(SPANS)]
        ops += [("while.3", t0 + 0.8, t0 + 0.95, "jit_step"),
                ("fusion.1", t0 + 0.8, t0 + 0.84, "jit_step"),
                ("fusion.2", t0 + 0.84, t0 + 0.95, "jit_step"),
                ("custom-call.4", t0 + 0.95, t0 + 0.99, "jit_step")]
    rec = layer_record(cell, {"devices": {"/device:TPU:0": ops},
                              "host": host}, TOY_HLO, k_shards=8, ranks=1,
                       chips=1, kind="TPU v5 lite", steps=2, window_s=2.0,
                       traced_s=2.0, memory_peak_bytes=1, compiled_bytes=1)
    m, t = cell.config["model"], cell.traffic
    shard = 3 * t["rows_per_shard"] * 11 * t["seq_len"] * m["d_model"]
    assert rec["step_flops"] == {"useful": shard, "backward": 8 * shard}
    got = layer_metrics(cell, rec)
    part = (2 * t["seq_len"] * m["d_model"] * m["d_ff"] * 8
            / (197e12 * 2 * 0.04 / 2))
    assert got["toy_part_roofline"] == pytest.approx(
        {"value": 100.0 * part, "unit": "%"})
    assert got["mfu"]["value"] == pytest.approx(
        100.0 * shard * 2 / 2.0 / 197e12)
    # the backward class and its scope still hold the nested part whole
    assert rec["trace"]["class_s"]["backward"] == pytest.approx(2 * 0.15)
    assert rec["scopes"]["scope_s"]["per_shard_grad"] == pytest.approx(
        2 * 0.15)
    # a cell of another configuration keeps the dense formula and does
    # not report the new metric
    other = load_cell("gclm-n8-xf", bench=bench, base=base)
    assert "toy_part_roofline" not in [x["name"] for x in other.per_layer]
    assert not hasattr(other.reference, "forward_flops")
