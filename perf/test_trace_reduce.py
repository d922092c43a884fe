"""``perf/trace_reduce.py``: interval arithmetic, classing by HLO, and the
reduction of a trace recorded on a TPU v5e (``perf/fixtures``)."""
import gzip
import json

import pytest

from perf import trace_reduce as T
from perf.spec import PERF_DIR

HLO = """HloModule jit_step, entry_computation_layout={()}

%fused (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(f32[8]{0} %p, f32[8]{0} %p)
}

%body (t: (f32[8])) -> (f32[8]) {
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fused
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, metadata={op_name="jit(step)/shard_map/while/body/jvp(jit(train_loss))/dot_general"}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, metadata={op_name="jit(step)/shard_map/while/body/transpose(jvp(jit(train_loss)))/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %while.3 = (f32[8]{0}) while((f32[8]{0}) %t), condition=%cond, body=%body
  %custom-call.4 = f32[1,512]{1,0} custom-call(f32[8]{0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/shard_map/pallas_call"}
  %all-reduce.5 = f32[8]{0} all-reduce(f32[8]{0} %b), replica_groups={}, to_apply=%add, metadata={op_name="jit(step)/shard_map/psum"}
  ROOT %fusion.6 = f32[8]{0} fusion(f32[8]{0} %c), kind=kLoop, metadata={op_name="jit(step)/adamw/mul"}
}
"""


def test_parse_and_classify():
    hlo = T.Hlo(HLO)
    assert hlo.module == "jit_step"
    cls = {k: hlo.classify(k) for k in hlo.instr}
    # fusion.7 has no op_name: it is backward because the per-shard loop
    # runs it, and so are the instructions of the computation it calls
    assert cls == {"p": "backward", "m": "backward", "fusion.7": "backward",
                   "fusion.1": "backward", "fusion.2": "backward",
                   "while.3": "control", "custom-call.4": "combine",
                   "all-reduce.5": "collective", "fusion.6": "other"}
    assert hlo.classify("gather.9") == "feed"


def test_interval_arithmetic():
    u = T.union([(3, 4), (0, 1), (0.5, 2), (5, 5)])
    assert u == [(0, 2), (3, 4)]
    assert T.overlap(u, [(1, 3.5)]) == pytest.approx(1.5)
    assert T.complement(u, -1, 6) == [(-1, 0), (2, 3), (4, 6)]


def test_reduce_synthetic_two_steps_one_chip():
    spans = ("batch_build", "straggler_draw", "dispatch", "wait")
    host = []
    for t0 in (0.0, 1.0):
        host += [("batch_build", t0, t0 + 0.1),
                 ("straggler_draw", t0 + 0.1, t0 + 0.15),
                 ("dispatch", t0 + 0.15, t0 + 0.2),
                 ("wait", t0 + 0.2, t0 + 1.0)]
    ops = []
    for t0 in (0.0, 1.0):
        ops += [("while.3", t0 + 0.2, t0 + 0.6, "jit_step"),
                ("fusion.1", t0 + 0.2, t0 + 0.4, "jit_step"),
                ("fusion.2", t0 + 0.4, t0 + 0.6, "jit_step"),
                ("custom-call.4", t0 + 0.6, t0 + 0.8, "jit_step"),
                ("all-reduce.5", t0 + 0.8, t0 + 0.9, "jit_step"),
                ("fusion.6", t0 + 0.85, t0 + 0.95, "jit_step"),
                ("gather.9", t0 + 0.05, t0 + 0.1, "jit_gather")]
    ev = {"devices": {"/device:TPU:0": ops}, "host": host}
    out = T.reduce_events(ev, HLO, 1, spans)
    assert out["steps"] == 2
    assert out["window_s"] == pytest.approx(2.0)
    assert out["class_s"]["backward"] == pytest.approx(0.8)
    assert out["class_s"]["combine"] == pytest.approx(0.4)
    assert out["class_s"]["collective"] == pytest.approx(0.2)
    assert out["class_s"]["feed"] == pytest.approx(0.1)
    # busy: 0.05-0.1 and 0.2-0.95 per step
    assert out["busy_s"] == pytest.approx(2 * 0.8)
    # the all-reduce overlaps fusion.6 for 0.05 of its 0.1
    assert out["collective_exposed_s"] == pytest.approx(2 * 0.05)
    idle = dict(out["idle_by_span"])
    assert idle["batch_build"] == pytest.approx(2 * 0.05)
    assert idle["straggler_draw"] == pytest.approx(2 * 0.05)
    assert idle["dispatch"] == pytest.approx(2 * 0.05)
    assert idle["wait"] == pytest.approx(2 * 0.05)


FIXTURE = PERF_DIR / "fixtures" / "trace-tiny-gclm.json.gz"


def test_reduce_a_trace_recorded_on_the_chip():
    """Two window steps of a tiny gc-lm cell (2 layers, d_model 64, N=8
    xf), traced on a TPU v5e: the events ``events_of`` read from the
    ``.xplane.pb`` and the step's HLO text, as recorded."""
    with gzip.open(FIXTURE, "rt") as f:
        blob = json.load(f)
    assert blob["device_kind"] == "TPU v5 lite"
    spans = tuple(blob["spans"])
    out = T.reduce_events(blob["events"], blob["hlo"], blob["n_chips"], spans)
    assert json.loads(json.dumps(out)) == blob["expected"]
    assert out["steps"] == 2
    assert 0 < out["busy_s"] <= out["window_s"]
    cls = out["class_s"]
    assert cls["backward"] > 0 and cls["combine"] > 0 and cls["other"] > 0
    assert "collective" not in cls        # one chip: no collective runs
    assert sum(cls.values()) <= out["busy_s"] * (1 + 1e-9)
    idle = sum(v for _, v in out["idle_by_span"])
    assert idle == pytest.approx(out["window_s"] - out["busy_s"])
    # every op of the step program is classed from the HLO text
    hlo = T.Hlo(blob["hlo"])
    ops = blob["events"]["devices"]["/device:TPU:0"]
    step_ops = [name for name, _, _, mod in ops if mod == hlo.module]
    assert step_ops and all(name in hlo.instr for name in step_ops)


#: a Pallas kernel inside the per-shard loop (an attention or expert
#: kernel of the model) beside the combine's kernel outside it
HLO_KERNELS = """HloModule jit_step, entry_computation_layout={()}

%body (t: (f32[8])) -> (f32[8]) {
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, metadata={op_name="jit(step)/per_shard_grad/while/body/jvp(jit(train_loss))/dot_general"}
  %custom-call.2 = f32[8]{0} custom-call(f32[8]{0} %fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/per_shard_grad/while/body/transpose(jvp(jit(train_loss)))/expert_gmm/pallas_call"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %while.3 = (f32[8]{0}) while((f32[8]{0}) %t), condition=%cond, body=%body
  %custom-call.4 = f32[1,512]{1,0} custom-call(f32[8]{0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/gc_combine/pallas_call"}
  %custom-call.5 = f32[8]{0} custom-call(f32[8]{0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(jit(train_loss))/attention/pallas_call"}
  ROOT %custom-call.6 = f32[8]{0} custom-call(f32[8]{0} %a), custom_call_target="Sharding"
}
"""


def test_a_custom_call_in_the_backward_is_backward():
    hlo = T.Hlo(HLO_KERNELS)
    # inside the per-shard loop's body, or under jvp( where XLA unrolled
    # the loop (K = 1): backward; outside both: the combine
    assert hlo.classify("custom-call.2") == "backward"
    assert hlo.classify("custom-call.5") == "backward"
    assert hlo.classify("custom-call.4") == "combine"
    assert hlo.classify("custom-call.6") == "other"


def test_the_chip_fixture_keeps_its_classes():
    """Every Pallas kernel of the recorded step lies outside the per-shard
    loop, so classing a custom call in the backward as backward moves
    none of its time."""
    with gzip.open(FIXTURE, "rt") as f:
        blob = json.load(f)
    hlo = T.Hlo(blob["hlo"])
    kernels = [n for n, (op, _, tgt, _) in hlo.instr.items()
               if op == "custom-call" and tgt == "tpu_custom_call"]
    assert kernels and {hlo.classify(n) for n in kernels} == {"combine"}
    out = T.reduce_events(blob["events"], blob["hlo"], blob["n_chips"],
                          tuple(blob["spans"]))
    want = blob["expected"]
    assert out["class_s"] == want["class_s"]
    assert [list(x) for x in out["top_ops"]] == want["top_ops"]
    assert out["collective_exposed_s"] == want["collective_exposed_s"]


def test_the_existing_metrics_read_the_fixture_as_before(tiny_cell):
    """The eight metrics that read the structural classes give the same
    values from ``bench.layer_record`` as from the reduction recorded
    with the fixture, and the scope metrics find no scope in a trace of
    a program that had none.  (The four-chip cell's list holds all
    eight; the one-chip trace has no collective to read.)"""
    from perf.bench import layer_metrics, layer_record

    with gzip.open(FIXTURE, "rt") as f:
        blob = json.load(f)
    cell = tiny_cell("gclm-dp4-n4-xf")
    rec = layer_record(cell, blob["events"], blob["hlo"], k_shards=8,
                       ranks=1, chips=blob["n_chips"],
                       kind=blob["device_kind"], steps=2,
                       window_s=blob["expected"]["window_s"], traced_s=0.5,
                       memory_peak_bytes=1 << 30, compiled_bytes=3 << 30)
    before = dict(rec, trace=blob["expected"])
    old = ["mfu", "backward_roofline", "combine_ms", "combine_roofline",
           "collective_exposed_ms", "host_gap_ms", "device_idle_share",
           "hbm_peak_gib"]
    assert [m["name"] for m in cell.per_layer][:8] == old
    got, want = layer_metrics(cell, rec), layer_metrics(cell, before)
    assert got == want
    assert sorted(got) == sorted(set(old) - {"collective_exposed_ms"})
