"""``perf/scopes.py``: device time by the program's named scopes, set
beside ``perf/trace_reduce.py``'s structural classes, and the clock check
of the step program's runs against the host spans."""
import gzip
import json
from types import SimpleNamespace as NS

import pytest

from perf import scopes as S
from perf import trace_reduce as T
from perf.spec import PERF_DIR

SPANS = ("batch_build", "straggler_draw", "dispatch", "wait")

HLO = """HloModule jit_step, entry_computation_layout={()}

%fused_mix (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %slice.1 = f32[8]{0} slice(f32[8]{0} %p), slice={[0:8]}, metadata={op_name="jit(step)/gc_unpack/slice"}
  ROOT %m = f32[8]{0} multiply(f32[8]{0} %slice.1, f32[8]{0} %slice.1), metadata={op_name="jit(step)/optimizer/mul"}
}

%fused_pack (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  ROOT %concatenate.2 = f32[8]{0} concatenate(f32[8]{0} %q), dimensions={0}, metadata={op_name="jit(step)/gc_pack/concatenate"}
}

%body (t: (f32[8])) -> (f32[8]) {
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, metadata={op_name="jit(step)/per_shard_grad/while/body/transpose(jvp(jit(train_loss)))/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %while.3 = (f32[8]{0}) while((f32[8]{0}) %t), condition=%cond, body=%body, metadata={op_name="jit(step)/per_shard_grad/while"}
  %fusion.4 = f32[8]{0} fusion(f32[8]{0} %g), kind=kLoop, calls=%fused_pack, metadata={op_name="jit(step)/gc_pack/concatenate"}
  %custom-call.5 = f32[1,512]{1,0} custom-call(f32[8]{0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/gc_combine/pallas_call"}
  %all-reduce.6 = f32[8]{0} all-reduce(f32[8]{0} %b), replica_groups={}, to_apply=%add, metadata={op_name="jit(step)/level_collective/psum"}
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %c), kind=kLoop, calls=%fused_mix, metadata={op_name="jit(step)/optimizer/mul"}
  %copy.8 = f32[8]{0} copy(f32[8]{0} %c)
  ROOT %fusion.9 = f32[8]{0} fusion(f32[8]{0} %c), kind=kLoop, metadata={op_name="jit(step)/monitor_forward/reduce_sum"}
}
"""

#: (instruction, start, end) within one step that starts at t0
STEP_OPS = [("while.3", 0.20, 0.50), ("fusion.1", 0.20, 0.50),
            ("fusion.4", 0.50, 0.55), ("custom-call.5", 0.55, 0.70),
            ("all-reduce.6", 0.70, 0.75), ("fusion.7", 0.75, 0.85),
            ("copy.8", 0.85, 0.87), ("fusion.9", 0.87, 0.95)]


def _events(n_steps=2):
    host, ops = [], []
    for t0 in map(float, range(n_steps)):
        host += [("batch_build", t0, t0 + 0.1),
                 ("straggler_draw", t0 + 0.1, t0 + 0.15),
                 ("dispatch", t0 + 0.15, t0 + 0.2),
                 ("wait", t0 + 0.2, t0 + 1.0)]
        ops += [(n, t0 + s, t0 + e, "jit_step") for n, s, e in STEP_OPS]
        ops.append(("gather.9", t0 + 0.05, t0 + 0.1, "jit_gather"))
    return {"devices": {"/device:TPU:0": ops}, "host": host}


def test_scope_of_takes_the_first_scope_in_the_path():
    assert S.scope_of("jit(step)/optimizer/jit(norm)/sqrt") == "optimizer"
    assert S.scope_of("jit(step)/gc_pack/gc_combine/x") == "gc_pack"
    assert S.scope_of("jit(step)/reshape") == S.UNSCOPED
    assert S.scope_of("") == S.UNSCOPED


def test_each_op_counts_in_its_own_scope():
    out = S.scope_times(_events(), HLO, 1, SPANS)
    assert out["scope_s"] == pytest.approx({
        "per_shard_grad": 2 * 0.30, "gc_pack": 2 * 0.05,
        "gc_combine": 2 * 0.15, "level_collective": 2 * 0.05,
        "gc_unpack": 0.0, "monitor_forward": 2 * 0.08,
        "optimizer": 2 * 0.10})
    # the scopes come in the program's order; gc_unpack is carried by an
    # instruction fused into the optimizer, so it is present, with no time
    assert list(out["scope_s"]) == [s for s in S.SCOPES]


def test_an_op_without_a_scope_is_unscoped():
    out = S.scope_times(_events(), HLO, 1, SPANS)
    assert out["unscoped_s"] == pytest.approx(2 * 0.02)
    assert out["top_unscoped"][0][0] == "copy.8"


def test_a_fusion_of_two_scopes_counts_as_mixed():
    hlo = S.ScopedHlo(HLO)
    assert hlo.mixed("fusion.7")          # gc_unpack's slice + optimizer
    assert not hlo.mixed("fusion.4")      # gc_pack alone
    assert not hlo.mixed("copy.8")
    out = S.scope_times(_events(), HLO, 1, SPANS)
    assert out["mixed_s"] == pytest.approx(2 * 0.10)
    assert out["scope_s"]["optimizer"] == pytest.approx(2 * 0.10)


def test_scopes_and_classes_share_one_total():
    ev = _events()
    out = S.scope_times(ev, HLO, 1, SPANS)
    red = T.reduce_events(ev, HLO, 1, SPANS)
    step_ops = sum(v for c, v in red["class_s"].items() if c != "feed")
    assert sum(out["scope_s"].values()) + out["unscoped_s"] == \
        pytest.approx(step_ops)
    assert out["cross_s"]["combine"] == pytest.approx({"gc_combine": 0.30})
    assert out["cross_s"]["backward"] == pytest.approx(
        {"per_shard_grad": 0.60})
    assert out["cross_s"]["collective"] == pytest.approx(
        {"level_collective": 0.10})
    assert "feed" not in out["cross_s"]


FIXTURE = PERF_DIR / "fixtures" / "trace-tiny-gclm.json.gz"


def test_a_trace_of_an_unscoped_program_is_all_unscoped():
    """The chip fixture was recorded before the program had scopes."""
    with gzip.open(FIXTURE, "rt") as f:
        blob = json.load(f)
    spans = tuple(blob["spans"])
    out = S.scope_times(blob["events"], blob["hlo"], blob["n_chips"], spans)
    assert out["scope_s"] == {} and out["mixed_s"] == 0.0
    step_ops = sum(v for c, v in blob["expected"]["class_s"].items()
                   if c != "feed")
    assert out["unscoped_s"] == pytest.approx(step_ops, rel=1e-12)
    assert sum(sum(row.values()) for row in out["cross_s"].values()) == \
        pytest.approx(step_ops, rel=1e-12)


def test_clock_check_pairs_each_run_with_its_step():
    host = _events()["host"]
    runs = {"/device:TPU:0": [(0.2, 0.95), (1.2, 0.95 + 1)]}
    out = S.clock_check(runs, host)
    assert out["matched"] and out["runs"] == out["steps"] == 2
    assert out["min_lead_s"] == pytest.approx(0.05)     # dispatch at t0+.15
    assert out["min_tail_s"] == pytest.approx(0.05)     # wait ends at t0+1
    early = {"/device:TPU:0": [(0.1, 0.95), (1.2, 1.95)]}
    assert S.clock_check(early, host)["min_lead_s"] == pytest.approx(-0.05)
    short = S.clock_check({"/device:TPU:0": [(0.2, 0.95)]}, host)
    assert not short["matched"]


def test_module_runs_reads_the_step_modules_of_each_chip():
    def ev(name, start, dur):
        return NS(name=name, start_ns=start, duration_ns=dur)

    pd = NS(planes=[
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=[ev("jit_step(7)", 300, 50),
                                           ev("jit_gather(8)", 100, 10),
                                           ev("jit_step(7)", 200, 50)]),
            NS(name="XLA Ops", events=[ev("%x = f32[] add()", 200, 5)])]),
        NS(name="/host:CPU", lines=[
            NS(name="XLA Modules", events=[ev("jit_step(7)", 0, 1)])])])
    runs = S.module_runs(pd, "jit_step")
    assert list(runs) == ["/device:TPU:0"]
    assert runs["/device:TPU:0"] == [pytest.approx((200e-9, 250e-9)),
                                     pytest.approx((300e-9, 350e-9))]


#: a part of the model (an expert layer, say) under its own scope inside
#: the per-shard backward, beside the same scope name outside it
HLO_NESTED = """HloModule jit_step, entry_computation_layout={()}

%body (t: (f32[8])) -> (f32[8]) {
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, metadata={op_name="jit(step)/per_shard_grad/while/body/jvp(jit(train_loss))/toy_part/dot_general"}
  %custom-call.2 = f32[8]{0} custom-call(f32[8]{0} %p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/per_shard_grad/while/body/transpose(jvp(jit(train_loss)))/toy_part/pallas_call"}
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, metadata={op_name="jit(step)/per_shard_grad/while/body/jvp(jit(train_loss))/toy_partner/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %while.4 = (f32[8]{0}) while((f32[8]{0}) %t), condition=%cond, body=%body, metadata={op_name="jit(step)/per_shard_grad/while"}
  %custom-call.5 = f32[8]{0} custom-call(f32[8]{0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/gc_combine/pallas_call"}
  %fusion.6 = f32[8]{0} fusion(f32[8]{0} %c), kind=kLoop, metadata={op_name="jit(step)/monitor_forward/toy_part/dot_general"}
  ROOT %fusion.7 = f32[8]{0} fusion(f32[8]{0} %c), kind=kLoop, metadata={op_name="jit(step)/optimizer/mul"}
}
"""

NESTED_OPS = [("while.4", 0.20, 0.50), ("fusion.1", 0.20, 0.30),
              ("custom-call.2", 0.30, 0.45), ("fusion.3", 0.45, 0.50),
              ("custom-call.5", 0.50, 0.60), ("fusion.6", 0.60, 0.64),
              ("fusion.7", 0.64, 0.70)]


def _nested_record(n_steps=2):
    ev = _events(n_steps)
    ev["devices"]["/device:TPU:0"] = [
        (n, t0 + s, t0 + e, "jit_step") for t0 in map(float, range(n_steps))
        for n, s, e in NESTED_OPS]
    sc = S.scope_times(ev, HLO_NESTED, 1, SPANS)
    return {"ops": sc["ops"], "scopes": sc,
            "trace": T.reduce_events(ev, HLO_NESTED, 1, SPANS)}


def test_ops_list_every_step_op_with_its_class_and_path():
    rec = _nested_record()
    ops = {name: (cls, op_name, sec) for name, cls, op_name, sec in rec["ops"]}
    assert set(ops) == {"fusion.1", "custom-call.2", "fusion.3",
                        "custom-call.5", "fusion.6", "fusion.7"}
    assert ops["custom-call.2"][0] == "backward"   # a kernel in the loop
    assert ops["custom-call.5"][0] == "combine"
    assert ops["fusion.6"][1] == "jit(step)/monitor_forward/toy_part/dot_general"
    assert ops["custom-call.2"][2] == pytest.approx(2 * 0.15)
    assert [op[0] for op in rec["ops"]][0] == "custom-call.2"   # longest


def test_part_s_times_a_scope_nested_in_another():
    rec = _nested_record()
    # toy_part inside the backward and inside the monitoring forward; not
    # toy_partner, whose name only begins the same
    assert S.part_s(rec, "toy_part") == pytest.approx(2 * (0.10 + 0.15
                                                           + 0.04))
    assert S.part_s(rec, "toy_partner") == pytest.approx(2 * 0.05)
    assert S.part_s(rec, "per_shard_grad") == pytest.approx(2 * 0.30)
    assert S.part_s(rec, "no_such_part") is None
    # the first scope of each path still takes the op whole
    scope_s = rec["scopes"]["scope_s"]
    assert scope_s["per_shard_grad"] == pytest.approx(2 * 0.30)
    assert scope_s["monitor_forward"] == pytest.approx(2 * 0.04)


def test_per_step_ms_sums_scopes_and_is_none_where_absent():
    rec = _nested_record()
    assert S.per_step_ms(rec, "optimizer") == pytest.approx(1e3 * 0.06)
    assert S.per_step_ms(rec, "optimizer", "monitor_forward") == \
        pytest.approx(1e3 * 0.10)
    assert S.per_step_ms(rec, "gc_pack", "gc_unpack") is None
    rec["trace"]["steps"] = 0
    assert S.per_step_ms(rec, "optimizer") is None


@pytest.mark.parametrize("metric, want", [
    ("optimizer_ms", 1e3 * 0.06), ("monitor_forward_ms", 1e3 * 0.04),
    ("pack_unpack_ms", None)])
def test_scope_metric_readers(metric, want):
    from perf.spec import load_module

    read = load_module(PERF_DIR / "metrics" / f"{metric}.py").read
    got = read(_nested_record())
    assert got is None if want is None else got == pytest.approx(want)
