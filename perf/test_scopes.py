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
