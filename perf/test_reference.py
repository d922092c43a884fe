"""The plain references against the program's coded step on the CPU.

Each run drives the whole harness on a tiny copy of a cell
(``conftest.tiny_tree``): the program's spmd step on a 1 x 1 mesh given
rank 0's batches, three checked steps, and the reference following
them.  On the CPU a float32 matrix product is exact to float32
rounding, so every number agrees to ~1e-6; 1e-4 leaves room for the
different order of the sums.
"""
import time

import numpy as np
import pytest

from perf import check
from perf.bench import run_cell

TOL = 1e-4


@pytest.mark.parametrize("name", ["gclm-n8-xf", "whisper-fp32-n8-xf",
                                  "gclm-n8-uniform"])
def test_reference_follows_the_one_rank_program(tiny_cell, name):
    cell = tiny_cell(name)
    cell.limits = dict(cell.limits, limits={
        "loss_gap": TOL, "grad_gap": TOL, "change_gap": TOL,
        "decode_residual": 1e-5})
    res = run_cell(cell, seed=2**33 + 11, seconds=0.2, trace=False,
                   t_start=time.perf_counter(), require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_decode_residual_catches_a_wrong_coding_row():
    rows = np.zeros((2, 1, 2))
    rows[0, 0] = [0.5, 0.5]
    rows[1, 0] = [0.5, 0.5]
    inp = check.StepInputs(model={}, traffic={}, seed=0, param_shapes=None,
                           n_workers=2, k_shards=2, ranks=1, b_rows=rows,
                           leaf_level=np.zeros(1, int),
                           dec_w=[np.ones((1, 2))])
    assert check.decode_residual(inp) == 0.0
    inp.b_rows = rows * 0.9
    assert abs(check.decode_residual(inp) - 0.1) < 1e-12


def test_gaps_ignore_still_leaves_and_compare_by_worst_leaf():
    ref = check.Readings(losses=[2.0, 2.0], grad_norms=np.array([1.0, 1e-9]),
                         change_norms=np.array([1.0, 1.0]))
    prog = check.Readings(losses=[2.0, 2.2], grad_norms=np.array([1.1, 0.0]),
                          change_norms=np.array([1.0, 5.0]))
    got = check.gaps(prog, ref)
    assert got["loss_gap"] == pytest.approx(0.1)
    assert got["grad_gap"] == pytest.approx(0.1)
    assert got["change_gap"] == 0.0   # the still leaf is left out
    ok, checks = check.judge(got, {"loss_gap": 0.2, "grad_gap": 0.05})
    assert not ok and checks["grad_gap"]["limit"] == 0.05
