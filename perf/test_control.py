"""The control, read at a size a test run can hold.

The control is the plain reference put in the program's place with its
activations and matmul operands in bfloat16: the step below the float32
the configurations state, which would tempt a later change.  At this
size it has to read finite, and further from the reference than the
program does by the factor a limit needs between them (step 5 of the
benchmark's rules): three times on at least one number the cell
compares.  Whether it fails the cells' own limits on the chip is read by
``perf/calibrate.py`` there (PERF.md).
"""
import math

import pytest

from perf import calibrate, check


@pytest.mark.parametrize("name", ["gclm-n8-xf", "whisper-fp32-n8-xf",
                                  "gclm-n8-uniform"])
def test_the_bfloat16_control_reads_further_than_the_program(tiny_cell,
                                                            name):
    cell = tiny_cell(name)
    got = {}
    calibrate.read_cell(cell, [3], [3],
                        lambda seed, what, values: got.setdefault(what, values),
                        require_tpu=False)
    limits = cell.limits["limits"]
    program_ok, checks = check.judge(got["program"], limits)
    assert program_ok, checks
    (control,) = [v for k, v in got.items() if k.startswith("control")]
    assert all(math.isfinite(v) for v in control.values()), control
    program = got["program"]
    compared = [k for k in control if k in limits]
    assert any(control[k] > 3 * program[k] for k in compared), (control,
                                                                  program)


def test_a_non_finite_reading_is_not_correct():
    nan = float("nan")
    ok, checks = check.judge({"grad_gap": nan, "change_gap": 0.0},
                             {"grad_gap": 0.02, "change_gap": 0.01})
    assert not ok and math.isnan(checks["grad_gap"]["value"])
