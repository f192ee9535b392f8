"""The control, at a size a test run holds: the plain reference in the
program's place, computed from bfloat16-rounded vectors, must fail the
cell's checks (``dist_err``), while the float32 program passes them
(``test_faults.test_sound_run_is_correct``)."""

import pytest

import control
import reference
from conftest import tiny_cell


@pytest.mark.parametrize("name,traffic", [("cohere-768d.knn-steady", None),
                                          ("cohere-768d.knn-steady", "range-1pct")])
def test_bf16_control_fails(name, traffic):
    checks = control.control_checks(tiny_cell(name, traffic=traffic), 2**31 + 77, 2.0, "bf16")
    assert not reference.passed(checks)
    assert checks["dist_err"]["value"] > checks["dist_err"]["limit"]
    # the control answers every probe with rows it really scored
    assert checks["unanswered"]["value"] == 0 and checks["bad_hits"]["value"] == 0
