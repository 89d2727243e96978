"""The control of each cell's comparison comes out as not correct: the
reference with one stated guarantee broken, through the same
``compare`` and limits a run uses.  At a size a test run can hold (the
chip-size readings are in PERF.md; ``control.py`` makes them)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import run as harness  # noqa: E402

CASES = [("tpcds_q3", {"rows": 400_000, "items": 1_000, "brands": 50},
          {"manufact": 3}),
         ("tpcds_q9", {"rows": 200_000}, {}),
         ("jcudf_rows", {"rows": 4096}, {"columns": 212})]


@pytest.mark.parametrize("seed", [11, 2_147_483_659, 3_000_000_019])
@pytest.mark.parametrize("name,sizes,params", CASES)
def test_control_is_not_correct(name, sizes, params, seed):
    ref = harness.load("reference", name)
    inputs = ref.make_inputs(sizes, params, seed)
    want = ref.answer(inputs, params)
    sound = ref.compare(ref.answer(inputs, params), want)
    assert all(sound[k] <= ref.LIMITS[k] for k in sound)
    broken = ref.compare(ref.control_answer(inputs, params), want)
    assert any(broken[k] > ref.LIMITS[k] for k in broken), broken
