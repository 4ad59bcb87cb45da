"""The control: the plain reference in the program's place, ranking in
bfloat16 (one step below the float32 in which the program ranks) and
reporting in float64 as the program does, is judged not correct by the
run's own comparison and limits; so is one computing in float32 (one
step below the float64 estimates).  At a size a test holds;
`bench/control.py` runs it at a cell's own size on the chip's host."""
import json

import pytest

from bench_tiny import REPO, TINY_CONFIG
from bench import control

TRAFFIC = json.loads((REPO / "bench" / "traffic" / "nobypass.json")
                     .read_text())


@pytest.mark.parametrize("rank,report,number", [
    ("bfloat16", "float64", "regret"),
    ("float32", "float32", "estimate_gap")])
@pytest.mark.parametrize("seed", [1, 2, 3000000019])
def test_control_is_not_correct(rank, report, number, seed):
    from bench.reference.check import judge
    nums = control.control_numbers(TINY_CONFIG, TRAFFIC, seed, rank, report)
    ok, _ = judge(nums, control.limits())
    assert not ok
    assert nums[number] > control.limits()[number]


def test_float64_reference_in_the_programs_place_is_correct():
    from bench.reference.check import judge
    nums = control.control_numbers(TINY_CONFIG, TRAFFIC, 5, "float64",
                                    "float64")
    ok, lines = judge(nums, control.limits())
    assert ok, lines
    assert nums["estimate_gap"] == 0.0 and nums["regret"] == 0.0
