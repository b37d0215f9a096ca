"""The control: the reference with every matmul's operands rounded to
float8 e4m3 (the precision below the bfloat16 the configurations compute
in), judged by the runner's own check, fails the limit that the program
passes, at smoke size on the CPU, on the first three seeds.  A window of
0 s runs the rounds that the check's sample needs and no more, so the
readings do not depend on the speed of the machine."""
import time

import pytest

from bench import control, harness


@pytest.mark.parametrize("workload", ["hymba-tiny.rounds",
                                      "starcoder2-tiny.rounds"])
def test_control_fails_where_the_program_passes(workload, data_root,
                                                clean_tuning):
    cell = harness.resolve(workload, root=data_root)
    rows = list(control.readings(cell, [1, 2, 3], 0.0, time.perf_counter()))
    assert [r["seed"] for r in rows] == [1, 2, 3]
    for r in rows:
        assert r["program"]["ok"] and not r["control"]["ok"], r
        assert r["program"]["value"] < r["control"]["value"], r
