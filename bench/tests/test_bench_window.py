"""The serving window at smoke size on the CPU: it closes at a round
boundary with every started round finished, and dispatching decode steps
ahead of the readback serves the same tokens as reading each one back
before the next step."""
import time

import numpy as np

from bench import harness


def test_window_closes_at_a_round_boundary(data_root, clean_tuning):
    cell = harness.resolve("hymba-tiny.rounds", root=data_root)
    serve = cell.runner
    state = serve.setup(cell, 7, time.perf_counter())
    b, _, g = state["shape"]
    # 0 s: the rounds that the check's sample needs, and no more
    win = serve.window(state, 0.0)
    assert len(win["rounds"]) == state["min_rounds"] >= 1
    for run in (win, serve.window(state, 0.2)):
        t0, t1 = run["window"]
        assert t1 == run["requests"][-1]["times"][-1]
        assert len(run["requests"]) == b * len(run["rounds"])
        for rd in run["rounds"]:
            assert rd["tokens"].shape == (b, g)
        for req in run["requests"]:
            assert len(req["times"]) == g
            assert t0 <= req["start"] < req["times"][0] <= t1


def test_dispatch_ahead_serves_the_same_tokens(data_root, clean_tuning):
    cell = harness.resolve("starcoder2-tiny.rounds", root=data_root)
    serve = cell.runner
    state = serve.setup(cell, 11, time.perf_counter())
    served = {}
    for step_s in (1e9, 1e-9):   # one step ahead; every step ahead
        state["step_s"] = step_s
        served[step_s] = [rd["tokens"] for rd in
                          serve.window(state, 0.0)["rounds"]]
    assert len(served[1e9]) == len(served[1e-9])
    for a, b in zip(served[1e9], served[1e-9]):
        np.testing.assert_array_equal(a, b)
