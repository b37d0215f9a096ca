"""Tails and rates are taken over every request and the whole window."""
import pytest

from bench import harness, stats


def rounds_record(n_rounds=20, batch=4, gen=8, prefill=0.5, step=0.03,
                  stall_at=None, stall=0.0, seconds=None):
    """Closed-loop rounds on a synthetic clock; `stall` seconds are added
    before decode step `stall_at` of round 3."""
    t, reqs = 0.0, []
    for r in range(n_rounds):
        start = t
        times = [start + prefill]
        for k in range(1, gen):
            extra = stall if (r == 3 and k == stall_at) else 0.0
            times.append(times[-1] + step + extra)
        t = times[-1]
        reqs += [{"start": start, "times": times} for _ in range(batch)]
    end = seconds if seconds is not None else t
    return {"window": [0.0, end], "requests": reqs}


def read(name, rec):
    return harness.code("metrics", name).read(rec)


def test_plain_rounds():
    rec = rounds_record()
    assert read("ttft_p90_ms", rec) == pytest.approx(500.0)
    assert read("itl_p95_ms", rec) == pytest.approx(30.0)
    # 20 rounds of 4 x 8 tokens in 20 x (0.5 + 7 x 0.03) s
    assert read("output_tok_s", rec) == pytest.approx(640 / 14.2)


def test_a_stall_moves_the_tail_and_the_rate():
    base = rounds_record()
    # one 2 s stall in one gap of 140: the rate drops, the p95 gap stays
    one = rounds_record(stall_at=2, stall=2.0)
    assert read("output_tok_s", one) < read("output_tok_s", base)
    assert read("itl_p95_ms", one) == pytest.approx(30.0)
    # a 0.2 s stall at decode steps 2 and 5 of every round (2 gaps in 7)
    stalled = {"window": [0.0, 0.0], "requests": []}
    t = 0.0
    for r in range(20):
        start = t
        times = [start + 0.5]
        for k in range(1, 8):
            times.append(times[-1] + 0.03 + (0.2 if k in (2, 5) else 0.0))
        t = times[-1]
        stalled["requests"] += [{"start": start, "times": times}] * 4
    stalled["window"][1] = t
    assert read("itl_p95_ms", stalled) == pytest.approx(230.0)
    assert read("itl_p95_ms", base) == pytest.approx(30.0)
    assert read("output_tok_s", stalled) < read("output_tok_s", base)


def test_window_cuts_count_what_arrived():
    rec = rounds_record(seconds=1.0)
    # round 0: 8 tokens by 0.71 s; round 1 starts 0.71, first token 1.21
    assert stats.tokens_in_window(rec) == 4 * 8
    assert read("output_tok_s", rec) == pytest.approx(32.0)
    # every request started in the window has a TTFT, even if after the close
    assert len(stats.ttfts(rec)) == 8


def test_percentile_interpolates_and_handles_empty():
    assert stats.percentile([], 90) is None
    assert stats.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 50) == 3.0
    assert stats.percentile(list(range(11)), 90) == pytest.approx(9.0)
    assert read("itl_p95_ms", {"window": [0, 1], "requests": []}) is None
