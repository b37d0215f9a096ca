"""The set-up readers on the program's own spans, at smoke size on the
CPU: each reads a value, the pretune and trace spans lie inside the
runner's own timings of the same calls, and set-up compiled something."""
import time

import pytest

from bench import harness

READERS = ("setup.pretune_s", "setup.trace_s", "setup.xla_s")


@pytest.fixture
def setup_record(data_root, clean_tuning):
    from repro import obs
    cell = harness.resolve("hymba-tiny.rounds", root=data_root)
    obs.reset()
    state = cell.runner.setup(cell, 5, time.perf_counter())
    return {"window": [time.perf_counter(), time.perf_counter()],
            "pretune_s": state["pretune_s"],
            "compile_s": state["compile_s"]}


def read(name, rec):
    return harness.code("metrics", name).read(rec)


def test_setup_readers_read_the_programs_spans(setup_record):
    rec = setup_record
    got = {name: read(name, rec) for name in READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["setup.pretune_s"] <= rec["pretune_s"]
    assert got["setup.trace_s"] <= rec["compile_s"]


def test_setup_readers_leave_out_what_follows_the_window(setup_record):
    rec = setup_record
    before = {name: read(name, rec) for name in READERS}
    from repro import obs
    with obs.span("pretune"), obs.span("trace.decode"):
        pass
    with obs.span("freeze"):
        pass
    assert {name: read(name, rec) for name in READERS} == before
    assert read("setup.xla_s", {"window": [0.0, 0.0]}) is None


def test_spans_under_pretune_are_not_counted_as_serving_traces(
        setup_record):
    from repro import obs
    spans = obs.spans()
    traces = [s for s in spans if s["name"].startswith("trace.")]
    nested = [s for s in traces if s["parent"] is not None]
    assert {spans[s["parent"]]["name"] for s in nested} == \
        {"pretune.enumerate"}
    assert {s["name"] for s in traces if s["parent"] is None} == \
        {"trace.prefill", "trace.decode"}
