"""The harness: names and units as the contract allows them, cells found by
name with no edit to a file that is there, and no run without a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
SPEC = harness.read_json(harness.ROOT / "BENCHMARK.json")


def test_benchmark_json_keys_names_and_units():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [c["name"] for c in SPEC["configs"]]
    names += [w["name"] for w in SPEC["workloads"]]
    names += [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [w["traffic"] for w in SPEC["workloads"]]
    names += [k for c in SPEC["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for text in ([w["why"] for w in SPEC["workloads"]]
                 + [c["why"] for c in SPEC["configs"]]
                 + [c["source"] for c in SPEC["configs"]]
                 + [m["layer"] for m in SPEC["per_layer"]] + SPEC["command"]):
        assert LINE.match(text), text


def test_every_name_resolves_to_its_files():
    ends = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        harness.code("metrics", m["name"])
        assert m.get("moves", "setup_s") in ends
    for c in SPEC["configs"]:
        cfg = harness.read_json(harness.ROOT / c["file"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        harness.code("reference", cfg["reference"])
    for w in SPEC["workloads"]:
        cell = harness.resolve(w["name"])
        assert cell.runner and cell.generator and cell.model_work
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
        assert cell.per_layer


def test_a_test_only_cell_needs_no_edit_to_a_file_that_is_there(data_root):
    """A configuration, traffic mix and limits that live only under a test
    data root are found by name; the code they name is the package's."""
    cell = harness.resolve("hymba-tiny.rounds", root=data_root)
    assert cell.config["model"]["d_model"] == 64
    assert cell.traffic["batch"] == 2
    assert cell.reference is harness.code("reference", "hymba-1.5b")
    assert [m["name"] for m in cell.end_to_end][-1] == "setup_s"
    with pytest.raises(SystemExit):
        harness.resolve("no-such.cell", root=data_root)


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(ValueError):
        harness.peaks_for("TPU v99")


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(harness.PKG / "run.py"), "--workload",
         "hymba-1.5b.long-prompt", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]


def test_result_line_carries_device_checks_last():
    rec = {"memory_peak_bytes": 5, "attempted": 4, "failed": 0,
           "setup_s": 3.5, "window": [0.0, 1.0], "trace": None,
           "requests": [{"start": 0.0, "times": [0.25, 0.5]}],
           "checks": [{"name": "served_logit_gap", "value": 0.1,
                       "limit": 0.5, "ok": True}]}

    class Runner:
        @staticmethod
        def run(cell, **kw):
            return rec

    cell = harness.resolve("hymba-1.5b.decode-heavy")
    cell.__class__ = type("C", (harness.Cell,), {"runner": Runner})
    out = harness.run_cell(cell, seed=1, seconds=1.0, trace=False, t0=0.0,
                           device={"platform": "tpu", "kind": "TPU v5 lite",
                                   "count": 1})
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert out["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1, "memory_peak_bytes": 5}
    assert set(out["metrics"]) == {"itl_p95_ms", "output_tok_s", "setup_s"}
    json.dumps(out)
    rec["checks"][0]["ok"] = False
    assert harness.run_cell(cell, seed=1, seconds=1.0, trace=False, t0=0.0,
                            device={})["correct"] is False
