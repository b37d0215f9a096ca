"""The reduction from trace events to per-layer numbers: exact on a
hand-made trace, and sound on a small trace recorded on the chip."""
import json
import pathlib

import pytest

from bench import harness, tracefile

DATA = pathlib.Path(__file__).resolve().parent / "data"
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def hand_trace():
    """A prefill (ns 100-400) holding a loop op over two ops, one a Pallas
    matmul, then two decode steps."""
    mm = ("%matmul_pallas.3 = bf16[4,8]{1,0} custom-call(bf16[4,2]{1,0} "
          "%a, bf16[2,8]{1,0} %b), custom_call_target=\"tpu_custom_call\"")
    ops = [tracefile.parse_op(t) | {"start": s, "dur": d} for t, s, d in [
        ("%while.1 = (s32[]) while(s32[] %x)", 100, 250),
        (mm, 110, 100),
        ("%fusion.2 = f32[4] fusion(f32[4] %y)", 250, 50),
        ("%fusion.9 = f32[4] fusion(f32[4] %z)", 380, 20),
        ("%fusion.5 = f32[4] fusion(f32[4] %y)", 500, 80),
        ("%fusion.5 = f32[4] fusion(f32[4] %y)", 700, 80),
    ]]
    return {"modules": [["jit_bench_prefill(1)", 100, 300],
                        ["jit_bench_decode(2)", 500, 100],
                        ["jit_bench_decode(2)", 700, 100]],
            "ops": ops}


def test_parse_op_reads_kernel_shapes():
    op = hand_trace()["ops"][1]
    assert op["instr"] == "matmul_pallas.3"
    assert op["kernel_fn"] == "matmul_pallas"
    assert op["out"] == [("bf16", (4, 8))]
    assert op["in"] == [("bf16", (4, 2)), ("bf16", (2, 8))]
    assert "kernel_fn" not in hand_trace()["ops"][2]


def test_hand_trace_reduces_exactly():
    names = {"matmul_pallas": "matmul"}
    work = {"matmul": harness.code("work", "matmul").work}
    red = tracefile.reduce(hand_trace(), names, work, PEAKS)
    # busy: 100-350, 380-400, 500-580, 700-780 in the window 100-800
    assert red["busy_s"] == pytest.approx(430e-9)
    assert red["window_s"] == pytest.approx(700e-9)
    # the first program of a trace has no period to wait in
    assert red["idle_share"]["prefill"] is None
    # decode periods 400-600 and 600-800, busy 80 each
    assert red["idle_share"]["decode"] == pytest.approx(1 - 160 / 400)
    assert red["module_s"]["prefill"] == pytest.approx([300e-9])
    assert red["module_s"]["decode"] == pytest.approx([100e-9, 100e-9])
    # matmul 4x2x8: 128 FLOPs (1.28 s at 100/s), 2*(8+16+32) = 112 bytes
    dev, req, calls = red["kernels"]["prefill"]["matmul"]
    assert (dev, calls) == (pytest.approx(100e-9), 1)
    assert req == pytest.approx(max(128 / 100.0, 112 / 10.0))
    top = dict(red["device_ops"])
    # the loop's own time excludes the two ops inside it
    assert top["prefill:while.1"] == pytest.approx(100e-9)
    assert top["decode:fusion.5"] == pytest.approx(160e-9)
    # 350-380 inside the prefill, 400-500 and 580-700 waiting for the host
    # before a decode step, 780-800 inside the last one
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"inside prefill": 30e-9, "before decode": 220e-9,
         "inside decode": 20e-9})


def test_recorded_chip_trace():
    """hymba-1.5b at 2 layers, batch 4 x prompt 2048, two rounds of a
    prefill and three decode steps, traced on one v5e.  The probe's
    wrappers were lambdas; here they get the runner's names."""
    ev = json.loads((DATA / "probe-hymba-2layer.json").read_text())
    names = {"jit__lambda(11692162049078372953)": "jit_bench_prefill",
             "jit__lambda(10960371488414017881)": "jit_bench_decode"}
    ev["modules"] = [[names[n], s, d] for n, s, d in ev["modules"]]
    peaks = harness.peaks_for("TPU v5 lite")
    ids = harness.kernel_names()
    work = {k: harness.code("work", k).work for k in set(ids.values())}
    red = tracefile.reduce(ev, ids, work, peaks)
    assert 0 < red["busy_s"] < red["window_s"]
    assert len(red["module_s"]["prefill"]) == 2
    assert len(red["module_s"]["decode"]) == 6
    assert 0.02 < red["module_s"]["prefill"][0] < 0.04
    # the second prefill waited on the host after round 0's last step
    assert 0 < red["idle_share"]["prefill"] < 0.2
    assert 0 < red["idle_share"]["decode"] < 0.8
    # the tuned prefill kernels: 2 layers x 2 prefills of the MLP pair,
    # compute-bound at 8192 rows, so their share stays under 100%
    pre = red["kernels"]["prefill"]
    assert set(pre) == {"rms_norm", "mlp_matmul", "matmul"}
    assert pre["mlp_matmul"][2] == pre["matmul"][2] == 4
    assert pre["rms_norm"][2] == 10          # ln1, ln2 per layer + final
    for kid in ("mlp_matmul", "matmul"):
        dev_s, req_s, _ = pre[kid]
        assert 0.5 < req_s / dev_s < 1.0
    # own times never exceed the busy time, and the loop op is not doubled
    assert sum(v for _, v in red["device_ops"]) <= red["busy_s"] * 1.0001
    assert all(v > 0 for _, v in red["idle_gaps"])
