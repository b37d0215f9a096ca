"""Required work counts against hand counts at the cells' shapes."""
import pytest

from bench import harness

HYMBA = harness.read_json(harness.PKG / "configs" / "hymba-1.5b.json")["model"]
SC2 = harness.read_json(harness.PKG / "configs" / "starcoder2-3b.json")["model"]
BF16 = "bf16"


def test_mlp_matmul_hymba_prefill_call():
    # x (8192, 1600) against w_gate, w_up (1600, 5504), out (8192, 5504)
    flops, nb = harness.code("work", "mlp_matmul").work(
        [(BF16, (8192, 5504))],
        [(BF16, (8192, 1600)), (BF16, (1600, 5504)), (BF16, (1600, 5504))])
    assert flops == 4 * 8192 * 1600 * 5504 == 288_568_115_200
    assert nb == 2 * (13_107_200 + 2 * 8_806_400 + 45_088_768)


def test_mlp_matmul_split_pass_is_half_a_call():
    w = harness.code("work", "mlp_matmul").work
    full = w([(BF16, (64, 256))], [(BF16, (64, 128)), (BF16, (128, 256)),
                                   (BF16, (128, 256))])
    half = w([("f32", (64, 256))], [(BF16, (64, 128)), (BF16, (128, 256))])
    assert half == (full[0] / 2, full[1] / 2)


def test_matmul_hymba_down_projection():
    flops, nb = harness.code("work", "matmul").work(
        [(BF16, (8192, 1600))], [(BF16, (8192, 5504)), (BF16, (5504, 1600))])
    assert flops == 144_284_057_600
    assert nb == 2 * (45_088_768 + 8_806_400 + 13_107_200)


def test_flash_attention_starcoder2_prefill_call():
    # (batch 2 x 24 heads, 3840 positions, head 128), causal
    s = (BF16, (48, 3840, 128))
    flops, nb = harness.code("work", "flash_attention").work([s], [s, s, s])
    assert flops == 4 * 128 * 48 * (3840 * 3841 // 2) == 181_241_118_720
    assert nb == 4 * 48 * 3840 * 128 * 2


def test_rms_norm_bytes():
    flops, nb = harness.code("work", "rms_norm").work(
        [(BF16, (7680, 3072))], [(BF16, (7680, 3072)), ("f32", (1, 3072))])
    assert nb == 2 * 7680 * 3072 * 2 + 3072 * 4 and flops == 4 * 7680 * 3072


# per-layer matmul weights by hand, from the published widths
HYMBA_LAYER = (1600 * 64 * (25 + 2 * 5) + 25 * 64 * 1600   # q, k, v, o
               + 3 * 1600 * 5504                           # gated MLP
               + 1600 * (2 * 3200 + 2 * 16 + 50) + 3200 * 1600)  # SSM in/out
SC2_LAYER = 3072 * 128 * (24 + 2 * 2) + 24 * 128 * 3072 + 2 * 3072 * 12288


def test_layer_weights():
    lm = harness.code("work", "model_lm")
    assert lm.layer_weights(HYMBA) == HYMBA_LAYER == 48_054_400
    assert lm.layer_weights(SC2) == SC2_LAYER == 95_944_704


def test_hymba_long_prompt_prefill_flops():
    # 3 global layers see 2048*2049/2 pairs, 29 windowed layers
    # 1024*1025/2 + (2048-1024)*1024
    pairs = 3 * 2_098_176 + 29 * (524_800 + 1_048_576)
    ssm = 6 * 50 * 16 * 64 + 2 * 4 * 3232
    want = (2 * 4 * 2048 * 32 * HYMBA_LAYER + 4 * 4 * 25 * 64 * pairs
            + 4 * 2048 * 32 * ssm + 2 * 4 * 1600 * 32001)
    got = harness.code("work", "model_lm").prefill(HYMBA, 4, 2048)
    assert got["flops"] == pytest.approx(want, rel=1e-12)
    assert 26.0e12 < got["flops"] < 27.0e12


def test_starcoder2_long_prompt_prefill_flops():
    want = (2 * 2 * 3840 * 15 * SC2_LAYER + 4 * 2 * 24 * 128 * 15 * 7_374_720
            + 2 * 2 * 3072 * 49152)
    got = harness.code("work", "model_lm").prefill(SC2, 2, 3840)
    assert got["flops"] == pytest.approx(want, rel=1e-12)


def test_hymba_decode_heavy_first_step():
    # input token at position 256: every layer attends 257 positions
    weights = 32 * HYMBA_LAYER + 1600 * 32001
    span = 32 * 257
    state = 4 * 50 * 16 * 64 + 2 * 3 * 3232
    got = harness.code("work", "model_lm").decode_step(HYMBA, 32, 256)
    assert got["bytes"] == pytest.approx(
        2 * weights + 2 * 5 * 64 * 2 * 32 * (span + 32)
        + 2 * 32 * 32 * state, rel=1e-12)
    assert got["flops"] == pytest.approx(
        2 * 32 * weights + 4 * 32 * 25 * 64 * span
        + 32 * 32 * (6 * 50 * 16 * 64 + 2 * 4 * 3232), rel=1e-12)


def test_windowed_layers_stop_growing_past_the_window():
    lm = harness.code("work", "model_lm")
    a = lm.decode_step(HYMBA, 4, 3000)["bytes"]
    b = lm.decode_step(HYMBA, 4, 3001)["bytes"]
    # only the 3 global layers read one more position
    assert b - a == pytest.approx(2 * 2 * 5 * 64 * 4 * 3)


def test_every_pallas_name_maps_to_one_kernel():
    names = harness.kernel_names()
    assert names["mlp_matmul_fused_pallas"] == "mlp_matmul"
    assert names["blocked_attention_pallas"] == "flash_attention"
    assert names["matmul_pallas"] == "matmul"
    assert names["rms_norm_pallas"] == "rms_norm"
