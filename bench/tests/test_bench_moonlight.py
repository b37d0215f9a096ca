"""The moonlight-16b-a3b cell's parts on the CPU: a whole run at smoke size
(latent attention, sigmoid routing, a dense first layer, 4 of 8 experts
held) is correct, a broken decode step is not, the decode-cache counter
reads what the latent cache holds, and the work counts match the
configuration's sizes."""
import dataclasses
import json

import jax
import pytest

from bench import harness
from bench.tests.conftest import CPU

CELL = "moonlight-16b-a3b.decode-heavy"


@pytest.fixture
def tiny_root(tmp_path):
    """A data root whose one cell serves the smoke configuration through
    the cell's reference, work count, traffic generator and runner.  The
    program computes in float32, so a sound run reads no gap at all
    (routing picks the reference's experts) and any fault shows."""
    from repro.configs import get_smoke
    cfg = get_smoke("moonlight-16b-a3b")
    m = dict(dataclasses.asdict(cfg), name="moonlight-tiny",
             experts_held=list(cfg.experts_held), dtype="float32")
    files = {
        "bench/configs/moonlight-tiny.json": {
            "name": "moonlight-tiny", "reference": "moonlight-16b-a3b",
            "work": "model_mla_moe", "model": m},
        "bench/traffic/tiny-rounds.json": {
            "generator": "batch_rounds", "runner": "serve", "batch": 4,
            "prompt": 24, "gen": 6},
        "bench/limits/moonlight-tiny.rounds.json": {
            "served_logit_gap": {"limit": 0.1, "sample_tokens": 12,
                                 "min_requests": 2, "reference_rows": 2}},
    }
    spec = harness.read_json(harness.ROOT / "BENCHMARK.json")
    files["BENCHMARK.json"] = {
        "workloads": [{"name": "moonlight-tiny.rounds",
                       "config": "moonlight-tiny", "traffic": "tiny-rounds",
                       "chips": 1}],
        "end_to_end": [dict(x, workloads=["moonlight-tiny.rounds"])
                       for x in spec["end_to_end"]
                       if CELL in x.get("workloads", [CELL])],
        "per_layer": []}
    for name, data in files.items():
        path = tmp_path / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(data))
    return tmp_path


def run(cell):
    """One round (the check's sample needs no more), so the requests
    checked do not depend on the host's speed."""
    return harness.run_cell(cell, seed=2 ** 33 + 5, seconds=0.0, trace=False,
                            t0=0.0, device=CPU)


def test_sound_run_is_correct(tiny_root, clean_tuning):
    out = run(harness.resolve("moonlight-tiny.rounds", root=tiny_root))
    assert out["correct"] is True
    assert out["attempted"] == 4 and out["failed"] == 0
    assert set(out["metrics"]) == {"itl_p95_ms", "output_tok_s", "setup_s"}
    # 3 layers of a 32-wide latent and an 8-wide rope key, in float32
    assert harness.code("metrics", "cache_bytes_per_token").read({}) \
        == 3 * (32 + 8) * 4


def test_stale_latent_cache_is_not_correct(tiny_root, clean_tuning,
                                           monkeypatch):
    """A decode step that writes nothing to the latent cache serves
    tokens the reference does not back."""
    import jax.numpy as jnp
    cell = harness.resolve("moonlight-tiny.rounds", root=tiny_root)
    serve = cell.runner
    sound = serve.serving_fns

    def stale(model, max_len):
        prefill, decode = sound(model, max_len)

        def step(p, c, t):
            tok, new = decode(p, jax.tree.map(jnp.copy, c), t)
            return tok, dict(new, c_kv=c["c_kv"], k_rope=c["k_rope"])
        return prefill, jax.jit(step)

    monkeypatch.setattr(serve, "serving_fns", stale)
    out = run(cell)
    assert out["correct"] is False, out["checks"]


def test_work_counts_the_held_experts_and_the_latent_cache():
    """Decode reads every layer weight and held expert once in bf16, the
    head, and the latent cache (576 values a position and layer); a
    token goes through 6 x 16/64 held experts on average."""
    m = harness.read_json(harness.ROOT / "bench" / "configs"
                          / "moonlight-16b-a3b.json")["model"]
    mw = harness.code("work", "model_mla_moe")
    d, h, f = 2048, 16, 1408
    attn = d * h * 192 + d * 576 + 512 * h * 256 + h * 128 * d
    expert = 3 * d * f
    dense = 3 * d * 11264
    held = 16 * expert + 2 * expert + d * 64      # + shared + router
    assert mw.stored_weights(m) == 9 * attn + dense + 8 * held
    assert mw.token_weights(m) == 9 * attn + dense + 8 * (
        held - 16 * expert + 6 * 16 / 64 * expert)
    head = d * 40960
    step = mw.decode_step(m, 64, 1100)
    assert step["bytes"] == 2 * (mw.stored_weights(m) + head) \
        + 2 * 576 * 64 * 1102 * 9
    assert step["flops"] == 2 * 64 * (mw.token_weights(m) + head) \
        + 2 * 64 * h * (2 * 512 + 64) * 1101 * 9
