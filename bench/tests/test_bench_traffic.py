"""Seeded traffic: the same seed gives the same inputs, another seed other
token ids, and every seed the same sizes."""
import numpy as np

from bench import harness

GEN = harness.code("traffic", "batch_rounds")
PARAMS = {"batch": 4, "prompt": 64, "gen": 8}


def test_same_seed_same_prompts():
    a = GEN.prompts(PARAMS, 7, 3, 32001)
    b = GEN.prompts(PARAMS, 7, 3, 32001)
    assert a.dtype == np.int32 and a.shape == (4, 64)
    np.testing.assert_array_equal(a, b)


def test_seeds_and_rounds_differ():
    base = GEN.prompts(PARAMS, 7, 0, 32001)
    assert (GEN.prompts(PARAMS, 8, 0, 32001) != base).mean() > 0.9
    assert (GEN.prompts(PARAMS, 7, 1, 32001) != base).mean() > 0.9


def test_large_seed_in_vocab_and_same_sizes():
    for seed in (0, 2**31 + 11, 2**33 + 5):
        p = GEN.prompts(PARAMS, seed, 0, 49152)
        assert p.shape == (4, 64)
        assert p.min() >= 0 and p.max() < 49152
    assert GEN.shape(PARAMS) == (4, 64, 8)


def test_every_traffic_file_names_code_that_exists():
    for path in sorted((harness.PKG / "traffic").glob("*.json")):
        t = harness.read_json(path)
        harness.code("traffic", t["generator"])
        harness.code("runners", t["runner"])
        assert len(harness.code("traffic", t["generator"]).shape(t)) == 3
