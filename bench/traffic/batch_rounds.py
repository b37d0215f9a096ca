"""Closed-loop rounds: `batch` requests of `prompt` tokens arrive together,
each asks for `gen` output tokens (the first from prefill, greedy), and the
next round starts when the last token of this one has been read back.

Every seed gets the same sizes and arrivals; the seed draws only the
token ids, uniformly over the vocabulary.
"""
from __future__ import annotations

import numpy as np


def shape(params) -> tuple:
    """(batch, prompt, gen) of every round."""
    return int(params["batch"]), int(params["prompt"]), int(params["gen"])


def prompts(params, seed: int, round_index: int, vocab: int) -> np.ndarray:
    """Prompt token ids of one round, (batch, prompt) int32."""
    b, p, _ = shape(params)
    rng = np.random.default_rng([int(seed), int(round_index)])
    return rng.integers(0, vocab, size=(b, p), dtype=np.int32)
