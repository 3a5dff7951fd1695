"""Training traffic: a stream of fixed-length token sequences, one per
chip per step, fed through the runtime's data plane. Plain causal
attention over each sequence (the program has no segment masks yet),
so a row is `seq_len + 1` seeded token ids: inputs and shifted targets.
"""

from __future__ import annotations

import numpy as np

DRIVER = "train"


def generate(params: dict, seed: int, chips: int, vocab_size: int) -> dict:
    """-> {"tokens": int32 [rows, seq_len + 1], "batch", "seq_len"};
    rows cover `max_steps` steps of `sequences_per_chip * chips`."""
    batch = int(params["sequences_per_chip"]) * int(chips)
    rows = batch * int(params["max_steps"])
    rng = np.random.default_rng([int(seed), 0x7A1A])
    tokens = rng.integers(
        0, vocab_size, size=(rows, int(params["seq_len"]) + 1),
        dtype=np.int32,
    )
    return {
        "tokens": tokens, "batch": batch, "seq_len": int(params["seq_len"])
    }
