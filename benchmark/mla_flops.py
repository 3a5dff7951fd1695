"""What a prefill chunk's selected attention requires of the chip.

A pair is one (query, key) of one layer that the query attends after
the indexer's selection. Each runs every head's score over its own key
dims and the shared rotary dims, and its weighted value: 2 x (nope +
rope) + 2 x v operations a head, `n_heads` heads. The kernel
(`ray_tpu/ops/selected_attention.py`, `selected_attn` in a trace)
computes every pair of a live tile and masks the unselected, so what it
is asked for here is the selected pairs alone: the share that reads is
the cost of running dense under a mask. Its bytes (a tile of keys and
values a step, 256 KB for 0.2 GFLOP) are far under its operations: the
operations bound it. `model` holds `LlamaConfig` keys.
"""

from __future__ import annotations


def pair_flops(model: dict) -> int:
    """Operations one attended (query, key) pair of one layer requires
    over all heads (forward)."""
    per_head = 2 * (
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    ) + 2 * model["v_head_dim"]
    return model["n_heads"] * per_head


def required(model: dict, pairs: int) -> dict:
    """{"flops"} for `pairs` attended (query, key, layer) triples."""
    return {"flops": pairs * pair_flops(model)}
