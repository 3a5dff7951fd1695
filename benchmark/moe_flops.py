"""What an expert layer requires of the chip: operations and bytes.

A pick is one (token, expert) assignment. Each runs the expert's three
matrices (gate, up, down: `dim` x `intermediate` each) over one token:
3 x 2 x dim x intermediate operations. The fewest bytes a forward can
read of the experts' weights are the matrices of the experts that got
at least one token, each once a layer however many tokens it got: a
chunk of 512 tokens under even routing touches every expert (all 64 x
12.6 MB a layer at OLMoE's widths), a decode step of a few rows only
those its rows picked. Activations (a few KB a pick) are not counted. `model` holds
`LlamaConfig` keys.
"""

from __future__ import annotations

BYTES_PER_WEIGHT = {"bfloat16": 2, "float32": 4}


def pick_flops(model: dict) -> int:
    """Operations one pick requires (forward)."""
    return 3 * 2 * model["dim"] * model["intermediate"]


def expert_bytes(model: dict, dtype: str = "bfloat16") -> int:
    """Bytes of one expert's three matrices in one layer."""
    return 3 * model["dim"] * model["intermediate"] * BYTES_PER_WEIGHT[dtype]


def required(model: dict, picks: int, experts_touched: int,
             dtype: str = "bfloat16") -> dict:
    """{"flops", "bytes"} for `picks` assignments over forwards that
    touched `experts_touched` (expert, layer, forward) triples."""
    return {
        "flops": picks * pick_flops(model),
        "bytes": experts_touched * expert_bytes(model, dtype),
    }
