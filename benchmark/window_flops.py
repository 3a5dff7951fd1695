"""Required operations of causal attention whose layers are not all
alike: a layer with a window needs, of a query at position p, the
`min(p + 1, window)` keys it sees, a full layer the p + 1 up to it.

Kept beside `flops.py`, whose `attention_flops_per_token_fwd` counts
every layer as a full one (right for the models whose layers are: over
a model with three window layers of four at 8,192 tokens under a
window of 2,048 it would count 1.8 times what is required, and a
kernel that skips the blocks it may skip would read past its
roofline). QK^T and AV are each 2 x heads x head_dim operations a
(query, key) pair; what a kernel masks inside a block it visits, and
what it recomputes, is not required work and counts only in its time.
"""

from __future__ import annotations


def seen_keys(seq_len: int, window: int) -> int:
    """(query, key) pairs of one sequence of `seq_len` positions under
    `window` (0: none): sum over p of min(p + 1, window), in closed
    form."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def layer_windows(model: dict) -> list:
    """The window of each of the model's layers (0: a full layer):
    `layer_kinds`' first entries, or every layer full."""
    kinds = model.get("layer_kinds")
    if not kinds:
        return [0] * model["n_layers"]
    return [int(kind[0]) for kind in kinds]


def attention_flops_fwd(model: dict, seq_len: int) -> float:
    """Forward operations of attention over one sequence: for each
    layer by its kind 2 (QK^T and AV) x 2 x heads x head_dim x the
    pairs it sees. `model` holds `LlamaConfig` keys."""
    heads = model["n_heads"]
    head_dim = model.get("custom_head_dim") or model["dim"] // heads
    pairs = sum(seen_keys(seq_len, w) for w in layer_windows(model))
    return 2.0 * 2.0 * heads * head_dim * pairs
