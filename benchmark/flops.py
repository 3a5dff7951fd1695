"""Required operations per token, and the chip's peaks.

The benchmark keeps its own count because the program's
(`models/llama.py` `flops_per_token` = 6 x `num_params()`) counts the
embedding lookup as a matrix multiplication. Here: every parameter that
takes part in a matmul counts 2 operations per token forward (so 6 with
the backward pass), the embedding lookup counts none, and causal
attention counts QK^T and AV over half the square. Recomputation
(remat) is not counted: it is work the algorithm does not require.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def matmul_params(model: dict) -> int:
    """Parameters that multiply activations: per layer wq, wk, wv, wo,
    w1, w2, w3; plus the output head. No embedding, norms or biases.
    `model` holds `LlamaConfig` keys."""
    dim = model["dim"]
    heads = model["n_heads"]
    kv_heads = model["n_kv_heads"]
    head_dim = model.get("custom_head_dim") or dim // heads
    per_layer = (
        dim * heads * head_dim
        + 2 * dim * kv_heads * head_dim
        + heads * head_dim * dim
        + 3 * dim * model["intermediate"]
    )
    return model["n_layers"] * per_layer + dim * model["vocab_size"]


def attention_flops_per_token_fwd(model: dict, seq_len: int) -> float:
    """Causal attention, forward, per token averaged over a sequence
    of `seq_len`: QK^T and AV are each 2 * width * (keys seen), and a
    token at position p sees p + 1 keys: (seq_len + 1) / 2 on average."""
    dim = model["dim"]
    heads = model["n_heads"]
    head_dim = model.get("custom_head_dim") or dim // heads
    width = heads * head_dim
    return model["n_layers"] * 2 * 2 * width * (seq_len + 1) / 2.0


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward + backward (backward = 2 x forward)."""
    forward = 2.0 * matmul_params(model) + attention_flops_per_token_fwd(
        model, seq_len
    )
    return 3.0 * forward


class UnknownDevice(KeyError):
    """The chip is not in `peaks.json`: an error, never a default."""


def peaks_for(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r} in "
            "benchmark/peaks.json; add the chip with its source"
        )
    return table[device_kind]
