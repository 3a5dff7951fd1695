"""Open-loop serving traffic: independent users.

A fixed number of requests, rate x seconds, arrive at the order
statistics of uniform draws over the window (a Poisson process
conditioned on its count), so the count and the length multiset are
the same in every run and the seed moves only order, gaps and token
ids.
Requests are sent when due whether or not earlier ones finished; each
is timed from its due time.
"""

from __future__ import annotations

from typing import List

import numpy as np

from .lengths import quantile_lengths

DRIVER = "serve"


def _prompts(rng, lengths: List[int], vocab_size: int) -> List[list]:
    return [
        rng.integers(1, vocab_size, size=n, dtype=np.int64).tolist()
        for n in lengths
    ]


def generate(params: dict, seed: int, seconds: float, vocab_size: int) -> dict:
    """-> {"loop": "open", "requests": [...], "warmup": [...]}; each
    request is {"due_s", "prompt", "max_new_tokens"}."""
    rng = np.random.default_rng([int(seed), 0x0BE7])
    n = max(1, int(round(params["rate_per_s"] * seconds)))
    prompt_lens = quantile_lengths(params["prompt_tokens"], n)
    output_lens = quantile_lengths(params["output_tokens"], n)
    rng.shuffle(prompt_lens)
    rng.shuffle(output_lens)
    due = np.sort(rng.uniform(0.0, seconds, size=n))
    limit = int(params["max_total_tokens"])
    requests = []
    for t, p_len, o_len, prompt in zip(
        due, prompt_lens, output_lens,
        _prompts(rng, prompt_lens, vocab_size),
    ):
        requests.append({
            "due_s": float(t),
            "prompt": prompt,
            "max_new_tokens": int(min(o_len, limit - p_len)),
        })
    warm_rng = np.random.default_rng([int(seed), 0x3A21])
    warm_lens = quantile_lengths(
        params["prompt_tokens"], int(params["warmup_requests"])
    )
    warmup = [
        {"due_s": 0.0, "prompt": prompt,
         "max_new_tokens": int(params["warmup_new_tokens"])}
        for prompt in _prompts(warm_rng, warm_lens, vocab_size)
    ]
    return {"loop": "open", "requests": requests, "warmup": warmup}
