"""Open-loop serving traffic: independent users.

A fixed number of requests, rate x seconds, arrive at the order
statistics of uniform draws over the window (a Poisson process
conditioned on its count), so the count and the length multiset
(quantile points of the stated distributions) are the same in every
run and the seed moves order, gaps and token ids: where the few long
answers fall, and how many requests a stretch of the window holds,
is the seed's, as it is a day's in a deployment.
Requests are sent when due whether or not earlier ones finished; each
is timed from its due time.

`lead_in_s` (0 when absent): rate x lead_in_s more requests are due in
the `lead_in_s` before the window opens, at negative `due_s`, drawn the
same way with a length multiset of their own, so that the window opens
on an engine that already carries its standing population. The window's
own count, multiset and draw do not depend on the lead-in.

`warmup` draws the warm-up requests alone: the serve driver sends them
itself, and only the client process (`drivers/serve_client.py`) draws a
window's requests, with `generate`.
"""

from __future__ import annotations

from typing import List

import numpy as np

from .lengths import quantile_lengths

DRIVER = "serve"
LOOP = "open"


def _prompts(rng, lengths: List[int], vocab_size: int) -> List[list]:
    return [
        rng.integers(1, vocab_size, size=n, dtype=np.int64).tolist()
        for n in lengths
    ]


def _segment(rng, params: dict, start_s: float, end_s: float,
             vocab_size: int, least: int = 0) -> List[dict]:
    """rate x (end - start) requests (`least` or more) due in
    [start, end): lengths at the quantile points of the stated
    distributions in a seeded order, arrivals the sorted uniform draws
    of the same seed."""
    n = max(least, int(round(params["rate_per_s"] * (end_s - start_s))))
    prompt_lens = quantile_lengths(params["prompt_tokens"], n)
    output_lens = quantile_lengths(params["output_tokens"], n)
    rng.shuffle(prompt_lens)
    rng.shuffle(output_lens)
    due = np.sort(rng.uniform(start_s, end_s, size=n))
    limit = int(params["max_total_tokens"])
    return [
        {
            "due_s": float(t),
            "prompt": prompt,
            "max_new_tokens": int(min(o_len, limit - p_len)),
        }
        for t, p_len, o_len, prompt in zip(
            due, prompt_lens, output_lens,
            _prompts(rng, prompt_lens, vocab_size),
        )
    ]


def warmup(params: dict, seed: int, vocab_size: int) -> List[dict]:
    """The warm-up requests: prompts at the quantile points of the
    prompt lengths, a few tokens each, from a draw of their own."""
    rng = np.random.default_rng([int(seed), 0x3A21])
    lens = quantile_lengths(
        params["prompt_tokens"], int(params["warmup_requests"])
    )
    return [
        {"due_s": 0.0, "prompt": prompt,
         "max_new_tokens": int(params["warmup_new_tokens"])}
        for prompt in _prompts(rng, lens, vocab_size)
    ]


def generate(params: dict, seed: int, seconds: float, vocab_size: int) -> dict:
    """-> {"loop": "open", "requests": [...]}; each request is
    {"due_s", "prompt", "max_new_tokens"}, in order of `due_s`, the
    lead-in's first (negative `due_s`)."""
    rng = np.random.default_rng([int(seed), 0x0BE7])
    requests = _segment(rng, params, 0.0, seconds, vocab_size, least=1)
    lead_in = float(params.get("lead_in_s", 0.0))
    if lead_in > 0:
        lead_rng = np.random.default_rng([int(seed), 0x1EAD])
        requests = _segment(
            lead_rng, params, -lead_in, 0.0, vocab_size
        ) + requests
    return {"loop": LOOP, "requests": requests}
