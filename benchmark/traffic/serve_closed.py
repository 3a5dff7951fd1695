"""Closed-loop serving traffic: a fixed pool of callers that each wait
for a reply (batch evaluation, a RAG pipeline's workers).

Documents are asked about several times. The shared request list takes
documents `group_docs` at a time: question 1 of each document of the
group, then question 2 of each, and so on, so two questions on one
document are `group_docs` requests apart. Every group holds the same
multiset of document and answer lengths (the quantile points of the
stated distributions); the seed shuffles them and draws the token ids.
"""

from __future__ import annotations

from typing import Iterator

import numpy as np

from .lengths import quantile_lengths

DRIVER = "serve"
LOOP = "closed"


def _group(params: dict, seed: int, index: int, vocab_size: int) -> list:
    rng = np.random.default_rng([int(seed), 0xD0C5, index])
    docs_n = int(params["group_docs"])
    asks = int(params["questions_per_doc"])
    doc_lens = quantile_lengths(params["document_tokens"], docs_n)
    rng.shuffle(doc_lens)
    answer_lens = quantile_lengths(params["answer_tokens"], docs_n * asks)
    rng.shuffle(answer_lens)
    q_len = int(params["question_tokens"])
    docs = [
        rng.integers(1, vocab_size, size=n, dtype=np.int64).tolist()
        for n in doc_lens
    ]
    out = []
    for ask in range(asks):
        for d, doc in enumerate(docs):
            question = rng.integers(
                1, vocab_size, size=q_len, dtype=np.int64
            ).tolist()
            out.append({
                "prompt": doc + question,
                "max_new_tokens": int(answer_lens[ask * docs_n + d]),
                "shared_tokens": len(doc) if ask else 0,
            })
    return out


def _stream(params: dict, seed: int, vocab_size: int) -> Iterator[dict]:
    index = 0
    while True:
        yield from _group(params, seed, index, vocab_size)
        index += 1


def warmup(params: dict, seed: int, vocab_size: int) -> list:
    """The warm-up requests, from a group of their own that the window
    never asks about; the serve driver sends them itself."""
    warm = _group(params, seed, 1 << 20, vocab_size)
    return [
        dict(r, max_new_tokens=int(params["warmup_new_tokens"]))
        for r in warm[: int(params["warmup_requests"])]
    ]


def generate(params: dict, seed: int, seconds: float, vocab_size: int) -> dict:
    """-> {"loop": "closed", "clients", "requests": endless iterator}:
    the window's load, which only the client process draws."""
    del seconds  # the list is endless; the window decides how far it gets
    return {
        "loop": LOOP,
        "clients": int(params["clients"]),
        "requests": _stream(params, seed, vocab_size),
    }
