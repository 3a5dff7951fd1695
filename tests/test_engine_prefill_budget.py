"""An iteration's prefill is a budget of tokens (llm/engine.py's
module docstring, step 2; ISSUE 60): chunks are dispatched, each
behind the one before, while those dispatched so far hold fewer than
`prefill_chunk` tokens, so a prompt's short last chunk lets the next
prompt in behind it and a whole chunk ends the iteration's prefill as
it always did. A tiny CPU engine (`prefill_chunk` 8, last chunks of 2,
4 or 8) whose admissions wait until every prompt of a case is queued,
so that its schedule is the rule's and no race's; one more engine a
kind of cache for the tokens.
"""

import contextlib
import os
import sys
import threading
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decode_oracle import greedy_uncached
from test_engine_queue_causes import _gained, _idle, _sums_hold, _until

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CHUNK, SLOTS = 8, 4
LONG = 240  # decode steps that keep a slot busy while a case sets up
#: (the engine the schedule's cases share holds such rows; a wider table
#: only compiles longer)
MAX_LEN = {"full": 256, "window": 64, "conv": 64}
ATTN, WINDOW, CONV = (0, 2, 1e6, False), (8, 2, 1e4, False), (0, 0, 0, False, 3)
EXPERTS = dict(moe_experts=2, moe_top_k=1, moe_router="sigmoid_groups")
MODELS = {
    "full": dict(
        vocab_size=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        intermediate=64, max_seq_len=MAX_LEN["full"], attention="reference",
    ),
    "window": dict(
        vocab_size=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        intermediate=32, max_seq_len=MAX_LEN["window"],
        layer_kinds=[list(ATTN), list(WINDOW)], **EXPERTS,
    ),
    "conv": dict(
        vocab_size=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        norm_eps=1e-5, rope_theta=1e6, qk_norm="head", intermediate=16,
        max_seq_len=MAX_LEN["conv"], layer_kinds=[list(CONV), list(ATTN)],
        **EXPERTS,
    ),
}


def _build(kind):
    """-> (engine, its uncached greedy continuation of a prompt)."""
    from ray_tpu.llm import EngineConfig, InferenceEngine
    from ray_tpu.models.llama import LlamaConfig, init_params

    model = MODELS[kind]
    cfg = LlamaConfig(**model, dtype=jnp.float32)
    forward = None
    if kind == "conv":
        # The training forward runs no conv layer: the benchmark's
        # plain reference is this model's uncached forward.
        from benchmark.reference import lfm2_moe_ref, weights

        params = weights.make(model, "float32", 5, lfm2_moe_ref)
        forward = lambda params, tokens: lfm2_moe_ref.forward(
            params, tokens[0], model, q_block=tokens.shape[1]
        )[None]
    else:
        params = init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(
        params, cfg,
        EngineConfig(
            slots=SLOTS, max_len=MAX_LEN[kind], prefill_chunk=CHUNK,
            kv_block_len=2, max_new_tokens=4,
        ),
    )
    assert eng._kv.chunk_shapes() == (2, 4, 8)
    list(eng.submit([1, 2, 3]))  # past the loop's warm-up
    return _Driven(eng), lambda prompt, n: greedy_uncached(
        params, cfg, prompt, n, forward=forward
    )


class _Driven:
    """An engine whose admissions wait while `queued()` is open, and
    which notes every chunk it dispatches: (the loop's iteration, the
    request's id, None if it was cancelled by then, the chunk's
    tokens)."""

    def __init__(self, eng):
        self.engine, self.log = eng, []
        self._open = threading.Event()
        self._open.set()
        admit, dispatch = eng._sched.admit_next, eng._dispatch_chunk
        eng._sched.admit_next = lambda gate=None: (
            admit(gate=gate) if self._open.is_set() else None
        )

        def noted(params, tokens, table, start, slot, *rest):
            req = eng._sched.running.get(slot)
            self.log.append((
                eng._loop_iterations, req and req.request_id,
                tokens.shape[1],
            ))
            return dispatch(params, tokens, table, start, slot, *rest)

        eng._dispatch_chunk = noted

    @contextlib.contextmanager
    def queued(self, idle=True):
        """What is submitted inside is all queued when the loop next
        looks (`idle`: and nothing else is running by then). -> the
        engine's counters as they stood before."""
        if idle:
            _idle(self.engine)
        self._open.clear()
        del self.log[:]
        try:
            yield self.engine.stats()
        finally:
            self._open.set()
            self.engine._wake.set()

    def iterations(self):
        """The chunks' tokens, one list an iteration that dispatched
        any."""
        by_iteration = {}
        for iteration, _, tokens in self.log:
            by_iteration.setdefault(iteration, []).append(tokens)
        return [by_iteration[i] for i in sorted(by_iteration)]


@pytest.fixture(scope="module")
def built():
    """kind of cache -> (driven engine, oracle), built when a case
    first asks."""
    made = {}

    def get(kind):
        if kind not in made:
            made[kind] = _build(kind)
        return made[kind]

    yield get
    for driven, _ in made.values():
        driven.engine.close()


def _prompts(case, lengths):
    """Prompts no two cases share a chunk of: a prefix hit would skip
    chunks the case counts."""
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    return [rng.integers(1, 64, size=n).tolist() for n in lengths]


def _serve(driven, prompts, budgets):
    with driven.queued() as before:
        streams = [
            driven.engine.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)
        ]
    outs = [list(s) for s in streams]
    _idle(driven.engine)
    return streams, outs, before, _sums_hold(driven.engine)


#: queued prompts' lengths -> the chunks of each iteration's prefill.
#: Last chunks: 1-2 tokens left run 2, 3-4 run 4, 5-8 run 8.
LAYOUTS = {
    "short_last_chunks_fill_the_budget": (
        (2, 1, 3, 5), [[2, 2, 4], [8]],
    ),
    "a_whole_chunk_behind_a_short_one": ((3, 13), [[4, 8], [8]]),
    "a_long_prompts_tail_lets_the_next_in": ((10, 4), [[8], [2, 4]]),
    "whole_chunks_run_one_an_iteration": ((8, 16), [[8], [8], [8]]),
    "the_budget_is_met_exactly": ((4, 3, 2), [[4, 4], [2]]),
    "a_queue_of_many_more_than_the_slots": (
        (3, 9, 1, 17, 2, 4, 30, 6, 12, 2, 5, 7), None,
    ),
}


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_an_iteration_prefills_until_the_budget_is_met(built, layout):
    driven, _ = built("full")
    lengths, expected = LAYOUTS[layout]
    streams, outs, before, after = _serve(
        driven, _prompts(layout, lengths), [4] * len(lengths)
    )
    assert [len(o) for o in outs] == [4] * len(lengths)
    ran = driven.iterations()
    if expected is not None:
        assert ran == expected
    # Every row a chunk started was in ITS iteration's step: a prompt
    # decodes in the iteration of its last chunk and the three after.
    started = {rid: iteration for iteration, rid, _ in driven.log}
    stepped = {i + k for i in started.values() for k in range(4)}
    assert after["steps"] - before["steps"] == len(stepped)
    # (b) a whole chunk ends its iteration's prefill, and the chunks
    # before it hold less than one: under two chunks an iteration.
    for chunks in ran:
        assert sum(chunks[:-1]) < CHUNK, ran
        assert sum(chunks) < 2 * CHUNK, ran
    joined = sum(len(chunks) - 1 for chunks in ran)
    assert joined > 0 or layout == "whole_chunks_run_one_an_iteration"
    counted = {
        name: after[name] - before[name] for name in (
            "prefill_joined_chunks", "prefill_chunks",
            "prefill_short_chunks", "admitted",
        )
    }
    assert counted == {
        "prefill_joined_chunks": joined,
        "prefill_chunks": sum(len(chunks) for chunks in ran),
        "prefill_short_chunks": sum(
            c < CHUNK for chunks in ran for c in chunks
        ),
        "admitted": len(lengths),
    }
    # (c) admitted in the order submitted, one prompt mid-prompt at a
    # time: a prompt's chunks are never interleaved with another's.
    ids = [rid for _, rid, _ in driven.log]
    assert [
        rid for i, rid in enumerate(ids) if i == 0 or ids[i - 1] != rid
    ] == [s.request_id for s in streams]
    assert after["compiles"] == before["compiles"]


def _pool_is_short(eng, keep):
    with eng._lock:
        alloc = eng._kv.full
        held = alloc.reserve(alloc.available() - keep)

    def give_back():
        with eng._lock:
            alloc.release(held)
        eng._wake.set()

    return give_back


def _the_gate_refuses(driven):
    # six pages: the first prompt's three, not the second's 22
    eng = driven.engine
    give_back = _pool_is_short(eng, 6)
    with driven.queued() as before:
        streams = [
            eng.submit([5, 6], max_new_tokens=4),
            eng.submit([7, 8, 9], max_new_tokens=40),
        ]
    return streams, before, give_back, "no_pages"


def _no_slot_is_free(driven):
    eng = driven.engine
    rows = [
        eng.submit([3 + i, 5, 7], max_new_tokens=LONG)
        for i in range(SLOTS - 1)
    ]
    for row in rows:  # its chunk is counted by its first token
        assert isinstance(next(row), int)
    with driven.queued(idle=False) as before:
        # (the first keeps the last slot as long as the rows keep theirs)
        streams = [
            eng.submit([5, 6], max_new_tokens=LONG),
            eng.submit([7, 8, 9]),
        ]

    def give_back():
        for row in rows:
            row.cancel()

    return streams, before, give_back, "no_slot"


def _the_queue_is_empty(driven):
    with driven.queued() as before:
        streams = [driven.engine.submit([5, 6])]
    return streams, before, lambda: None, None


ENDS = {
    "the_gate_refuses": _the_gate_refuses,
    "no_slot_is_free": _no_slot_is_free,
    "the_queue_is_empty": _the_queue_is_empty,
}


@pytest.mark.parametrize("end", list(ENDS))
def test_what_ends_an_iterations_prefill_short_of_its_budget(built, end):
    """(d) The first prompt's chunk leaves six tokens of the budget,
    and the iteration's next admission finds nothing it can admit: its
    prefill ends there, and what refused stands as the queue's cause
    until it gives way."""
    driven, _ = built("full")
    eng = driven.engine
    streams, before, give_back, cause = ENDS[end](driven)
    try:
        assert isinstance(next(streams[0]), int)
        if cause is not None:
            _until(lambda: eng.stats()["queue_ms"][cause]
                   > before["queue_ms"][cause] + 5.0)
            assert driven.iterations() == [[2]]
            assert eng.stats()["waiting"] == 1
    finally:
        give_back()
    for stream in streams[::-1]:
        list(stream)
    _idle(eng)
    after = _sums_hold(eng)
    assert driven.iterations() == [[2], [4]][:len(streams)]
    assert after["prefill_joined_chunks"] == before["prefill_joined_chunks"]
    stood = _gained(before, after, "queue_ms")
    # (`behind_prefill`: the first prompt's moment between its
    # admission and its chunk's dispatch)
    assert set(stood) <= {cause, "admissible", "behind_prefill"}
    if cause is not None:
        late = streams[1]._req
        assert late.queue_cause_ms[cause] >= 5.0
        assert late.queue_cause_ms[cause] >= 0.5 * sum(
            late.queue_cause_ms.values()
        )


@pytest.mark.parametrize("kind", list(MODELS))
def test_joined_prompts_stream_the_uncached_forwards_tokens(built, kind):
    """(e) Four prompts queued together, the second a prefix hit on
    the first's whole chunk, their last chunks short: three of the
    five chunks join an iteration behind another, and every request
    streams what a forward without any cache gives it."""
    driven, oracle = built(kind)
    rng = np.random.default_rng(60)
    first = rng.integers(1, 64, size=10).tolist()
    prompts = [
        first, first[:8] + rng.integers(1, 64, size=3).tolist(),
        rng.integers(1, 64, size=3).tolist(),
        rng.integers(1, 64, size=2).tolist(),
    ]
    # (each to 20 tokens in all: the oracle's one shape)
    budgets = [20 - len(p) for p in prompts]
    _, outs, before, after = _serve(driven, prompts, budgets)
    assert driven.iterations() == [[8], [2, 4, 4], [2]]
    assert after["prefill_joined_chunks"] - before["prefill_joined_chunks"] == 2
    assert after["prefix_hits"] - before["prefix_hits"] == 1
    assert after["prefix_tokens_saved"] - before["prefix_tokens_saved"] == 8
    for prompt, budget, out in zip(prompts, budgets, outs):
        assert out == oracle(prompt, budget)
    assert after["compiles"] == before["compiles"]


def _cancelled_mid_prompt(driven):
    """A four-chunk prompt cancelled after its second chunk, a short
    prompt queued behind it."""
    eng = driven.engine
    dispatch = eng._dispatch_chunk
    with driven.queued() as before:
        long = eng.submit(list(range(1, 31)))
        short = eng.submit([9, 9, 9])

        def cancel_at_the_second(*a, **k):
            if len(driven.log) == 1:
                long.cancel()
            return dispatch(*a, **k)

        eng._dispatch_chunk = cancel_at_the_second
    try:
        assert list(long) == [] and long.finish_reason == "cancelled"
        outs = list(short)
    finally:
        eng._dispatch_chunk = dispatch
    # the prompt's first two chunks; the reap; then the short prompt
    return before, short, outs, [[8], [8], [4]]


def _cancelled_while_joined(driven):
    """Three short prompts in one iteration, the second cancelled
    between its admission and its chunk: the chunk is dispatched for
    nothing, the slot goes to the third in the same iteration."""
    eng = driven.engine
    patch = eng._patch_slot
    with driven.queued() as before:
        streams = [eng.submit(p) for p in ([5], [6, 7, 8], [9, 9, 9])]
        doomed = streams[1]

        def cancel_at_its_admission(slot, row):
            if row is doomed._req.table:
                doomed.cancel()
            patch(slot, row)

        eng._patch_slot = cancel_at_its_admission
    try:
        assert list(doomed) == [] and doomed.finish_reason == "cancelled"
        assert len(list(streams[0])) == 4
        outs = list(streams[2])
    finally:
        eng._patch_slot = patch
    return before, streams[2], outs, [[2, 4, 4]]


CANCELLED = {
    "mid_prompt": _cancelled_mid_prompt,
    "while_joined": _cancelled_while_joined,
}


@pytest.mark.parametrize("when", list(CANCELLED))
def test_a_cancelled_prompt_leaves_both_sums_whole(built, when):
    """(f) ISSUE 59's identities: the admitted requests' waits by cause
    sum to their waits, slot-time by state to slots x elapsed."""
    driven, oracle = built("full")
    eng = driven.engine
    before, kept, outs, expected = CANCELLED[when](driven)
    _idle(eng)
    after = _sums_hold(eng)
    assert driven.iterations() == expected
    assert outs == oracle(kept._req.prompt, 4)
    requests = 2 if when == "mid_prompt" else 3
    assert after["admitted"] - before["admitted"] == requests
    assert after["requests_done"] - before["requests_done"] == requests
    assert after["kv_blocks_used"] == 0 and not after["dead"]
