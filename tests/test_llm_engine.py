"""Continuous-batching engine tests (ISSUE 10): scheduler invariants,
engine-vs-uncached-forward parity, cancellation, multiplex isolation,
and chaos — in-flight requests get errors, never hangs."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decode_oracle import greedy_uncached, serial_streams
from ray_tpu.llm.scheduler import (
    EngineOverloaded,
    SlotScheduler,
)


# ---------------------------------------------------------------------
# scheduler invariants (pure bookkeeping, no jax)
# ---------------------------------------------------------------------

def test_scheduler_fifo_admission_and_slot_reuse():
    sched = SlotScheduler(2, max_waiting=8)
    for name in ("a", "b", "c", "d"):
        sched.submit(name)
    first = sched.admit_next()
    second = sched.admit_next()
    assert (first[0], second[0]) == ("a", "b")  # FIFO
    assert sched.admit_next() is None  # no free slot
    freed = first[1]
    assert sched.release(freed) == "a"
    third = sched.admit_next()
    assert third[0] == "c"  # still FIFO
    assert third[1] == freed  # the evicted slot is reused
    assert sched.stats() == {
        "slots_total": 2, "slots_used": 2, "waiting": 1,
    }


def test_scheduler_overload_and_waiting_removal():
    sched = SlotScheduler(1, max_waiting=2)
    sched.submit("a")
    sched.submit("b")
    with pytest.raises(EngineOverloaded):
        sched.submit("c")
    assert sched.remove_waiting("b")
    assert not sched.remove_waiting("b")
    sched.submit("d")  # freed waiting capacity
    assert [r for r in sched.waiting] == ["a", "d"]


def test_scheduler_drain_returns_everything():
    sched = SlotScheduler(2, max_waiting=8)
    for name in ("a", "b", "c"):
        sched.submit(name)
    sched.admit_next()
    sched.admit_next()
    doomed = sched.drain()
    assert sorted(doomed) == ["a", "b", "c"]
    assert sched.stats()["slots_used"] == 0
    assert sched.admit_next() is None


# ---------------------------------------------------------------------
# engine (tiny model; ONE shape family so XLA compiles once per suite)
# ---------------------------------------------------------------------

ENGINE_KW = dict(slots=2, max_len=48, prefill_chunk=8)


@pytest.fixture(scope="module")
def tiny_model():
    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        intermediate=128, max_seq_len=128, dtype=jnp.float32,
        attention="reference",
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture
def engine(tiny_model):
    from ray_tpu.llm import EngineConfig, InferenceEngine

    cfg, params = tiny_model
    eng = InferenceEngine(
        params, cfg, EngineConfig(max_new_tokens=8, **ENGINE_KW),
        family="tiny",
    )
    yield eng
    eng.close()


def test_engine_matches_uncached_greedy(tiny_model, engine):
    """Satellite 1 parity: tokens decoded through the shared paged
    cache (concurrent requests, per-row positions, chunked prefill)
    must equal greedy decoding by the uncached forward per prompt."""
    cfg, params = tiny_model
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 128, size=n).tolist() for n in (5, 8, 11)]
    streams = [engine.submit(p, max_new_tokens=8) for p in prompts]
    outs = [list(s) for s in streams]
    assert [s.finish_reason for s in streams] == ["length"] * 3
    for prompt, out in zip(prompts, outs):
        assert out == greedy_uncached(params, cfg, prompt, 8)


def test_prefix_hit_parity_with_uncached_greedy(tiny_model):
    """ISSUE 11 satellite: with the paged cache AND prefix caching ON,
    a request whose prompt prefix hits the pool must skip prefill for
    the shared blocks and STILL decode token-for-token what the
    uncached forward produces — including a request that shares only
    the prefix, not the whole prompt."""
    from ray_tpu.llm import EngineConfig, InferenceEngine

    cfg, params = tiny_model
    eng = InferenceEngine(
        params, cfg,
        EngineConfig(max_new_tokens=8, prefix_cache=True, **ENGINE_KW),
        family="tiny",
    )
    try:
        rng = np.random.default_rng(21)
        base = rng.integers(1, 128, size=20).tolist()
        prompts = [
            base,  # seeds the prefix cache (miss)
            list(base),  # identical prompt: full-prefix hit
            base[:16] + rng.integers(1, 128, size=5).tolist(),
            # ^ shares only the first two blocks (16 tokens)
        ]
        outs = []
        for prompt in prompts:
            stream = eng.submit(prompt, max_new_tokens=8)
            outs.append(list(stream))
            assert stream.finish_reason == "length"
        stats = eng.stats()
        # Prompt 1 missed; prompts 2 and 3 hit (block_len=8: two full
        # blocks of `base` are cached, and skip is chunk-aligned at
        # 16 tokens for both).
        assert stats["prefix_misses"] >= 1
        assert stats["prefix_hits"] == 2
        assert stats["prefix_tokens_saved"] == 32
        for prompt, out in zip(prompts, outs):
            assert out == greedy_uncached(params, cfg, prompt, 8)
    finally:
        eng.close()


def test_midprefill_row_not_corrupted_by_interleaved_decode(
    tiny_model,
):
    """Review-caught paged-cache corruption: while a request CHUNK-
    PREFILLS, its block table is already built but its row is not yet
    alive — the interleaved decode step over the full slot batch must
    NOT scatter its junk row (stale position, masked token) into the
    request's real pages. Pre-fix, a slot whose previous occupant
    finished at a low position wrote junk INSIDE the new prompt's
    already-prefilled region (position 0 here), and the output
    diverged from the uncached forward's."""
    from ray_tpu.llm import EngineConfig, InferenceEngine

    cfg, params = tiny_model
    eng = InferenceEngine(
        params, cfg,
        EngineConfig(max_new_tokens=8, prefix_cache=False,
                     **ENGINE_KW),
        family="tiny",
    )
    try:
        # Keep the decode batch hot so every prefill chunk of the
        # long request interleaves with a decode step.
        busy = eng.submit([9, 9, 9, 9], max_new_tokens=30)
        assert isinstance(next(busy), int)
        rng = np.random.default_rng(5)
        prompt = rng.integers(1, 128, size=20).tolist()  # 3 chunks
        stream = eng.submit(prompt, max_new_tokens=8)
        out = list(stream)
        busy.cancel()
        list(busy)
        assert out == greedy_uncached(params, cfg, prompt, 8)
    finally:
        eng.close()


@pytest.mark.parametrize("nth", [3, 1])
def test_engine_eos_stops_row(tiny_model, engine, nth):
    """The row ends with the EOS it emitted: the EOS counts, nothing
    after it does — also when it is the very first token."""
    cfg, params = tiny_model
    prompt = [3, 14, 15, 9]
    ref = greedy_uncached(params, cfg, prompt, 8)
    eos = ref[nth - 1]  # declare the nth token EOS
    assert eos not in ref[: nth - 1]
    stream = engine.submit(prompt, max_new_tokens=8, eos_token=eos)
    out = list(stream)
    assert stream.finish_reason == "stop"
    assert out == ref[:nth]
    assert out == greedy_uncached(params, cfg, prompt, 8, eos=eos)


def test_engine_sampled_tokens_in_vocab(tiny_model):
    """Temperature + top-k sampling through the engine: every row runs
    its whole budget and every token is a vocabulary id."""
    from ray_tpu.llm import EngineConfig, InferenceEngine

    cfg, params = tiny_model
    eng = InferenceEngine(
        params, cfg,
        EngineConfig(
            max_new_tokens=8, temperature=0.8, top_k=20, seed=9,
            **ENGINE_KW,
        ),
        family="tiny",
    )
    try:
        rng = np.random.default_rng(5)
        streams = [
            eng.submit(rng.integers(0, 128, size=5).tolist())
            for _ in range(3)
        ]
        outs = np.asarray([list(s) for s in streams])
    finally:
        eng.close()
    assert outs.shape == (3, 8)
    assert ((outs >= 0) & (outs < 128)).all()
    assert [s.finish_reason for s in streams] == ["length"] * 3


def test_slot_reuse_after_eviction(engine):
    """3 requests through 2 slots: the third admits into a slot one
    of the first two vacated, and the waiting queue drains."""
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]]
    streams = [engine.submit(p, max_new_tokens=6) for p in prompts]
    # With 2 slots the third request must wait first.
    assert engine.stats()["waiting"] >= 1 or list(streams[2])
    outs = [list(s) for s in streams]
    assert all(len(o) == 6 for o in outs)
    slots = [s._req.slot for s in streams]
    assert slots[2] in (slots[0], slots[1])  # reused, not grown
    stats = engine.stats()
    assert stats["slots_used"] == 0
    assert stats["waiting"] == 0
    assert stats["requests_done"] >= 3


def test_admission_fifo_no_long_prompt_starvation(engine):
    """Both slots busy; a LONG-prompt request queued ahead of short
    ones is admitted first when a slot frees (FIFO — chunked prefill
    bounds its cost instead of its priority)."""
    busy = [
        engine.submit([1 + i, 2, 3, 4], max_new_tokens=24)
        for i in range(2)
    ]
    long_req = engine.submit(
        list(range(1, 21)), max_new_tokens=4
    )  # 20-token prompt => 3 prefill chunks
    shorts = [
        engine.submit([40 + i, 41, 42, 43], max_new_tokens=4)
        for i in range(2)
    ]

    first_token_at = {}

    def consume(tag, stream):
        # The loop's own reading of the first token: when a consumer's
        # thread gets to run says nothing of the order of admission.
        for _tok in stream:
            pass
        first_token_at[tag] = stream.first_token_ts

    threads = [
        threading.Thread(target=consume, args=(tag, s), daemon=True)
        for tag, s in [
            ("b0", busy[0]), ("b1", busy[1]), ("long", long_req),
            ("s0", shorts[0]), ("s1", shorts[1]),
        ]
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert set(first_token_at) == {"b0", "b1", "long", "s0", "s1"}
    assert first_token_at["long"] < first_token_at["s0"]
    assert first_token_at["long"] < first_token_at["s1"]


def test_cancel_frees_slot_mid_decode(engine):
    stream = engine.submit([7, 7, 7, 7], max_new_tokens=32)
    first = next(stream)
    assert isinstance(first, int)
    stream.cancel()
    rest = list(stream)
    assert stream.finish_reason == "cancelled"
    assert 1 + len(rest) < 32  # budget NOT decoded to the end
    deadline = time.time() + 10
    while time.time() < deadline:
        if engine.stats()["slots_used"] == 0:
            break
        time.sleep(0.02)
    assert engine.stats()["slots_used"] == 0
    # The freed slot serves a new request normally.
    out = list(engine.submit([8, 8, 8, 8], max_new_tokens=4))
    assert len(out) == 4


def test_cancel_mid_prefill_does_not_kill_engine(engine):
    """Cancelling while the prompt is still CHUNK-PREFILLING must
    free the slot exactly once — the prefilling request is both the
    scheduler's slot holder and the engine's prefill cursor, and a
    double release used to kill the whole loop (every other request
    failed with EngineDead)."""
    # 20-token prompt = 3 chunks at prefill_chunk=8: cancel lands in
    # the prefill window with high probability; the invariant must
    # hold regardless of where it lands.
    for attempt in range(5):
        stream = engine.submit(
            list(range(1, 21)), max_new_tokens=4
        )
        time.sleep(0.002 * attempt)
        stream.cancel()
        list(stream)
        assert stream.finish_reason in ("cancelled", "length")
    # Engine survived every cancel point and still serves.
    out = list(engine.submit([2, 4, 6, 8], max_new_tokens=4))
    assert len(out) == 4
    assert engine.stats()["dead"] is False


def test_cancel_waiting_request_never_admitted(engine):
    busy = [
        engine.submit([1, 2, 3, 4], max_new_tokens=24)
        for _ in range(2)
    ]
    queued = engine.submit([9, 9, 9, 9], max_new_tokens=4)
    deadline = time.time() + 10
    while time.time() < deadline:
        if engine.stats()["slots_used"] == 2:  # busy pair admitted
            break
        time.sleep(0.01)
    assert engine.stats()["waiting"] == 1
    queued.cancel()
    assert list(queued) == []
    assert queued.finish_reason == "cancelled"
    assert engine.stats()["waiting"] == 0
    for stream in busy:
        stream.cancel()
        list(stream)


def test_engine_overload_rejects(tiny_model):
    from ray_tpu.llm import (
        EngineConfig, EngineOverloaded as Overloaded, InferenceEngine,
    )

    cfg, params = tiny_model
    eng = InferenceEngine(
        params, cfg,
        EngineConfig(max_new_tokens=8, max_waiting=1, **ENGINE_KW),
        family="tiny",
    )
    try:
        busy = []
        for n in range(2):
            busy.append(
                eng.submit([1 + n, 2, 3, 4], max_new_tokens=40)
            )
            deadline = time.time() + 10
            while time.time() < deadline:
                if eng.stats()["slots_used"] == n + 1:
                    break
                time.sleep(0.01)
        eng.submit([5, 5, 5, 5])  # fills the 1-deep waiting queue
        with pytest.raises(Overloaded):
            eng.submit([6, 6, 6, 6])
        for stream in busy:
            stream.cancel()
    finally:
        eng.close()


def test_engine_death_fails_inflight_not_hangs(tiny_model):
    """Chaos: the step loop dying mid-decode must surface as an error
    on every in-flight stream (and on later submits), never a hang."""
    from ray_tpu.llm import EngineConfig, EngineDead, InferenceEngine

    cfg, params = tiny_model
    eng = InferenceEngine(
        params, cfg, EngineConfig(max_new_tokens=8, **ENGINE_KW),
        family="tiny",
    )
    live = eng.submit([1, 2, 3, 4])
    assert len(list(live)) == 8  # engine is healthy
    eng._kv.pool = None  # chaos: corrupt the loop's device state
    doomed = eng.submit([5, 6, 7, 8])
    with pytest.raises(EngineDead):
        list(doomed)  # the step loop died on this request
    deadline = time.time() + 10
    while True:  # once dead, submit must reject — never queue/hang
        try:
            eng.submit([1, 2, 3])
        except EngineDead:
            break
        assert time.time() < deadline, "engine death not latched"
        time.sleep(0.02)
    eng.close()


# ---------------------------------------------------------------------
# dispatch ahead, retire behind (ISSUE 27): the pipelined loop streams
# what a plain serial loop would, token for token
# ---------------------------------------------------------------------

PIPE_KW = dict(slots=3, max_len=64, prefill_chunk=8)


class held_engine:
    """An engine whose admissions wait until `open()`: what is
    submitted before that is all queued when the loop first looks, so
    its schedule is the policy's and no race's."""

    def __init__(self, model, **config):
        from ray_tpu.llm import EngineConfig, InferenceEngine

        cfg, params = model
        self.config = EngineConfig(**{**PIPE_KW, **config})
        self.engine = InferenceEngine(
            params, cfg, self.config, family="tiny"
        )
        self._opened = threading.Event()
        admit = self.engine._sched.admit_next
        self.engine._sched.admit_next = lambda gate=None: (
            admit(gate=gate) if self._opened.is_set() else None
        )

    def open(self):
        self._opened.set()
        self.engine._wake.set()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.engine.close()


def run_jobs(model, jobs, **config):
    with held_engine(model, **config) as held:
        streams = [
            held.engine.submit(prompt, max_new_tokens=n, eos_token=eos)
            for prompt, n, eos in jobs
        ]
        held.open()
        outs = [list(s) for s in streams]
        return outs, [s.finish_reason for s in streams], held.engine.stats()


def pipe_jobs(kind):
    rng = np.random.default_rng(11)
    if kind == "ends_mid_batch":
        # Three rows alive together; budgets of 5, 14 and 9 tokens end
        # two of them in the middle of the batch (an EOS ends another
        # below).
        lengths, budgets = (5, 7, 6), (5, 14, 9)
    else:
        # Prompts of 1, 3 and 2 chunks: the second and third are
        # admitted, and their last chunks land, while the rows before
        # them have a step in flight.
        lengths, budgets = (6, 21, 12), (12, 6, 8)
    return [
        (rng.integers(1, 128, size=n).tolist(), budget, -1)
        for n, budget in zip(lengths, budgets)
    ]


SAMPLING = {
    "greedy": dict(temperature=0.0),
    "sampled": dict(temperature=0.8, seed=5),
    "sampled_top_k": dict(temperature=0.8, top_k=8, seed=6),
}


@pytest.mark.parametrize("sampling", list(SAMPLING))
@pytest.mark.parametrize("kind", ["ends_mid_batch", "chunks_land_mid_step"])
def test_streams_equal_the_serial_reference(tiny_model, kind, sampling):
    from ray_tpu.llm import EngineConfig

    cfg, params = tiny_model
    config = SAMPLING[sampling]
    ec = EngineConfig(**{**PIPE_KW, **config})
    jobs = pipe_jobs(kind)
    # The longest stream's fourth token becomes that row's EOS: it
    # ends by `stop` with other rows alive around it.
    longest = max(range(len(jobs)), key=lambda i: jobs[i][1])
    free = serial_streams(params, cfg, ec, jobs)
    eos = free[longest][3]
    jobs[longest] = (*jobs[longest][:2], eos)
    want = serial_streams(params, cfg, ec, jobs)
    assert want[longest] == free[longest][: free[longest].index(eos) + 1]
    outs, reasons, stats = run_jobs(tiny_model, jobs, **config)
    assert outs == want
    assert reasons == [
        "stop" if i == longest else "length" for i in range(len(jobs))
    ]
    # Every step but the first few was dispatched with one in flight.
    assert stats["programs_ahead"] >= stats["programs"] - 2
    assert stats["pipeline_drains"] == 0


def test_a_few_hundred_steps_run_ahead(tiny_model):
    jobs = [([3, 1, 4, 1, 5], 300, -1), ([9, 2, 6], 250, -1)]
    outs, _, stats = run_jobs(tiny_model, jobs, max_len=512)
    assert [len(o) for o in outs] == [300, 250]
    assert stats["steps"] == 300  # no step without a row alive
    assert stats["programs"] == 302  # two chunks and the steps
    assert stats["programs_ahead"] / stats["programs"] > 0.9
    # An admission and a start a request; nothing else is patched.
    assert stats["state_patches"] == 4
    assert stats["pipeline_drains"] == 0


def device_state(eng):
    return {k: np.asarray(v) for k, v in eng._state.items()}


def test_mirrors_follow_the_device_state(tiny_model, monkeypatch):
    """After every retirement the host's mirrors say, of the rows the
    step decoded, what the device's state said when the step ended;
    and with nothing in flight the two are equal row for row."""
    from ray_tpu.llm import EngineConfig

    cfg, params = tiny_model
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 128, size=4 + 3 * i).tolist() for i in range(5)]
    # The second request ends by an EOS its stream really meets.
    free = serial_streams(
        params, cfg, EngineConfig(**PIPE_KW), [(prompts[1], 6, -1)]
    )[0]
    eos = next(t for i, t in enumerate(free[:5]) if i and t not in free[:i])
    checked = []
    with held_engine(tiny_model, slots=2) as held:
        eng = held.engine
        retire = eng._retire_step

        def check(step):
            rows = [
                (slot, req) for slot, req in step.rows
                if eng._sched.running.get(slot) is req
            ]
            retire(step)
            state = {k: np.asarray(v) for k, v in step.state.items()}
            for slot, _ in rows:
                assert eng._positions[slot] == state["positions"][slot]
                assert eng._alive[slot] == state["alive"][slot]
                assert eng._budget[slot] == state["budget"][slot]
                assert eng._eos[slot] == state["eos"][slot]
                if eng._alive[slot]:
                    assert (
                        eng._tables[slot] == state["tables"][slot]
                    ).all()
            assert eng._steps == state["step"]
            checked.append(len(rows))

        monkeypatch.setattr(eng, "_retire_step", check)
        # Five requests through two slots, one of them ending by EOS:
        # slots are reused while steps are in flight.
        streams = [
            eng.submit(
                prompt, max_new_tokens=5 + i,
                eos_token=eos if i == 1 else None,
            )
            for i, prompt in enumerate(prompts)
        ]
        held.open()
        assert [len(list(s)) > 0 for s in streams] == [True] * 5
        assert streams[1].finish_reason == "stop"
        deadline = time.time() + 10
        while eng._inflight and time.time() < deadline:
            time.sleep(0.01)
        assert not eng._inflight
        state = device_state(eng)
        assert (state["alive"] == eng._alive).all()
        assert not state["alive"].any()
        assert (state["positions"] == eng._positions).all()
        assert (state["budget"] == eng._budget).all()
        assert (state["eos"] == eng._eos).all()
        assert state["step"] == eng._steps == eng.stats()["steps"]
    assert sum(checked) == eng.stats()["tokens_emitted"]
    assert max(checked) == 2


def test_a_dead_row_writes_nothing(tiny_model):
    """A row that met its EOS in step N is dead in step N+1, which
    was dispatched before the host saw N's tokens: past its last
    token its pages stay as the pool was made, whatever the rows
    around it go on to write."""
    prompt = [3, 14, 15, 9, 2, 6, 5, 35]  # a whole chunk, no padding
    with held_engine(tiny_model) as held:
        eng = held.engine
        probe = eng.submit(prompt, max_new_tokens=8)
        held.open()
        free = list(probe)
    # The EOS: a token the stream meets after its first, and not before.
    ends = next(i for i in range(1, 8) if free[i] not in free[:i])
    eos = free[ends]
    with held_engine(tiny_model, prefix_cache=False) as held:
        eng = held.engine
        short = eng.submit(prompt, max_new_tokens=8, eos_token=eos)
        other = eng.submit([7, 7, 7], max_new_tokens=40)
        held.open()
        assert isinstance(next(short), int)
        blocks = eng._tables[short._req.slot]
        blocks = blocks[blocks != 0]  # its own, not the null block
        assert len(list(short)) == ends and short.finish_reason == "stop"
        assert len(list(other)) == 40  # dozens of steps after the EOS
        written = len(prompt) + ends + 1  # the prompt and its tokens
        for name in ("k", "v"):
            pages = np.asarray(eng._kv.pool[name])[:, blocks]
            # [layers, blocks, kv_heads, block_len, hd] -> positions
            keys = pages.transpose(0, 2, 1, 3, 4).reshape(
                pages.shape[0], pages.shape[2], -1, pages.shape[4]
            )
            assert np.abs(keys[:, :, :written]).sum(axis=(0, 1, 3)).all()
            assert not keys[:, :, written:].any()


def test_cancel_with_a_step_in_flight(tiny_model, monkeypatch):
    """A cancellation reaped while the row's step is in flight: that
    step's token is dropped, the blocks go back once, the row beside
    it streams on untouched and the slot serves the next request."""
    from ray_tpu.llm import EngineConfig

    cfg, params = tiny_model
    ec = EngineConfig(**PIPE_KW)
    jobs = [([5, 6, 7, 8, 9], 58, -1), ([2, 4, 6], 40, -1)]
    want = serial_streams(params, cfg, ec, jobs)
    seen = {}
    with held_engine(tiny_model) as held:
        eng = held.engine
        keeper, doomed = (
            eng.submit(p, max_new_tokens=n) for p, n, _ in jobs
        )
        patch = eng._patch_slot

        def spy(slot, row):
            if row is eng._null_row:
                seen.update(
                    inflight=len(eng._inflight),
                    emitted=doomed._req.emitted,
                )
            patch(slot, row)

        monkeypatch.setattr(eng, "_patch_slot", spy)
        held.open()
        got = [next(doomed) for _ in range(3)]
        doomed.cancel()
        got.extend(doomed)
        assert doomed.finish_reason == "cancelled"
        # The slot serves the next request, beside the row that never
        # stopped.
        again = eng.submit(jobs[1][0], max_new_tokens=20)
        assert list(again) == want[1][:20]
        assert again._req.slot == doomed._req.slot
        assert list(keeper) == want[0]
        # No token after the cancellation was reaped, though a step
        # that held the row was in flight then.
        assert seen["inflight"] >= 1
        assert len(got) == seen["emitted"] < 40
        assert got == want[1][: len(got)]
        stats = eng.stats()
        assert stats["slots_used"] == 0 and not stats["dead"]
        assert eng._kv.alloc.used() == 0  # returned, and only once


@pytest.mark.parametrize("shared", ["whole_prompt", "prefix_only"])
def test_prefix_hit_replays_the_serial_reference(tiny_model, shared):
    from ray_tpu.llm import EngineConfig

    cfg, params = tiny_model
    ec = EngineConfig(**PIPE_KW)
    rng = np.random.default_rng(21)
    first = rng.integers(1, 128, size=20).tolist()
    second = first if shared == "whole_prompt" else first[:16] + [5, 9, 2]
    with held_engine(tiny_model, prefix_cache=True) as held:
        eng = held.engine
        held.open()
        outs = [
            list(eng.submit(p, max_new_tokens=8)) for p in (first, second)
        ]
        stats = eng.stats()
    # The second prompt's first two chunks were never computed ...
    assert stats["prefix_hits"] == 1 and stats["prefix_tokens_saved"] == 16
    assert stats["programs"] == 3 + 1 + 16
    # ... and it streams what a prompt computed whole does.
    assert outs == [
        serial_streams(params, cfg, ec, [(p, 8, -1)])[0]
        for p in (first, second)
    ]


def test_update_weights_window_drains_and_resumes(tiny_model):
    """Old and new streams decode together through the mixed window
    (serial steps, the pipeline drained), each exact on its weights;
    when the old ones are gone the loop runs ahead again."""
    from ray_tpu.llm import EngineConfig
    from ray_tpu.models.llama import init_params

    cfg, p_old = tiny_model
    p_new = init_params(jax.random.PRNGKey(99), cfg)
    ec = EngineConfig(**PIPE_KW)
    prompt = [8, 6, 7, 5, 3, 9]

    def ref(params, n):
        return serial_streams(params, cfg, ec, [(prompt, n, -1)])[0]

    with held_engine(tiny_model) as held:
        eng = held.engine
        held.open()
        old = eng.submit(prompt, max_new_tokens=24)
        head = [next(old), next(old)]  # provably mid-decode
        assert eng.update_weights(p_new) == 1
        new = eng.submit(prompt, max_new_tokens=40)
        assert head + list(old) == ref(p_old, 24)
        assert list(new) == ref(p_new, 40)
        stats = eng.stats()
        assert stats["pipeline_drains"] >= 1
        assert stats["weight_gens"] == 1
        # Both ended alone on the new weights, dispatched ahead again.
        before = stats
        assert list(eng.submit(prompt, max_new_tokens=30)) == ref(p_new, 30)
        after = eng.stats()
        assert after["pipeline_drains"] == before["pipeline_drains"]
        ahead = after["programs_ahead"] - before["programs_ahead"]
        assert ahead >= after["programs"] - before["programs"] - 2
        assert (device_state(eng)["alive"] == eng._alive).all()


def test_multiplex_swap_blocks_only_affected_family(
    tiny_model, monkeypatch
):
    """Loading family B (slow) must not stall family A's decode loop:
    A's tokens keep arriving DURING B's load window."""
    import ray_tpu.llm.serving as serving
    from ray_tpu.llm.serving import LLMServer

    cfg, params = tiny_model
    spec_a = {"kind": "init", "seed": 0, "config": None}
    spec_b = {"kind": "init", "seed": 1, "config": None}

    load_window = {}

    def build_model(spec):
        if spec is spec_b:
            load_window["start"] = time.perf_counter()
            time.sleep(1.0)  # a slow swap (HF checkpoint load)
            load_window["end"] = time.perf_counter()
        return params, cfg

    monkeypatch.setattr(serving, "build_model", build_model)
    server = LLMServer(
        {"a": spec_a, "b": spec_b},
        engine=dict(max_new_tokens=40, **ENGINE_KW),
    )
    a_times = []
    b_done = threading.Event()

    def consume_a():
        for _chunk in server({"prompt": [1, 2, 3], "model": "a",
                              "max_new_tokens": 40}):
            a_times.append(time.perf_counter())

    def consume_b():
        list(server({"prompt": [4, 5, 6], "model": "b",
                     "max_new_tokens": 4}))
        b_done.set()

    ta = threading.Thread(target=consume_a, daemon=True)
    ta.start()
    while not a_times:  # family A is decoding
        time.sleep(0.005)
    tb = threading.Thread(target=consume_b, daemon=True)
    tb.start()
    ta.join(timeout=60)
    assert b_done.wait(timeout=60)
    during_load = [
        t for t in a_times
        if load_window["start"] <= t <= load_window["end"]
    ]
    assert during_load, (
        "family A produced no tokens while family B loaded — the "
        "swap blocked the wrong family"
    )


# ---------------------------------------------------------------------
# serve-level chaos: replica death mid-stream errors, doesn't hang
# ---------------------------------------------------------------------

@pytest.mark.timeout(240)
def test_replica_death_fails_inflight_stream(rt_session):
    rt = rt_session
    import ray_tpu.serve as serve
    from ray_tpu.llm import build_llm_app

    tiny = {
        "kind": "init", "seed": 0,
        "config": {
            "vocab_size": 128, "dim": 64, "n_layers": 2,
            "n_heads": 4, "n_kv_heads": 2, "intermediate": 128,
            "max_seq_len": 128, "dtype": "float32",
        },
    }
    try:
        handle = serve.run(
            build_llm_app(
                {"tiny": tiny},
                # Big per-slot capacity: the in-flight stream must
                # still be decoding (900-token budget, seconds of
                # work) when the replica dies.
                engine={
                    "slots": 2, "max_len": 1024,
                    "prefill_chunk": 8, "max_new_tokens": 900,
                },
                max_ongoing_requests=8,
            ),
            name="llm-chaos",
            route_prefix=None,
        )
        warm = handle.options(stream=True).remote(
            {"prompt": [1, 2, 3], "max_new_tokens": 2}
        )
        assert len(list(warm)) == 2
        stream = handle.options(stream=True).remote(
            {"prompt": [5, 6, 7], "max_new_tokens": 900}
        )
        first = next(stream)
        assert first  # stream is live
        controller = rt.get_actor(
            "SERVE_CONTROLLER", namespace="serve"
        )
        replicas = rt.get(
            controller.get_replicas.remote("llm-chaos", "llm"),
            timeout=30,
        )
        assert replicas
        rt.kill(replicas[0]["actor"])
        outcome = None
        deadline = time.time() + 120
        try:
            while time.time() < deadline:
                next(stream)
        except StopIteration:
            outcome = "clean_stop"
        except BaseException as e:  # noqa: BLE001 — the assertion
            outcome = repr(e)
        # The dead replica must surface as an ERROR within the
        # deadline — not a hang, and not a well-formed early stop
        # that hides the truncation.
        assert outcome not in (None, "clean_stop"), outcome
    finally:
        serve.shutdown()


# ---------------------------------------------------------------------
# a model with window layers: two page pools under one admission gate
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def window_model():
    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(
        vocab_size=128, dim=64, n_layers=3, n_heads=4, n_kv_heads=2,
        custom_head_dim=16, intermediate=32, max_seq_len=512,
        dtype=jnp.float32, moe_experts=4, moe_top_k=2,
        moe_router="sigmoid_groups",
        layer_kinds=[[0, 2, 1e6, False], [8, 4, 1e4, True], [8, 4, 1e4, True]],
    )
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


WINDOW_KW = dict(slots=2, max_len=512, prefill_chunk=16, kv_block_len=4)


def test_a_rows_window_pages_stay_inside_the_stated_bound(window_model):
    """A 10-chunk prompt and 300 steps: the window pool never pins
    more than the row's ring, `window - 1 + chunk` tokens' pages plus
    one, however long the row grows; the full pool holds its length."""
    from ray_tpu.llm import EngineConfig, InferenceEngine

    cfg, params = window_model
    eng = InferenceEngine(
        params, cfg, EngineConfig(max_new_tokens=300, **WINDOW_KW),
    )
    bound = -(-(8 - 1 + 16) // 4) + 1
    try:
        stream = eng.submit(list(range(1, 128)) + list(range(1, 34)))
        seen, most = 0, 0
        for _ in stream:
            seen += 1
            if seen % 25 == 0:
                most = max(most, eng.stats()["window_blocks_used"])
        stats = eng.stats()
    finally:
        eng.close()
    assert seen == 300
    assert 0 < most <= bound and stats["window_pool_used"] <= bound
    assert stats["full_pool_used"] == -(-(160 + 300) // 4)
    assert stats["window_pages_recycled"] == -(-(160 + 299) // 4) - bound
    assert stats["window_blocks_used"] == stats["kv_blocks_used"] == 0


def test_the_gate_covers_the_window_pool(window_model):
    """Slots and full pages are free but the window pool holds one
    ring: the second request WAITS behind the first and is served
    after it releases, with the tokens it gets when alone."""
    from ray_tpu.llm import EngineConfig, InferenceEngine
    from ray_tpu.llm.kv_slots import BlockAllocator

    cfg, params = window_model
    prompts = [list(range(1, 40)), list(range(50, 95))]
    alone = InferenceEngine(
        params, cfg,
        EngineConfig(max_new_tokens=24, prefix_cache=False, **WINDOW_KW),
    )
    try:
        want = [list(alone.submit(p)) for p in prompts]
    finally:
        alone.close()
    eng = InferenceEngine(
        params, cfg,
        EngineConfig(max_new_tokens=24, prefix_cache=False, **WINDOW_KW),
    )
    try:
        ring = eng._kv.window.ring
        eng._kv.window.alloc = BlockAllocator(ring + 3)  # one ring and a bit
        first, second = (eng.submit(p) for p in prompts)
        waited = False
        got_first = []
        for token in first:
            got_first.append(token)
            stats = eng.stats()
            waited |= stats["waiting"] == 1 and stats["slots_used"] == 1
        assert waited  # a slot was free all along; the ring was not
        assert got_first == want[0] and list(second) == want[1]
        stats = eng.stats()
        assert stats["dead"] is False and stats["window_blocks_used"] == 0
    finally:
        eng.close()
