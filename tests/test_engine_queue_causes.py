"""Why an engine's queue stands (llm/engine.py's module docstring,
ISSUE 59): a CPU engine driven into each cause reads that cause and,
beside the loop's own latency (`admissible`), no other; a request's
wait by cause sums to its wait, and slot-time by state to slots x
elapsed. Three tiny engines for the whole file (one a kind of pool),
each shared by its cases.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.serve.observability import QUEUE_CAUSES

LONG = 400  # decode steps that keep a slot busy while a case sets up


def _engine(cfg, **kw):
    from ray_tpu.llm import EngineConfig, InferenceEngine
    from ray_tpu.models.llama import init_params

    return InferenceEngine(
        init_params(jax.random.PRNGKey(0), cfg), cfg,
        EngineConfig(
            slots=2, prefix_cache=False, max_new_tokens=4, **kw
        ),
    )


@pytest.fixture(scope="module")
def engines():
    """kind of pool -> engine, built when a case first asks."""
    from ray_tpu.models.llama import LlamaConfig

    attn, conv = (0, 2, 1e6, False), (0, 0, 0, False, 3)
    configs = {
        "full": (
            LlamaConfig(
                vocab_size=128, dim=64, n_layers=2, n_heads=4,
                n_kv_heads=2, intermediate=128, max_seq_len=512,
                dtype=jnp.float32, attention="reference",
            ),
            dict(max_len=512, prefill_chunk=8, kv_block_len=8),
        ),
        "window": (
            LlamaConfig(
                vocab_size=128, dim=64, n_layers=2, n_heads=4,
                n_kv_heads=2, custom_head_dim=16, intermediate=32,
                max_seq_len=128, dtype=jnp.float32, moe_experts=4,
                moe_top_k=2, moe_router="sigmoid_groups",
                layer_kinds=[[0, 2, 1e6, False], [8, 4, 1e4, True]],
            ),
            dict(max_len=128, prefill_chunk=8, kv_block_len=8),
        ),
        "state": (
            LlamaConfig(
                vocab_size=64, dim=32, n_layers=2, n_heads=4,
                n_kv_heads=2, intermediate=16, qk_norm="head",
                layer_kinds=(conv, attn), moe_experts=2, moe_top_k=1,
                moe_router="sigmoid_groups", dtype=jnp.float32,
            ),
            dict(max_len=64, prefill_chunk=8, kv_block_len=8),
        ),
    }
    built = {}

    def get(kind):
        if kind not in built:
            cfg, kw = configs[kind]
            built[kind] = _engine(cfg, **kw)
            # (past the loop's warm-up, which a first request would
            # wait out as `admissible`)
            list(built[kind].submit([1, 2, 3]))
        return built[kind]

    yield get
    for eng in built.values():
        eng.close()


def _until(what, timeout=30.0):
    deadline = time.monotonic() + timeout
    while not what():
        assert time.monotonic() < deadline, "the engine never got there"
        time.sleep(0.001)


def _idle(eng):
    _until(lambda: not (
        (s := eng.stats())["slots_used"] or s["waiting"]
    ))


def _sums_hold(eng):
    stats = eng.stats()
    by_cause = stats["admit_wait_by_cause_ms_total"]
    assert set(by_cause) == set(stats["queue_ms"]) == set(QUEUE_CAUSES)
    assert sum(by_cause.values()) == pytest.approx(
        stats["admit_wait_ms_total"], abs=1.0
    )
    elapsed_ms = (eng._acct_ts - eng._acct_t0) * 1e3
    assert sum(stats["slot_ms"].values()) == pytest.approx(
        stats["slots_total"] * elapsed_ms, abs=1.0
    )
    return stats


def _gained(before, after, key):
    return {
        k: after[key][k] - before[key][k]
        for k in after[key] if after[key][k] - before[key][k] > 0
    }


def _busy(eng, n):
    """`n` rows decoding for a while and nothing prefilling."""
    rows = [
        eng.submit([3 + i, 5, 7], max_new_tokens=LONG) for i in range(n)
    ]
    _until(lambda: (
        (s := eng.stats())["slots_used"] == n
        and not s["prefilling"] and not s["waiting"]
    ))
    return rows


def _pool_is_short(kind, eng):
    """Take all of one pool but a block; -> how to give it back."""
    alloc = {
        "full": lambda: eng._kv.full,
        "window": lambda: eng._kv.window.alloc,
        "state": lambda: eng._kv.state.alloc,
    }[kind]()
    with eng._lock:
        held = alloc.reserve(max(alloc.available() - (kind != "state"), 1))

    def give_back():
        with eng._lock:
            alloc.release(held)
        eng._wake.set()

    return give_back


def _no_slot(eng):
    rows = _busy(eng, 2)
    before = eng.stats()
    late = eng.submit([9, 9, 9])
    _until(lambda: eng.stats()["queue_ms"]["no_slot"]
           > before["queue_ms"]["no_slot"] + 5.0)
    rows[0].cancel()
    return [late] + rows, before


def _behind_prefill(eng):
    # (50 chunks, each held back a little: the prompt is still
    # prefilling when the second request arrives, whatever the load)
    dispatch = eng._dispatch_chunk
    eng._dispatch_chunk = lambda *a, **k: (
        time.sleep(0.004), dispatch(*a, **k)
    )[1]
    try:
        rows = [eng.submit(list(range(1, 401)))]
        _until(lambda: eng.stats()["prefilling"])
        before = eng.stats()
        late = eng.submit([9, 9, 9])
        _until(lambda: late._req.admitted_ts is not None)
    finally:
        eng._dispatch_chunk = dispatch
    return [late] + rows, before


def _a_pool(kind, cause):
    def drive(eng):
        give_back = _pool_is_short(kind, eng)
        before = eng.stats()
        row = eng.submit(list(range(1, 20)))
        _until(lambda: eng.stats()["queue_ms"][cause]
               > before["queue_ms"][cause] + 5.0)
        give_back()
        return [row], before

    return drive


def _nothing_in_the_way(eng):
    before = eng.stats()
    return [eng.submit([9, 9, 9])], before


CASES = {
    "no_slot": ("full", _no_slot),
    "behind_prefill": ("full", _behind_prefill),
    "no_pages": ("full", _a_pool("full", "no_pages")),
    "no_window_pages": ("window", _a_pool("window", "no_window_pages")),
    "no_state_slots": ("state", _a_pool("state", "no_state_slots")),
    "admissible": ("full", _nothing_in_the_way),
}


@pytest.mark.parametrize("cause", list(CASES))
def test_the_queue_stands_for_one_cause(engines, cause):
    kind, drive = CASES[cause]
    eng = engines(kind)
    _idle(eng)
    rows, before = drive(eng)
    first = rows[0]._req
    list(rows[0])
    for row in rows[1:]:  # what kept the engine busy has done its part
        row.cancel()
        list(row)
    _idle(eng)
    after = _sums_hold(eng)
    waited = _gained(before, after, "admit_wait_by_cause_ms_total")
    stood = _gained(before, after, "queue_ms")
    # the one cause and, until the loop came round, none: no other
    assert set(waited) <= {cause, "admissible"} and cause in waited
    assert set(stood) == set(waited)
    assert after["admitted"] - before["admitted"] == 1
    assert waited[cause] >= (
        0.5 * sum(waited.values()) if cause != "admissible" else 0.0
    )
    # the request's own split is what the totals gained, and its span's
    assert first.queue_cause_ms == pytest.approx(waited)
    assert sum(waited.values()) == pytest.approx(
        (first.admitted_ts - first.submitted_ts) * 1e3, abs=1e-6
    )
    slots = _gained(before, after, "slot_ms")
    assert "empty_queued" in slots or cause == "no_slot"
    assert ("prefilling" in slots) and ("decoding" in slots)


def test_a_cancelled_waiter_leaves_both_sums_whole(engines):
    eng = engines("full")
    _idle(eng)
    rows = _busy(eng, 2)
    before = eng.stats()
    gone, kept = eng.submit([8, 8, 8]), eng.submit([9, 9, 9])
    _until(lambda: eng.stats()["waiting"] == 2)
    gone.cancel()
    assert list(gone) == [] and gone.finish_reason == "cancelled"
    rows[1].cancel()
    list(kept)
    rows[0].cancel()
    for row in rows:
        list(row)
    _idle(eng)
    after = _sums_hold(eng)
    assert after["admitted"] - before["admitted"] == 1
    waited = _gained(before, after, "admit_wait_by_cause_ms_total")
    assert sum(waited.values()) == pytest.approx(
        (kept._req.admitted_ts - kept._req.submitted_ts) * 1e3, abs=1e-6
    )
    assert gone._req.queue_cause_ms == {}
    assert after["queue_ms"]["no_slot"] > before["queue_ms"]["no_slot"]
