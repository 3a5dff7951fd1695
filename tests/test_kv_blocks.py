"""Paged-KV block allocator invariants (ISSUE 11): alloc/free/
refcount, double-free detection, prefix pin/register/LRU-evict, and
the engine-level memory contracts — a request the pool can never hold
is SHED at submit, and a block-starved admission WAITS (FIFO, no
crash, no skip-ahead) until running requests release their pages."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.kv_slots import (
    BlockAllocator,
    BlocksExhausted,
    PagedKVCache,
    default_block_len,
)


# ---------------------------------------------------------------------
# allocator invariants (pure bookkeeping, no jax)
# ---------------------------------------------------------------------

def test_reserve_release_roundtrip():
    alloc = BlockAllocator(9)  # 8 usable + reserved null block
    assert alloc.capacity() == 8
    assert alloc.available() == 8
    blocks = alloc.reserve(5)
    assert len(set(blocks)) == 5
    assert 0 not in blocks  # the null block is never handed out
    assert alloc.used() == 5
    assert alloc.available() == 3
    alloc.release(blocks)
    assert alloc.used() == 0
    assert alloc.available() == 8


def test_oom_raises_and_grants_nothing_partial():
    alloc = BlockAllocator(5)
    alloc.reserve(3)
    avail = alloc.available()
    with pytest.raises(BlocksExhausted):
        alloc.reserve(avail + 1)
    assert alloc.available() == avail  # all-or-nothing


def test_double_free_raises():
    alloc = BlockAllocator(4)
    blocks = alloc.reserve(1)
    alloc.release(blocks)
    with pytest.raises(ValueError):
        alloc.release(blocks)


def test_refcount_shared_prefix_block():
    alloc = BlockAllocator(8)
    [block] = alloc.reserve(1)
    alloc.register(block, ("p",))
    # A second request pins the same prefix block.
    assert alloc.match_prefix([("p",)]) == [block]
    alloc.release([block])  # first owner done
    assert alloc.used() == 1  # still pinned by the second
    alloc.release([block])  # second owner done
    assert alloc.used() == 0
    assert alloc.cached() == 1  # refcount 0 but reusable
    # Still matchable from the cached-free state (re-pins it).
    assert alloc.match_prefix([("p",)]) == [block]
    alloc.release([block])


def test_eviction_is_lru_and_drops_prefix_entry():
    alloc = BlockAllocator(3)  # 2 usable
    a, b = alloc.reserve(2)
    alloc.register(a, ("a",))
    alloc.register(b, ("b",))
    alloc.release([a])  # a becomes cached-free first (older)
    alloc.release([b])
    [evicted] = alloc.reserve(1)
    assert evicted == a  # oldest cached-free evicts first
    assert alloc.peek_prefix([("a",)]) == 0  # its prefix entry is gone
    assert alloc.peek_prefix([("b",)]) == 1  # the newer one survives


def test_match_pins_block_out_of_eviction():
    alloc = BlockAllocator(3)
    a, b = alloc.reserve(2)
    alloc.register(a, ("a",))
    alloc.register(b, ("b",))
    alloc.release([a])
    alloc.release([b])
    assert alloc.match_prefix([("a",)]) == [a]  # pin a
    [evicted] = alloc.reserve(1)
    assert evicted == b  # the reservation cannot steal the pinned hit
    alloc.release([a])


def test_register_first_writer_wins_and_requires_pin():
    alloc = BlockAllocator(4)
    a, b = alloc.reserve(2)
    assert alloc.register(a, ("k",)) is True
    assert alloc.register(b, ("k",)) is False  # prefix taken: no-op
    assert alloc.match_prefix([("k",)]) == [a]
    alloc.release([a])
    with pytest.raises(ValueError):
        alloc.register(99, ("other",))  # unpinned block


def test_peek_prefix_stops_at_first_gap():
    alloc = BlockAllocator(8)
    a, b = alloc.reserve(2)
    alloc.register(a, ("p1",))
    alloc.register(b, ("p3",))
    assert alloc.peek_prefix([("p1",), ("p2",), ("p3",)]) == 1
    assert alloc.match_prefix([("p1",), ("p2",), ("p3",)]) == [a]
    alloc.release([a])  # the match pin
    alloc.release([a, b])  # the original reservations


# ---------------------------------------------------------------------
# geometry
# ---------------------------------------------------------------------

def test_default_block_len_divides_chunk():
    assert default_block_len(32) == 16
    assert default_block_len(8) == 8
    assert default_block_len(24) == 12
    assert default_block_len(7) == 7
    for chunk in (7, 8, 16, 24, 32, 48):
        assert chunk % default_block_len(chunk) == 0


def test_paged_cache_geometry_validation():
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(
        vocab_size=32, dim=16, n_layers=1, n_heads=2, n_kv_heads=1,
        intermediate=32, max_seq_len=64, dtype=jnp.float32,
        attention="reference",
    )
    with pytest.raises(ValueError):  # block doesn't divide chunk
        PagedKVCache(cfg, 8, 16, 64, prefill_chunk=8)
    with pytest.raises(ValueError):  # max_len not a block multiple
        PagedKVCache(cfg, 8, 8, 60, prefill_chunk=8)
    kv = PagedKVCache(cfg, 8, 8, 64, prefill_chunk=8)
    assert kv.max_blocks == 8
    assert kv.blocks_for(1) == 1
    assert kv.blocks_for(8) == 1
    assert kv.blocks_for(9) == 2


def test_prefix_keys_cover_only_full_blocks_and_bind_whole_prefix():
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(
        vocab_size=32, dim=16, n_layers=1, n_heads=2, n_kv_heads=1,
        intermediate=32, max_seq_len=64, dtype=jnp.float32,
        attention="reference",
    )
    kv = PagedKVCache(cfg, 8, 8, 64, prefill_chunk=8)
    prompt = list(range(20))  # 2 full blocks + 4-token partial
    keys = kv.prefix_keys(prompt)
    assert len(keys) == 2  # the partial block never gets a key
    # Deterministic, and equal prefixes produce equal keys.
    assert keys == kv.prefix_keys(prompt[:17])
    # The chain binds the WHOLE prefix: same second block behind a
    # different first block must yield a different second key.
    other = kv.prefix_keys([99] + list(range(1, 20)))
    assert other[0] != keys[0]
    assert other[1] != keys[1]
    # Shared first block, divergent second.
    branch = kv.prefix_keys(list(range(8)) + [77] * 8)
    assert branch[0] == keys[0]
    assert branch[1] != keys[1]


# ---------------------------------------------------------------------
# engine-level memory contracts (tiny model)
# ---------------------------------------------------------------------

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


def test_engine_pool_oom_sheds_at_submit(tiny_model):
    """A request that could NEVER get its pages (bigger than the whole
    pool) is shed at submit with EngineOverloaded; the engine stays
    alive and keeps serving pool-sized requests."""
    from ray_tpu.llm import (
        EngineConfig, EngineOverloaded, InferenceEngine,
    )

    cfg, params = tiny_model
    eng = InferenceEngine(
        params, cfg,
        EngineConfig(
            slots=2, max_len=48, prefill_chunk=8, kv_blocks=4,
            max_new_tokens=8,
        ),
        family="tiny",
    )
    try:
        # 29-token prompt + 8 budget = 37 tokens = 5 blocks of 8, but
        # the pool only holds 3 usable blocks.
        with pytest.raises(EngineOverloaded):
            eng.submit(list(range(1, 30)), max_new_tokens=8)
        out = list(eng.submit([1, 2, 3], max_new_tokens=4))
        assert len(out) == 4
        assert eng.stats()["dead"] is False
    finally:
        eng.close()


def test_block_starved_admission_waits_then_serves(tiny_model):
    """Two requests that each need more than half the pool: slots are
    free but blocks are not, so the second request WAITS (gated FIFO
    admission) and is served after the first releases its pages —
    never a reserve failure that would kill the loop."""
    from ray_tpu.llm import EngineConfig, InferenceEngine

    cfg, params = tiny_model
    eng = InferenceEngine(
        params, cfg,
        EngineConfig(
            slots=2, max_len=48, prefill_chunk=8, kv_blocks=7,
            max_new_tokens=16, prefix_cache=False,
        ),
        family="tiny",
    )
    try:
        # Each needs ceil((16 + 16) / 8) = 4 of the 6 usable blocks.
        first = eng.submit(list(range(1, 17)), max_new_tokens=16)
        second = eng.submit(list(range(101, 117)), max_new_tokens=16)
        assert len(list(first)) == 16
        assert len(list(second)) == 16
        stats = eng.stats()
        assert stats["dead"] is False
        assert stats["kv_blocks_used"] == 0  # everything released
    finally:
        eng.close()


def test_peek_cached_distinguishes_live_pins_from_cached_free():
    alloc = BlockAllocator(8)
    a, b = alloc.reserve(2)
    alloc.register(a, ("p1",))
    alloc.register(b, ("p2",))
    alloc.release([b])  # b cached-free; a stays live-pinned
    assert alloc.peek_cached([("p1",), ("p2",)], 2) == 1
    assert alloc.peek_cached([("p1",), ("p2",)], 1) == 0  # a is live
    alloc.release([a])


def test_sharing_live_prefix_relaxes_admission(tiny_model):
    """Review-caught gate bug: hit blocks pinned by a LIVE request
    cost no availability to share, so a prefix-sharing request must
    fit in a pool the naive `available >= total` arithmetic says is
    full — both requests decode CONCURRENTLY."""
    import threading

    from ray_tpu.llm import EngineConfig, InferenceEngine

    cfg, params = tiny_model
    eng = InferenceEngine(
        params, cfg,
        EngineConfig(
            slots=2, max_len=48, prefill_chunk=8, kv_blocks=8,
            max_new_tokens=8, prefix_cache=True,
        ),
        family="tiny",
    )
    try:
        shared = list(range(1, 17))  # 2 full blocks
        # A: 5 of the 7 usable blocks (16 prompt + 24 budget).
        first = eng.submit(shared, max_new_tokens=24)
        consumed = []
        consumer = threading.Thread(
            target=lambda: consumed.extend(first), daemon=True
        )
        consumer.start()
        deadline = time.time() + 30
        while time.time() < deadline and not consumed:
            time.sleep(0.005)  # A is decoding (prefix registered)
        # B: identical prompt, 3 total blocks, skip 1 shared block ->
        # needs 2 fresh of the 2 still available. Old gate demanded 3.
        second = eng.submit(shared, max_new_tokens=8)
        concurrent = False
        while time.time() < deadline:
            stats = eng.stats()
            if stats["slots_used"] == 2:
                concurrent = True
                break
            time.sleep(0.005)
        assert concurrent, "prefix-sharing request was not admitted " \
            "while the prefix owner was still decoding"
        assert len(list(second)) == 8
        consumer.join(timeout=30)
        assert len(consumed) == 24
        assert eng.stats()["prefix_hits"] >= 1
    finally:
        eng.close()


def test_engine_block_accounting_in_stats(tiny_model):
    from ray_tpu.llm import EngineConfig, InferenceEngine

    cfg, params = tiny_model
    eng = InferenceEngine(
        params, cfg,
        EngineConfig(slots=2, max_len=48, prefill_chunk=8,
                     max_new_tokens=4),
        family="tiny",
    )
    try:
        stats = eng.stats()
        assert stats["kv_block_len"] == 8
        assert stats["kv_blocks_total"] == 2 * (48 // 8)
        assert stats["kv_blocks_used"] == 0
        list(eng.submit([5, 6, 7], max_new_tokens=4))
        stats = eng.stats()
        assert stats["kv_blocks_used"] == 0
        # The full prompt had no full block (3 tokens < 8), so
        # nothing registers in the prefix cache either.
        assert stats["kv_blocks_cached"] == 0
    finally:
        eng.close()


# ---------------------------------------------------------------------
# the window layers' pages (llm/kv_window.py): a second pool, a ring a
# row, the tails the prefix cache keeps
# ---------------------------------------------------------------------

def test_window_pool_geometry_and_the_stated_bound():
    from ray_tpu.llm.kv_window import WindowPages

    window, bl, chunk = 128, 16, 1024
    pages = WindowPages(window, bl, chunk, slots=32, full_blocks=24576)
    # never more than `window - 1 + chunk` tokens need, plus one
    assert pages.ring == -(-(window - 1 + chunk) // bl) + 1 == 73
    assert pages.tail_blocks == 8  # the 127 keys before a boundary
    assert pages.ring_for(5) == 5 and pages.ring_for(10 ** 6) == 73
    # every slot's ring, a tail for every whole-chunk boundary the full
    # pool can hold, and the null block
    assert pages.alloc.n_blocks == 32 * 73 + (24576 * bl // chunk) * 8 + 1
    with pytest.raises(ValueError, match="shorter than the window"):
        WindowPages(window, bl, 64, slots=1, full_blocks=8)


def test_a_rows_ring_holds_its_pages_over_ten_chunks_and_300_steps():
    """The bookkeeping of one row's whole life: 10 chunks of prompt
    (every boundary's tail kept, or dropped where the pool is tight),
    then 300 steps. The row's pinned pages never pass the ring, which
    is the stated bound; the kept tails are refcount 0."""
    from ray_tpu.llm.kv_window import WindowPages

    window, bl, chunk = 8, 4, 16
    pages = WindowPages(window, bl, chunk, slots=2, full_blocks=64)
    bound = -(-(window - 1 + chunk) // bl) + 1
    keys = [("doc", i) for i in range(10 * chunk // bl)]
    total_blocks = -(-(10 * chunk + 300) // bl)
    assert pages.gate(keys, 0, total_blocks)
    ring, copy = pages.admit(keys, 0, total_blocks)
    assert copy is None and len(ring) == pages.ring <= bound
    for boundary in range(chunk, 10 * chunk + 1, chunk):
        kept = pages.keep_tail(keys, ring, boundary)
        assert kept is not None
        src, dst = kept
        assert set(src) <= set(ring) and not set(dst) & set(ring)
        assert len(src) == len(dst) == pages.tail_blocks == 2
        assert pages.alloc.used() == len(ring)  # the tails are not pinned
    assert pages.alloc.cached() == 10 * pages.tail_blocks
    # the same boundary again: already held, nothing to copy
    assert pages.keep_tail(keys, ring, chunk) is None
    # a hit finds the longest boundary whose tail is whole
    assert pages.usable_skip(keys, 9 * chunk) == 9 * chunk
    other = [("other", i) for i in range(len(keys))]
    assert pages.usable_skip(other, 9 * chunk) == 0
    pages.alloc.release(ring)
    assert pages.alloc.used() == 0


def test_a_hit_copies_its_tail_into_the_ring_and_a_tight_pool_keeps_no_tail():
    from ray_tpu.llm.kv_slots import BlockAllocator
    from ray_tpu.llm.kv_window import WindowPages

    window, bl, chunk = 8, 4, 16
    pages = WindowPages(window, bl, chunk, slots=1, full_blocks=64)
    keys = [("doc", i) for i in range(12)]
    first, _ = pages.admit(keys, 0, 40)
    src, kept = pages.keep_tail(keys, first, 2 * chunk)
    pages.alloc.release(first)
    # the hit: boundary 32 is logical blocks 6 and 7 of a ring of 7
    ring, (tail, into) = pages.admit(keys, 2 * chunk, 40)
    assert tail == kept and into == [ring[6], ring[7 % pages.ring]]
    assert pages.alloc.used() == len(ring)  # the tail is unpinned again
    # every page pinned by rings: keeping a tail is skipped, not an error
    pages.alloc = BlockAllocator(len(ring) + 1)
    ring = pages.alloc.reserve(len(ring))
    assert pages.keep_tail([("x", i) for i in range(12)], ring, chunk) is None
    assert not pages.gate(keys, 0, 40)  # and no second ring fits


def test_a_one_kind_model_gets_todays_tables_and_state_bit_for_bit(tiny_model):
    """`for_engine`, `row_table` and `step_state` of a model whose
    layers are all alike: the values the engine's own lines gave
    before they moved behind the cache (what the benchmark's probe
    pins: `tests/benchmark/test_benchmark_probe.py`)."""
    cfg, _ = tiny_model
    cache = PagedKVCache.for_engine(
        cfg, slots=3, max_len=48, prefill_chunk=8, kv_block_len=0,
        kv_blocks=0,
    )
    assert cache.block_len == default_block_len(8) == 8
    assert cache.full.n_blocks == 3 * (48 // 8) + 1
    assert cache.alloc is cache.full and cache.window is None
    assert sorted(cache.pool) == ["k", "v"]
    blocks = [cache.alloc.reserve(cache.blocks_for(20)), None, [7, 9]]
    assert blocks[0] == [1, 2, 3]
    table = cache.row_table(0, blocks[0])
    assert table.dtype == jnp.int32
    assert np.array_equal(np.asarray(table), [[1, 2, 3, 0, 0, 0]])
    state = cache.step_state(
        blocks, np.array([20, 0, 3]), np.array([True, False, True]),
        np.full(3, -1), np.array([5, 0, 2]), 4,
    )
    assert sorted(state) == [
        "alive", "budget", "eos", "positions", "step", "tables",
    ]
    assert np.array_equal(
        np.asarray(state["tables"]),
        [[1, 2, 3, 0, 0, 0], [0] * 6, [7, 9, 0, 0, 0, 0]],
    )
    for name, dtype in (
        ("tables", jnp.int32), ("positions", jnp.int32), ("alive", bool),
        ("eos", jnp.int32), ("budget", jnp.int32), ("step", jnp.int32),
    ):
        assert state[name].dtype == dtype, name
    assert int(state["step"]) == 4 and state["step"].shape == ()
