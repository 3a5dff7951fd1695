"""Host bookkeeping of the conv layers' state slots (llm/kv_state.py):
a row's slot reserved and released with its pages, snapshots registered
under a boundary's prefix key and evicted oldest first, the admission
gate over pages and state slots, and a row that never reads a slot it
does not own. No JAX program runs here."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ray_tpu.llm.kv_slots import NULL_BLOCK, BlocksExhausted  # noqa: E402
from ray_tpu.llm.kv_state import StateSlots  # noqa: E402

BL, CHUNK = 4, 16


def _slots(rows=2, full_blocks=12):
    """`rows` rows and a page pool of `full_blocks` blocks: 12 x 4 // 16
    = 3 snapshots' worth of slots beside the rows' and the null slot."""
    return StateSlots(BL, CHUNK, rows, full_blocks, slot_bytes=100)


def _keys(tag, blocks=12):
    return [f"{tag}-{i}" for i in range(blocks)]


def test_the_pool_is_a_slot_a_row_a_slot_a_chunk_and_the_null_slot():
    state = _slots()
    assert state.alloc.n_blocks == 2 + 3 + 1
    assert state.alloc.capacity() == 5 and state.alloc.reserved == 1
    assert StateSlots(16, 512, 64, 16384).alloc.n_blocks == 64 + 512 + 1
    # an engine with no slot count still holds a row
    assert StateSlots(BL, CHUNK, 0, 0).alloc.capacity() == 1


def test_a_row_reserves_one_slot_and_gives_it_back():
    state = _slots()
    own, read = state.admit(_keys("a"), 0)
    assert len(own) == 1 and own[0] != NULL_BLOCK and read is None
    other, _ = state.admit(_keys("b"), 0)
    assert other != own and state.alloc.used() == 2
    state.alloc.release(own)
    assert state.alloc.used() == 1
    with pytest.raises(ValueError, match="double free"):
        state.alloc.release(own)
    assert state.stats()["conv_hits_restored"] == 0


def test_a_boundarys_key_is_that_of_the_block_that_ends_there():
    state, keys = _slots(), _keys("a")
    assert state._key(keys, CHUNK) == "a-3"
    assert state._key(keys, 2 * CHUNK) == "a-7"
    assert state._key(keys, 0) is None  # no block ends at 0
    assert state._key(keys, CHUNK + 1) is None  # not block-aligned
    assert state._key(keys[:3], CHUNK) is None  # the prompt has no such block
    assert state._key([], CHUNK) is None  # a prefix cache switched off


def test_a_snapshot_is_registered_released_at_once_and_found_again():
    state, keys = _slots(), _keys("a")
    slot = state.keep(keys, CHUNK)
    assert slot != NULL_BLOCK
    assert state.alloc.used() == 0 and state.alloc.cached() == 1
    assert state.keep(keys, CHUNK) == NULL_BLOCK  # the pool holds it already
    assert state.keep([], CHUNK) == NULL_BLOCK  # no key: nothing kept
    assert state.usable_skip(keys, CHUNK) == CHUNK
    assert state.stats()["conv_snapshots_written"] == 1
    # a hit starts from it and writes a slot of its own
    own, read = state.admit(keys, CHUNK)
    assert read == slot and own[0] not in (slot, NULL_BLOCK)
    assert state.alloc.cached() == 1  # unpinned again: still a snapshot
    assert state.stats()["conv_hits_restored"] == 1


def test_usable_skip_falls_back_to_a_shorter_boundary_or_to_a_miss():
    state, keys = _slots(), _keys("a")
    for boundary in (CHUNK, 2 * CHUNK):
        state.keep(keys, boundary)
    assert state.usable_skip(keys, 2 * CHUNK) == 2 * CHUNK
    assert state.usable_skip(keys, 3 * CHUNK) == 2 * CHUNK  # no snapshot at 48
    assert state.usable_skip(_keys("b"), 2 * CHUNK) == 0  # another prompt's
    assert state.usable_skip(keys, 0) == 0


def test_snapshots_are_evicted_oldest_first_and_counted():
    state, keys = _slots(rows=1, full_blocks=8), _keys("a")  # 1 + 2 + null
    first = state.keep(keys, CHUNK)
    second = state.keep(keys, 2 * CHUNK)
    own, _ = state.admit(_keys("b"), 0)  # the last free slot
    assert state.stats()["conv_snapshots_evicted"] == 0
    third = state.keep(keys, 3 * CHUNK)  # no free slot: the oldest goes
    assert third == first and state.stats()["conv_snapshots_evicted"] == 1
    assert state.usable_skip(keys, CHUNK) == 0
    assert state.usable_skip(keys, 2 * CHUNK) == 2 * CHUNK
    assert state.usable_skip(keys, 3 * CHUNK) == 3 * CHUNK
    # a hit makes its snapshot the newest: the next to go is `third`
    state.alloc.release(own)
    hit, read = state.admit(keys, 2 * CHUNK)
    assert read == second and hit == own  # (the freed slot first)
    assert state.keep(_keys("c"), CHUNK) == third
    assert state.usable_skip(keys, 3 * CHUNK) == 2 * CHUNK
    assert state.stats()["conv_snapshots_evicted"] == 2


def test_keeping_a_snapshot_never_takes_a_rows_slot():
    state = _slots(rows=2, full_blocks=0)  # two slots, both for rows
    a, _ = state.admit(_keys("a"), 0)
    b, _ = state.admit(_keys("b"), 0)
    assert state.keep(_keys("a"), CHUNK) == NULL_BLOCK
    assert state.stats()["conv_snapshots_written"] == 0
    assert not state.gate(_keys("c"), 0)
    state.alloc.release(a)
    assert state.gate(_keys("c"), 0)


def test_the_gate_counts_a_hits_own_snapshot_as_pinned():
    """One slot left and it is the snapshot the row would start from:
    pinned while the row's slot is reserved, it cannot also be that
    slot, so the row waits (and a row that skips nothing may evict it)."""
    state, keys = _slots(rows=1, full_blocks=4), _keys("a")  # 1 + 1 + null
    state.keep(keys, CHUNK)
    holder, _ = state.admit(_keys("b"), 0)
    assert state.alloc.available() == 1  # the snapshot alone
    assert not state.gate(keys, CHUNK)
    assert state.gate(keys, 0)
    state.alloc.release(holder)
    assert state.gate(keys, CHUNK)
    own, read = state.admit(keys, CHUNK)
    assert read != own[0]


def test_a_reservation_made_while_a_snapshot_is_read_cannot_evict_it():
    state, keys = _slots(rows=1, full_blocks=4), _keys("a")  # 1 + 1 + null
    kept = state.keep(keys, CHUNK)
    own, read = state.admit(keys, CHUNK)
    assert read == kept and own[0] != kept
    assert state.usable_skip(keys, CHUNK) == CHUNK  # still registered


def test_the_gauges_count_rows_slots_and_kept_snapshots():
    state, keys = _slots(), _keys("a")
    assert state.stats()["conv_state_bytes_in_use"] == 0
    own, _ = state.admit(keys, 0)
    state.keep(keys, CHUNK)
    state.keep(keys, 2 * CHUNK)
    stats = state.stats()
    assert stats["conv_state_bytes_in_use"] == 3 * 100
    assert (stats["conv_state_slots_used"], stats["conv_state_slots_cached"]) == (1, 2)
    assert stats["conv_state_slots_total"] == 5
    state.alloc.release(own)
    assert state.stats()["conv_state_bytes_in_use"] == 2 * 100


# -- with the pages: one reservation, one gate --------------------------

def _cache(slots=2, kv_blocks=0):
    import jax.numpy as jnp

    from ray_tpu.llm.kv_slots import PagedKVCache
    from ray_tpu.models.llama import LlamaConfig

    attn, conv = (0, 2, 1e6, False), (0, 0, 0, False, 3)
    cfg = LlamaConfig(
        vocab_size=64, dim=32, n_layers=4, n_heads=4, n_kv_heads=2,
        intermediate=16, qk_norm="head", layer_kinds=(conv, conv, attn, conv),
        moe_experts=2, moe_top_k=1, moe_router="sigmoid_groups",
        dense_layers=2, dense_intermediate=32, dtype=jnp.float32,
    )
    return PagedKVCache.for_engine(
        cfg, slots=slots, max_len=64, prefill_chunk=CHUNK, kv_block_len=BL,
        kv_blocks=kv_blocks,
    )


def test_a_rows_blocks_are_its_pages_and_its_slot_reserved_together():
    cache = _cache()
    blocks = cache.alloc.reserve(3)
    assert sorted(blocks) == ["full", "state"]
    assert len(blocks["full"]) == 3 and len(blocks["state"]) == 1
    rows = cache.host_rows([blocks, None])
    assert rows["tables"][0, :3].tolist() == blocks["full"]
    assert rows["state_slots"].tolist() == [blocks["state"], [NULL_BLOCK]]
    table = cache.row_table(0, blocks)
    own = blocks["state"][0]
    assert table.conv.tolist() == [[own, own, NULL_BLOCK, -1]]
    assert cache.row_table(0, blocks, read=9, snapshot=7, length=21).conv.tolist() == [
        [9, own, 7, 21]
    ]
    assert cache.row_table(0, None).conv.tolist() == [[0, 0, 0, -1]]
    state = cache.step_state([blocks, None], [5, 0], [True, False], [-1, -1], [3, 0], 0)
    assert state["state_slots"].tolist() == [[own], [NULL_BLOCK]]
    cache.alloc.release(blocks)
    assert cache.full.used() == 0 and cache.state.alloc.used() == 0


def test_a_reservation_without_a_state_slot_takes_no_pages():
    cache = _cache(slots=1, kv_blocks=3)  # 1 row + 0 snapshots + null
    held = cache.alloc.reserve(1)
    with pytest.raises(BlocksExhausted, match="state slot"):
        cache.alloc.reserve(1)
    assert cache.full.used() == 1  # nothing half reserved
    cache.alloc.release(held)
    assert cache.alloc.reserve(1)["state"] == held["state"]


def test_a_model_with_window_and_conv_layers_is_refused():
    import jax.numpy as jnp

    from ray_tpu.llm.kv_slots import PagedKVCache
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(
        vocab_size=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        intermediate=16, layer_kinds=((8, 2, 1e4, False), (0, 0, 0, False, 3)),
        moe_experts=2, moe_router="sigmoid_groups", dtype=jnp.float32,
    )
    with pytest.raises(ValueError, match="window layers and conv layers"):
        PagedKVCache.for_engine(
            cfg, slots=2, max_len=64, prefill_chunk=CHUNK, kv_block_len=BL
        )
