"""How many keys a paged forward attends over (ISSUE 24): the rule as
a pure function — the one the program's trip count and the engine's
`kv_keys_read` both call — and the two counters in `engine.stats()`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import generate as g

TILE, MAX_LEN = 256, 4096


@pytest.mark.parametrize("length,tiles", [
    (0, 0), (1, 1), (TILE, 1), (TILE + 1, 2), (MAX_LEN, MAX_LEN // TILE),
])
@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "traced"])
def test_tiles_read_reach_the_longest_alive_row(xp, length, tiles):
    # Two short rows, the row under test, and a dead one whose stale
    # length must hold nothing open.
    valid_len = xp.asarray([min(length, 1), length, 0, MAX_LEN], np.int32)
    alive = xp.asarray([True, True, True, False])
    read = g.paged_tiles_read
    if xp is jnp:
        read = jax.jit(read, static_argnums=2)
    assert int(read(valid_len, alive, TILE)) == tiles


def test_all_rows_dead_read_nothing():
    valid_len = np.asarray([7, MAX_LEN, 300], np.int32)
    assert int(g.paged_tiles_read(valid_len, np.zeros(3, bool), TILE)) == 0
    # A prefill chunk has no dead row: `alive` defaults to all.
    assert int(g.paged_tiles_read(valid_len, True, TILE)) == MAX_LEN // TILE


@pytest.mark.parametrize("block_len,width,q_len,keys", [
    (16, 256, 1, g.PAGED_TILE_KEYS),       # the benchmark's decode step
    (16, 256, 512, 2 * g.PAGED_TILE_KEYS),  # and its chunk
    (8, 6, 1, 48),                          # a table shorter than a tile
    (1024, 4, 1, 1024),                     # a block longer than a tile
])
def test_tile_is_whole_blocks_within_the_table(block_len, width, q_len, keys):
    assert g.paged_tile_keys(block_len, width, q_len) == keys


def test_engine_counts_keys_live_and_keys_read():
    from ray_tpu.llm import EngineConfig, InferenceEngine
    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        intermediate=128, max_seq_len=128, dtype=jnp.float32,
        attention="reference",
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    slots, new = 2, 6
    engine = InferenceEngine(
        params, cfg,
        EngineConfig(slots=slots, max_len=48, prefill_chunk=8,
                     max_new_tokens=new),
        family="tiny",
    )
    try:
        assert engine.stats()["kv_keys_live"] == 0
        assert engine.stats()["kv_keys_read"] == 0
        prompt = list(range(1, 12))
        assert len(list(engine.submit(prompt, max_new_tokens=new))) == new
        once = engine.stats()
        # One row alive: step i attends over len(prompt) + i + 1 keys.
        assert once["kv_keys_live"] == sum(
            len(prompt) + i + 1 for i in range(new)
        )
        # The table (48 keys) is shorter than a tile, so every step
        # walks one tile of 48 keys for each of the two slots.
        tile = g.paged_tile_keys(once["kv_block_len"], 48 // once["kv_block_len"], 1)
        assert tile == 48
        assert once["kv_keys_read"] == new * slots * tile
        list(engine.submit(prompt[:5], max_new_tokens=new))
        twice = engine.stats()
        assert twice["kv_keys_live"] > once["kv_keys_live"]
        assert twice["kv_keys_read"] == 2 * once["kv_keys_read"]
    finally:
        engine.close()


def _engine(**model):
    from ray_tpu.llm import EngineConfig, InferenceEngine
    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
        intermediate=32, max_seq_len=128, dtype=jnp.float32,
        attention="reference", **model,
    )
    return cfg, InferenceEngine(
        init_params(jax.random.PRNGKey(0), cfg), cfg,
        EngineConfig(slots=3, max_len=48, prefill_chunk=8, max_new_tokens=6),
        family="tiny",
    )


def test_engine_adds_up_the_expert_counts_of_a_moe_config():
    cfg, engine = _engine(
        moe_experts=8, moe_top_k=2, moe_router="softmax", qk_norm="proj"
    )
    try:
        assert engine.stats()["moe_picks_prefill"] == 0
        # 11 and 5 tokens: two chunks of 8 and one; 6 steps each, the
        # two rows alive together for as many steps as they overlap.
        streams = [
            engine.submit(list(range(1, n + 1)), max_new_tokens=6)
            for n in (11, 5)
        ]
        assert [len(list(s)) for s in streams] == [6, 6]
        stats = engine.stats()
        chunks, k, layers, experts = 3, cfg.moe_top_k, cfg.n_layers, 8
        # picks = tokens x k x layers: a chunk computes all 8 of its
        # tokens, a step only its live rows (dead slots pick nothing).
        assert stats["moe_picks_prefill"] == chunks * 8 * k * layers
        assert stats["moe_picks_decode"] == stats["tokens_emitted"] * k * layers
        assert stats["tokens_emitted"] == 12
        assert stats["moe_chunk_layers"] == chunks * layers
        assert stats["moe_step_layers"] == stats["steps"] * layers
        # the fullest expert of a chunk holds its even share or more,
        # and never more than the chunk's tokens
        even = 8 * k / experts
        assert even * stats["moe_chunk_layers"] <= stats["moe_chunk_max_load"]
        assert stats["moe_chunk_max_load"] <= 8 * stats["moe_chunk_layers"]
        # 16 picks of a chunk-layer touch 2 experts at least, 8 at most
        assert k * stats["moe_chunk_layers"] <= stats["moe_chunk_experts"]
        assert stats["moe_chunk_experts"] <= experts * stats["moe_chunk_layers"]
        # a step touches at least k experts a layer, at most one a pick
        assert k * stats["moe_step_layers"] <= stats["moe_experts_touched"]
        assert stats["moe_experts_touched"] <= stats["moe_picks_decode"]
    finally:
        engine.close()


def test_a_dense_engine_reports_no_expert_counter():
    _, engine = _engine()
    try:
        assert len(list(engine.submit([1, 2, 3], max_new_tokens=2))) == 2
        assert not [k for k in engine.stats() if k.startswith("moe_")]
        assert "moe_counts" not in engine._kv.pool
    finally:
        engine.close()
