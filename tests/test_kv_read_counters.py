"""How many keys a paged forward attends over (ISSUE 24, ISSUE 40,
ISSUE 54): the rule as a pure function — `paged_row_tiles`, the one
the program's walk (the work list's trip count, the kernel's tiles a
row) and the engine's `kv_keys_read` both call — and the two counters
in `engine.stats()`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import generate as g

TILE, MAX_LEN = 256, 4096


@pytest.mark.parametrize("length,tiles,trips", [
    (0, 0, 0), (1, 1, 1), (TILE, 1, 1), (TILE + 1, 2, 1),
    (MAX_LEN, MAX_LEN // TILE, 5),
])
@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "traced"])
def test_trips_read_are_the_live_pairs_over_the_rows(xp, length, tiles, trips):
    # Two short rows, the row under test, and a dead one whose stale
    # length must add no pair: four rows, so four pairs a trip.
    valid_len = xp.asarray([min(length, 1), length, 0, MAX_LEN], np.int32)
    alive = xp.asarray([True, True, True, False])
    read = g.paged_tiles_read
    if xp is jnp:
        read = jax.jit(read, static_argnums=2)
    # The work list holds the first row's tile (if it has a key) and
    # this row's `tiles`.
    assert int(read(valid_len, alive, TILE)) == trips == -(
        -(min(length, 1) + tiles) // 4
    )
    # One row (`b == 1`, a prefill chunk) walks its own tiles, one a
    # trip.
    assert int(read(valid_len[1:2], alive[1:2], TILE)) == tiles


def test_all_rows_dead_read_nothing():
    valid_len = np.asarray([7, MAX_LEN, 300], np.int32)
    assert int(g.paged_tiles_read(valid_len, np.zeros(3, bool), TILE)) == 0
    # A prefill chunk has no dead row: `alive` defaults to all. 1 + 16
    # + 2 pairs, three a trip.
    assert int(g.paged_tiles_read(valid_len, True, TILE)) == 7


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "traced"])
def test_ragged_rows_among_dead_slots_read_little_more_than_they_hold(xp):
    # `chat_loaded`'s shape: 4 of 16 slots alive, ragged, the dead
    # ones with stale lengths. The walk to the longest alive row (the
    # rule until ISSUE 40) read 16 slots x 6 tiles, 6.5 keys for each
    # live one; the work list holds 6 + 3 + 4 + 3 pairs: one trip.
    valid_len = np.full(16, 3000, np.int32)
    alive = np.zeros(16, bool)
    valid_len[[1, 6, 7, 12]] = [1500, 700, 1000, 600]
    alive[[1, 6, 7, 12]] = True
    live = int(valid_len[alive].sum())
    to_the_longest = 16 * TILE * -(-1500 // TILE)
    assert to_the_longest / live > 6
    trips = int(
        g.paged_tiles_read(xp.asarray(valid_len), xp.asarray(alive), TILE)
    )
    assert trips == 1
    assert 16 * TILE * trips / live < 1.5


@pytest.mark.parametrize("block_len,width,q_len,keys", [
    (16, 256, 1, g.PAGED_TILE_KEYS),       # the benchmark's decode step
    (16, 256, 512, 2 * g.PAGED_TILE_KEYS),  # and its chunk
    (8, 6, 1, 48),                          # a table shorter than a tile
    (1024, 4, 1, 1024),                     # a block longer than a tile
])
def test_tile_is_whole_blocks_within_the_table(block_len, width, q_len, keys):
    assert g.paged_tile_keys(block_len, width, q_len) == keys


@pytest.mark.parametrize("tile_keys,read_tiles", [
    # The table (48 keys) is shorter than a tile: every step is one
    # trip of two pairs (one per slot) of 48 keys.
    (g.PAGED_TILE_KEYS, 6),
    # Tiles of 16 keys: the row's 12..17 keys are 1, 1, 1, 1, 1 and 2
    # pairs, each step still one trip of two (the walk to the longest
    # alive row read 7 tiles a slot).
    (16, 6),
    # Tiles of 8: 2, 2, 2, 2, 2 and 3 pairs: the last step takes two
    # trips.
    (8, 7),
])
def test_engine_counts_keys_live_and_keys_read(
    monkeypatch, tile_keys, read_tiles
):
    from ray_tpu.llm import EngineConfig, InferenceEngine
    from ray_tpu.models.llama import LlamaConfig, init_params

    monkeypatch.setattr(g, "PAGED_TILE_KEYS", tile_keys)
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
        # A program reads a tile for every slot a trip.
        tile = g.paged_tile_keys(once["kv_block_len"], 48 // once["kv_block_len"], 1)
        assert tile == min(tile_keys, 48)
        assert once["kv_keys_read"] == read_tiles * slots * tile
        list(engine.submit(prompt, max_new_tokens=new))
        twice = engine.stats()
        assert twice["kv_keys_live"] == 2 * once["kv_keys_live"]
        assert twice["kv_keys_read"] == 2 * once["kv_keys_read"]
    finally:
        engine.close()


@pytest.mark.parametrize("slots", [16, 64])
@pytest.mark.parametrize("in_place", [True, False], ids=["kernel", "work_list"])
def test_the_programs_walk_and_the_engines_count_are_one_rule(
    monkeypatch, slots, in_place
):
    """What the program walks (its plan, traced) and what the engine
    counts (numpy arrays of the same lengths) both go through
    `paged_row_tiles`, and agree: each alive row's whole tiles where
    the kernel reads the pool in place, a tile for every row a trip of
    the work list's."""
    rng = np.random.default_rng(slots)
    valid_len = rng.integers(1, MAX_LEN + 1, slots).astype(np.int32)
    alive = rng.random(slots) < 0.8
    calls = []
    rule = g.paged_row_tiles

    def counted(valid_len, alive, tile_keys):
        calls.append(type(valid_len))
        return rule(valid_len, alive, tile_keys)

    monkeypatch.setattr(g, "paged_row_tiles", counted)
    block, width = 16, MAX_LEN // 16
    tile = g.paged_tile_keys(block, width, 1, in_place)
    assert tile == (2 * TILE if in_place else TILE)
    plan = jax.jit(
        lambda valid_len, alive: {
            name: value for name, value in g._paged_plan(
                jnp.zeros((slots, width), jnp.int32), valid_len[:, None] - 1,
                valid_len, alive, slots * width + 1, block, 1,
                in_place=in_place,
            ).items() if name in ("tiles", "n_trips")
        }
    )(valid_len, alive)
    traced = len(calls)
    assert traced == 1 and calls[0] is not np.ndarray
    if in_place:
        walked = tile * int(plan["tiles"].sum())
        assert (np.asarray(plan["tiles"])[~alive] == 0).all()
    else:
        walked = slots * tile * int(plan["n_trips"])
    assert int(g.paged_keys_read(valid_len, alive, tile, in_place)) == walked
    assert len(calls) == traced + 1 and calls[-1] is np.ndarray
    live = int(valid_len[alive].sum())
    assert live <= walked < live + (alive.sum() + slots * (not in_place)) * tile


def test_engine_counts_each_alive_rows_own_tiles_where_the_kernel_reads(
    monkeypatch,
):
    """A pool with the kv-head axis and entries of whole lanes: the
    step's attention is the kernel's, a row at a time, and the engine
    counts an alive row's tiles and nothing for the other slot. Tiles
    of 16 keys: the row's 12..17 keys are 1, 1, 1, 1, 1 and 2."""
    from decode_oracle import greedy_uncached
    from ray_tpu.llm import EngineConfig, InferenceEngine
    from ray_tpu.models.llama import LlamaConfig, init_params

    monkeypatch.setattr(g, "PAGED_TILE_KEYS", 8)
    cfg = LlamaConfig(
        vocab_size=128, dim=256, n_layers=2, n_heads=2, n_kv_heads=1,
        intermediate=128, max_seq_len=128, dtype=jnp.float32,
        attention="reference",
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    new = 6
    engine = InferenceEngine(
        params, cfg,
        EngineConfig(slots=2, max_len=48, prefill_chunk=8, max_new_tokens=new),
        family="tiny",
    )
    try:
        assert g.step_reads_in_place(cfg, engine._kv.pool) == {"full": True}
        prompt = list(range(1, 12))
        got = list(engine.submit(prompt, max_new_tokens=new))
        assert got == greedy_uncached(params, cfg, prompt, new)
        stats = engine.stats()
        assert stats["kv_keys_live"] == sum(
            len(prompt) + i + 1 for i in range(new)
        )
        assert stats["kv_keys_read"] == 7 * 16
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


@pytest.mark.parametrize("router", ["even", "skewed"])
def test_engine_counts_the_expert_forwards_that_passed_their_row_budget(router):
    """One rank's share of an expert layer computes `held_row_budget`
    rows of a forward's picks and, where more picks than that meet a
    held expert, every row (ops/moe.py): `moe_forwards_spilled` counts
    the expert layers' forwards that did. A chunk of 256 tokens x 4
    picks with 4 of 16 experts held has a budget of 512 rows of 1,024:
    a drawn router deals the held experts a quarter of the picks; a
    bias on the held experts deals them every pick."""
    from ray_tpu.llm import EngineConfig, InferenceEngine
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.ops.moe import held_row_budget

    full = [0, 2, 1e4, False]
    cfg = LlamaConfig(
        vocab_size=128, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        custom_head_dim=16, intermediate=16, max_seq_len=512,
        dtype=jnp.float32, layer_kinds=[full, full], moe_experts=4,
        moe_top_k=4, moe_router="sigmoid_groups", moe_router_experts=16,
        moe_first_expert=4,
    )
    chunk, new = 256, 3
    assert held_row_budget(chunk * 4, 4, 16) == 512
    # (a step's picks are fewer than the round number: no budget)
    assert held_row_budget(2 * 4, 4, 16) == 2 * 4
    params = init_params(jax.random.PRNGKey(0), cfg)
    if router == "skewed":
        bias = jnp.zeros((2, 16)).at[:, 4:8].set(10.0)
        params["layers"] = dict(params["layers"], router_bias=bias)
    engine = InferenceEngine(
        params, cfg,
        EngineConfig(slots=2, max_len=512, prefill_chunk=chunk,
                     max_new_tokens=new),
        family="tiny",
    )
    try:
        assert engine.stats()["moe_forwards_spilled"] == 0
        prompt = np.random.default_rng(3).integers(1, 128, size=chunk).tolist()
        assert len(list(engine.submit(prompt, max_new_tokens=new))) == new
        stats = engine.stats()
    finally:
        engine.close()
    assert stats["moe_chunk_layers"] == 2  # one chunk, two expert layers
    assert stats["moe_picks_routed"] == 2 * 4 * (chunk + new)
    if router == "skewed":
        assert stats["moe_picks_prefill"] == 2 * 4 * chunk  # every pick held
        assert stats["moe_forwards_spilled"] == 2  # the chunk's two layers
    else:
        assert stats["moe_picks_prefill"] < 2 * 512
        assert stats["moe_forwards_spilled"] == 0
