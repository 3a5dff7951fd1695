"""The paged attention's work list (ISSUE 40) against a dense float32
oracle: `_paged_attention` walks live (row, tile) pairs, as many a
trip as the forward has rows, and merges each pair's softmax sums
into its row's; the oracle gathers every row's whole table and takes
one masked softmax. One parametrised test, GQA grouping x scenario,
and one that the two programs the engine drives compile once however
the live pairs change.

Geometry: blocks of 8 keys, rows to 128 keys (16 table entries), a
decode tile of 32 keys (4 a row) and a chunk tile of 64."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import compile_watch
from ray_tpu.models import generate as g
from ray_tpu.models.llama import LlamaConfig, init_params

BL, MAX_LEN, HD, LAYERS = 8, 128, 16, 2
WIDTH = MAX_LEN // BL
TILE = 32

#: 1, a tile's edge, a tile + 1, the table's end, and what lies between.
RAGGED = [1, 32, 33, 128, 31, 64, 65, 96, 97, 127, 2, 100, 50, 128, 1, 77]


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(g, "PAGED_TILE_KEYS", TILE)


def dense_oracle(q, k_pool, v_pool, layer, tables, q_pos, valid_len):
    """Plain attention, a row at a time over its whole table: float64
    scores, one masked softmax; a row that sees no key gives zeros."""
    b, heads, t, hd = q.shape
    kv_heads = k_pool.shape[2]
    out = np.zeros((b, heads, t, hd))
    k_pos = np.arange(tables.shape[1] * BL)
    for row in range(b):
        # [entries, kvH, bl, hd] -> [kvH, keys, hd]
        k, v = (
            np.asarray(pool, np.float64)[layer, tables[row]]
            .transpose(1, 0, 2, 3).reshape(kv_heads, -1, hd)
            for pool in (k_pool, v_pool)
        )
        for head in range(heads):
            kv = head // (heads // kv_heads)
            s = np.asarray(q, np.float64)[row, head] @ k[kv].T / np.sqrt(hd)
            seen = (k_pos <= q_pos[row][:, None]) & (k_pos < valid_len[row])
            if not seen.any():
                continue
            s = np.where(seen, s, -np.inf)
            p = np.exp(s - s.max(axis=-1, keepdims=True))
            out[row, head] = (p / p.sum(axis=-1, keepdims=True)) @ v[kv]
    return out


def step_rows(alive):
    """`t == 1` at `b == 16`: each row's one query at its last key; a
    dead row keeps a stale length and position."""
    valid_len = np.asarray(RAGGED, np.int32)
    return valid_len[:, None] - 1, valid_len, np.asarray(alive, bool)


def chunk_row(offset, t=32):
    """A chunk at `b == 1`: `t` queries from `offset` on, the row valid
    to the chunk's end, as `_paged_prefill_impl` calls it."""
    return (
        offset + np.arange(t, dtype=np.int32)[None],
        np.asarray([offset + t], np.int32),
        np.ones(1, bool),
    )


SCENARIOS = {
    "step_ragged_16_alive": step_rows([True] * 16),
    "step_dead_rows_between_live_ones": step_rows(
        [True, False, False, True, True, False, True, False,
         False, False, True, True, False, False, True, False]
    ),
    "step_every_row_dead": step_rows([False] * 16),
    "chunk_at_0": chunk_row(0),
    "chunk_one_tile_in": chunk_row(64),
    "chunk_at_the_tables_end": chunk_row(MAX_LEN - 32),
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
@pytest.mark.parametrize(
    "heads,kv_heads", [(8, 1), (4, 4)], ids=["gqa8", "mha"]
)
def test_work_list_attention_matches_the_dense_oracle(
    heads, kv_heads, scenario
):
    q_pos, valid_len, alive = SCENARIOS[scenario]
    b, t = q_pos.shape
    rng = np.random.default_rng(b + heads)
    n_blocks = b * WIDTH + 1
    tables = (
        1 + rng.permutation(b * WIDTH).astype(np.int32)
    ).reshape(b, WIDTH)
    q = rng.standard_normal((b, heads, t, HD)).astype(np.float32)
    k_pool, v_pool = (
        rng.standard_normal((LAYERS, n_blocks, kv_heads, BL, HD)).astype(
            np.float32
        )
        for _ in range(2)
    )
    tile = g.paged_tile_keys(BL, WIDTH, t)

    @jax.jit
    def attend(q, k_pool, v_pool, tables, q_pos, valid_len, alive):
        # As `_paged_forward` calls it: the trip count from the stale
        # lengths and `alive`, the list from the masked lengths.
        n_trips = g.paged_tiles_read(valid_len, alive, tile)
        work = g._paged_work_list(
            tables, jnp.tile(q_pos, (1, heads // kv_heads)),
            valid_len * alive, tile // BL, BL, n_blocks,
        )
        return g._paged_attention(q, k_pool, v_pool, 1, work, n_trips)

    got = np.asarray(
        attend(q, k_pool, v_pool, tables, q_pos, valid_len, alive)
    )
    assert got.shape == q.shape and np.isfinite(got).all()
    want = dense_oracle(q, k_pool, v_pool, 1, tables, q_pos, valid_len)
    np.testing.assert_allclose(got[alive], want[alive], rtol=2e-5, atol=2e-5)
    # A dead row sees no key, whatever its stale length says.
    assert (got[~alive] == 0).all()


def test_the_engines_programs_compile_once_whatever_the_live_pairs():
    cfg = LlamaConfig(
        vocab_size=64, dim=32, n_layers=LAYERS, n_heads=4, n_kv_heads=2,
        intermediate=64, max_seq_len=MAX_LEN, dtype=jnp.float32,
        attention="reference",
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    slots, chunk = 4, 32
    tables = 1 + np.arange(slots * WIDTH, dtype=np.int32).reshape(slots, WIDTH)
    pool = g.init_block_pool(cfg, slots * WIDTH + 1, BL)

    def compiles(name):
        return compile_watch.program_stats(name)["compiles"]

    before = {
        name: compiles(name)
        for name in ("generate.paged_prefill", "generate.paged_engine_step")
    }
    # Chunks at other offsets walk 1, 2 and 2 pairs.
    tokens = jnp.ones((1, chunk), jnp.int32)
    cached = []  # each jit's programs after its first call here
    for offset in (0, 32, 96):
        _, pool = g.paged_prefill(
            params, cfg, tokens, pool, jnp.asarray(tables[:1]),
            jnp.int32(offset), jnp.int32(offset + chunk),
        )
        cached.append(g._paged_prefill_jit.wrapped._cache_size())
    # Steps over 4, 2, 1 and no rows alive, from 1 key to the table's
    # end: 0 to 4 trips of 4 pairs.
    last_logits = jnp.zeros((slots, cfg.vocab_size), jnp.float32)
    for alive, positions in [
        ([True] * 4, [0, 31, 32, 126]),
        ([True] * 4, [120, 121, 122, 123]),
        ([False, True, False, True], [5, 60, 90, 100]),
        ([False, False, True, False], [0, 0, 40, 0]),
        ([False] * 4, [9, 9, 9, 9]),
    ]:
        state = {
            "tables": jnp.asarray(tables),
            "positions": jnp.asarray(positions, jnp.int32),
            "alive": jnp.asarray(alive),
            "eos": jnp.full(slots, -1, jnp.int32),
            "budget": jnp.full(slots, 8, jnp.int32),
            "step": jnp.int32(0),
        }
        fetch, pool, last_logits, state = g.paged_engine_step(
            params, cfg, pool, last_logits, state, jax.random.PRNGKey(0)
        )
        assert np.isfinite(np.asarray(last_logits)).all()
        cached.append(g._paged_engine_step_jit.wrapped._cache_size())
    for name, was in before.items():
        assert compiles(name) == was + 1, name
    # The watch counts by argument shapes; the jits' own caches agree.
    assert len(set(cached[:3])) == 1 and len(set(cached[3:])) == 1
