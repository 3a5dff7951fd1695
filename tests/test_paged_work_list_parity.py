"""The paged attention's two walks against a dense float64 oracle:
`_paged_attention` walks a work list of live (row, tile) pairs (ISSUE
40), as many a trip as the forward has rows, and merges each pair's
softmax sums into its row's; a step over a pool the kernel reads in
place walks each row's own tiles (ISSUE 54, ops/paged_attention.py,
interpreted here); the oracle gathers every row's whole table and
takes one masked softmax. One parametrised test, walk x GQA grouping x
scenario (the kernel's cases also: key wider than value, key and value
in one entry, a window, a sink), one that a row's output from the
kernel is bit-equal whatever the other rows hold, and one that the two
programs the engine drives compile once however the live pairs change.

Geometry: blocks of 8 keys, rows to 128 keys (16 table entries), a
decode tile of 32 keys (4 a row), and 64 for a chunk and the kernel."""

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


def dense_oracle(
    q, k_pool, v_pool, layer, tables, q_pos, valid_len, *, scale=None,
    v_width=None, window=0, sink=None,
):
    """Plain attention, a row at a time over its whole table: float64
    scores, one masked softmax; a row that sees no key gives zeros.
    `v_width`: the values are the entries' leading dims; `window`: a
    query sees that many keys, itself the last; `sink` [heads]: a logit
    more in the softmax's sum, with no value."""
    b, heads, t, hd = q.shape
    kv_heads = k_pool.shape[2]
    v_width = v_width or v_pool.shape[-1]
    scale = scale or 1 / np.sqrt(hd)
    out = np.zeros((b, heads, t, v_width))
    k_pos = np.arange(tables.shape[1] * BL)
    for row in range(b):
        # [entries, kvH, bl, hd] -> [kvH, keys, hd]
        k, v = (
            np.asarray(pool, np.float64)[layer, tables[row]]
            .transpose(1, 0, 2, 3).reshape(kv_heads, -1, pool.shape[-1])
            for pool in (k_pool, v_pool)
        )
        for head in range(heads):
            kv = head // (heads // kv_heads)
            s = np.asarray(q, np.float64)[row, head] @ k[kv].T * scale
            seen = (k_pos <= q_pos[row][:, None]) & (k_pos < valid_len[row])
            if window:
                seen &= k_pos > q_pos[row][:, None] - window
            if not seen.any():
                continue
            s = np.where(seen, s, -np.inf)
            top = s.max(axis=-1, keepdims=True)
            if sink is not None:
                top = np.maximum(top, sink[head])
            p = np.exp(s - top)
            total = p.sum(axis=-1, keepdims=True)
            if sink is not None:
                total = total + np.exp(sink[head] - top)
            out[row, head] = (p / total) @ v[kv][:, :v_width]
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


#: A slot admitted again: rows that held long sequences hold short
#: ones, their tables still naming the pages behind them.
SCENARIOS["step_readmitted_slots"] = (
    np.asarray([3, 128, 1, 40, 9, 2, 128, 17] * 2, np.int32)[:, None] - 1,
    np.asarray([3, 128, 1, 40, 9, 2, 128, 17] * 2, np.int32),
    np.asarray([True, True, False, True] * 4),
)
STEPS = sorted(name for name in SCENARIOS if name.startswith("step"))
GROUPINGS = {"gqa8": (8, 1), "gqa4": (8, 2), "mha": (4, 4)}
#: What else a pool the kernel reads can be, at 4 queries a kv head:
#: -> (key width, value width or None for ONE `[v | k]` entry, window,
#: whether every head has a sink).
KINDS = {
    "plain": (HD, HD, 0, False),
    "key_wider_than_value": (2 * HD, HD, 0, False),
    "key_and_value_in_one_entry": (2 * HD, None, 0, False),
    "window": (HD, HD, 40, False),
    "window_and_sink": (HD, HD, 40, True),
    "sink": (HD, HD, 0, True),
}
CASES = [
    # (the work list's, as they were)
    *[("list", grouping, scenario, "plain")
      for grouping in ("gqa8", "mha") for scenario in sorted(SCENARIOS)
      if scenario != "step_readmitted_slots"],
    *[("kernel", grouping, scenario, "plain")
      for grouping in GROUPINGS for scenario in STEPS],
    *[("kernel", "gqa4", "step_dead_rows_between_live_ones", kind)
      for kind in KINDS if kind != "plain"],
]


def _inputs(grouping, scenario, kind, seed=0):
    """-> (q, k_pool, v_pool or None, tables, sink or None)."""
    heads, kv_heads = GROUPINGS[grouping]
    q_pos, _, _ = SCENARIOS[scenario]
    b, t = q_pos.shape
    k_width, v_width, _, has_sink = KINDS[kind]
    rng = np.random.default_rng(b + heads + seed)
    n_blocks = b * WIDTH + 1
    tables = (
        1 + rng.permutation(b * WIDTH).astype(np.int32)
    ).reshape(b, WIDTH)
    q = rng.standard_normal((b, heads, t, k_width)).astype(np.float32)
    if v_width is None:  # the queries behind zeros where the value lies
        q[..., :HD] = 0
    k_pool, v_pool = (
        rng.standard_normal(
            (LAYERS, n_blocks, kv_heads, BL, width)
        ).astype(np.float32) if width else None
        for width in (k_width, v_width)
    )
    sink = rng.standard_normal(heads).astype(np.float32) if has_sink else None
    return q, k_pool, v_pool, tables, sink


def _attend(how, grouping, rows, kind, q, k_pool, v_pool, tables, sink):
    """The walk `how` names over `rows` (a scenario's positions,
    lengths and alive rows), as `_paged_forward` makes its plan and
    `_paged_attend` calls it."""
    heads, kv_heads = GROUPINGS[grouping]
    q_pos, valid_len, alive = rows
    _, v_width, window, _ = KINDS[kind]

    @jax.jit
    def attend(q, k_pool, v_pool, tables, q_pos, valid_len, alive):
        plan = g._paged_plan(
            tables, q_pos, valid_len, alive, k_pool.shape[1], BL,
            heads // kv_heads, in_place=how == "kernel",
        )
        assert ("work" in plan) == (how == "list")
        return g._attend_pages(
            q, k_pool, k_pool if v_pool is None else v_pool, 1, plan,
            scale=HD ** -0.5, v_width=HD if v_pool is None else None,
            window=window, sink=sink,
        )

    return np.asarray(
        attend(q, k_pool, v_pool, tables, q_pos, valid_len, alive)
    )


@pytest.mark.parametrize(
    "how,grouping,scenario,kind", CASES, ids=["-".join(c) for c in CASES]
)
def test_work_list_attention_matches_the_dense_oracle(
    how, grouping, scenario, kind
):
    q_pos, valid_len, alive = SCENARIOS[scenario]
    q, k_pool, v_pool, tables, sink = _inputs(grouping, scenario, kind)
    got = _attend(
        how, grouping, SCENARIOS[scenario], kind, q, k_pool, v_pool, tables,
        sink,
    )
    assert got.shape == (*q.shape[:3], HD) and np.isfinite(got).all()
    want = dense_oracle(
        q, k_pool, k_pool if v_pool is None else v_pool, 1, tables, q_pos,
        valid_len, scale=HD ** -0.5, v_width=HD, window=KINDS[kind][2],
        sink=sink,
    )
    np.testing.assert_allclose(got[alive], want[alive], rtol=2e-5, atol=2e-5)
    # A dead row sees no key, whatever its stale length says.
    assert (got[~alive] == 0).all()


def test_a_rows_output_from_the_kernel_is_its_own_whatever_the_others_hold():
    """The work list puts a row's tiles into trips by what the rows
    before it hold, so its sums are merged in another order when they
    change; the kernel walks a row's tiles alone and in order: bit for
    bit the same output beside other rows, other lengths, dead rows."""
    grouping, scenario, other = "gqa4", "step_ragged_16_alive", (
        "step_readmitted_slots"
    )
    q, k_pool, v_pool, tables, sink = _inputs(grouping, scenario, "plain")
    among = _attend(
        "kernel", grouping, SCENARIOS[scenario], "plain", q, k_pool, v_pool,
        tables, sink,
    )
    row = 9  # 127 keys: two tiles
    q2, _, _, tables2, _ = _inputs(grouping, other, "plain", seed=1)
    q2[row], tables2[row] = q[row], tables[row]
    lengths = SCENARIOS[other][1].copy()
    lengths[row] = SCENARIOS[scenario][1][row]
    alive = np.zeros(16, bool)
    alive[[0, row, 15]] = True
    alone = _attend(
        "kernel", grouping, (lengths[:, None] - 1, lengths, alive), "plain",
        q2, k_pool, v_pool, tables2, sink,
    )
    assert np.abs(among[row]).max() > 0
    assert (alone[row] == among[row]).all()


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
