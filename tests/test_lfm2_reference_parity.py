"""A tiny LFM2-MoE (gated short convolutions among attention layers in
the published pattern conv, conv, attention, conv; a q/k norm a head;
two leading dense layers; a sigmoid router over more experts than are
held) with seeded weights: `paged_prefill` in chunks, then
`paged_engine_step`, through the cache object the engine uses (pages
for the attention layers, a STATE slot a row for the conv layers),
against the benchmark's plain reference
`benchmark/reference/lfm2_moe_ref.py`, in float32; then the engine
itself, with prefix hits that start from a snapshot of the state."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import compare, lfm2_moe_ref, weights  # noqa: E402
from ray_tpu.llm.engine import EngineConfig, InferenceEngine  # noqa: E402
from ray_tpu.llm.kv_slots import PagedKVCache, chunk_shapes  # noqa: E402
from ray_tpu.models import generate as g  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402

ATTN, CONV = [0, 2, 1e6, False], [0, 0, 0, False, 3]
MODEL = dict(
    vocab_size=211, dim=64, n_layers=12, n_heads=8, n_kv_heads=2,
    norm_eps=1e-5, rope_theta=1e6, qk_norm="head", intermediate=32,
    max_seq_len=256, layer_kinds=[CONV, CONV, ATTN, CONV] * 3,
    moe_experts=4, moe_top_k=4, moe_router="sigmoid_groups",
    moe_router_experts=16, moe_first_expert=0,
    dense_layers=2, dense_intermediate=96,
)
BL, CHUNK, SLOTS, MAX_LEN = 4, 16, 4, 128
#: float32 on both sides: the program and the reference differ by
#: summation order alone.
F32_LIMIT = 1e-5
#: what a missing or altered piece of the mathematics has to read
FAR = 100 * F32_LIMIT


def _build(seed=5, **changed):
    model = dict(MODEL, **changed)
    cfg = LlamaConfig(**model, dtype=jnp.float32)
    return cfg, model, weights.make(model, "float32", seed, lfm2_moe_ref)


def _cache(cfg):
    return PagedKVCache.for_engine(
        cfg, slots=SLOTS, max_len=MAX_LEN, prefill_chunk=CHUNK,
        kv_block_len=BL, kv_blocks=0,
    )


def _reference(params, model, tokens):
    tokens = jnp.asarray(np.asarray(tokens), jnp.int32)
    return np.asarray(
        lfm2_moe_ref.forward(params, tokens, model, q_block=len(tokens))
    )


def _prefill(cfg, params, pool, table, prompt, shapes=None):
    """`prompt` through `paged_prefill`, chunk after chunk as the probe
    feeds it (whole chunks, the last padded with 0, no length given),
    or in chunks of `shapes`, the last padded."""
    n = len(prompt)
    shapes = shapes or [CHUNK] * -(-n // CHUNK)
    assert sum(shapes) >= n > sum(shapes[:-1])
    padded = np.zeros((1, sum(shapes)), np.int32)
    padded[0, :n] = prompt
    kept, s = [], 0
    for shape in shapes:
        logits, pool = g.paged_prefill(
            params, cfg, jnp.asarray(padded[:, s:s + shape]), pool, table,
            np.int32(s), np.int32(s + shape),
        )
        kept.append(np.asarray(logits[0, :min(shape, n - s)]))
        s += shape
    return np.concatenate(kept), pool


def _serve(cfg, params, cache, prompts, alive, steps, pool=None):
    """Slot r holds `prompts[r]` (None: no row), fed in chunks; then
    `steps` steps over all slots with `alive`. -> (prefill logits a
    row, decode logits [steps, slots, vocab], tokens [steps, slots],
    the rows' blocks, the pool)."""
    pool = cache.pool if pool is None else pool
    blocks, prefill = [], []
    last = jnp.zeros((SLOTS, cfg.vocab_size), jnp.float32)
    positions = np.zeros(SLOTS, np.int32)
    for row, prompt in enumerate(prompts):
        if prompt is None:
            blocks.append(None)
            prefill.append(None)
            continue
        blocks.append(
            cache.alloc.reserve(cache.blocks_for(len(prompt) + steps))
        )
        logits, pool = _prefill(
            cfg, params, pool, cache.row_table(row, blocks[row]), prompt
        )
        prefill.append(logits)
        last = last.at[row].set(logits[-1])
        positions[row] = len(prompt)
    state = cache.step_state(
        blocks, positions, alive, np.full(SLOTS, -1, np.int32),
        np.full(SLOTS, steps + 1, np.int32), 0,
    )
    tokens, decoded = [], []
    for _ in range(steps):
        fetch, pool, last, state = g.paged_engine_step(
            params, cfg, pool, last, state, jax.random.PRNGKey(0),
            temperature=0.0, top_k=0,
        )
        tokens.append(np.asarray(fetch["token"]))
        decoded.append(np.asarray(last))
    return prefill, np.stack(decoded), np.stack(tokens), blocks, pool


@pytest.fixture(scope="module")
def built():
    return _build()


#: a row's length -> what it exercises
ROWS = {
    "several_chunks_and_a_padded_last": 3 * CHUNK + 5,
    "shorter_than_a_chunk": 7,
    "a_few_tokens_past_a_chunk_boundary": CHUNK + 2,
    "ends_on_a_chunk_boundary": 2 * CHUNK,
    "one_token": 1,
    "two_tokens": 2,
}


@pytest.mark.parametrize("case", sorted(ROWS))
def test_chunked_prefill_then_stepped_decode_is_the_references_forward(
    built, case
):
    cfg, model, params = built
    n, steps = ROWS[case], 16
    prompt = np.random.default_rng(n).integers(1, MODEL["vocab_size"], size=n)
    prompts, alive = [None] * SLOTS, np.zeros(SLOTS, bool)
    prompts[1], alive[1] = prompt, True
    prefill, decoded, tokens, _, _ = _serve(
        cfg, params, _cache(cfg), prompts, alive, steps
    )
    want = _reference(params, model, np.concatenate([prompt, tokens[:, 1]]))
    assert compare.relative_rms_error(prefill[1], want[:n]) < F32_LIMIT
    # decode step j returns the logits of position n + j
    assert compare.relative_rms_error(
        decoded[:, 1], want[n:n + steps]
    ) < F32_LIMIT


@pytest.mark.parametrize("last", chunk_shapes(CHUNK, BL))
@pytest.mark.parametrize("given", ["length_given", "by_the_tokens"])
def test_a_padded_last_chunk_of_each_shape_leaves_the_state_at_the_last_token(
    built, last, given
):
    """A prompt of a whole chunk and a last chunk of `last` positions of
    which the final two are padding: the state a step starts from is
    the columns at the prompt's last token, whether the caller gives the
    row's length (the engine) or pads with 0 and gives none (the
    probe)."""
    cfg, model, params = built
    cache = _cache(cfg)
    assert chunk_shapes(CHUNK, BL) == (4, 8, 16)
    n = CHUNK + last - 2
    prompt = np.random.default_rng(last).integers(1, MODEL["vocab_size"], size=n)
    blocks = cache.alloc.reserve(cache.blocks_for(n + 4))
    length = n if given == "length_given" else -1
    padded = np.zeros((1, CHUNK + last), np.int32)
    padded[0, :n] = prompt
    if given == "length_given":
        padded[0, n:] = 7  # (what pads the chunk is then not looked at)
    pool, kept = cache.pool, []
    for start, shape in ((0, CHUNK), (CHUNK, last)):
        logits, pool = g.paged_prefill(
            params, cfg, jnp.asarray(padded[:, start:start + shape]), pool,
            cache.row_table(0, blocks, length=min(length, start + shape)),
            np.int32(start), np.int32(start + shape),
        )
        kept.append(np.asarray(logits[0]))
    logits = np.concatenate(kept)[:n]
    rows = [blocks] + [None] * (SLOTS - 1)
    state = cache.step_state(
        rows, np.array([n, 0, 0, 0], np.int32), np.arange(SLOTS) == 0,
        np.full(SLOTS, -1, np.int32), np.full(SLOTS, 9, np.int32), 0,
    )
    last_logits = jnp.zeros((SLOTS, cfg.vocab_size)).at[0].set(logits[-1])
    decoded, tokens = [], []
    for _ in range(4):
        fetch, pool, last_logits, state = g.paged_engine_step(
            params, cfg, pool, last_logits, state, jax.random.PRNGKey(0),
            temperature=0.0, top_k=0,
        )
        tokens.append(int(fetch["token"][0]))
        decoded.append(np.asarray(last_logits[0]))
    want = _reference(params, model, np.concatenate([prompt, tokens]))
    assert compare.relative_rms_error(logits, want[:n]) < F32_LIMIT
    assert compare.relative_rms_error(np.stack(decoded), want[n:]) < F32_LIMIT


def test_every_slot_alive_for_16_steps_and_a_dead_row_beside_them(built):
    cfg, model, params = built
    lengths, steps = [37, 5, 2 * CHUNK + 1, 16], 16
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, MODEL["vocab_size"], size=n) for n in lengths]
    for alive in (np.ones(SLOTS, bool), np.array([True, True, True, False])):
        prefill, decoded, tokens, _, _ = _serve(
            cfg, params, _cache(cfg), prompts, alive, steps
        )
        for row, prompt in enumerate(prompts):
            n = len(prompt)
            seq = np.concatenate([prompt, tokens[:, row]]) if alive[row] else prompt
            want = _reference(params, model, seq)
            assert compare.relative_rms_error(prefill[row], want[:n]) < F32_LIMIT
            if alive[row]:
                assert compare.relative_rms_error(
                    decoded[:, row], want[n:n + steps]
                ) < F32_LIMIT
            else:
                assert not tokens[:, row].any()  # a dead row emits nothing


def test_a_slot_given_to_a_shorter_row_after_a_longer_one_holds_no_stale_state(
    built
):
    cfg, model, params = built
    cache, steps = _cache(cfg), 6
    rng = np.random.default_rng(11)
    first = rng.integers(1, MODEL["vocab_size"], size=50)
    prompts, alive = [None] * SLOTS, np.zeros(SLOTS, bool)
    prompts[0], alive[0] = first, True
    *_, blocks, pool = _serve(cfg, params, cache, prompts, alive, steps)
    cache.alloc.release(blocks[0])
    second = rng.integers(1, MODEL["vocab_size"], size=9)
    prompts[0], prompts[2] = None, second
    alive[0], alive[2] = False, True
    prefill, decoded, tokens, blocks2, pool = _serve(
        cfg, params, cache, prompts, alive, steps, pool=pool
    )
    # the second row was dealt the first one's state slot, junk and all
    assert blocks2[2]["state"] == blocks[0]["state"]
    assert np.abs(np.asarray(pool["conv_state"][:, :, blocks2[2]["state"][0]])).max() > 0
    want = _reference(params, model, np.concatenate([second, tokens[:, 2]]))
    assert compare.relative_rms_error(prefill[2], want[:9]) < F32_LIMIT
    assert compare.relative_rms_error(decoded[:, 2], want[9:9 + steps]) < F32_LIMIT


def test_a_plain_table_and_the_cache_objects_give_the_same_bits(built):
    """The caller that keeps one id space (`init_block_pool` with a
    plain number, a `[1, width]` table: the benchmark's hand-built
    oracle) reads its row's state slot off its table's first block."""
    cfg, _, params = built
    prompt = np.random.default_rng(8).integers(1, MODEL["vocab_size"], size=41)
    cache = _cache(cfg)
    blocks = cache.alloc.reserve(cache.blocks_for(41))
    mine, _ = _prefill(cfg, params, cache.pool, cache.row_table(0, blocks), prompt)
    pool = g.init_block_pool(cfg, 40, BL)
    assert pool["conv_state"].shape == (9, 2, 40, 64)
    table = np.zeros((1, MAX_LEN // BL), np.int32)
    table[0, :11] = np.arange(5, 16)
    plain, pool = _prefill(cfg, params, pool, jnp.asarray(table), prompt)
    assert np.array_equal(mine, plain)
    held = np.asarray(pool["conv_state"])
    assert np.abs(held[:, :, 5]).max() > 0 and not held[:, :, 6:].any()


def _faulty_plan(fault):
    sound = g._state_plan

    def plan(table, tokens, q_pos):
        out = sound(table, tokens, q_pos)
        if fault == "every_chunk_starts_from_zeros":
            if tokens.shape[1] > 1:
                out["fresh"] = jnp.ones_like(out["fresh"])
        elif tokens.shape[1] == 1:  # the step leaves the state as it was
            out["write"] = jnp.zeros_like(out["write"])
        return out

    return plan


@pytest.mark.parametrize("fault", [
    "every_chunk_starts_from_zeros", "the_step_does_not_advance_the_state",
    "no_expert_bias",
])
def test_a_conv_layer_or_a_router_without_its_own_mathematics_reads_far(
    built, fault, monkeypatch
):
    """The state not carried over a chunk boundary, the state not
    advanced by a step (every decoded position convolves the prompt's
    last two columns), the bias left out of the router's choice: each
    reads far from the reference where the sound program reads float32
    rounding."""
    cfg, model, params = built
    n, steps = 3 * CHUNK + 5, 8
    prompt = np.random.default_rng(n).integers(1, MODEL["vocab_size"], size=n)
    prompts, alive = [None] * SLOTS, np.zeros(SLOTS, bool)
    prompts[1], alive[1] = prompt, True
    served = params
    if fault == "no_expert_bias":
        served = dict(params, layers=dict(
            params["layers"],
            router_bias=jnp.zeros_like(params["layers"]["router_bias"]),
        ))
    else:
        monkeypatch.setattr(g, "_state_plan", _faulty_plan(fault))
        jax.clear_caches()  # (the programs are traced anew, with the fault)
    try:
        prefill, decoded, tokens, _, _ = _serve(
            cfg, served, _cache(cfg), prompts, alive, steps
        )
    finally:
        monkeypatch.undo()
        if served is params:
            jax.clear_caches()
    want = _reference(params, model, np.concatenate([prompt, tokens[:, 1]]))
    errors = (
        compare.relative_rms_error(prefill[1], want[:n]),
        compare.relative_rms_error(decoded[:, 1], want[n:n + steps]),
    )
    if fault == "the_step_does_not_advance_the_state":
        assert errors[0] < F32_LIMIT  # the chunks are sound
        assert errors[1] > FAR
    else:
        assert errors[0] > FAR


def test_the_tree_and_the_pool_are_the_kinds_own(built):
    cfg, _, params = built
    pool = _cache(cfg).pool
    # 3 attention layers: a head's key and value in one 128-wide entry
    assert pool["kv"].shape == (3, SLOTS * MAX_LEN // BL + 1, 2, BL, 128)
    assert "k" not in pool and "v" not in pool
    # 9 conv layers: a row's two columns, a slot a row and a snapshot a
    # whole chunk of the pool, and the null slot
    slots = SLOTS + (SLOTS * MAX_LEN // BL + 1) * BL // CHUNK + 1
    assert slots == 37  # held in whole tiles of 16 rows
    assert pool["conv_state"].shape == (9, 2, 48, 64)
    assert params["layers"]["wq"].shape == (10, 64, 64)
    assert params["dense_layers"]["wq"].shape == (2, 64, 64)
    assert params["attn_conv"]["taps"].shape == (9, 3, 64)
    assert params["attn_full"]["q_norm"].shape == (3, 8)
    assert cfg.num_params() == sum(
        leaf.size for leaf in jax.tree.leaves(params)
    )
    for what in ("the training layout", "the training forward"):
        with pytest.raises(NotImplementedError, match="serve path only"):
            cfg.require_plain_attention(what)


def test_the_scan_covers_the_whole_periods_and_counts_every_expert_layer(built):
    """Ten expert layers in the pattern attention, conv, conv, conv: two
    whole periods scanned and two layers unrolled behind them, the
    counters an entry a layer in the layers' order."""
    cfg, _, params = built
    cache = _cache(cfg)
    blocks = cache.alloc.reserve(cache.blocks_for(CHUNK))
    tokens = jnp.asarray(np.arange(1, CHUNK + 1)[None], jnp.int32)
    text = jax.jit(
        lambda p, pool, table: g._paged_forward(
            p, cfg, tokens, pool, table, jnp.arange(CHUNK)[None],
            jnp.full((1,), CHUNK),
        )
    ).lower(params, cache.pool, cache.row_table(0, blocks)).as_text()
    # one body a stack with whole periods (the dense layers' and the
    # expert layers'), beside the attention's own loops
    assert text.count("stablehlo.while") >= 2
    _, pool = g.paged_prefill(
        params, cfg, tokens, cache.pool, cache.row_table(0, blocks),
        np.int32(0), np.int32(CHUNK),
    )
    counts = np.asarray(pool["moe_counts"])
    assert counts.shape == (10, 4) and (counts.sum(axis=1) > 0).all()
    assert np.asarray(pool["moe_routed"]).tolist() == [CHUNK * 4] * 10


# -- through the engine: prefix hits that start from a snapshot ---------

def _engine(cfg, params, **changed):
    settings = dict(
        slots=SLOTS, max_len=MAX_LEN, prefill_chunk=CHUNK, kv_block_len=BL,
        max_new_tokens=8, prefix_cache=True,
    )
    settings.update(changed)
    return InferenceEngine(params, cfg, EngineConfig(**settings))


def _greedy(engine, prompt, n=8):
    return list(engine.submit(list(map(int, prompt)), max_new_tokens=n))


@pytest.mark.parametrize("snapshot", ["present", "evicted", "partly_evicted"])
def test_a_prefix_hit_equals_the_miss_token_for_token(built, snapshot):
    """A second question on a document skips the document's whole
    chunks only while the snapshot of the conv layers' state at that
    boundary is still held; where it is gone the engine falls back to a
    shorter boundary that has one (or to a miss), and the tokens are
    the miss's either way."""
    cfg, model, params = built
    rng = np.random.default_rng(17)
    document = rng.integers(1, MODEL["vocab_size"], size=3 * CHUNK + 5)
    ask = [rng.integers(1, MODEL["vocab_size"], size=6) for _ in range(2)]
    miss = _engine(cfg, params, prefix_cache=False)
    try:
        want = [_greedy(miss, np.concatenate([document, q])) for q in ask]
        assert miss.stats()["conv_snapshots_written"] == 0
    finally:
        miss.close()
    engine = _engine(cfg, params)
    try:
        assert _greedy(engine, np.concatenate([document, ask[0]])) == want[0]
        state = engine._kv.state
        assert engine.stats()["conv_snapshots_written"] == 3
        keys = engine._kv.prefix_keys(list(map(int, document)))
        if snapshot != "present":
            # evict the last boundary's snapshot (and for "evicted"
            # every boundary's) as a crowded pool would
            gone = {"evicted": 3, "partly_evicted": 1}[snapshot]
            for boundary in (3 * CHUNK, 2 * CHUNK, CHUNK)[:gone]:
                held = state.alloc.match_prefix([keys[boundary // BL - 1]])
                key = state.alloc._block_prefix.pop(held[0])
                del state.alloc._prefix_to_block[key]
                state.alloc.release(held)
        before = engine.stats()
        assert _greedy(engine, np.concatenate([document, ask[1]])) == want[1]
        after = engine.stats()
    finally:
        engine.close()
    saved = after["prefix_tokens_saved"] - before["prefix_tokens_saved"]
    full = after["prefix_tokens_full_hit"] - before["prefix_tokens_full_hit"]
    assert full == 3 * CHUNK  # what the pages alone could skip
    assert saved == {
        "present": 3 * CHUNK, "partly_evicted": 2 * CHUNK, "evicted": 0,
    }[snapshot]
    restored = after["conv_hits_restored"] - before["conv_hits_restored"]
    assert restored == (snapshot != "evicted")
    # and the reference agrees with what both engines said
    seq = np.concatenate([document, ask[1], want[1]])
    logits = _reference(params, model, seq)
    n = len(document) + len(ask[1])
    assert [int(t) for t in logits[n - 1:-1].argmax(axis=-1)] == want[1]


def test_a_chunk_that_starts_from_a_snapshot_gives_the_cold_runs_bits(built):
    """The same chunk over the same positions, once behind the chunks
    before it and once from their snapshot in another row's slot: the
    logits are equal bit for bit (the chunks are the same program)."""
    cfg, _, params = built
    cache = _cache(cfg)
    prompt = np.random.default_rng(23).integers(1, MODEL["vocab_size"], size=3 * CHUNK)
    tokens = jnp.asarray(prompt[None], jnp.int32)
    cold = cache.alloc.reserve(cache.blocks_for(len(prompt)))
    keys = cache.prefix_keys(list(map(int, prompt)))
    pool, logits = cache.pool, []
    for start in range(0, 3 * CHUNK, CHUNK):
        snapshot = cache.state.keep(keys, start + CHUNK)
        assert snapshot not in (0, cold["state"][0])
        out, pool = g.paged_prefill(
            params, cfg, tokens[:, start:start + CHUNK], pool,
            cache.row_table(0, cold, snapshot=snapshot, length=start + CHUNK),
            np.int32(start), np.int32(start + CHUNK),
        )
        logits.append(np.asarray(out))
    cache.publish(cold, 0, keys)
    # a second row over the same prompt: its pages are the first row's
    # first two chunks', its state the snapshot at 2 x CHUNK
    shared = cache.full.match_prefix(keys)[:2 * CHUNK // BL]
    own, read = cache.state.admit(keys, 2 * CHUNK)
    assert read not in (None, own[0], cold["state"][0])
    hit = {
        "full": shared + cache.full.reserve(CHUNK // BL), "state": own,
    }
    out, pool = g.paged_prefill(
        params, cfg, tokens[:, 2 * CHUNK:], pool,
        cache.row_table(1, hit, read=read, length=3 * CHUNK),
        np.int32(2 * CHUNK), np.int32(3 * CHUNK),
    )
    assert np.array_equal(np.asarray(out), logits[2])
    held = np.asarray(pool["conv_state"])
    assert np.array_equal(held[:, :, own[0]], held[:, :, cold["state"][0]])


def test_the_engine_readmits_a_slot_and_counts_its_state(built):
    cfg, model, params = built
    engine = _engine(cfg, params, slots=2)
    rng = np.random.default_rng(2)
    try:
        for n in (90, 9, 33, 5):  # two slots: every row after the second re-uses one
            prompt = rng.integers(1, MODEL["vocab_size"], size=n)
            got = _greedy(engine, prompt, n=8)
            logits = _reference(params, model, np.concatenate([prompt, got]))
            assert [int(t) for t in logits[n - 1:-1].argmax(axis=-1)] == got
        stats = engine.stats()
    finally:
        engine.close()
    # 90 tokens pass five whole chunks, 33 two: a snapshot each
    assert stats["conv_snapshots_written"] == 7
    slot_bytes = 9 * 2 * 64 * 4
    assert stats["conv_state_bytes_in_use"] == 7 * slot_bytes  # no row left
    assert stats["kv_bytes_in_use"] == stats["kv_blocks_cached"] * (
        3 * 2 * BL * 128 * 4
    )
    assert stats["conv_state_slots_used"] == 0
