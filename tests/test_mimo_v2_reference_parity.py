"""A tiny MiMo-V2 (window and full attention layers mixed, each kind
with its own kv heads and rotary base, a sink logit in the window
layers' softmax, keys wider than values, rotary on a part of the head,
a leading dense layer, a sigmoid router over more experts than are
held) with seeded weights: `paged_prefill` in chunks, then
`paged_engine_step`, through the cache object the engine uses (two page
pools, a row's ring of window pages), against the benchmark's plain
reference `benchmark/reference/mimo_v2_ref.py`, in float32. The window
(8 keys) is shorter than a chunk (16) and than an attention tile, and
the ring (7 pages of 4) shorter than most rows, so pages are
overwritten in place while a row runs."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import compare, mimo_v2_ref, weights  # noqa: E402
from ray_tpu.llm.engine import EngineConfig, InferenceEngine  # noqa: E402
from ray_tpu.llm.kv_slots import PagedKVCache  # noqa: E402
from ray_tpu.models import generate as g  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402

WINDOW = 8
FULL, WIN = [0, 2, 5e6, False], [WINDOW, 4, 1e4, True]
MODEL = dict(
    vocab_size=211, dim=64, n_layers=5, n_heads=8, n_kv_heads=2,
    custom_head_dim=24, v_head_dim=16, rotary_dim=8, value_scale=0.707,
    norm_eps=1e-5, intermediate=32, max_seq_len=256,
    layer_kinds=[FULL, WIN, WIN, FULL, WIN],
    moe_experts=4, moe_top_k=4, moe_router="sigmoid_groups",
    moe_router_experts=16, moe_first_expert=0,
    dense_layers=1, dense_intermediate=96,
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
    return cfg, model, weights.make(model, "float32", seed, mimo_v2_ref)


def _cache(cfg):
    return PagedKVCache.for_engine(
        cfg, slots=SLOTS, max_len=MAX_LEN, prefill_chunk=CHUNK,
        kv_block_len=BL, kv_blocks=0,
    )


def _reference(params, model, tokens):
    tokens = jnp.asarray(np.asarray(tokens), jnp.int32)
    return np.asarray(
        mimo_v2_ref.forward(params, tokens, model, q_block=len(tokens))
    )


def _prefill(cfg, params, pool, table, prompt):
    n = len(prompt)
    padded = np.zeros((1, -(-n // CHUNK) * CHUNK), np.int32)
    padded[0, :n] = prompt
    kept = []
    for s in range(0, padded.shape[1], CHUNK):
        logits, pool = g.paged_prefill(
            params, cfg, jnp.asarray(padded[:, s:s + CHUNK]), pool, table,
            np.int32(s), np.int32(s + CHUNK),
        )
        kept.append(np.asarray(logits[0, :min(CHUNK, n - s)]))
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
    "several_chunks_and_a_ring_that_wraps": 70,
    "decode_crosses_the_window": WINDOW - 3,
    "ends_on_a_chunk_boundary": 2 * CHUNK,
    "one_token": 1,
}


@pytest.mark.parametrize("case", sorted(ROWS))
def test_chunked_prefill_then_cached_decode_is_the_references_forward(
    built, case
):
    cfg, model, params = built
    n, steps = ROWS[case], 12
    cache = _cache(cfg)
    assert cache.window.ring * BL < 70 and WINDOW < CHUNK
    prompt = np.random.default_rng(n).integers(1, MODEL["vocab_size"], size=n)
    prompts, alive = [None] * SLOTS, np.zeros(SLOTS, bool)
    prompts[1], alive[1] = prompt, True
    prefill, decoded, tokens, _, _ = _serve(
        cfg, params, cache, prompts, alive, steps
    )
    want = _reference(params, model, np.concatenate([prompt, tokens[:, 1]]))
    assert compare.relative_rms_error(prefill[1], want[:n]) < F32_LIMIT
    # decode step j returns the logits of position n + j
    assert compare.relative_rms_error(
        decoded[:, 1], want[n:n + steps]
    ) < F32_LIMIT


def test_rows_of_mixed_lengths_and_a_dead_row_in_one_step(built):
    cfg, model, params = built
    lengths, steps = [37, 5, 70, 16], 10
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, MODEL["vocab_size"], size=n) for n in lengths]
    alive = np.array([True, True, True, False])
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


def test_a_released_window_page_overwritten_by_another_row_changes_nothing(
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
    second = rng.integers(1, MODEL["vocab_size"], size=61)
    prompts[0], prompts[2] = None, second
    alive[0], alive[2] = False, True
    prefill, decoded, tokens, blocks2, _ = _serve(
        cfg, params, cache, prompts, alive, steps, pool=pool
    )
    # the second row was dealt the first one's pages, junk and all
    assert set(blocks2[2]["window"]) & set(blocks[0]["window"])
    want = _reference(params, model, np.concatenate([second, tokens[:, 2]]))
    assert compare.relative_rms_error(prefill[2], want[:61]) < F32_LIMIT
    assert compare.relative_rms_error(
        decoded[:, 2], want[61:61 + steps]
    ) < F32_LIMIT


@pytest.mark.parametrize("fault", ["no_sink", "no_window", "full_theta"])
def test_a_layer_kind_without_its_own_mathematics_reads_far(built, fault):
    """The sink left out, the window switched off, the window layers
    at the full layers' rotary base: the program's own numbers against
    a reference that has the fault."""
    cfg, model, params = built
    n = 70
    prompt = np.random.default_rng(n).integers(1, MODEL["vocab_size"], size=n)
    prompts, alive = [None] * SLOTS, np.zeros(SLOTS, bool)
    prompts[1], alive[1] = prompt, True
    prefill, *_ = _serve(cfg, params, _cache(cfg), prompts, alive, 1)
    faulty = {
        "no_sink": [WINDOW, 4, 1e4, False],
        "no_window": [10 ** 6, 4, 1e4, True],
        "full_theta": [WINDOW, 4, 5e6, True],
    }[fault]
    changed = dict(model, layer_kinds=[
        faulty if kind[0] else kind for kind in model["layer_kinds"]
    ])
    faulted = dict(params)
    if fault == "no_sink":
        faulted["attn_window"] = {
            k: v for k, v in params["attn_window"].items() if k != "sink"
        }
    assert compare.relative_rms_error(
        prefill[1], _reference(faulted, changed, prompt)
    ) > FAR


def test_each_kind_keeps_its_own_heads_and_keys_wider_than_values(built):
    cfg, _, params = built
    pool = _cache(cfg).pool
    # [the kind's layers, its pool's blocks, ITS kv heads, block, lanes]
    assert pool["k"].shape[0] == 2 and pool["k"].shape[2] == 2
    assert pool["window_k"].shape[0] == 3 and pool["window_k"].shape[2] == 4
    assert pool["window_k"].shape[1] != pool["k"].shape[1]
    full, window = params["attn_full"], params["attn_window"]
    assert full["wk"].shape == (2, 64, 2 * 24)
    assert window["wk"].shape == (3, 64, 4 * 24)
    assert window["wv"].shape == (3, 64, 4 * 16)
    assert params["layers"]["wo"].shape[1:] == (8 * 16, 64)
    assert "sink" in window and "sink" not in full


# -- through the engine: the prefix cache over two pools ---------------

def _engine(cfg, params, **changed):
    settings = dict(
        slots=SLOTS, max_len=MAX_LEN, prefill_chunk=CHUNK, kv_block_len=BL,
        max_new_tokens=8, prefix_cache=True,
    )
    settings.update(changed)
    return InferenceEngine(params, cfg, EngineConfig(**settings))


def _greedy(engine, prompt, n=8):
    return list(engine.submit(list(map(int, prompt)), max_new_tokens=n))


@pytest.mark.parametrize("tail", ["present", "evicted", "partly_evicted"])
def test_a_prefix_hit_equals_the_miss_token_for_token(built, tail):
    """A second question on a document skips the document's whole
    chunks only while the window pool still holds the tail of that
    boundary; where it is gone the engine falls back to a shorter
    boundary whose tail it has (or to a miss), and the tokens are the
    miss's either way."""
    cfg, model, params = built
    rng = np.random.default_rng(17)
    document = rng.integers(1, MODEL["vocab_size"], size=3 * CHUNK + 5)
    ask = [rng.integers(1, MODEL["vocab_size"], size=6) for _ in range(2)]
    miss = _engine(cfg, params, prefix_cache=False)
    try:
        want = [_greedy(miss, np.concatenate([document, q])) for q in ask]
    finally:
        miss.close()
    engine = _engine(cfg, params)
    try:
        assert _greedy(engine, np.concatenate([document, ask[0]])) == want[0]
        window = engine._kv.window
        keys = engine._kv.prefix_keys(list(map(int, document)))
        if tail != "present":
            # evict the last boundary's tail (and for "evicted" every
            # boundary's) as a crowded pool would: oldest first
            gone = {"evicted": 3, "partly_evicted": 1}[tail]
            for boundary in (3 * CHUNK, 2 * CHUNK, CHUNK)[:gone]:
                end = boundary // BL
                pages = window.alloc.match_prefix(
                    keys[end - window.tail_blocks:end]
                )
                for page in pages:
                    key = window.alloc._block_prefix.pop(page)
                    del window.alloc._prefix_to_block[key]
                window.alloc.release(pages)
        before = engine.stats()
        assert _greedy(engine, np.concatenate([document, ask[1]])) == want[1]
        after = engine.stats()
    finally:
        engine.close()
    saved = after["prefix_tokens_saved"] - before["prefix_tokens_saved"]
    full = after["prefix_tokens_full_hit"] - before["prefix_tokens_full_hit"]
    assert full == 3 * CHUNK  # what the full pool alone could skip
    assert saved == {
        "present": 3 * CHUNK, "partly_evicted": 2 * CHUNK, "evicted": 0,
    }[tail]
    # and the reference agrees with what both engines said
    seq = np.concatenate([document, ask[1], want[1]])
    logits = _reference(params, model, seq)
    n = len(document) + len(ask[1])
    assert [int(t) for t in logits[n - 1:-1].argmax(axis=-1)] == want[1]


def test_the_engine_counts_what_the_window_layers_walked(built):
    cfg, _, params = built
    engine = _engine(cfg, params)
    try:
        prompt = np.random.default_rng(2).integers(
            1, MODEL["vocab_size"], size=90
        )
        _greedy(engine, prompt, n=8)
        stats = engine.stats()
    finally:
        engine.close()
    assert 0 < stats["swa_keys_read"] < stats["swa_keys_unwindowed"]
    # 90 + 8 tokens in blocks of 4 through a ring of 7 pages
    assert stats["window_pages_recycled"] == -(-97 // BL) - 7
    assert stats["window_pool_used"] == 7 and stats["full_pool_used"] == 25
    assert stats["window_blocks_used"] == 0  # the ring went back
    # three whole-chunk boundaries... five (16..80), two pages each, kept
    assert stats["window_blocks_cached"] == 5 * engine._kv.window.tail_blocks
    assert stats["kv_keys_read"] >= stats["kv_keys_live"] > 0
