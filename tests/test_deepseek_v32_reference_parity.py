"""A tiny DeepSeek-V3.2 (latent attention, an indexer's top-k selection
of keys, sigmoid group-limited router over more experts than are held,
a shared expert, a leading dense layer) with seeded weights:
`paged_prefill` in chunks, then `paged_engine_step` through the latent
cache, against the benchmark's plain reference
`benchmark/reference/deepseek_v32_ref.py`, in float32. Rows are several
times `index_topk` long, so most keys of a query are NOT selected, and
every negative below reads far outside the tolerance."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import compare, deepseek_v32_ref, weights  # noqa: E402
from ray_tpu.models import generate as g  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402

MODEL = dict(
    vocab_size=211, dim=64, n_layers=3, n_heads=4, n_kv_heads=4,
    intermediate=32, rope_theta=10000.0, max_seq_len=256, norm_eps=1e-6,
    rope_scaling=["yarn", 40, 1, 32, 64],
    kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16,
    index_topk=16, index_n_heads=16, index_head_dim=16,
    moe_experts=4, moe_top_k=4, moe_router="sigmoid_groups",
    moe_router_experts=16, moe_first_expert=0, moe_groups=4,
    moe_top_groups=2, moe_route_scale=2.5, moe_shared_intermediate=32,
    dense_layers=1, dense_intermediate=96,
)
BL, CHUNK, SLOTS = 8, 32, 4
WIDTH = MODEL["max_seq_len"] // BL
#: float32: the program and the reference differ by summation order.
F32_LIMIT = 1e-4
#: what a missing or altered piece of the mathematics has to read
FAR = 100 * F32_LIMIT


def _build(seed=5, **changed):
    model = dict(MODEL, **changed)
    cfg = LlamaConfig(**model, dtype=jnp.float32)
    return cfg, model, weights.make(model, "float32", seed, deepseek_v32_ref)


def _reference(params, model, tokens, pad=128):
    fed = np.zeros(-(-len(tokens) // pad) * pad, np.int32)
    fed[:len(tokens)] = tokens
    return np.asarray(
        deepseek_v32_ref.forward(params, jnp.asarray(fed), model)
    )[:len(tokens)]


def _prefill(cfg, params, pool, table, prompt, start=0):
    """The prompt's chunks from `start` on, as the engine walks them:
    -> (logits of positions start.., pool)."""
    n = len(prompt)
    padded = np.zeros((1, -(-n // CHUNK) * CHUNK), np.int32)
    padded[0, :n] = prompt
    kept = []
    for s in range(start, padded.shape[1], CHUNK):
        logits, pool = g.paged_prefill(
            params, cfg, jnp.asarray(padded[:, s:s + CHUNK]), pool,
            jnp.asarray(table), np.int32(s), np.int32(s + CHUNK),
        )
        kept.append(np.asarray(logits[0, :min(CHUNK, n - s)]))
    return np.concatenate(kept), pool


def _table(first_block, tokens):
    table = np.zeros((1, WIDTH), np.int32)
    need = -(-tokens // BL)
    table[0, :need] = np.arange(first_block, first_block + need)
    return table, first_block + need


def _run(cfg, params, prompt, steps=6):
    """-> (prefill logits [n, vocab], decode logits [steps, vocab],
    the sequence with its greedy tokens, the counters of every forward)."""
    n = len(prompt)
    pool = g.init_block_pool(cfg, 64, BL)
    table, _ = _table(1, n + steps)
    prefill, pool = _prefill(cfg, params, pool, table, prompt)
    counted = [{k: np.asarray(pool[k]) for k in g.COUNTER_LEAVES if k in pool}]
    tables = np.zeros((SLOTS, WIDTH), np.int32)
    tables[1] = table[0]
    state = {
        "tables": jnp.asarray(tables),
        "positions": jnp.asarray(np.array([0, n, 0, 0], np.int32)),
        "alive": jnp.asarray([False, True, False, False]),
        "eos": jnp.full(SLOTS, -1, jnp.int32),
        "budget": jnp.full(SLOTS, steps + 1, jnp.int32),
        "step": jnp.zeros((), jnp.int32),
    }
    last = jnp.zeros((SLOTS, cfg.vocab_size), jnp.float32).at[1].set(prefill[-1])
    seq, decoded = list(prompt), []
    for _ in range(steps):
        fetch, pool, last, state = g.paged_engine_step(
            params, cfg, pool, last, state, jax.random.PRNGKey(0),
            temperature=0.0, top_k=0,
        )
        seq.append(int(fetch["token"][1]))
        decoded.append(np.asarray(last[1]))
        counted.append(
            {k: np.asarray(fetch[k]) for k in g.COUNTER_LEAVES if k in fetch}
        )
    return prefill, np.stack(decoded), seq, counted


@pytest.fixture(scope="module")
def sound():
    cfg, model, params = _build()
    prompt = np.random.default_rng(0).integers(1, MODEL["vocab_size"], size=70)
    prefill, decoded, seq, counted = _run(cfg, params, prompt)
    return dict(
        cfg=cfg, model=model, params=params, prompt=prompt, seq=seq,
        prefill=prefill, decoded=decoded, counted=counted,
        want=_reference(params, model, seq),
    )


def test_chunked_prefill_and_cached_decode_are_the_references_forward(sound):
    n = len(sound["prompt"])
    assert n > 4 * MODEL["index_topk"] and n > 2 * CHUNK  # three chunks
    assert compare.relative_rms_error(
        sound["prefill"], sound["want"][:n]
    ) < F32_LIMIT
    # decode step j returns the logits of position n + j
    assert compare.relative_rms_error(
        sound["decoded"], sound["want"][n:n + len(sound["decoded"])]
    ) < F32_LIMIT


def test_the_program_counts_the_pairs_it_saw_and_kept_and_the_picks(sound):
    n, k = len(sound["prompt"]), MODEL["index_topk"]
    chunks, *steps = sound["counted"]
    # the last chunk: positions 64..95 of which 70.. are padding; every
    # one of its 32 queries sees its own position's keys, keeps 16
    first = 2 * CHUNK
    visible = sum(range(first + 1, first + CHUNK + 1))
    assert chunks["dsa_counts"].shape == (MODEL["n_layers"], 2)
    assert (chunks["dsa_counts"][:, 0] == visible).all()
    assert (chunks["dsa_counts"][:, 1] >= CHUNK * k).all()  # ties add
    assert (chunks["dsa_counts"][:, 1] < CHUNK * k + 16).all()
    for j, step in enumerate(steps):
        # one live row of four: its n + j + 1 keys, 16 kept, a layer
        assert (step["dsa_counts"] == [n + j + 1, k]).all()
        assert (step["moe_routed"] == MODEL["moe_top_k"]).all()
        assert step["moe_counts"].shape == (2, MODEL["moe_experts"])
        assert (step["moe_counts"].sum(axis=1) <= MODEL["moe_top_k"]).all()
    assert (chunks["moe_routed"] == CHUNK * MODEL["moe_top_k"]).all()
    held = chunks["moe_counts"].sum() / chunks["moe_routed"].sum()
    assert 0.0 < held < 1.0  # some picks met a held expert, some not


def test_a_prefix_hit_on_shared_latent_and_indexer_pages_reads_as_a_miss(sound):
    """A second request whose prompt starts with the first's two whole
    chunks takes those pages from the first's table and prefills its
    last chunk alone: the logits of its own positions are a miss's."""
    cfg, params, prompt = sound["cfg"], sound["params"], sound["prompt"]
    n, shared = len(prompt), 2 * CHUNK
    other = np.concatenate(
        [prompt[:shared], np.random.default_rng(1).integers(1, 211, size=20)]
    )
    pool = g.init_block_pool(cfg, 64, BL)
    table_a, free = _table(1, n)
    _, pool = _prefill(cfg, params, pool, table_a, prompt)
    table_b, _ = _table(free, len(other))
    table_b[0, :shared // BL] = table_a[0, :shared // BL]  # the hit
    hit, pool = _prefill(cfg, params, pool, table_b, other, start=shared)
    miss, _ = _prefill(
        cfg, params, g.init_block_pool(cfg, 64, BL), _table(1, len(other))[0],
        other,
    )
    assert compare.relative_rms_error(hit, miss[shared:]) < 1e-5
    want = _reference(params, sound["model"], other)
    assert compare.relative_rms_error(hit, want[shared:]) < F32_LIMIT


@pytest.mark.parametrize("changed", [
    dict(index_topk=256),  # selection off: every visible key attended
    dict(index_topk=20),   # a wrong k
], ids=["selection-off", "wrong-topk"])
def test_another_selection_reads_far_outside_the_tolerance(sound, changed):
    cfg, _, _ = _build(**changed)
    prefill, decoded, _, counted = _run(cfg, sound["params"], sound["prompt"])
    n = len(sound["prompt"])
    assert compare.relative_rms_error(prefill, sound["want"][:n]) > FAR
    # (the greedy tokens may part ways: the first decoded position is
    # still the same sequence's)
    assert compare.relative_rms_error(decoded[0], sound["want"][n]) > FAR
    if changed["index_topk"] == 256:
        assert (counted[0]["dsa_counts"][:, 0] == counted[0]["dsa_counts"][:, 1]).all()


@pytest.mark.parametrize("leaf, value", [
    ("shared_down", 0.0), ("router_bias", 0.0), ("ik_bias", 0.0),
    ("kv_norm", 1.0), ("q_norm", 1.0),
], ids=["no-shared-expert", "no-correction-bias", "no-indexer-bias",
        "no-latent-norm", "no-q-norm"])
def test_a_dropped_piece_reads_far_outside_the_tolerance(sound, leaf, value):
    params = jax.tree.map(lambda x: x, sound["params"])
    params["layers"] = dict(
        params["layers"],
        **{leaf: jnp.full_like(params["layers"][leaf], value)},
    )
    prefill, _, _, _ = _run(sound["cfg"], params, sound["prompt"], steps=1)
    n = len(sound["prompt"])
    assert compare.relative_rms_error(prefill, sound["want"][:n]) > FAR


def test_the_reference_routes_over_all_outputs_and_runs_the_held_share(sound):
    """Held experts 4..7 in the program's place of 0..3, same weights:
    other picks meet them, so both sides move, and still agree."""
    cfg, model, _ = _build(moe_first_expert=4)
    prefill, _, _, _ = _run(cfg, sound["params"], sound["prompt"], steps=1)
    n = len(sound["prompt"])
    want = _reference(sound["params"], model, sound["prompt"])
    assert compare.relative_rms_error(prefill, want) < F32_LIMIT
    assert compare.relative_rms_error(prefill, sound["want"][:n]) > FAR


def test_training_and_conversion_refuse_latent_attention():
    from ray_tpu.models import hf_convert, llama

    cfg, _, params = _build()
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="latent attention"):
        llama.forward_and_aux(params, tokens, cfg)
    with pytest.raises(NotImplementedError, match="latent attention"):
        llama.loss_fn(params, tokens, tokens, cfg)
    with pytest.raises(NotImplementedError, match="latent attention"):
        llama.param_annotations(cfg)
    with pytest.raises(NotImplementedError, match="latent attention"):
        hf_convert.convert_hf_llama({}, cfg)

    class Published:
        kv_lora_rank = 512

    with pytest.raises(NotImplementedError, match="latent attention"):
        hf_convert.config_from_hf(Published())


def test_the_program_lays_out_the_tree_the_reference_names():
    from ray_tpu.models.llama import init_params

    cfg, model, made = _build()
    theirs = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    assert jax.tree.structure(theirs) == jax.tree.structure(made)
    assert jax.tree.map(lambda a: a.shape, theirs) == jax.tree.map(
        lambda a: a.shape, made
    )
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(made))


def test_the_pools_bytes_are_its_cache_leaves_whatever_their_names():
    from ray_tpu.llm.kv_slots import PagedKVCache

    cfg, _, _ = _build()
    kv = PagedKVCache(cfg, 9, BL, 64, CHUNK)
    # 3 layers x 9 blocks x 8 positions x (a latent entry and an
    # indexer key, each in whole lanes of 128) x 4 bytes
    assert set(g.cache_leaves(kv.pool)) == {"latent", "index_k"}
    assert kv.nbytes() == 3 * 9 * 8 * (128 + 128) * 4
    plain = PagedKVCache(LlamaConfig.tiny(), 9, BL, 64, CHUNK)
    assert set(g.cache_leaves(plain.pool)) == {"k", "v"}
    assert plain.nbytes() == 2 * 2 * 9 * 4 * 8 * 16 * 4


def test_the_engine_serves_it_and_adds_up_what_the_program_counted():
    """`InferenceEngine` over the same programs: greedy tokens that the
    reference puts first, a second request that enters through the
    prefix cache and gets the same tokens, and in `stats()` the
    counters the forwards left in the pool, exact."""
    from ray_tpu.llm import EngineConfig, InferenceEngine

    cfg, model, params = _build()
    engine = InferenceEngine(
        params, cfg,
        EngineConfig(
            slots=3, max_len=128, prefill_chunk=CHUNK, kv_block_len=BL,
            max_new_tokens=5, prefix_cache=True,
        ),
        family="tiny",
    )
    try:
        stats = engine.stats()
        for name in ("dsa_keys_visible", "dsa_keys_selected", "moe_picks_routed"):
            assert stats[name] == 0
        prompt = np.random.default_rng(2).integers(1, 211, size=70).tolist()
        first = list(engine.submit(prompt, max_new_tokens=5))
        once = engine.stats()
        again = list(engine.submit(prompt, max_new_tokens=5))
        twice = engine.stats()
    finally:
        engine.close()
    assert first == again and twice["prefix_hits"] == 1
    want = _reference(params, model, prompt + first)
    assert [int(t) for t in np.argmax(want[69:74], axis=-1)] == first
    layers, k, top = MODEL["n_layers"], MODEL["index_topk"], MODEL["moe_top_k"]
    # two chunks of 32 and the last 6 tokens at the quarter's shape, 8
    # (`kv_slots.chunk_shapes`): 72 computed positions, then 5 steps at
    # 70.. keys
    visible = sum(range(1, 73)) + sum(70 + j + 1 for j in range(5))
    assert once["dsa_keys_visible"] == layers * visible
    selected = sum(min(i, k) for i in range(1, 73)) + 5 * k
    assert layers * selected <= once["dsa_keys_selected"] < layers * (selected + 40)
    assert once["moe_picks_routed"] == 2 * top * (72 + 5)
    held = once["moe_picks_prefill"] + once["moe_picks_decode"]
    assert 0 < held < once["moe_picks_routed"]
    assert once["moe_chunk_layers"] == 3 * 2 and once["moe_step_layers"] == 5 * 2
    # the hit skipped two whole chunks: the last one and 5 steps more
    assert twice["moe_picks_routed"] - once["moe_picks_routed"] == 2 * top * (8 + 5)
    assert twice["kv_keys_live"] == 2 * once["kv_keys_live"]


def test_latent_attention_without_an_indexer_attends_every_visible_key(sound):
    """No `index_topk` (DeepSeek-V3's attention): a step walks the live
    tiles with absorbed queries (`_paged_attention` over a pool with
    no kv-head axis) and a chunk's kernel is masked by position alone.
    Both are what a selection that keeps everything computes."""
    plain, _, _ = _build(index_topk=0, index_n_heads=0, index_head_dim=0)
    every, _, _ = _build(index_topk=256)
    pool = g.init_block_pool(plain, 64, BL)
    assert set(pool) == {"latent", "moe_counts", "moe_routed", "moe_spilled"}
    a = _run(plain, sound["params"], sound["prompt"], steps=3)
    b = _run(every, sound["params"], sound["prompt"], steps=3)
    assert a[2] == b[2]  # the same greedy tokens
    assert compare.relative_rms_error(a[0], b[0]) < 1e-5
    assert compare.relative_rms_error(a[1], b[1]) < 1e-5
