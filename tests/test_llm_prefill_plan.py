"""A prompt's last chunk is as long as its tokens need (ISSUE 43): the
plan of a prompt's chunks (`kv_slots.bucket_for`, `chunk_shapes`), the
engine that walks it, the shapes it warms as it starts and the four
counters that say how often a short chunk ran. Tiny model, a chunk of
four KV blocks, so the chunk, its half and its quarter are all
offered."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decode_oracle import greedy_uncached
from ray_tpu._private import compile_watch
from ray_tpu.llm.kv_slots import bucket_for, chunk_shapes

CHUNK, BLOCK = 16, 4
ENGINE_KW = dict(
    slots=2, max_len=64, prefill_chunk=CHUNK, kv_block_len=BLOCK,
    max_new_tokens=6,
)
#: A prompt length of every class of tail, by name -> (tokens, the
#: shape its last chunk runs at).
CLASSES = {
    "quarter": (CHUNK + 3, 4),
    "half": (CHUNK + 7, 8),
    "whole": (CHUNK + 13, 16),
    "one_chunk": (CHUNK, 16),
    "one_over": (CHUNK + 1, 4),
    "under_a_quarter": (3, 4),
    "two_chunks_and_a_half": (2 * CHUNK + 8, 8),
}
COUNTERS = (
    "prefill_chunks", "prefill_short_chunks", "prefill_tokens_computed",
    "prefill_tokens_needed",
)


def plan(n: int, skip: int = 0) -> dict:
    """The counters' deltas one request of `n` tokens leaves, from the
    issue's arithmetic (not from `bucket_for`)."""
    last = (n - 1) // CHUNK * CHUNK
    shape = next(s for s in (4, 8, 16) if s >= n - last)
    return {
        "prefill_chunks": (last - skip) // CHUNK + 1,
        "prefill_short_chunks": int(shape < CHUNK),
        "prefill_tokens_computed": last - skip + shape,
        "prefill_tokens_needed": n - skip,
    }


def prompt_of(name: str) -> list:
    n, _ = CLASSES[name]
    rng = np.random.default_rng([43, sorted(CLASSES).index(name)])
    return rng.integers(1, 128, size=n).tolist()


@pytest.fixture(scope="module")
def tiny_model():
    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        intermediate=128, max_seq_len=128, dtype=jnp.float32,
        attention="reference",
    )
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def engine(tiny_model):
    from ray_tpu.llm import EngineConfig, InferenceEngine

    cfg, params = tiny_model
    eng = InferenceEngine(
        params, cfg, EngineConfig(**ENGINE_KW), family="tiny-plan"
    )
    yield eng
    eng.close()


def serve(eng, prompt: list) -> tuple:
    """-> (tokens, what the request added to the four counters)."""
    before = eng.stats()
    tokens = list(eng.submit(prompt))
    after = eng.stats()
    return tokens, {k: after[k] - before[k] for k in COUNTERS}


# -- the plan -----------------------------------------------------------

@pytest.mark.parametrize("chunk, block, shapes", [
    (16, 4, (4, 8, 16)),
    (512, 16, (128, 256, 512)),  # qwen2.5-3b's geometry
    (2048, 16, (512, 1024, 2048)),  # olmoe-1b-7b-l8's
    (128, 16, (32, 64, 128)),  # chip_smoke.py's
    (32, 16, (16, 32)),  # the quarter is under a block
    (8, 8, (8,)),  # so is the half
    (24, 8, (24,)),  # a half of 12 is no whole number of blocks
    (6, 2, (6,)),  # nor of 3, and the chunk has no quarter
])
def test_chunk_shapes_are_whole_blocks(chunk, block, shapes):
    assert chunk_shapes(chunk, block) == shapes


@pytest.mark.parametrize("name", sorted(CLASSES))
def test_bucket_is_where_the_last_chunk_ends(name):
    n, shape = CLASSES[name]
    last = (n - 1) // CHUNK * CHUNK
    assert bucket_for(n, CHUNK, 64, BLOCK) == last + shape
    # never past the whole-chunk bucket it was, never short of the prompt
    assert n <= last + shape <= -(-n // CHUNK) * CHUNK


@pytest.mark.parametrize("chunk, computed, needed", [
    (2048, 2432, 2112),  # doc_score_moe: padding 31.3 -> 13.2 %
    (512, 2144, 2112),  # the same prompts at qwen2.5-3b's chunk, no hit
])
def test_plan_of_the_closed_cells_prompts(chunk, computed, needed):
    """ISSUE 43's table, from the closed cells' 16 prompt lengths
    (1152 + 128 i): mean positions computed a request."""
    prompts = [1152 + 128 * i for i in range(16)]
    buckets = [bucket_for(n, chunk, 4096, 16) for n in prompts]
    assert sum(buckets) / 16 == computed and sum(prompts) / 16 == needed
    whole = [-(-n // chunk) * chunk for n in prompts]
    assert all(b <= w for b, w in zip(buckets, whole))


def test_bucket_refuses_what_it_refused():
    with pytest.raises(ValueError):
        bucket_for(0, CHUNK, 64, BLOCK)
    with pytest.raises(ValueError):
        bucket_for(65, CHUNK, 64, BLOCK)
    # a tail that fits only because its last chunk is short
    assert bucket_for(35, CHUNK, 40, BLOCK) == 36


# -- the engine that walks it ------------------------------------------

@pytest.mark.parametrize("name", sorted(CLASSES))
def test_greedy_tokens_are_the_uncached_forwards(tiny_model, engine, name):
    cfg, params = tiny_model
    prompt = prompt_of(name)
    tokens, counted = serve(engine, prompt)
    assert tokens == greedy_uncached(params, cfg, prompt, 6)
    assert counted == plan(len(prompt))
    assert counted["prefill_tokens_computed"] == bucket_for(
        len(prompt), CHUNK, 64, BLOCK
    )


@pytest.mark.parametrize("name", sorted(CLASSES))
def test_a_hit_runs_the_last_chunk_a_miss_runs(tiny_model, name):
    """A prompt sent as a miss and again as a hit: equal tokens, and
    the hit's one chunk is the miss's last (same start, same shape:
    what the hit skips is whole chunks under it)."""
    from ray_tpu.llm import EngineConfig, InferenceEngine

    cfg, params = tiny_model
    eng = InferenceEngine(
        params, cfg, EngineConfig(**ENGINE_KW), family="tiny-plan"
    )
    try:
        n, shape = CLASSES[name]
        prompt = prompt_of(name)
        last = (n - 1) // CHUNK * CHUNK
        miss_tokens, miss = serve(eng, prompt)
        hits = eng.stats()["prefix_hits"]
        hit_tokens, hit = serve(eng, list(prompt))
        assert hit_tokens == miss_tokens
        assert eng.stats()["prefix_hits"] - hits == int(last > 0)
        assert miss == plan(n) and hit == plan(n, skip=last)
        assert hit["prefill_chunks"] == 1
        assert hit["prefill_tokens_computed"] == shape
        assert hit["prefill_short_chunks"] == miss["prefill_short_chunks"]
    finally:
        eng.close()


def test_a_started_engine_compiles_for_no_prompt_length(tiny_model):
    """The CPU twin of the benchmark's `compiles_in_window`: the loop
    runs every shape of a last chunk before its first admission, so
    after one request (the step and the patch compile with it, as in
    any warm-up) a prompt of every class moves no count of the compile
    watch."""
    from ray_tpu.llm import EngineConfig, InferenceEngine

    cfg, params = tiny_model
    eng = InferenceEngine(
        params, cfg, EngineConfig(**ENGINE_KW), family="tiny-plan"
    )
    try:
        _, counted = serve(eng, prompt_of("whole"))
        first = eng.stats()
        # Two whole chunks ran, and every shape is compiled: the
        # warming runs are in no counter.
        assert counted == plan(CHUNK + 13)
        assert [first[k] for k in COUNTERS] == list(counted.values())
        assert first["programs"] == 2 + first["steps"]
        assert first["state_patches"] == 2  # admission, the row's start
        chunk_programs = {
            k: dict(first["compiles"][k]) for k in ("prefill", "finish_chunk")
        }
        assert chunk_programs["prefill"]["distinct_shapes"] >= 3
        # (a chunk of any shape hands `finish_chunk` one row: ISSUE 53)
        assert chunk_programs["finish_chunk"]["distinct_shapes"] >= 1

        def counts():
            return {
                name: row["compiles"]
                for name, row in compile_watch.snapshot().items()
            }

        warm = counts()
        for name in sorted(CLASSES):
            list(eng.submit(prompt_of(name)))
        assert counts() == warm
        after = eng.stats()["compiles"]
        assert {k: after[k] for k in chunk_programs} == chunk_programs
    finally:
        eng.close()


def test_a_geometry_without_a_quarter_offers_fewer_shapes(tiny_model):
    """A chunk of two blocks has a half and no quarter; one of one
    block has neither, and runs the parent's whole-chunk plan."""
    from ray_tpu.llm import EngineConfig, InferenceEngine

    cfg, params = tiny_model
    for block, shapes in ((8, (8, 16)), (16, (16,))):
        eng = InferenceEngine(
            params, cfg,
            EngineConfig(**{**ENGINE_KW, "kv_block_len": block}),
            family="tiny-plan",
        )
        try:
            assert eng._kv.chunk_shapes() == shapes
            prompt = prompt_of("quarter")
            tokens, counted = serve(eng, prompt)
            assert tokens == greedy_uncached(params, cfg, prompt, 6)
            assert counted["prefill_tokens_computed"] == CHUNK + shapes[0]
            assert counted["prefill_short_chunks"] == int(len(shapes) > 1)
        finally:
            eng.close()
