"""Which positions a chunk runs its head for is the caller's to say
(ISSUE 53): `paged_prefill(..., row=r)` is row `r` of
`paged_prefill(...)`, final norm, head and float32 logits computed for
that one position, with the same pool behind it; and the engine's
chunks are that form, warmed at every shape as the loop starts.

The six families of `test_serve_projection_pin.py` at the sizes of
their configuration files' `rehearsal` groups, a last chunk of every
shape `kv_slots.chunk_shapes` offers, behind a whole first chunk (so
at an offset, as a prefix hit's), read at a position that is not the
chunk's last (a padded last chunk). The two forms are two programs: a
`[1, d] x [d, vocab]` product may round its last bits otherwise than a
row of `[t, d] x [d, vocab]`, so logits compare to a float32
tolerance; the pool, which both write before the head, bit for bit.
Nothing here compiles for a described chip."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import SingleDeviceSharding

from benchmark import compile_rehearsal
from benchmark.drivers import serve_cache
from ray_tpu._private import compile_watch
from ray_tpu.llm import kv_slots
from ray_tpu.models import generate
from ray_tpu.models.llama import LlamaConfig, init_params
from test_serve_projection_pin import (
    FAMILIES, _cfg, _drawn, _equations, _settings,
)

VOCAB = 520


def _shapes(family):
    engine = _settings(family)["engine"]
    return kv_slots.chunk_shapes(
        engine["prefill_chunk"], engine["kv_block_len"]
    )


#: (family, a shape its last chunk can have): files read, no device
CASES = [(family, shape) for family in FAMILIES for shape in _shapes(family)]


def _chunk_program(cfg):
    """The chunk's forward under a jit of its own: the public entry
    point counts every new shape as a compile of the process's."""
    return jax.jit(functools.partial(generate._paged_prefill_impl, cfg=cfg))


@functools.cache
def _behind_a_first_chunk(family):
    """-> (cfg, params, prefill, pool, table, chunk): a row whose
    first whole chunk is in the pool, at drawn weights."""
    cfg, engine = _cfg(family), _settings(family)["engine"]
    chunk = engine["prefill_chunk"]
    cache = serve_cache.engine_cache(cfg, engine)
    params = _drawn(init_params(jax.random.PRNGKey(0), cfg), 5)
    table = serve_cache.row_table(
        cache, 1, cache.alloc.reserve(cache.blocks_for(2 * chunk))
    )
    prefill = _chunk_program(cfg)
    _, pool = prefill(
        params, tokens=_tokens(cfg, chunk, 0), pool=cache.pool, table=table,
        offset=jnp.int32(0), valid_len=jnp.int32(chunk),
    )
    return cfg, params, prefill, pool, table, chunk


def _tokens(cfg, t, start):
    return (
        (jnp.arange(start, start + t, dtype=jnp.int32)[None] * 7 + 3)
        % cfg.vocab_size
    )


@pytest.mark.parametrize("family,shape", CASES)
def test_a_row_of_the_chunk_is_the_row_asked_for(family, shape):
    cfg, params, prefill, pool, table, chunk = _behind_a_first_chunk(family)
    chunk_of = functools.partial(
        prefill, params, tokens=_tokens(cfg, shape, chunk), pool=pool,
        table=table, offset=jnp.int32(chunk),
        valid_len=jnp.int32(chunk + shape),
    )
    every, pool_every = chunk_of()
    assert every.shape == (1, shape, cfg.vocab_size)
    # (a prompt that ends three short of the chunk's end, the chunk's
    # first and last positions; and a row a ROW, as `valid_len` may be)
    rows = [(at, jnp.int32(at)) for at in (shape - 4, 0, shape - 1)]
    rows.append((shape - 4, jnp.asarray([shape - 4], jnp.int32)))
    for at, row in rows:
        one, pool_one = chunk_of(row=row)
        assert one.shape == (1, cfg.vocab_size) and one.dtype == jnp.float32
        want = np.asarray(every[:, at])
        assert np.isfinite(want).all() and want.std() > 0
        np.testing.assert_allclose(
            np.asarray(one), want, rtol=1e-5, atol=1e-5 * np.abs(want).max()
        )
        assert set(pool_one) == set(pool_every)
        for name, leaf in pool_every.items():
            np.testing.assert_array_equal(
                np.asarray(pool_one[name]), np.asarray(leaf), err_msg=name
            )


@pytest.mark.parametrize("family", FAMILIES)
def test_the_row_form_holds_no_chunk_of_logits(family):
    """Neither traced nor lowered: no array of the row form is
    `[.., t, vocab]`, where the all-position form's result is one.
    (At a vocabulary no other size of the rehearsal model equals.)"""
    settings = _settings(family)
    settings["model"] = {**settings["model"], "vocab_size": VOCAB}
    cfg, a = compile_rehearsal.serve_arguments(
        settings, SingleDeviceSharding(jax.devices()[0])
    )
    shapes = [a[n] for n in (
        "params", "tokens", "pool", "table", "scalar", "scalar", "scalar"
    )]
    (b, t), vocab = a["tokens"].shape, cfg.vocab_size
    assert b == 1 and t > 1 and vocab == VOCAB

    def call(params, *args, row=None):
        return generate._paged_prefill_impl(params, cfg, *args, row=row)

    def row_form(*args):
        return call(*args[:-1], row=args[-1])

    logits, pool = jax.eval_shape(row_form, *shapes)
    assert (logits.shape, logits.dtype) == ((1, vocab), jnp.float32)
    every, pool_every = jax.eval_shape(call, *shapes[:-1])
    assert (every.shape, every.dtype) == ((1, t, vocab), jnp.float32)
    assert jax.tree.structure(pool) == jax.tree.structure(pool_every)
    assert jax.tree.leaves(pool) == jax.tree.leaves(pool_every)

    def wide(jaxpr):
        return [
            var.aval.shape for eqn in _equations(jaxpr) for var in eqn.outvars
            if var.aval.shape[-2:] == (t, vocab)
        ]

    assert wide(jax.make_jaxpr(call)(*shapes[:-1]).jaxpr)
    assert wide(jax.make_jaxpr(row_form)(*shapes).jaxpr) == []
    text = jax.jit(row_form).lower(*shapes).as_text()
    chunk_of_logits = f"{t}x{vocab}xf32>"
    assert chunk_of_logits in jax.jit(call).lower(*shapes[:-1]).as_text()
    assert chunk_of_logits not in text and f"tensor<1x{vocab}xf32>" in text


def _prefill_programs():
    """How many programs `generate.paged_prefill`'s jit holds."""
    return generate._paged_prefill_jit.wrapped._cache_size()


@pytest.mark.parametrize("experts", [0, 4], ids=["dense", "moe"])
def test_an_engines_chunks_are_the_row_form_and_warmed(experts):
    """The loop runs the row form at every shape of a last chunk
    before its first admission and no other chunk program after it:
    prompts of every class of tail compile nothing, and the
    all-position program of the same shapes is still to be compiled
    when a caller that reads every position (the benchmark's probe)
    asks for it."""
    from ray_tpu.llm import EngineConfig, InferenceEngine

    cfg = LlamaConfig(
        vocab_size=96, dim=48, n_layers=2, n_heads=4, n_kv_heads=2,
        intermediate=64, max_seq_len=128, dtype=jnp.float32,
        attention="reference", moe_experts=experts,
        moe_top_k=2 if experts else 0,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngine(
        params, cfg,
        EngineConfig(
            slots=2, max_len=64, prefill_chunk=16, kv_block_len=4,
            max_new_tokens=4,
        ),
        family="tiny-row-head",
    )
    try:
        rng = np.random.default_rng(53)
        first = list(eng.submit(rng.integers(1, 96, size=21).tolist()))
        assert len(first) == 4
        shapes = eng._kv.chunk_shapes()
        assert shapes == (4, 8, 16)
        held = _prefill_programs()
        compiles = {
            k: dict(v) for k, v in eng.stats()["compiles"].items()
        }
        assert compiles["prefill"]["distinct_shapes"] >= len(shapes)
        watched = compile_watch.program_stats("generate.paged_prefill")
        for n in (3, 16, 17, 23, 29, 40):  # every class of tail, twice over
            out = list(eng.submit(rng.integers(1, 96, size=n).tolist()))
            assert len(out) == 4
        assert _prefill_programs() == held
        assert eng.stats()["compiles"] == compiles
        assert compile_watch.program_stats("generate.paged_prefill") == watched
        # the all-position form at a warmed shape: a program of its own
        table, pool = eng._null_row, eng._kv.pool
        logits, pool = generate.paged_prefill(
            params, cfg, np.zeros((1, 8), np.int32), pool, table,
            np.int32(0), np.int32(8),
        )
        eng._kv.pool = pool
        assert logits.shape == (1, 8, 96)
        assert _prefill_programs() == held + 1
    finally:
        eng.close()
