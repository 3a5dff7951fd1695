"""A projection that feeds a head split, on the serve path: the paged
attention half (`generate._paged_attend`) computes it in two halves (`generate._qkv_flat`, the products
and what acts on a whole projection; `generate._split_heads`, the
split and what acts on a head) and HOLDS the flat products row-major
between them (`generate._row_major`: the compiler then reads a layer's
weight where the stack holds it and re-lays the activation). The
training layer keeps `llama.project_qkv`, the one-piece form of the
same arithmetic, and carries no such hold. The two share arithmetic
and not code (models/llama.py is the train cells' and stays as it is),
so THIS FILE is what keeps "the training layer and the serving layer
use the same projection" true: the halves composed equal `project_qkv`
bit for bit in every family.

Five families of plain attention at the sizes of their configuration
files' `rehearsal` groups: dense with biases, an expert model that
norms the whole projection, a norm a head, a model of two KINDS of
attention layer, and one whose attention layers (a norm a head, under
`layer_kinds`) stand among gated short convolutions, which have no
head split and hold nothing. Latent attention under an indexer (`_latent_attend`) is NOT
held: its programs are the ones they were (PERF.md section 6, PR 50).
The six families' two programs also return the pool they were given,
leaf for leaf (the last test).
Nothing here compiles for a described chip, and nothing lowers a
full-size train step."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import compile_rehearsal, harness
from benchmark.drivers import serve_cache
from ray_tpu.models import generate, llama
from ray_tpu.models.llama import LlamaConfig, init_params, loss_fn

#: family -> (the configuration file, the model keys changed)
FAMILIES = {
    "dense_bias": ("qwen2.5-3b", {}),
    "moe_proj_norm": ("olmoe-1b-7b-l8", {}),
    "head_norm": ("qwen2.5-3b", {"qk_norm": "head"}),
    "layer_kinds": ("mimo-v2-flash-l7-ep16", {}),
    "latent": ("deepseek-v3.2-l5-ep16", {}),
    "conv_kinds": ("lfm2-24b-a2b-ep8", {}),
}
#: The families `_paged_attend` serves.
PAGED = (
    "dense_bias", "moe_proj_norm", "head_norm", "layer_kinds", "conv_kinds"
)
#: The families the training layer serves.
TRAINED = ("dense_bias", "moe_proj_norm", "head_norm")
PROGRAMS = ("paged_engine_step", "paged_prefill")


def _settings(family, **engine):
    name, changed = FAMILIES[family]
    settings = harness.apply_rehearsal(
        harness.load_config(harness.load_manifest(), name)
    )
    settings["model"] = {**settings["model"], **changed}
    settings["engine"] = {**settings["engine"], **engine}
    return settings


def _cfg(family):
    settings = _settings(family)
    return LlamaConfig(
        **settings["model"], dtype=jnp.dtype(settings["dtype"])
    )


def _serve_programs(family, **engine):
    """-> (cfg, {program's name: (call, its arguments' shapes)}): the
    replica's two programs at the arguments `compile_rehearsal.decode`
    gives them, `engine`'s keys in place of the file's."""
    cfg, a = compile_rehearsal.serve_arguments(
        _settings(family, **engine), SingleDeviceSharding(jax.devices()[0])
    )

    # (the forwards themselves, not the public entry points: those
    # count every new shape as a compile of the process's, and
    # `rt.diagnose()` in a later test of the same worker would read
    # five families' shapes as a recompile storm)
    def paged_engine_step(params, pool, last_logits, state, key):
        return generate._paged_engine_step_impl(
            params, cfg, pool, last_logits, state, key, 0.0, 0
        )

    def paged_prefill(params, tokens, pool, table, offset, valid_len):
        return generate._paged_prefill_impl(
            params, cfg, tokens, pool, table, offset, valid_len
        )

    return cfg, {
        "paged_engine_step": (paged_engine_step, [a[n] for n in (
            "params", "pool", "last_logits", "state", "key")]),
        "paged_prefill": (paged_prefill, [a[n] for n in (
            "params", "tokens", "pool", "table", "scalar", "scalar")]),
    }


def _equations(jaxpr):
    """Every equation of `jaxpr` and of the programs its equations
    hold (a scan's body, a jit's, a loop's, a branch's)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for held in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(held, "jaxpr", held)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@functools.cache
def _hold_primitive():
    """The primitive `generate._row_major` holds an array with."""
    (eqn,) = jax.make_jaxpr(generate._row_major)(
        jnp.zeros((1, 2, 16)), (jnp.zeros((1, 2, 4)),)
    ).eqns
    return eqn.primitive.name


def _holds(jaxpr):
    """A list for each hold of `jaxpr`: the shapes of what it holds."""
    return [
        [tuple(var.aval.shape) for var in eqn.invars]
        for eqn in _equations(jaxpr)
        if eqn.primitive.name == _hold_primitive()
    ]


def _expected_holds(cfg, rows, t):
    """What a serve forward over `[rows, t]` tokens holds: ONE hold of
    (q, k, v) for every layer BODY the program traces (a scanned
    stack's once, an unrolled layer's each); a latent layer holds
    nothing."""
    if cfg.kv_lora_rank:
        return []
    if cfg.layer_kinds:
        return sorted(
            [(rows, t, cfg.n_heads * cfg.head_dim),
             (rows, t, kind.kv_heads * cfg.head_dim),
             (rows, t, kind.kv_heads * (cfg.v_head_dim or cfg.head_dim))]
            for kind in _traced_bodies(cfg) if not kind.conv
        )
    return [[
        (rows, t, heads * cfg.head_dim)
        for heads in (cfg.n_heads, cfg.n_kv_heads, cfg.n_kv_heads)
    ]]


def _traced_bodies(cfg):
    """The kinds of the layer bodies a forward of a `layer_kinds` model
    traces, by `_paged_forward`'s rule: in each stack (the leading
    dense layers, the others) two or more whole periods of the pattern
    are ONE body a period long, scanned, and what is left behind them,
    or a stack of under two periods, a body a layer."""
    bodies, first = [], 0
    for depth in (cfg.dense_layers, cfg.n_layers - cfg.dense_layers):
        kinds = cfg.layer_kinds[first:first + depth]
        first += depth
        if not depth:
            continue
        period = next(
            p for p in range(1, depth + 1)
            if all(kinds[i].cache == kinds[i + p].cache for i in range(depth - p))
        )
        whole = depth // period if depth >= 2 * period else 0
        bodies += list(kinds[:period] if whole else ())
        bodies += list(kinds[whole * period:])
    return bodies


def _drawn(tree, seed):
    """`tree`'s floating leaves drawn anew (a zero bias, a norm of
    ones or a sink of zeros would hide itself)."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten([
        0.3 * jax.random.normal(k, w.shape, w.dtype)
        if jnp.issubdtype(w.dtype, jnp.floating) else w
        for k, w in zip(keys, leaves)
    ])


def _one_layer(cfg):
    """-> (h [2, 8, dim], a layer's weights, its kind or None)."""
    params = _drawn(init_params(jax.random.PRNGKey(0), cfg), 1)
    # (the first attention kind past layer 0: MiMo's window kind)
    kind = next(
        (k for k in cfg.layer_kinds[1:] if not k.conv), None
    ) if cfg.layer_kinds else None
    # (a kind's layer: `wq` lies beside its FFN, `wk` / `wv` by kind)
    stack = {
        **params["layers"],
        **(params[f"attn_{kind.cache}"] if kind else {}),
    }
    layer = {
        name: w[0] for name, w in stack.items()
        if name not in llama.EXPERT_LEAVES
    }
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.dim), cfg.dtype)
    return h, layer, kind


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jitted"])
@pytest.mark.parametrize("family", PAGED)
def test_the_serve_halves_are_project_qkv(family, jitted):
    """(a) `_split_heads(_qkv_flat(...))`, held or not, is
    `llama.project_qkv` bit for bit."""
    cfg = _cfg(family)
    h, layer, kind = _one_layer(cfg)

    def halves(h, layer):
        flat = generate._qkv_flat(cfg, h, layer, kind)
        return flat, generate._split_heads(cfg, *flat, layer, kind)

    def held(h, layer):
        flat = generate._row_major(h, generate._qkv_flat(cfg, h, layer, kind))
        return generate._split_heads(cfg, *flat, layer, kind)

    def whole(h, layer):
        return llama.project_qkv(cfg, h, layer, kind)

    if jitted:
        halves, held, whole = jax.jit(halves), jax.jit(held), jax.jit(whole)
    assert 2 * 8 < cfg.dim  # `held` does hold
    flat, split = halves(h, layer)
    for f, a, b, c in zip(flat, split, held(h, layer), whole(h, layer)):
        assert f.shape[:2] == h.shape[:2] and f.ndim == 3  # [b, t, heads * hd]
        assert c.shape == (2, c.shape[1], 8, c.shape[3])  # [b, heads, t, hd]
        assert np.asarray(c, np.float32).std() > 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        np.testing.assert_array_equal(np.asarray(b), np.asarray(c))


def _make_programs(cfg):
    """The two forwards under jits of their own, made anew at every
    call ON PURPOSE: the public entry points keep their traces, and a
    kept trace would not meet a patched hold."""
    return (
        jax.jit(functools.partial(generate._paged_prefill_impl, cfg=cfg)),
        jax.jit(functools.partial(
            generate._paged_engine_step_impl, cfg=cfg, temperature=0.0,
            top_k=0,
        )),
    )


def _served(family):
    """One row's chunk prefilled and one step over every slot, two of
    them alive, at drawn weights -> what the two programs gave."""
    cfg, engine = _cfg(family), _settings(family)["engine"]
    slots, chunk = engine["slots"], engine["prefill_chunk"]
    cache = serve_cache.engine_cache(cfg, engine)
    params = _drawn(init_params(jax.random.PRNGKey(0), cfg), 3)
    blocks = [
        cache.alloc.reserve(cache.blocks_for(chunk + 4)) for _ in range(2)
    ] + [None] * (slots - 2)
    prefill, step = _make_programs(cfg)
    pool, last = cache.pool, []
    for slot in range(2):
        tokens = (
            jnp.arange(chunk, dtype=jnp.int32)[None] * (slot + 3) + slot
        ) % cfg.vocab_size
        logits, pool = prefill(
            params, tokens=tokens, pool=pool,
            table=serve_cache.row_table(cache, slot, blocks[slot]),
            offset=jnp.int32(0), valid_len=jnp.int32(chunk),
        )
        last.append(logits)
    chunk_logits = jnp.concatenate(last)
    last_logits = jnp.zeros((slots, cfg.vocab_size), jnp.float32)
    last_logits = last_logits.at[:2].set(
        chunk_logits.reshape(2, -1, cfg.vocab_size)[:, -1]
    )
    alive = np.arange(slots) < 2
    state = serve_cache.step_state(
        cache, blocks, np.where(alive, chunk, 0), alive,
        np.full(slots, -1), np.full(slots, 4), 0,
    )
    fetch, pool, step_logits, state = step(
        params, pool=pool, last_logits=last_logits, state=state,
        base_key=jax.random.PRNGKey(0),
    )
    return {
        "paged_prefill": np.asarray(chunk_logits),
        "paged_engine_step": np.asarray(step_logits),
        "token": np.asarray(fetch["token"]),
    }


@pytest.mark.parametrize("family", PAGED)
def test_the_hold_changes_no_logit(family, monkeypatch):
    """(b) Held or not, the two programs compute the same numbers: the
    hold says where a product lies, not what it is."""
    held = _served(family)
    monkeypatch.setattr(generate, "_row_major", lambda h, flat: flat)
    free = _served(family)
    for program in PROGRAMS:
        assert np.isfinite(held[program]).all() and held[program].std() > 0
        np.testing.assert_array_equal(held[program], free[program])
    np.testing.assert_array_equal(held["token"], free["token"])


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("family", FAMILIES)
def test_serve_programs_hold_every_head_split_projection(family, program):
    """(c) One hold at every head-split projection of `_paged_attend`;
    (d) none in `_latent_attend`'s programs."""
    cfg, programs = _serve_programs(family)
    call, shapes = programs[program]
    rows, t = shapes[1].shape if program == "paged_prefill" else (
        shapes[2].shape[0], 1
    )
    assert rows * t < cfg.dim
    holds = _holds(jax.make_jaxpr(call)(*shapes).jaxpr)
    assert sorted(holds) == _expected_holds(cfg, rows, t)


@pytest.mark.parametrize("family", PAGED)
def test_a_chunk_as_long_as_the_weight_is_left_to_the_compiler(family):
    """(c) The hold makes the activation the side that is re-laid;
    where it has as many rows as the weight it is not the smaller
    side, and the program is the one it was."""
    cfg, programs = _serve_programs(family, prefill_chunk=64)
    assert 64 >= cfg.dim
    call, shapes = programs["paged_prefill"]
    assert _holds(jax.make_jaxpr(call)(*shapes).jaxpr) == []
    call, shapes = programs["paged_engine_step"]
    slots = shapes[2].shape[0]
    assert sorted(_holds(jax.make_jaxpr(call)(*shapes).jaxpr)) == (
        _expected_holds(cfg, slots, 1)
    )


@pytest.mark.parametrize("family", TRAINED)
def test_training_layer_holds_nothing(family):
    """(d) The train step is `llama._layer`'s, which calls
    `project_qkv` in one piece."""
    cfg = _cfg(family)
    params = jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0)
    )
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    traced = jax.make_jaxpr(
        lambda p, t: jax.grad(loss_fn)(p, t, t, cfg)
    )(params, tokens)
    names = {eqn.primitive.name for eqn in _equations(traced.jaxpr)}
    assert "dot_general" in names
    assert _hold_primitive() not in names


#: family -> the counters its forwards leave in the pool
COUNTERS = {
    "dense_bias": set(),
    "moe_proj_norm": {"moe_counts"},
    "head_norm": set(),
    "layer_kinds": {"moe_counts", "moe_routed", "moe_spilled"},
    "latent": {"moe_counts", "moe_routed", "moe_spilled", "dsa_counts"},
    "conv_kinds": {"moe_counts", "moe_routed", "moe_spilled"},
}


def _leaves(tree):
    return {name: (a.shape, a.dtype) for name, a in tree.items()}


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("family", FAMILIES)
def test_serve_programs_return_the_pool_they_were_given(family, program):
    """(e) A chunk and a step give back `init_block_pool`'s tree, leaf
    for leaf, counters included: what the pool's donation rests on (a
    leaf of another shape is a second pool on the chip) and what the
    engine fetches by name (`counter_leaves`). No compute."""
    cfg, programs = _serve_programs(family)
    call, shapes = programs[program]
    given = shapes[2 if program == "paged_prefill" else 1]
    first = next(
        leaf for name, leaf in generate.cache_leaves(given).items()
        if name != generate.STATE_LEAF  # (a page's: block_len its axis -2)
    )
    n_blocks = first.shape[1]
    if cfg.layer_kinds:  # a pool a kind, each of its own size
        n_blocks = {  # (a state leaf is [layers, columns, slots, dim])
            kind: given[next(iter(cache.leaves))].shape[2 if cache.state else 1]
            for kind, cache in generate._pool_plan(cfg)[0].items()
        }
    made = _leaves(jax.eval_shape(
        lambda: generate.init_block_pool(cfg, n_blocks, first.shape[-2])
    ))
    assert set(made) & set(generate.COUNTER_LEAVES) == COUNTERS[family]
    assert _leaves(given) == made
    out = jax.eval_shape(call, *shapes)
    assert _leaves(out[1]) == made
    if program == "paged_engine_step":  # the step's fetch: copies of them
        counted = {n: a for n, a in out[0].items() if n in COUNTERS[family]}
        assert _leaves(counted) == {n: made[n] for n in COUNTERS[family]}
