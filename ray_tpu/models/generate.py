"""Autoregressive decoding for the Llama family.

The serving-side counterpart of models/llama.py (the reference serves
models through vLLM-on-Ray rather than shipping its own decoder; a
TPU-native framework needs one in-tree). Decode is a two-phase jitted
program, the standard TPU inference shape:

  * prefill — one full forward over the padded prompt writes the KV
    cache (flash attention, MXU-bound);
  * decode  — `lax.scan` over steps, each a single-token forward
    against the cache (HBM-bandwidth-bound), with greedy / temperature
    / top-k sampling under a fixed token budget (static shapes; rows
    that hit EOS keep computing but emit padding — the XLA-friendly
    trade).

The KV cache layout [layers, batch, heads, max_len, head_dim] shards
over tp on heads, so tensor-parallel decode needs no cache reshuffle.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .._private import compile_watch
from ..ops.norms import apply_rotary, rotary_embedding
from .llama import embed_tokens, model_norm
from .llama import EXPERT_LEAVES, LlamaConfig, _mlp, project_qkv


def init_kv_cache(
    cfg: LlamaConfig, batch: int, max_len: int
) -> Dict[str, jax.Array]:
    shape = (
        cfg.n_layers,
        batch,
        cfg.n_kv_heads,
        max_len,
        cfg.head_dim,
    )
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def _layer_with_cache(
    cfg: LlamaConfig,
    x: jax.Array,  # [b, t, dim]
    layer: Dict[str, jax.Array],
    cos,
    sin,
    k_cache,  # [b, kv_heads, max_len, hd]
    v_cache,
    cache_pos: jax.Array,  # [b] per-row start offset of x
    valid_len: jax.Array,  # [b] per-row valid length incl. x
):
    b, t, _ = x.shape
    hd = cfg.head_dim
    h = model_norm(cfg, x, layer["attn_norm"])
    q, k, v = project_qkv(cfg, h, layer)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)
    if cache_pos.ndim:
        # Per-row offsets (the engine's slot batch: rows sit at
        # different sequence positions) — vmapped update lowers to a
        # batched scatter.
        _update = jax.vmap(
            lambda c, n, p: jax.lax.dynamic_update_slice(
                c, n, (0, p, 0)
            )
        )
        k_cache = _update(k_cache, k.astype(k_cache.dtype), cache_pos)
        v_cache = _update(v_cache, v.astype(v_cache.dtype), cache_pos)
    else:
        # Uniform offset (generate's scan decode, whole-prompt
        # prefill): keep the contiguous single dynamic_update_slice —
        # a scatter here would tax the HBM-bound hot path for
        # nothing.
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, 0, cache_pos, 0)
        )
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, 0, cache_pos, 0)
        )
    max_len = k_cache.shape[2]
    groups = cfg.n_heads // cfg.n_kv_heads
    kf = jnp.repeat(k_cache, groups, axis=1)
    vf = jnp.repeat(v_cache, groups, axis=1)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    logits = (
        jnp.einsum(
            "bhqd,bhkd->bhqk",
            q.astype(jnp.float32),
            kf.astype(jnp.float32),
        )
        * scale
    )
    # Causal + cache-validity mask over absolute positions; q_pos and
    # valid_len each broadcast from scalar (uniform) or per-row form.
    k_pos = jnp.arange(max_len)
    if cache_pos.ndim:
        q_pos = cache_pos[:, None] + jnp.arange(t)[None, :]  # [b, t]
    else:
        q_pos = (cache_pos + jnp.arange(t))[None, :]  # [1, t]
    vl = (
        valid_len[:, None, None] if valid_len.ndim else valid_len
    )
    mask = (k_pos[None, None, :] <= q_pos[:, :, None]) & (
        k_pos[None, None, :] < vl
    )  # [b or 1, t, max_len]
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    attn = jnp.einsum("bhqk,bhkd->bhqd", probs, vf.astype(jnp.float32))
    attn = attn.astype(cfg.dtype).transpose(0, 2, 1, 3).reshape(b, t, -1)
    x = x + attn @ layer["wo"]
    x, _, _ = _mlp(cfg, x, layer)
    return x, k_cache, v_cache


def _forward_with_cache(
    params, cfg: LlamaConfig, tokens, cache, cache_pos, valid_len
):
    """tokens [b, t] -> (logits [b, t, vocab], new cache).

    `cache_pos` / `valid_len` may each (independently) be scalars
    (whole batch at one offset, the `generate` path) or `[b]` arrays
    (per-row offsets/lengths — the engine's slot batch, ragged
    `generate_stream` prefill). Scalars keep the original contiguous
    cache update; per-row offsets take the vmapped scatter."""
    b, t = tokens.shape
    cache_pos = jnp.asarray(cache_pos, jnp.int32)
    valid_len = jnp.asarray(valid_len, jnp.int32)
    row_pos = cache_pos[:, None] if cache_pos.ndim else cache_pos
    positions = row_pos + jnp.broadcast_to(jnp.arange(t), (b, t))
    x = embed_tokens(cfg, params, tokens)
    cos, sin = rotary_embedding(
        positions, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
    )

    def body(carry, inputs):
        x = carry
        layer, k_cache, v_cache = inputs
        x, k_cache, v_cache = _layer_with_cache(
            cfg, x, layer, cos, sin, k_cache, v_cache, cache_pos,
            valid_len,
        )
        return x, (k_cache, v_cache)

    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"])
    )
    x = model_norm(cfg, x, params["final_norm"])
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, {"k": new_k, "v": new_v, "length": cache["length"]}


def _sample(logits, key, temperature: float, top_k: int):
    """logits [b, vocab] -> token ids [b]."""
    with jax.named_scope("sample"):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits / temperature
        if top_k > 0:
            top_vals, _ = jax.lax.top_k(logits, top_k)
            cutoff = top_vals[:, -1][:, None]
            logits = jnp.where(logits < cutoff, -1e30, logits)
        return jax.random.categorical(key, logits).astype(jnp.int32)


# ---------------------------------------------------------------------
# Shared decode kernel: `generate` (scan body), `generate_stream` and
# the continuous-batching engine (llm/engine.py) all run THIS step —
# one sampling implementation, one cache-update implementation. The
# jitted wrappers are the per-step dispatch entry points; `generate`
# inlines `_decode_step` inside its own jit/scan.
# ---------------------------------------------------------------------


def _decode_step(
    params,
    cfg: LlamaConfig,
    cache,
    last_logits,  # [b, vocab] logits of each row's last valid token
    positions,  # [] or [b] current per-row sequence length
    alive,  # [b] bool; dead rows feed token 0 (ignored downstream)
    key,
    temperature: float,
    top_k: int,
):
    """Sample one token from `last_logits`, run the single-token
    forward against the cache at `positions`, and return
    (token [b], new cache, next last_logits [b, vocab])."""
    token = _sample(last_logits, key, temperature, top_k)
    token = jnp.where(alive, token, 0)
    logits, cache = _forward_with_cache(
        params, cfg, token[:, None], cache, positions, positions + 1
    )
    return token, cache, logits[:, 0]


def accel_donate(*argnums: int):
    """`donate_argnums` for a per-step serving jit: donate (in-place
    update) on accelerator backends — decode is HBM-bound and the KV
    cache must not be copied per token — but NOT on CPU, where XLA
    donation is broken under forced host devices (same gating as
    bench.py's donate=False CPU fallback, PR 4). Called lazily so
    importing this module never initializes a backend."""
    return () if jax.default_backend() == "cpu" else argnums


_decode_step_jit = None


def decode_step(
    params,
    cfg: LlamaConfig,
    cache,
    last_logits,
    positions,
    alive,
    key,
    *,
    temperature: float = 0.0,
    top_k: int = 0,
):
    """Jitted single-step decode — the per-step dispatch entry point
    shared by `generate_stream` and the engine. Compiles once per
    (batch, cache, sampling) shape; `positions` may be per-row. On
    accelerator backends the passed-in `cache`/`last_logits` buffers
    are DONATED (updated in place): treat them as consumed and use
    the returned values."""
    global _decode_step_jit
    if _decode_step_jit is None:
        _decode_step_jit = compile_watch.instrument(
            "generate.decode_step",
            partial(
                jax.jit,
                static_argnames=("temperature", "top_k", "cfg"),
                donate_argnums=accel_donate(2, 3),
            )(_decode_step),
        )
    return _decode_step_jit(
        params, cfg, cache, last_logits, positions, alive, key,
        temperature=temperature, top_k=top_k,
    )


_prefill_jit = None


def prefill(params, cfg: LlamaConfig, tokens, cache, cache_pos, valid_len):
    """Jitted KV-cache prefill: one forward over `tokens` writing the
    cache at `cache_pos`. Shared by `generate_stream` and the engine's
    chunked prefill (one compile per (chunk, cache) shape bucket).
    `cache` is donated on accelerator backends — use the returned
    cache."""
    global _prefill_jit
    if _prefill_jit is None:
        _prefill_jit = compile_watch.instrument(
            "generate.prefill",
            partial(
                jax.jit,
                static_argnames=("cfg",),
                donate_argnums=accel_donate(3),
            )(_forward_with_cache),
        )
    return _prefill_jit(
        params, cfg, tokens, cache, cache_pos, valid_len
    )


# ---------------------------------------------------------------------
# Paged KV: one shared block pool instead of per-row [max_len] arenas.
# A sequence's cache lives in `block_len`-sized blocks scattered across
# the pool; a per-row BLOCK TABLE maps logical block j -> physical
# block id. A forward touches the pool IN PLACE: the layer loop
# carries both arrays (all layers), each layer scatters its new k/v
# at [layer, block, :, offset], and attention walks the row's table a
# TILE of entries at a time — gathering that tile's pages at the
# pool's own dtype, once per kv head (GQA queries grouped onto their
# kv head, nothing repeated) — with the online-softmax recurrence
# (float32 running max, sum and accumulator, as ops/attention.py).
# The walk stops after the longest live row's last tile (a traced
# trip count: one compiled program per shape, whatever the lengths),
# so the math equals the contiguous cache above over the keys inside
# `valid_len` and the paged engine stays token-for-token equal to
# `generate()` (llm/kv_slots.py owns the allocator/refcounting; this
# module owns the compute).
# ---------------------------------------------------------------------

#: Keys of one attention tile in a single-token step: the page
#: gathers and the two products run over this many keys of every row
#: per trip of the walk. Measured on the v5e at the benchmark's
#: geometry (PERF.md, PR 24): such a step is bound by the gathers'
#: bytes, so a shorter tile wastes less past the longest row's end
#: (128 pays more launches than it saves); a chunk's trip is bound by
#: its launches and takes twice as many keys.
PAGED_TILE_KEYS = 256


def init_block_pool(
    cfg: LlamaConfig, n_blocks: int, block_len: int
) -> Dict[str, jax.Array]:
    """The shared pool: k/v of shape
    [layers, n_blocks, kv_heads, block_len, head_dim]; for a MoE
    config also `moe_counts` [layers, E], where each paged forward
    leaves its picks per expert (`_paged_forward`)."""
    shape = (
        cfg.n_layers,
        n_blocks,
        cfg.n_kv_heads,
        block_len,
        cfg.head_dim,
    )
    pool = {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
    }
    if cfg.moe_experts:
        pool["moe_counts"] = jnp.zeros(
            (cfg.n_layers, cfg.moe_experts), jnp.int32
        )
    return pool


def paged_tile_keys(block_len: int, table_width: int, q_len: int) -> int:
    """Keys in one attention tile for a pool geometry and `q_len`
    query tokens a row: whole blocks, `PAGED_TILE_KEYS` for a
    single-token step and twice that for a chunk, never more than a
    row's table holds."""
    keys = PAGED_TILE_KEYS if q_len == 1 else 2 * PAGED_TILE_KEYS
    return max(1, min(keys // block_len, table_width)) * block_len


def paged_tiles_read(valid_len, alive, tile_keys: int):
    """Tiles of `tile_keys` keys a paged forward attends over, per
    row: up to the end of the longest ALIVE row, whole tiles. The one
    rule behind the program's trip count (traced arrays) and the
    engine's `kv_keys_read` counter (numpy arrays of the same
    lengths), so the two cannot drift. A dead row's stale length
    holds nothing open; all rows dead reads nothing."""
    longest = (valid_len * alive).max()
    return (longest + tile_keys - 1) // tile_keys


def _paged_attention(
    q: jax.Array,  # [b, heads, t, hd], rotated
    k_pool,  # [layers, n_blocks, kv_heads, block_len, hd]
    v_pool,
    layer_idx,  # [] which layer's pages
    tables: jax.Array,  # [b, whole tiles of entries] physical block ids
    q_pos: jax.Array,  # [b, t]
    valid_len: jax.Array,  # [b]
    n_tiles,  # [] traced trip count (paged_tiles_read)
    tile_blocks: int,
) -> jax.Array:
    """Attention of `q` over the pages `tables` names, read where they
    lie: -> [b, heads, t, hd] float32. Keys past a query's position or
    the row's `valid_len` are masked per tile; tiles past `n_tiles`
    are never read."""
    b, n_heads, t, hd = q.shape
    n_blocks, kv_heads, bl = k_pool.shape[1:4]
    groups = n_heads // kv_heads
    tile = tile_blocks * bl
    # The queries of one kv head side by side: row g * t + i of the
    # grouped axis is head (kv, g) at chunk position i, so both
    # products are plain matmuls against that head's keys.
    qg = q.reshape(b, kv_heads, groups * t, hd)
    pos_g = jnp.tile(q_pos, (1, groups))[:, None, :, None]
    len_g = valid_len[:, None, None, None]
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    key_offsets = jnp.arange(tile)
    # Entries wholly past a row's `valid_len` (every entry of a dead
    # row) name no key the row may see, and on the host they all name
    # the null block: gathered as they are, every row's copies hit one
    # address and the chip serialises them (a step with 15 dead rows
    # took 23.5 ms against 19.5). Each is pointed at a block of its
    # own instead; whatever it holds is masked like the null block's
    # junk.
    elsewhere = jnp.arange(b * tile_blocks).reshape(b, -1) % n_blocks

    def one_tile(j, carry):
        m, l, acc = carry
        with jax.named_scope("paged/gather_kv"):
            ids = jax.lax.dynamic_slice_in_dim(
                tables, j * tile_blocks, tile_blocks, axis=1
            )
            starts = j * tile + jnp.arange(tile_blocks) * bl
            ids = jnp.where(
                starts < valid_len[:, None], ids, elsewhere
            )
            # The layer rides inside the gather's indices: one gather
            # of [kv_heads, block_len, hd] pages, no per-layer slice
            # of the pool materialised.
            kt = k_pool[layer_idx, ids]  # [b, tile_blocks, kvH, bl, hd]
            vt = v_pool[layer_idx, ids]
        with jax.named_scope("paged/attention"):
            s = jnp.einsum(
                "bhqd,bnhkd->bhqnk", qg, kt,
                preferred_element_type=jnp.float32,
            ).reshape(b, kv_heads, groups * t, tile) * scale
            k_pos = j * tile + key_offsets
            s = jnp.where((k_pos <= pos_g) & (k_pos < len_g), s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = l * alpha + p.sum(axis=-1)
            pv = jnp.einsum(
                "bhqnk,bnhkd->bhqd",
                p.astype(vt.dtype).reshape(
                    b, kv_heads, groups * t, tile_blocks, bl
                ),
                vt,
                preferred_element_type=jnp.float32,
            )
            return m_new, l, acc * alpha[..., None] + pv

    rows = (b, kv_heads, groups * t)
    _, l, acc = jax.lax.fori_loop(
        0,
        n_tiles,
        one_tile,
        (
            jnp.full(rows, -1e30, jnp.float32),
            jnp.zeros(rows, jnp.float32),
            jnp.zeros(rows + (hd,), jnp.float32),
        ),
    )
    # A row no tile was read for (every row dead) has l == 0: its
    # output is junk nobody reads, but keep it finite.
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.reshape(b, n_heads, t, hd)


def _paged_write(pool, layer_idx, tables, q_pos, new):
    """`new` [b, kv_heads, t, hd], the k or v of tokens at positions
    `q_pos` [b, t], written into `pool` at this layer: position p of
    row i lands in physical block tables[i, p // bl] at offset p % bl.
    Rows never share a writable block (the allocator hands a block to
    one sequence; dead rows all point at the reserved null block 0,
    whose junk no live row reads unmasked), so writes only collide,
    harmlessly, on the null block."""
    _, n_blocks, kv_heads, bl, hd = pool.shape
    b, _, t, _ = new.shape
    new = new.astype(pool.dtype)
    if t < bl:
        # A token or a few: one row of head_dim per (token, kv head),
        # scattered into the pool seen as rows (a view: only major
        # dimensions merge). A scatter over [kv_heads, head_dim]
        # windows made the compiler re-lay the whole pool around
        # every layer; rows leave it in the layout the page gather
        # reads.
        phys = jnp.take_along_axis(tables, q_pos // bl, axis=1)
        rows = (
            ((layer_idx * n_blocks + phys) * kv_heads)[..., None]
            + jnp.arange(kv_heads)
        ) * bl + (q_pos % bl)[..., None]  # [b, t, kv_heads]
        flat = pool.reshape(-1, hd).at[rows.reshape(-1)].set(
            new.transpose(0, 2, 1, 3).reshape(-1, hd)
        )
        return flat.reshape(pool.shape)
    # A chunk (consecutive positions, as both callers make them):
    # row by row the scatter above took 75 us a layer for 512 tokens.
    # Instead read the blocks the chunk touches — one more than it
    # fills, in case it starts inside a block — lay the new rows over
    # them and write whole blocks back (4.3 ms a chunk saved).
    first, shift = q_pos[:, 0] // bl, q_pos[:, 0] % bl
    span = (t + bl - 2) // bl + 1
    phys = jnp.take_along_axis(
        tables, first[:, None] + jnp.arange(span), axis=1
    )
    held = pool[layer_idx, phys].transpose(0, 2, 1, 3, 4).reshape(
        b, kv_heads, span * bl, hd
    )
    held = jax.vmap(
        lambda old, rows, at: jax.lax.dynamic_update_slice(
            old, rows, (0, at, 0)
        )
    )(held, new, shift)
    return pool.at[layer_idx, phys].set(
        held.reshape(b, kv_heads, span, bl, hd).transpose(0, 2, 1, 3, 4)
    )


def _paged_layer(
    cfg: LlamaConfig,
    x: jax.Array,  # [b, t, dim]
    layer: Dict[str, jax.Array],
    layer_idx,  # [] this layer's index into the pool
    cos,
    sin,
    k_pool,  # [layers, n_blocks, kv_heads, block_len, hd]: the pool
    v_pool,
    tables: jax.Array,  # [b, whole tiles of entries] physical block ids
    q_pos: jax.Array,  # [b, t] absolute positions of x's tokens
    valid_len: jax.Array,  # [b] valid cache length incl. x
    n_tiles,  # [] attention's trip count
    tile_blocks: int,
    live=None,  # [b] rows that are real (None: all)
):
    """-> (x, k_pool, v_pool, counts): counts is the layer's picks
    per expert [E] for a MoE config, None for a dense one."""
    b, t, _ = x.shape
    with jax.named_scope("layer/attn_qkv"):
        h = model_norm(cfg, x, layer["attn_norm"])
        q, k, v = project_qkv(cfg, h, layer)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    # Write BEFORE attention so the chunk attends to its own tokens
    # (prefill self-attention).
    with jax.named_scope("paged/scatter_kv"):
        k_pool = _paged_write(k_pool, layer_idx, tables, q_pos, k)
        v_pool = _paged_write(v_pool, layer_idx, tables, q_pos, v)
    attn = _paged_attention(
        q, k_pool, v_pool, layer_idx, tables, q_pos, valid_len,
        n_tiles, tile_blocks,
    )
    with jax.named_scope("layer/attn_out"):
        attn = attn.astype(cfg.dtype).transpose(0, 2, 1, 3).reshape(
            b, t, -1
        )
        x = x + attn @ layer["wo"]
    with jax.named_scope("paged/mlp"):
        # A MoE layer's experts arrive as whole stacks (below).
        x, _, counts = _mlp(
            cfg, x, layer, live=live,
            layer_idx=layer_idx if cfg.moe_experts else None,
        )
    return x, k_pool, v_pool, counts


def _paged_forward(
    params, cfg: LlamaConfig, tokens, pool, tables, q_pos, valid_len,
    alive=True,
):
    """tokens [b, t] at absolute positions q_pos [b, t] (consecutive
    along a row) -> (logits [b, t, vocab], new pool). The paged analog
    of `_forward_with_cache`; `tables` maps each row's logical blocks
    to pool blocks and `valid_len` [b] bounds what attention may see.
    `alive` [b] names the rows whose length bounds the walk over key
    tiles (a dead row still computes, over whatever tiles the live
    ones need, and sees none of their keys). The pool is carried
    through the layer loop and written in place. For a MoE config the
    new pool also holds this forward's picks per layer and expert,
    `moe_counts` [layers, E] int32 (overwritten, not summed: the
    engine adds them up); a dead row picks no expert."""
    q_pos = jnp.asarray(q_pos, jnp.int32)
    valid_len = jnp.asarray(valid_len, jnp.int32)
    t = tokens.shape[1]
    bl, width = pool["k"].shape[3], tables.shape[1]
    tile = paged_tile_keys(bl, width, t)
    tile_blocks = tile // bl
    n_tiles = paged_tiles_read(valid_len, alive, tile)
    # A dead row sees no key: its stale length must not keep its table
    # entries (all the null block) in the gathers either.
    valid_len = valid_len * alive
    # Whole tiles of table entries, and room for the one block past
    # its last that a chunk's write reads: the padding names the null
    # block, at key positions no `valid_len` reaches.
    spare = (t + bl - 2) // bl
    tables = jnp.pad(
        tables, ((0, 0), (0, -(width + spare) % tile_blocks + spare))
    )
    with jax.named_scope("embed"):
        x = embed_tokens(cfg, params, tokens)
    cos, sin = rotary_embedding(
        q_pos, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
    )

    live = None if alive is True else alive
    # The loop slices each layer's weights out of their stacks, all
    # but a MoE layer's experts: a slice of those would be copied
    # before the grouped-matmul kernel (805 MB a layer at OLMoE's
    # widths), so they stay whole and the expert layer finds its own
    # in them (ops/moe.py). A dense model has none: its loop is as it
    # was.
    layers = params["layers"]
    experts = {n: layers[n] for n in EXPERT_LEAVES if n in layers}
    sliced = {n: w for n, w in layers.items() if n not in experts}

    def body(carry, inputs):
        x, k_pool, v_pool = carry
        layer, layer_idx = inputs
        *carry, counts = _paged_layer(
            cfg, x, {**layer, **experts}, layer_idx, cos, sin, k_pool,
            v_pool, tables, q_pos, valid_len, n_tiles, tile_blocks, live,
        )
        return tuple(carry), counts

    (x, new_k, new_v), counts = jax.lax.scan(
        body,
        (x, pool["k"], pool["v"]),
        (sliced, jnp.arange(cfg.n_layers)),
    )
    with jax.named_scope("final_norm"):
        x = model_norm(cfg, x, params["final_norm"])
    with jax.named_scope("lm_head"):
        logits = (x @ params["lm_head"]).astype(jnp.float32)
    new_pool = {"k": new_k, "v": new_v}
    if counts is not None:
        new_pool["moe_counts"] = counts
    return logits, new_pool


def _paged_prefill_impl(
    params, cfg: LlamaConfig, tokens, pool, table, offset, valid_len
):
    b, t = tokens.shape
    q_pos = (
        jnp.asarray(offset, jnp.int32)
        + jnp.broadcast_to(jnp.arange(t), (b, t))
    )
    return _paged_forward(
        params, cfg, tokens, pool, table,
        q_pos, jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32), (b,)),
    )


_paged_prefill_jit = None


def paged_prefill(
    params, cfg: LlamaConfig, tokens, pool, table, offset, valid_len
):
    """Jitted chunked prefill straight into the block pool: one
    forward over `tokens` [1, chunk] at positions [offset, offset +
    chunk) of the sequence whose block table is `table` [1, nb].
    Because the chunk shape and the table width are static while
    `offset` is traced, this compiles ONCE per (chunk, nb, model) —
    not once per prompt bucket — and a prefix-cache hit simply starts
    at a later offset with the shared blocks already in the pool.
    `pool` is donated on accelerator backends."""
    global _paged_prefill_jit
    if _paged_prefill_jit is None:
        _paged_prefill_jit = compile_watch.instrument(
            "generate.paged_prefill",
            partial(
                jax.jit,
                static_argnames=("cfg",),
                donate_argnums=accel_donate(3),
            )(_paged_prefill_impl),
        )
    return _paged_prefill_jit(
        params, cfg, tokens, pool, table, offset, valid_len
    )


def _paged_decode_step_impl(
    params,
    cfg: LlamaConfig,
    pool,
    tables,
    last_logits,
    positions,
    alive,
    key,
    temperature: float,
    top_k: int,
):
    token = _sample(last_logits, key, temperature, top_k)
    token = jnp.where(alive, token, 0)
    # Dead rows must not scatter into REAL blocks: a freed slot's
    # table is zeroed host-side, but a slot mid-admission (its table
    # already built, its prefill still running, alive not yet set)
    # would otherwise write this step's junk k/v at its STALE position
    # into the new request's — possibly shared prefix-cache — pages.
    # Masking to the null block here makes the guarantee kernel-level,
    # independent of host bookkeeping order.
    tables = jnp.where(alive[:, None], tables, 0)
    logits, pool = _paged_forward(
        params, cfg, token[:, None], pool, tables,
        positions[:, None], positions + 1, alive,
    )
    return token, pool, logits[:, 0]


_paged_decode_jit = None


def paged_decode_step(
    params,
    cfg: LlamaConfig,
    pool,
    tables,
    last_logits,
    positions,
    alive,
    key,
    *,
    temperature: float = 0.0,
    top_k: int = 0,
):
    """Jitted single-step decode over the FULL slot batch against the
    block pool (the paged analog of `decode_step`): sample one token
    per row from `last_logits`, scatter its k/v into each row's
    current block in place, and attend over the tiles of block-table
    entries the longest alive row reaches.
    Compiles once per (batch, pool, table) shape. `pool` and
    `last_logits` are donated on accelerator backends — treat them as
    consumed."""
    global _paged_decode_jit
    if _paged_decode_jit is None:
        _paged_decode_jit = compile_watch.instrument(
            "generate.paged_decode_step",
            partial(
                jax.jit,
                static_argnames=("temperature", "top_k", "cfg"),
                donate_argnums=accel_donate(2, 4),
            )(_paged_decode_step_impl),
        )
    return _paged_decode_jit(
        params, cfg, pool, tables, last_logits, positions, alive, key,
        temperature=temperature, top_k=top_k,
    )


# -- the engine's step state ------------------------------------------
# What a decode step needs besides the pool and `last_logits` lives on
# the device too, in one dict the engine owns (llm/engine.py keeps
# numpy mirrors and says when each entry changes):
#
#   tables    [slots, width] int32  each row's physical block ids
#   positions [slots] int32         where a row's next token goes
#   alive     [slots] bool          rows that decode
#   eos       [slots] int32         a row's EOS id (-1: none)
#   budget    [slots] int32         tokens a row may still emit
#   step      [] int32              steps that had a row alive
#
# The step program advances it, so step N+1 can be dispatched before
# the host has seen step N's tokens; the host only PATCHES it, one
# slot at a time with a traced index, where it changes something the
# device cannot know: a table row at admission or release
# (`patch_step_slot`) and a row's start after its prompt's last chunk
# (`finish_chunk`).


def _paged_engine_step_impl(
    params,
    cfg: LlamaConfig,
    pool,
    last_logits,
    state,
    base_key,
    temperature: float,
    top_k: int,
):
    alive = state["alive"]
    token, pool, last_logits = _paged_decode_step_impl(
        params, cfg, pool, state["tables"], last_logits,
        state["positions"], alive,
        jax.random.fold_in(base_key, state["step"]),
        temperature, top_k,
    )
    # The engine's two release rules, `stop` and `length`: a row that
    # ends here is dead in the next step and writes nothing there.
    budget = state["budget"] - alive
    state = {
        **state,
        "positions": state["positions"] + alive,
        "alive": alive & (token != state["eos"]) & (budget > 0),
        "budget": budget,
        "step": state["step"] + jnp.any(alive),
    }
    # What the host fetches when it retires the step, in buffers of
    # their own: the pool's `moe_counts` is donated to the next program
    # before the host gets to read it.
    fetch = {"token": token, "step": state["step"]}
    if "moe_counts" in pool:
        fetch["moe_counts"] = pool["moe_counts"] + 0
    return fetch, pool, last_logits, state


_paged_engine_step_jit = None


def paged_engine_step(
    params,
    cfg: LlamaConfig,
    pool,
    last_logits,
    state,
    base_key,
    *,
    temperature: float = 0.0,
    top_k: int = 0,
):
    """`paged_decode_step` with the step state on the device (layout
    above): the key is `fold_in(base_key, state["step"])`, made inside
    the program, and the state comes back advanced. -> (fetch, pool,
    last_logits, state): `fetch` holds the step's `token` [slots] (0
    in a dead row), the advanced `step` and, for a MoE config,
    `moe_counts`. `pool` and `last_logits` are donated on accelerator
    backends; the state's few kilobytes are not, so whoever holds an
    older state may still read it."""
    global _paged_engine_step_jit
    if _paged_engine_step_jit is None:
        _paged_engine_step_jit = compile_watch.instrument(
            "generate.paged_engine_step",
            partial(
                jax.jit,
                static_argnames=("temperature", "top_k", "cfg"),
                donate_argnums=accel_donate(2, 3),
            )(_paged_engine_step_impl),
        )
    return _paged_engine_step_jit(
        params, cfg, pool, last_logits, state, base_key,
        temperature=temperature, top_k=top_k,
    )


def _patch_step_slot_impl(state, slot, table_row):
    return {
        **state,
        "tables": state["tables"].at[slot].set(table_row[0]),
        "alive": state["alive"].at[slot].set(False),
    }


_patch_step_slot_jit = compile_watch.instrument(
    "generate.patch_step_slot", jax.jit(_patch_step_slot_impl)
)


def patch_step_slot(state, slot, table_row):
    """-> the state with `slot`'s table row replaced by `table_row`
    [1, width] (as `paged_prefill` takes it) and the row dead: an admission (the request's blocks; the
    row starts at its prompt's last chunk) or a release the device
    cannot know of (a cancellation: the null row). `slot` is traced:
    one program for every slot."""
    return _patch_step_slot_jit(state, slot, table_row)


def _finish_chunk_impl(
    state, last_logits, logits, moe_counts, slot, local, last,
    position, budget, eos,
):
    row = logits[0, local]
    last_logits = last_logits.at[slot].set(
        jnp.where(last, row, last_logits[slot])
    )

    def start(values, value):
        return values.at[slot].set(jnp.where(last, value, values[slot]))

    state = {
        **state,
        "positions": start(state["positions"], position),
        "alive": start(state["alive"], True),
        "budget": start(state["budget"], budget),
        "eos": start(state["eos"], eos),
    }
    fence = row[:1] if moe_counts is None else moe_counts + 0
    return state, last_logits, fence


_finish_chunk_jit = None


def finish_chunk(
    state, last_logits, logits, moe_counts, slot, local, last,
    position, budget, eos,
):
    """What follows every `paged_prefill` chunk of the engine, in one
    small program whose scalars are all traced. If the chunk was the
    prompt's `last`, the row starts: `last_logits[slot]` becomes the
    chunk's logits at its `local` position (the prompt's last token)
    and the state's row gets its `position`, `budget` and `eos` and is
    alive. Otherwise nothing changes. -> (state, last_logits, fence):
    the fence is ready when the chunk is, and small enough to keep
    while the chunk's logits of every position are dropped; for a MoE
    config it is a copy of the chunk's `moe_counts` (the pool's own is
    donated to the next program). `last_logits` is donated on
    accelerator backends."""
    global _finish_chunk_jit
    if _finish_chunk_jit is None:
        _finish_chunk_jit = compile_watch.instrument(
            "generate.finish_chunk",
            jax.jit(
                _finish_chunk_impl, donate_argnums=accel_donate(1)
            ),
        )
    return _finish_chunk_jit(
        state, last_logits, logits, moe_counts, slot, local, last,
        position, budget, eos,
    )


@partial(
    jax.jit,
    static_argnames=(
        "cfg",
        "max_new_tokens",
        "temperature",
        "top_k",
        "eos_token",
    ),
)
def generate(
    params: Dict[str, Any],
    prompt_tokens: jax.Array,  # [b, prompt_len] padded with pad_id
    prompt_lengths: jax.Array,  # [b] true lengths
    cfg: LlamaConfig,
    *,
    max_new_tokens: int = 64,
    temperature: float = 0.0,
    top_k: int = 0,
    eos_token: int = -1,
    rng: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (generated [b, max_new_tokens], lengths [b]).

    Static token budget; rows that emit `eos_token` stop counting (the
    returned per-row length excludes everything after EOS) but keep
    stepping — shapes stay static for XLA.
    """
    b, prompt_len = prompt_tokens.shape
    max_len = prompt_len + max_new_tokens
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    cache = init_kv_cache(cfg, b, max_len)

    # Phase 1: prefill the cache with the full (padded) prompt.
    logits, cache = _forward_with_cache(
        params,
        cfg,
        prompt_tokens,
        cache,
        jnp.int32(0),
        jnp.int32(prompt_len),
    )
    # Next-token logits come from each row's LAST VALID position.
    last = jnp.take_along_axis(
        logits, (prompt_lengths - 1)[:, None, None], axis=1
    )[:, 0]

    def step(carry, key):
        cache, last_logits, position, alive = carry
        token, cache, next_logits = _decode_step(
            params, cfg, cache, last_logits, position, alive, key,
            temperature, top_k,
        )
        next_alive = alive & (token != eos_token)
        return (
            (cache, next_logits, position + 1, next_alive),
            (token, alive),
        )

    keys = jax.random.split(rng, max_new_tokens)
    # NOTE: rows shorter than prompt_len decode against a cache that
    # includes pad positions; masking uses valid_len = full prefix, so
    # equal-length prompts are exact and ragged batches approximate
    # (standard left-pad serving handles raggedness upstream).
    _, (tokens, alive_flags) = jax.lax.scan(
        step,
        (cache, last, jnp.int32(prompt_len), jnp.ones(b, bool)),
        keys,
    )
    tokens = tokens.T  # [b, max_new_tokens]
    lengths = jnp.sum(alive_flags.T.astype(jnp.int32), axis=1)
    return tokens, lengths


# Rebind through the compile watch so whole-batch generation shows up
# in `rt.diagnose()`'s verdict.compile by name instead of as
# "(unregistered)". Module-level rebinding keeps the name importable
# and picklable by reference.
generate = compile_watch.instrument("generate.generate", generate)


def generate_stream(
    params: Dict[str, Any],
    prompt_tokens: jax.Array,
    prompt_lengths: jax.Array,
    cfg: LlamaConfig,
    *,
    max_new_tokens: int = 64,
    temperature: float = 0.0,
    top_k: int = 0,
    eos_token: int = -1,
    rng: Optional[jax.Array] = None,
    cache_len: Optional[int] = None,
):
    """Incremental analog of `generate`: yields one `[b]` int token
    array per decode step, as sampled — the producer side of token
    streaming (`num_returns="streaming"` actor methods hand each step
    to consumers while decoding continues). Trades the scan-fused
    decode loop for per-step dispatch of a single jitted step, so
    time-to-first-token is one prefill + one step instead of the whole
    budget. Stops early when every row has emitted `eos_token`.

    `cache_len` sets the KV cache to an EXACT fixed size so a serving
    caller compiles once per prompt bucket instead of once per
    (bucket, budget) pair (extra positions stay masked). It must hold
    the padded prompt AND every row's true length + budget — decode
    starts at per-row TRUE lengths, so a near-capacity request fits
    whenever true_len + max_new_tokens <= cache_len even if the
    padded bucket + budget would not."""
    import numpy as np

    b, prompt_len = prompt_tokens.shape
    if cache_len is not None:
        if cache_len != int(cache_len):
            raise ValueError(
                f"cache_len must be integral, got {cache_len!r}"
            )
        max_len = int(cache_len)
        needed = int(np.max(np.asarray(prompt_lengths)))
        if prompt_len > max_len or needed + max_new_tokens > max_len:
            raise ValueError(
                f"cache_len={max_len} cannot hold the padded prompt "
                f"({prompt_len}) and true length ({needed}) + "
                f"max_new_tokens ({max_new_tokens})"
            )
    else:
        max_len = prompt_len + max_new_tokens
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    cache = init_kv_cache(cfg, b, max_len)

    # Per-row valid lengths + decode positions: rows shorter than the
    # padded prompt start decoding at their TRUE length, so padding
    # never enters attention (each new token overwrites the pad KV at
    # its position before valid_len covers it) — unlike `generate`,
    # ragged batches are EXACT here.
    logits, cache = prefill(
        params, cfg, prompt_tokens, cache,
        jnp.int32(0), prompt_lengths.astype(jnp.int32),
    )
    last = jnp.take_along_axis(
        logits, (prompt_lengths - 1)[:, None, None], axis=1
    )[:, 0]

    alive = jnp.ones(b, bool)
    position = prompt_lengths.astype(jnp.int32)
    for key in jax.random.split(rng, max_new_tokens):
        token, cache, last = decode_step(
            params, cfg, cache, last, position, alive, key,
            temperature=temperature, top_k=top_k,
        )
        alive = alive & (token != eos_token)
        yield np.asarray(token)  # rt: noqa[RT303] — the stream contract IS one host token per step; this sync is the product, not overhead
        position = position + 1
        # Post-step mask: once every row has emitted EOS there is no
        # token left to produce — stop without dispatching a dead step.
        if not np.asarray(alive).any():  # rt: noqa[RT303] — early-stop predicate must reach the host; it saves whole dead dispatches, worth one scalar sync
            return
