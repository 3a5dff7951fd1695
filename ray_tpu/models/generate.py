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
from .llama import embed_tokens, model_glu, model_norm
from .llama import LlamaConfig, project_qkv


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
    h = model_norm(cfg, x, layer["mlp_norm"])
    x = x + model_glu(cfg, h @ layer["w1"], h @ layer["w3"]) @ layer["w2"]
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
# block id. Attention gathers each row's blocks back into logical
# order, so the math is identical to the contiguous cache above with
# max_len == n_logical_blocks * block_len — the paged engine stays
# token-for-token equal to `generate()` (llm/kv_slots.py owns the
# allocator/refcounting; this module owns the compute).
# ---------------------------------------------------------------------


def init_block_pool(
    cfg: LlamaConfig, n_blocks: int, block_len: int
) -> Dict[str, jax.Array]:
    """The shared pool: k/v of shape
    [layers, n_blocks, kv_heads, block_len, head_dim]."""
    shape = (
        cfg.n_layers,
        n_blocks,
        cfg.n_kv_heads,
        block_len,
        cfg.head_dim,
    )
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
    }


def _paged_layer(
    cfg: LlamaConfig,
    x: jax.Array,  # [b, t, dim]
    layer: Dict[str, jax.Array],
    cos,
    sin,
    k_pool,  # [n_blocks, kv_heads, block_len, hd] (one layer's slice)
    v_pool,
    tables: jax.Array,  # [b, n_logical_blocks] physical block ids
    q_pos: jax.Array,  # [b, t] absolute positions of x's tokens
    valid_len: jax.Array,  # [b] valid cache length incl. x
):
    b, t, _ = x.shape
    hd = cfg.head_dim
    bl = k_pool.shape[2]
    nb = tables.shape[1]
    with jax.named_scope("layer/attn_qkv"):
        h = model_norm(cfg, x, layer["attn_norm"])
        q, k, v = project_qkv(cfg, h, layer)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    # Scatter this step's k/v: position p of row i lands in physical
    # block tables[i, p // bl] at offset p % bl. Rows never share a
    # writable block (the allocator hands a block to one sequence;
    # dead rows all point at the reserved null block 0, whose junk is
    # never gathered by a live row), so the flattened scatter indices
    # only collide harmlessly on the null block.
    with jax.named_scope("paged/scatter_kv"):
        phys = jnp.take_along_axis(tables, q_pos // bl, axis=1)  # [b, t]
        off = q_pos % bl
        flat_phys = phys.reshape(-1)
        flat_off = off.reshape(-1)
        k_rows = k.transpose(0, 2, 1, 3).reshape(
            b * t, cfg.n_kv_heads, hd
        )
        v_rows = v.transpose(0, 2, 1, 3).reshape(
            b * t, cfg.n_kv_heads, hd
        )
        k_pool = k_pool.at[flat_phys, :, flat_off].set(
            k_rows.astype(k_pool.dtype)
        )
        v_pool = v_pool.at[flat_phys, :, flat_off].set(
            v_rows.astype(v_pool.dtype)
        )
    # Gather each row's cache back into logical order: [b, nb, kvH,
    # bl, hd] -> [b, kvH, nb*bl, hd]. Gather AFTER the scatter so the
    # chunk attends to its own tokens (prefill self-attention).
    with jax.named_scope("paged/gather_kv"):
        kf = k_pool[tables].transpose(0, 2, 1, 3, 4).reshape(
            b, cfg.n_kv_heads, nb * bl, hd
        )
        vf = v_pool[tables].transpose(0, 2, 1, 3, 4).reshape(
            b, cfg.n_kv_heads, nb * bl, hd
        )
        groups = cfg.n_heads // cfg.n_kv_heads
        kf = jnp.repeat(kf, groups, axis=1)
        vf = jnp.repeat(vf, groups, axis=1)
    with jax.named_scope("paged/attention"):
        scale = 1.0 / jnp.sqrt(jnp.float32(hd))
        logits = (
            jnp.einsum(
                "bhqd,bhkd->bhqk",
                q.astype(jnp.float32),
                kf.astype(jnp.float32),
            )
            * scale
        )
        k_pos = jnp.arange(nb * bl)
        mask = (k_pos[None, None, :] <= q_pos[:, :, None]) & (
            k_pos[None, None, :] < valid_len[:, None, None]
        )  # [b, t, nb*bl]
        logits = jnp.where(mask[:, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        attn = jnp.einsum(
            "bhqk,bhkd->bhqd", probs, vf.astype(jnp.float32)
        )
    with jax.named_scope("layer/attn_out"):
        attn = attn.astype(cfg.dtype).transpose(0, 2, 1, 3).reshape(
            b, t, -1
        )
        x = x + attn @ layer["wo"]
    with jax.named_scope("paged/mlp"):
        h = model_norm(cfg, x, layer["mlp_norm"])
        x = x + (
            model_glu(cfg, h @ layer["w1"], h @ layer["w3"])
            @ layer["w2"]
        )
    return x, k_pool, v_pool


def _paged_forward(
    params, cfg: LlamaConfig, tokens, pool, tables, q_pos, valid_len
):
    """tokens [b, t] at absolute positions q_pos [b, t] -> (logits
    [b, t, vocab], new pool). The paged analog of
    `_forward_with_cache`; `tables` maps each row's logical blocks to
    pool blocks and `valid_len` [b] bounds what attention may see."""
    q_pos = jnp.asarray(q_pos, jnp.int32)
    valid_len = jnp.asarray(valid_len, jnp.int32)
    with jax.named_scope("embed"):
        x = embed_tokens(cfg, params, tokens)
    cos, sin = rotary_embedding(
        q_pos, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
    )

    def body(carry, inputs):
        x = carry
        layer, k_pool, v_pool = inputs
        x, k_pool, v_pool = _paged_layer(
            cfg, x, layer, cos, sin, k_pool, v_pool, tables, q_pos,
            valid_len,
        )
        return x, (k_pool, v_pool)

    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["layers"], pool["k"], pool["v"])
    )
    with jax.named_scope("final_norm"):
        x = model_norm(cfg, x, params["final_norm"])
    with jax.named_scope("lm_head"):
        logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, {"k": new_k, "v": new_v}


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
        positions[:, None], positions + 1,
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
    current block, and gather-attend over the row's block table.
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
