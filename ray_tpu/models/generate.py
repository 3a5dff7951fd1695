"""Autoregressive decoding for the Llama family, over a paged KV pool.

The serving-side counterpart of models/llama.py (the reference serves
models through vLLM-on-Ray rather than shipping its own decoder; a
TPU-native framework needs one in-tree). The continuous-batching
engine (llm/engine.py) drives these jitted programs, each compiled
once per shape:

  * `paged_prefill` — one forward over a fixed-size chunk of a prompt,
    writing its k/v into the sequence's blocks of the shared pool;
  * `paged_decode_step` — one token for every slot: sample from the
    rows' last logits (greedy / temperature / top-k), write the
    token's k/v, attend over the row's blocks;
  * `paged_engine_step`, `patch_step_slot`, `finish_chunk` — the same
    step with its state kept on the device, so the engine can dispatch
    the next program before it has fetched the last one's tokens.

The pool is [layers, blocks, kv_heads, block_len, head_dim]; a row's
block table maps its logical blocks to pool blocks (llm/kv_slots.py
owns the allocator and the refcounts; this module owns the compute).
A layer that is no attention (a gated short convolution) keeps a STATE
that belongs to a row and not to a page, in slots of a leaf of the same
pool (`_conv_mix`, llm/kv_state.py).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp

from .._private import compile_watch
from ..ops.latent_expand import latent_expand
from ..ops.moe import held_row_budget
from ..ops.paged_attention import paged_attention
from ..ops.norms import (
    apply_rotary, layer_norm, rms_norm, rotary_embedding, yarn_mscale,
)
from ..ops.selected_attention import selected_attention
from .llama import embed_tokens, model_norm
from .llama import EXPERT_LEAVES, AttnKind, LlamaConfig, _mlp


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


def accel_donate(*argnums: int):
    """`donate_argnums` for a per-step serving jit: donate (in-place
    update) on accelerator backends — decode is HBM-bound and the KV
    cache must not be copied per token — but NOT on CPU, where XLA
    donation is broken under forced host devices. Called lazily so
    importing this module never initializes a backend."""
    return () if jax.default_backend() == "cpu" else argnums


# ---------------------------------------------------------------------
# Paged KV: one shared block pool. A sequence's cache lives in
# `block_len`-sized blocks scattered across the pool; a per-row BLOCK
# TABLE maps logical block j -> physical block id. A forward touches
# the pool IN PLACE: the layer loop carries both arrays (all layers),
# each layer scatters its new k/v
# at [layer, block, :, offset], and attention reads each alive row's
# own TILES of table entries up to its `valid_len`, nothing of a dead
# row. A single-token step over a pool with a kv-head axis and entries
# of whole lanes is one Pallas kernel a layer, which reads the pages
# where they lie, a row at a time (ops/paged_attention.py). A chunk, a
# latent pool and a pool whose entries are not whole lanes (a DMA
# cannot slice those) walk a WORK LIST of the
# forward's live (row, tile) pairs — a TILE of a row's table entries
# each, as many pairs a trip as the forward has rows — gathering the
# pairs' pages at the pool's own dtype, once per kv head (GQA queries
# grouped onto their kv head, nothing repeated), and merging each
# pair's softmax sums into its row's (float32 running max, sum and
# accumulator, as ops/attention.py). The list holds each live row's
# tiles up to its own `valid_len` and nothing of a dead row, and the
# walk stops with it (a traced trip count: one compiled program per
# shape, whatever the lengths), so the math equals plain causal
# attention over the keys inside `valid_len` and the engine stays
# token-for-token equal to greedy decoding by the uncached
# `llama.forward`.
# ---------------------------------------------------------------------

#: Keys of one attention tile in a single-token step: the page
#: gathers and the two products run over this many keys of every
#: (row, tile) pair of a trip. Measured on the v5e at the benchmark's
#: geometry (PERF.md, PR 24 and PR 40): a trip of 16 pairs is bound by
#: its gathers' bytes — 13.7 us a layer at 2 KV heads (the k and the v
#: gather 4.0 us each for 2 MB, the products, the mask and the by-row
#: merge the rest), 102 us at 16 (23.9 us each for 16.8 MB; the two
#: products 16 us each) — so a shorter tile wastes less past a row's
#: end (128 pays more launches than it saves); a chunk's trip is bound
#: by its launches and takes twice as many keys.
PAGED_TILE_KEYS = 256


#: The pool's leaves that hold no cache: what a paged forward counted,
#: left there for the engine to fetch (overwritten, not summed).
COUNTER_LEAVES = ("moe_counts", "moe_routed", "moe_spilled", "dsa_counts")


def cache_leaves(pool: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """The pool's cache arrays, whatever a configuration names them."""
    return {n: a for n, a in pool.items() if n not in COUNTER_LEAVES}


def counter_leaves(pool: Dict[str, jax.Array]):
    """The counters a paged forward left in the pool, or None where a
    configuration counts nothing. Whoever fetches them copies them
    first (`+ 0`): the pool's own are donated to the next program
    before the host reads them."""
    return {n: pool[n] for n in COUNTER_LEAVES if n in pool} or None


def _lanes(width: int) -> int:
    """`width` rounded up to whole TPU lanes (128): what a row of a
    tiled array occupies on the chip whatever its declared width. A
    latent entry of 576 numbers is declared 640 wide, the rest zero."""
    return -(-width // 128) * 128


def _pad_last(x, width: int):
    """x with zeros behind its last axis up to `width`."""
    return jnp.pad(
        x, ((0, 0),) * (x.ndim - 1) + ((0, width - x.shape[-1]),)
    )


class KindTables(NamedTuple):
    """A row's (or every slot's) block table in each kind's pool of a
    `layer_kinds` model: what `paged_prefill` and `patch_step_slot`
    take as the row's table. In the step's state the second lies under
    `window_rings`, beside `tables`."""

    full: jax.Array  # [b, width]
    window: jax.Array  # [b, ring]: logical block j at entry j mod ring


class StateTables(NamedTuple):
    """A row's (or every slot's) table of pages and its STATE slots,
    of a model with conv layers (`AttnKind.conv`): what `paged_prefill`
    and `patch_step_slot` take as the row's table. The state of a conv
    layer is a row's, not a page's: a slot of the pool's `conv_state`
    holds the columns before the row's next position, and the forward
    is told which slot it starts from, which it leaves the state in,
    and which one more takes a copy (a SNAPSHOT at a whole-chunk
    boundary, for the prefix cache: llm/kv_state.py). In the step's
    state a row's own slot lies under `state_slots`, beside `tables`."""

    full: jax.Array  # [b, width]
    #: [b, 4] int32: the slot read, the slot written, the slot a
    #: snapshot goes to (0, the null slot: none), and the row's length
    #: (-1: not given; the chunk's tokens then end at its last nonzero
    #: token, `_state_plan`)
    conv: jax.Array


#: The pool's k and v leaves of each cache of plain attention
#: (`AttnKind.cache`): the full-attention layers' under the names every
#: model of one kind has, a `layer_kinds` model's window layers' beside
#: them. Where a head's key and value fit ONE row of lanes together
#: (`_joint_kv`) they are one leaf, the first name with a `v` behind.
KIND_LEAVES = {"full": ("k", "v"), "window": ("window_k", "window_v")}
#: The pool's leaf of the conv layers' states.
STATE_LEAF = "conv_state"


class _Cache(NamedTuple):
    """One cache a model keeps and the attention that reads it: what
    `_pool_plan` reads off a `LlamaConfig`, and all that the pool, a
    forward's plans and its walk over the layers know of a family."""

    layers: tuple  # the model's layers that keep it, in order
    #: pool leaf -> (a page's axes before block_len, an entry's width)
    leaves: Dict[str, tuple]
    attend: Callable  # its layers' attention half
    groups: int  # query heads a cached head (a work list tiles q_pos so)
    rotary: tuple  # (dims, base) of the rotary embedding its layers apply
    out_scope: str  # what a trace names its layers' residual through `wo`
    window: int = 0  # keys a query sees (0: all before it)
    #: >0: no pages at all. Its leaves are [layers, state, slots, width],
    #: this many columns a slot (a conv layer's `taps - 1`).
    state: int = 0


def _pool_plan(cfg: LlamaConfig):
    """THE reading of a `LlamaConfig` for the serve path -> ({cache:
    `_Cache`}, {counter leaf: shape}): which caches the pool holds
    (`init_block_pool` says why each is laid out as it is), which
    attention half reads each, and what a forward counts. The pool and
    `_paged_forward` both follow it and ask the configuration nothing
    else of its family: latent attention (`kv_lora_rank`) is one cache
    of entries without a kv-head axis; `layer_kinds` a cache a kind;
    any other model is the one-kind case, every layer a full-attention
    layer of `n_kv_heads` at `rope_theta` (a head as wide as it is,
    not whole lanes: the pool such a model has had).

    `moe_counts` has an entry an expert layer; a model whose layers
    lie in stacks of their own (the first two families) holds a share
    of the experts it routes over and counts `moe_routed` and
    `moe_spilled` beside it."""
    for key in ("attn_gate", "post_norms"):
        if getattr(cfg, key):
            raise NotImplementedError(
                f"the paged forwards (models/generate.py) have no {key}: "
                "their block has no gate on the attention output and no "
                "norm on a half's output; such a configuration runs on "
                "the train path only (models/llama.py)"
            )
    every = tuple(range(cfg.n_layers))
    counters = {}
    if cfg.kv_lora_rank:
        leaves = {
            "latent": ((), _lanes(cfg.kv_lora_rank + cfg.qk_rope_head_dim))
        }
        if cfg.index_topk:
            leaves["index_k"] = ((), _lanes(cfg.index_head_dim))
            counters["dsa_counts"] = (cfg.n_layers, 2)
        caches = {"latent": _Cache(
            every, leaves, _latent_attend, 1,
            (cfg.qk_rope_head_dim, cfg.rope_theta), "mla/out",
        )}
    else:
        kinds = cfg.attn_kinds() if cfg.layer_kinds else {
            "full": (AttnKind(0, cfg.n_kv_heads, cfg.rope_theta), every)
        }
        lanes = _lanes if cfg.layer_kinds else int
        widths = (cfg.head_dim, cfg.v_head_dim or cfg.head_dim)
        caches = {}
        for name, (kind, layers) in kinds.items():
            if kind.conv:
                caches[name] = _Cache(
                    tuple(layers), {STATE_LEAF: ((), cfg.dim)},
                    partial(_conv_mix, kind=kind), 1, (0, 0.0),
                    "layer/conv_out", state=kind.conv - 1,
                )
                continue
            leaves = dict(zip(KIND_LEAVES[name], map(lanes, widths)))
            if _joint_kv(cfg):
                leaves = {KIND_LEAVES[name][0] + "v": _lanes(sum(widths))}
            caches[name] = _Cache(
                tuple(layers),
                {leaf: ((kind.kv_heads,), width)
                 for leaf, width in leaves.items()},
                partial(_paged_attend, kind=kind),
                cfg.n_heads // kind.kv_heads,
                (cfg.rotary_dim or cfg.head_dim, kind.rope_theta),
                "layer/attn_out", kind.window,
            )
    if cfg.moe_experts:
        expert_layers = cfg.n_layers - cfg.dense_layers
        counters["moe_counts"] = (expert_layers, cfg.moe_experts)
        if cfg.kv_lora_rank or cfg.layer_kinds:
            counters["moe_routed"] = (expert_layers,)
            counters["moe_spilled"] = (expert_layers,)
    return caches, counters


def _joint_kv(cfg: LlamaConfig) -> bool:
    """Whether a head's key and value lie side by side in ONE pool
    entry, `[v | k]`: a `layer_kinds` model whose keys are as wide as
    its values and fit one row of lanes with them (heads of 64). Each
    padded to lanes of its own they would take twice the bytes a token;
    together they take what they are, the queries meet the entry behind
    zeros where the value lies, and the values are the entry's leading
    dims (`_paged_attention`'s `v_width`, as a latent entry is key and
    value in one). A model whose values are narrower than its keys
    (MiMo-V2) keeps a leaf each, as it has."""
    alike = cfg.v_head_dim in (0, cfg.head_dim)
    return bool(cfg.layer_kinds) and alike and 2 * cfg.head_dim <= 128


def init_block_pool(
    cfg: LlamaConfig, n_blocks, block_len: int
) -> Dict[str, jax.Array]:
    """The shared pool: k/v of shape
    [layers, n_blocks, kv_heads, block_len, head_dim]; for a MoE
    config also `moe_counts` [layers, E], where each paged forward
    leaves its picks per expert (`_paged_forward`).

    A latent-attention config (`kv_lora_rank`) caches no k and v: one
    `latent` entry a token a layer, [layers, n_blocks, block_len,
    kv_lora_rank + rope dims] (the compressed key-value latent and the
    one rotary key all heads share), and where an indexer selects the
    keys (`index_topk`) its key beside it, `index_k` [..., index dim]:
    both are functions of the token prefix alone, so they live under
    the same block tables, allocator and prefix cache, and a shared
    page stays shareable. (No unit axis where the kv heads stand, and
    an entry as wide as whole lanes, `_lanes`: the TPU keeps an array
    whose rows are not whole lanes with another axis innermost, and
    the compiler then re-laid the whole pool around every step.) Its
    counters (`COUNTER_LEAVES`) count expert layers only, and
    `dsa_counts` [layers, 2] the (query, key) pairs each layer's
    attention could see and did attend.

    A `layer_kinds` model: k and v of each kind of layer under
    `KIND_LEAVES`' names, [the kind's layers, the kind's own
    `n_blocks[cache]`, its kv heads, block_len, width]: a pool a kind,
    since a window layer keeps a row's last keys and a full layer all
    of them (llm/kv_window.py has the bookkeeping). A plain number of
    blocks gives both kinds that many: a caller that keeps ONE id
    space and hands every kind a row's one full-width table. Counters
    as a latent pool's: an entry an expert layer. Heads of 64 keep
    key and value in one entry, leaf `kv` (`_joint_kv`).

    Conv layers (`AttnKind.conv`) keep no pages: `conv_state` [conv
    layers, taps - 1, `n_blocks["conv"]` state slots, dim], a row's
    columns before its next position in a slot of its own and the
    prefix cache's snapshots in further slots (llm/kv_state.py); slot
    0 is the null slot. (The slots are the rows of a plane a column:
    with a whole number of 16-row tiles of them, `PagedKVCache` sees
    to that, the leaf seen as rows of `dim` is the leaf as it lies; as
    [.., slots, 2, dim] the chip pads the 2 to a tile and re-lays the
    whole leaf around every layer's write, 46 % of the device's time in
    PR 52's first traced run.) A plain number gives
    as many slots as blocks: the caller that keeps one id space, whose
    rows' slots are their tables' first block ids (`_state_plan`)."""
    caches, counters = _pool_plan(cfg)
    pool = {}
    for name, cache in caches.items():
        blocks = n_blocks[name] if isinstance(n_blocks, dict) else n_blocks
        for leaf, (heads, width) in cache.leaves.items():
            shape = (blocks, *heads, block_len, width)
            if cache.state:
                shape = (cache.state, blocks, width)
            pool[leaf] = jnp.zeros((len(cache.layers), *shape), cfg.dtype)
    for name, shape in counters.items():
        pool[name] = jnp.zeros(shape, jnp.int32)
    return pool


def window_view_blocks(window: int, block_len: int, q_len: int) -> int:
    """Table entries of a window layer's VIEW of a row in a forward of
    `q_len` consecutive queries: the blocks that hold the `window - 1`
    keys before the first query, the queries' own, and the one more a
    chunk's write reads (`_paged_write`). What a row's ring of window
    pages must at least hold (llm/kv_window.py)."""
    return (window - 1 + q_len - 1) // block_len + 2


def window_first_key(q_first, window: int, block_len: int):
    """Position of the first key of a window layer's view: the start
    of the block that holds the earliest key the query at `q_first`
    (a forward's first, [b]) may see. numpy or traced alike: the one
    rule behind the program's view and the engine's `swa_keys_read`."""
    return (q_first - (window - 1)).clip(0) // block_len * block_len


def _window_view(tables, q_first, window: int, block_len: int, q_len: int):
    """`tables` [b, R], a row's RING of window pages (logical block j
    at entry j mod R: a page is overwritten in place once its last key
    has left every later query's window), seen as a plain table of the
    blocks a forward's queries can see -> (view [b, entries], the
    position of the view's first key [b]). Positions less that first
    key, the view is a row's table like any other: the write, the work
    list and the attention walk it as they walk a full layer's."""
    ring = tables.shape[1]
    entries = window_view_blocks(window, block_len, q_len)
    if ring < entries:
        raise ValueError(
            f"a ring of {ring} window pages a row is narrower than the "
            f"{entries} blocks a forward of {q_len} queries sees"
        )
    first_key = window_first_key(q_first, window, block_len)
    blocks = first_key[:, None] // block_len + jnp.arange(entries)
    return jnp.take_along_axis(tables, blocks % ring, axis=1), first_key


def paged_tile_keys(
    block_len: int, table_width: int, q_len: int, in_place: bool = False
) -> int:
    """Keys in one attention tile for a pool geometry and `q_len`
    query tokens a row: whole blocks, `PAGED_TILE_KEYS` for a
    single-token step that walks the work list and twice that for a
    chunk and for a step whose kernel reads the pool `in_place` (a tile
    there is a trip of a row's loop, 0.7 us of fixed cost whatever it
    holds: 0.66 ms a layer at 256 keys against 0.57 at 512, 64 rows of
    2.6k keys x 8 heads), never more than a row's table holds."""
    one = q_len == 1 and not in_place
    keys = PAGED_TILE_KEYS if one else 2 * PAGED_TILE_KEYS
    return max(1, min(keys // block_len, table_width)) * block_len


def paged_row_tiles(valid_len, alive, tile_keys: int):
    """Tiles of `tile_keys` keys that a paged forward's attention reads
    of each row [b]: an alive row's `ceil(valid_len / tile_keys)`, none
    of a dead one whatever its stale length. THE rule of what the
    attention walks, for traced arrays (the program: the kernel's trips
    a row, the work list's trip count) and numpy ones of the same
    lengths (the engine's `kv_keys_read`), so the two cannot drift."""
    return (valid_len * alive + tile_keys - 1) // tile_keys


def paged_tiles_read(valid_len, alive, tile_keys: int):
    """Trips of the WORK LIST's walk (`_paged_attention`): the list
    holds the live (row, tile) pairs (`paged_row_tiles`), row after
    row, and a trip takes as many pairs as the forward has rows (so
    the page gather keeps the shape PR 24 tuned): a trip reads `rows x
    tile_keys` keys, and the trips are the pairs over the rows, rounded
    up. All rows dead reads nothing; one row (a prefill chunk) walks
    its own tiles, one a trip."""
    pairs = paged_row_tiles(valid_len, alive, tile_keys).sum()
    rows = valid_len.shape[0]
    return (pairs + rows - 1) // rows


def _reads_in_place(cache: _Cache, pool) -> bool:
    """Whether a single-token step's attention over `cache`'s pages is
    the kernel's (ops/paged_attention.py), which reads each alive row's
    tiles where they lie: a pool with the kv-head axis whose entries
    are whole lanes. What the code can see of a pool and nothing else.
    A latent pool has no such axis (its step gathers a selection, or
    walks the list with absorbed queries); an entry that is not whole
    lanes (a one-kind model's head of 64) is padded to them in the
    chip's memory, and a DMA cannot slice it."""
    return all(
        pool[leaf].ndim == 5 and pool[leaf].shape[-1] % 128 == 0
        for leaf in cache.leaves
    )


def step_reads_in_place(cfg: LlamaConfig, pool) -> Dict[str, bool]:
    """Cache of pages (`_pool_plan`) -> `_reads_in_place`: what the
    engine's `kv_keys_read` asks, as the program's plans do."""
    return {
        name: _reads_in_place(cache, pool)
        for name, cache in _pool_plan(cfg)[0].items() if not cache.state
    }


def paged_keys_read(valid_len, alive, tile_keys: int, in_place: bool):
    """Keys a paged forward's attention reads of the pool, a layer:
    each alive row's whole tiles where the kernel walks them
    (`in_place`), a tile for every row of the forward a trip where the
    work list is walked."""
    if in_place:
        return tile_keys * paged_row_tiles(valid_len, alive, tile_keys).sum()
    return valid_len.shape[0] * tile_keys * paged_tiles_read(
        valid_len, alive, tile_keys
    )


def _paged_work_list(
    tables, q_pos, valid_len, tile_blocks: int, block_len: int,
    n_blocks: int,
):
    """The live (row, tile) pairs of a paged forward, made once for
    all its layers from `tables` [b, whole tiles of entries], `q_pos`
    [b, queries] and `valid_len` [b] (0 for a dead row). Pair p is
    the tile `p - start_of_row` of the row whose tiles span p, rows in
    order; the list is as long as the tables have tiles, and a pair
    past the live ones (padding) is no row's and sees no key. -> dict
    of [pairs, ...] arrays: `ids` [tile_blocks] the pool blocks of the
    pair's tile, `pos` [queries] its row's `q_pos`, and `at` [3]: its
    row (b, which is none, for padding), the position of its first key
    and its row's `valid_len` (0 for padding)."""
    b, entries = tables.shape
    n_pairs = b * (entries // tile_blocks)
    tile = tile_blocks * block_len
    tiles = (valid_len + tile - 1) // tile
    ends = jnp.cumsum(tiles)
    p = jnp.arange(n_pairs)
    owner = (p[:, None] >= ends).sum(axis=1)  # b past the live pairs
    live, row = owner < b, jnp.minimum(owner, b - 1)
    tile_of = jnp.where(live, p - (ends - tiles)[row], 0)
    length = jnp.where(live, valid_len[row], 0)
    blocks = tile_of[:, None] * tile_blocks + jnp.arange(tile_blocks)
    # Entries wholly past a row's `valid_len` (in its last tile; every
    # entry of a padding pair) name no key the row may see, and on the
    # host they all name the null block: gathered as they are, every
    # pair's copies hit one address and the chip serialises them (a
    # step with 15 dead rows took 23.5 ms against 19.5). Each is
    # pointed at a block of its own instead; whatever it holds is
    # masked like the null block's junk.
    elsewhere = jnp.arange(n_pairs * tile_blocks).reshape(n_pairs, -1)
    ids = jnp.where(
        blocks * block_len < length[:, None],
        tables[row[:, None], blocks],
        elsewhere % n_blocks,
    )
    at = jnp.stack([owner, tile_of * tile, length], axis=1)
    return {"ids": ids, "pos": q_pos[row], "at": at}


def _paged_attention(
    q: jax.Array,  # [b, heads, t, hd], rotated
    k_pool,  # [layers, n_blocks, kv_heads, block_len, hd]
    v_pool,
    layer_idx,  # [] which layer's pages
    work,  # the forward's live (row, tile) pairs (_paged_work_list)
    n_trips,  # [] traced trip count (paged_tiles_read)
    scale=None,  # the softmax scale where it is not hd ** -0.5
    v_width=None,  # the leading dims of a page that are its value
    window: int = 0,  # keys a query sees, itself the last (0: all before)
    sink=None,  # [heads] a logit a head in the softmax's denominator
) -> jax.Array:
    """Attention of `q` over the pages the work list names, read where
    they lie: -> [b, heads, t, hd] float32 ([.., v_width] where that is
    given: a latent page is key and value in one). A trip takes b
    pairs off the list (`paged_tiles_read`): their pages in one gather, each
    pair's scores over its own tile against its own row's queries
    (keys past a query's position or the row's `valid_len` masked),
    and the pairs' softmax sums merged into their rows' running ones.
    Pairs past `n_trips` trips are never read."""
    b, n_heads, t, hd = q.shape
    # (a latent pool has no kv-head axis: its pages are one head's)
    paged_heads = k_pool.ndim == 5
    kv_heads, bl = k_pool.shape[2:4] if paged_heads else (1, k_pool.shape[2])
    tile_blocks = work["ids"].shape[1]
    groups = n_heads // kv_heads
    tile = tile_blocks * bl
    # The queries of one kv head side by side: row g * t + i of the
    # grouped axis is head (kv, g) at chunk position i, so both
    # products are plain matmuls against that head's keys.
    qg = q.reshape(b, kv_heads, groups * t, hd)
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    # (values may be narrower than keys: the accumulator is theirs)
    hd = v_pool.shape[-1] if v_width is None else v_width
    key_offsets = jnp.arange(tile)
    rows = jnp.arange(b)

    def one_trip(j, carry):
        m, l, acc = carry
        pair = {
            name: jax.lax.dynamic_slice_in_dim(of_all, j * b, b)
            for name, of_all in work.items()
        }
        ids, (row, first_key, length) = pair["ids"], pair["at"].T
        with jax.named_scope("paged/gather_kv"):
            # The layer rides inside the gather's indices: one gather
            # of [kv_heads, block_len, hd] pages, no per-layer slice
            # of the pool materialised.
            kt = k_pool[layer_idx, ids]  # [b, tile_blocks, kvH, bl, hd]
            vt = v_pool[layer_idx, ids]
            if not paged_heads:
                kt, vt = kt[:, :, None], vt[:, :, None]
            if v_width is not None:
                vt = vt[..., :v_width]
        with jax.named_scope("paged/attention"):
            # (a padding pair's row, b, is clamped to the last by the
            # gather: any will do, it sees no key)
            s = jnp.einsum(
                "bhqd,bnhkd->bhqnk", qg[row], kt,
                preferred_element_type=jnp.float32,
            ).reshape(b, kv_heads, groups * t, tile) * scale
            k_pos = (first_key[:, None] + key_offsets)[:, None, None]
            seen = (k_pos <= pair["pos"][:, None, :, None]) & (
                k_pos < length[:, None, None, None]
            )
            if window:
                seen &= k_pos > pair["pos"][:, None, :, None] - window
            s = jnp.where(seen, s, -1e30)
        with jax.named_scope("paged/merge"):
            # The split-key softmax merge, by row: a row's new max is
            # its old one or that of its pairs of this trip; every
            # pair's weights are taken against its own row's new max
            # (as a lone tile's are against the running max), and
            # their sums are added up by row. A padding pair is no
            # row's: its junk is added to nothing.
            mine = (row[:, None] == rows)[..., None, None]  # [pair, row, 1, 1]
            m_new = jnp.maximum(
                m, jnp.where(mine, s.max(axis=-1)[:, None], -1e30).max(axis=0)
            )
            m_row = jnp.where(mine, m_new, -1e30).max(axis=1)
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_row[..., None])
            pv = jnp.einsum(
                "bhqnk,bnhkd->bhqd",
                p.astype(vt.dtype).reshape(
                    b, kv_heads, groups * t, tile_blocks, bl
                ),
                vt,
                preferred_element_type=jnp.float32,
            )
            l = l * alpha + jnp.where(
                mine, p.sum(axis=-1)[:, None], 0.0
            ).sum(axis=0)
            acc = acc * alpha[..., None] + jnp.where(
                mine[..., None], pv[:, None], 0.0
            ).sum(axis=0)
            return m_new, l, acc

    per_row = (b, kv_heads, groups * t)
    if sink is None:
        m0 = jnp.full(per_row, -1e30, jnp.float32)
        l0 = jnp.zeros(per_row, jnp.float32)
    else:
        # The sink is one more term of every query's softmax sum that
        # carries no value: the walk starts from it, (m, l, acc) =
        # (sink, 1, 0), laid out as the grouped queries are.
        m0 = jnp.broadcast_to(
            jnp.repeat(
                sink.astype(jnp.float32).reshape(kv_heads, groups), t, axis=1
            ), per_row,
        )
        l0 = jnp.ones(per_row, jnp.float32)
    _, l, acc = jax.lax.fori_loop(
        0,
        n_trips,
        one_trip,
        (m0, l0, jnp.zeros(per_row + (hd,), jnp.float32)),
    )
    # A row no pair was read for (a dead row) has l == 0: its output
    # is junk nobody reads, but keep it finite.
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


def _qkv_flat(cfg: LlamaConfig, h, layer, kind: AttnKind):
    """The first half of `llama.project_qkv`, the train layer's
    one-piece form of the same arithmetic: the three products and what
    the configuration has that acts on a whole projection (Qwen2-family
    biases, OLMoE's norm, a `value_scale` on the values), heads not
    yet split. h: [b, t, dim] -> each of q/k/v: [b, t, heads *
    head_dim]. The training layer and the serve layer must use the
    SAME projection or their logits silently diverge; the two share
    arithmetic and not code while an edit of models/llama.py would
    cost the train cells their byte-identical programs, and
    tests/test_serve_projection_pin.py holds `_split_heads(_qkv_flat)`
    equal to `project_qkv` bit for bit in every family. (`kind`
    stands where `project_qkv` has it and is not read: what a kind
    changes of a projection, its kv heads and its values' width, is
    in the weights' own shapes.)"""
    q, k, v = h @ layer["wq"], h @ layer["wk"], h @ layer["wv"]
    if cfg.attn_bias:
        q, k, v = q + layer["bq"], k + layer["bk"], v + layer["bv"]
    if cfg.qk_norm == "proj":
        q = rms_norm(q, layer["q_norm"], eps=cfg.norm_eps)
        k = rms_norm(k, layer["k_norm"], eps=cfg.norm_eps)
    if cfg.value_scale != 1.0:
        v = v * jnp.asarray(cfg.value_scale, v.dtype)
    return q, k, v


def _row_major(h, flat):
    """`flat`, the projections of `h` [b, t, dim] about to be split
    into heads, held as the matmul leaves them. Left free, the
    compiler folds the head split's transposition into the WEIGHT: it
    slices `wq` / `wk` (a chunk's `wv` too) out of their stacks and
    copies them heads-major before the product, a layer a forward (8
    MB of `wq` at qwen2.5-3b's widths, 100 MB at MiMo's), where the
    activation it would otherwise re-lay has 16 to 512 rows. Held, the
    product reads its weight in the stack, as `wo`'s and the FFN's do,
    and the transposition falls on the activation. Where the
    activation has as many rows as the weight (OLMoE's chunk of 2,048)
    it is no longer the smaller side, and the compiler keeps its
    choice."""
    b, t, dim = h.shape
    if b * t >= dim:
        return flat
    return jax.lax.optimization_barrier(flat)


def _split_heads(cfg: LlamaConfig, q, k, v, layer, kind: AttnKind):
    """The second half of `llama.project_qkv`: each of q/k/v [b, t,
    heads * head_dim] -> [b, heads, t, head_dim] (+ Qwen3's norm a
    head, BEFORE RoPE): as many kv heads as the key projection holds
    heads of `head_dim`, values as wide as theirs leaves them."""
    b, t, _ = q.shape
    kv_heads = k.shape[-1] // cfg.head_dim

    def heads(x, n):
        return x.reshape(b, t, n, -1).transpose(0, 2, 1, 3)

    q, k, v = heads(q, cfg.n_heads), heads(k, kv_heads), heads(v, kv_heads)
    if cfg.qk_norm == "head":
        q = rms_norm(q, layer["q_norm"], eps=cfg.norm_eps)
        k = rms_norm(k, layer["k_norm"], eps=cfg.norm_eps)
    return q, k, v


def _attend_pages(
    q, k_pool, v_pool, layer_idx, plan, *, scale=None, v_width=None,
    window: int = 0, sink=None,
):
    """Attention of `q` over its rows' pages as `plan` says they are
    walked (`_paged_plan`), `_paged_attention`'s arguments and result:
    a plan with a work list walks it; one without (a step over a pool
    the kernel reads in place, `_reads_in_place`) is the kernel's, a row at
    a time over `plan["tiles"]` tiles of its table."""
    if "work" in plan:
        return _paged_attention(
            q, k_pool, v_pool, layer_idx, plan["work"], plan["n_trips"],
            scale=scale, v_width=v_width, window=window, sink=sink,
        )
    out = paged_attention(
        q, k_pool, None if v_pool is k_pool else v_pool, layer_idx,
        plan["tables"], plan["tiles"], plan["q_pos"][:, 0],
        plan["valid_len"], tile_blocks=plan["tile_blocks"],
        scale=q.shape[-1] ** -0.5 if scale is None else scale,
        window=window, sink=sink,
    )
    return out if v_width is None else out[..., :v_width]


def _paged_attend(cfg: LlamaConfig, h, layer, cache, plan, at, *, kind):
    """The attention half of a layer of plain attention, of `kind`:
    h [b, t, dim] the normed activation, `layer` its weights, `cache`
    the pool's cache leaves, `plan` its kind's (`_plans`: pool, table,
    positions and work list; a window layer's are its VIEW of the
    row, `_window_view`), `at` its index into its kind's leaves ->
    (the attention's output [b, heads, t, value width] before `wo`,
    the cache, no counts of its own)."""
    k_name, v_name = KIND_LEAVES[kind.cache]
    with jax.named_scope("layer/attn_qkv"):
        flat = _row_major(h, _qkv_flat(cfg, h, layer, kind))
        q, k, v = _split_heads(cfg, *flat, layer, kind)
        q = apply_rotary(q, plan["cos"], plan["sin"])
        k = apply_rotary(k, plan["cos"], plan["sin"])
    if k_name + "v" in cache:
        # Key and value in one entry, `[v | k]` (`_joint_kv`): one
        # write, and the queries behind zeros where the value lies.
        pool = cache[k_name + "v"]
        v_width = v.shape[-1]
        with jax.named_scope("paged/scatter_kv"):
            entry = _pad_last(jnp.concatenate([v, k], -1), pool.shape[-1])
            pool = _paged_write(pool, at, plan["tables"], plan["q_pos"], entry)
            q = _pad_last(
                jnp.concatenate([jnp.zeros_like(q[..., :v_width]), q], -1),
                pool.shape[-1],
            )
        with jax.named_scope(f"attn/{kind.cache}"):
            out = _attend_pages(
                q, pool, pool, at, plan,
                scale=cfg.head_dim ** -0.5, v_width=v_width,
                window=kind.window, sink=layer.get("sink"),
            )
        return out, {**cache, k_name + "v": pool}, {}
    k_pool, v_pool = cache[k_name], cache[v_name]
    # Write BEFORE attention so the chunk attends to its own tokens
    # (prefill self-attention).
    with jax.named_scope("paged/scatter_kv"):
        v_width, scale = v.shape[-1], None
        if k.shape[-1] != k_pool.shape[-1]:
            # Whole lanes, as the pool keeps them; the softmax scale
            # stays that of the head as it is.
            q, k = (_pad_last(a, k_pool.shape[-1]) for a in (q, k))
            v = _pad_last(v, v_pool.shape[-1])
            scale = cfg.head_dim ** -0.5
        k_pool = _paged_write(k_pool, at, plan["tables"], plan["q_pos"], k)
        v_pool = _paged_write(v_pool, at, plan["tables"], plan["q_pos"], v)
    with jax.named_scope(f"attn/{kind.cache}"):
        out = _attend_pages(
            q, k_pool, v_pool, at, plan,
            scale=scale, window=kind.window, sink=layer.get("sink"),
        )[..., :v_width]
    return out, {**cache, k_name: k_pool, v_name: v_pool}, {}


def _paged_plan(
    tables, q_pos, valid_len, alive, n_blocks: int, bl: int, groups: int,
    in_place: bool = False,
):
    """What a paged forward's attention walks, made once for all its
    layers from `tables` [b, width], `q_pos` [b, t], `valid_len` [b]
    and `alive` -> the plan: `tables` padded to whole tiles,
    `valid_len` with a dead row's 0, `q_pos`, and either the work list
    of live (row, tile) pairs with `q_pos` tiled `groups` times and its
    trip count (`work`, `n_trips`), or, where a step's kernel reads the
    pool `in_place`, each row's `tiles` of `tile_blocks` entries."""
    t, width = q_pos.shape[1], tables.shape[1]
    tile = paged_tile_keys(bl, width, t, in_place)
    tile_blocks = tile // bl
    # (the trip count before the rest, where the work list's programs
    # have had it: they lower to the text they had)
    if in_place:
        walk = dict(
            tiles=paged_row_tiles(valid_len, alive, tile),
            tile_blocks=tile_blocks,
        )
    else:
        n_trips = paged_tiles_read(valid_len, alive, tile)
    # A dead row sees no key: its stale length adds no pair to the
    # work list.
    valid_len = valid_len * alive
    # Whole tiles of table entries, and room for the one block past
    # its last that a chunk's write reads: the padding names the null
    # block, at key positions no `valid_len` reaches.
    spare = (t + bl - 2) // bl
    tables = jnp.pad(
        tables, ((0, 0), (0, -(width + spare) % tile_blocks + spare))
    )
    if not in_place:
        walk = dict(n_trips=n_trips, work=_paged_work_list(
            tables, jnp.tile(q_pos, (1, groups)), valid_len, tile_blocks,
            bl, n_blocks,
        ))
    return dict(tables=tables, q_pos=q_pos, valid_len=valid_len, **walk)


# ---------------------------------------------------------------------
# Latent attention with a learned selection of keys (DeepSeek-V3.2: MLA
# under DSA). The cache holds one `latent` entry a token a layer (the
# compressed key-value latent and the rotary key all heads share) and
# the indexer's key `index_k` beside it. A layer: project q through its
# latent and ABSORB the keys' expansion into it (q~_i = qN_i Wkv_b[i]^T,
# so a head's score is q~_i . cKV + qR_i . kR against the cache entry
# itself: 128 queries a latent entry, no key or value expanded); write
# the token's entry and indexer key; the indexer scores every live key
# over the work list's (row, tile) pairs; each query keeps its
# `index_topk` best; attention runs over those alone; values are the
# latents, expanded after the softmax sum (Wkv_b's value part), then
# Wo. That is a DECODE STEP: its rows each GATHER their own selected
# entries, a shorter list. A CHUNK's selection differs by query (2,048
# queries x 2,048 of up to 16k keys: a gather of 4 M cache rows a
# layer), and absorbed scores cost 3.4 times the operations of a
# head's own (2 x (576 + 512) a pair against 2 x (192 + 128)), which
# a step hides behind the weights it reads and a chunk does not. So a
# chunk takes latent attention's OTHER form, the one the equations are
# written in: the live tiles' latents are expanded to every head's
# keys and values once a layer (`_expand_latent`), and a flash kernel
# runs dense over them and attends where the selection allows
# (ops/selected_attention.py).
# ---------------------------------------------------------------------


def _latent_write(pool, layer_idx, tables, q_pos, new):
    """`_paged_write` for a pool leaf with no kv-head axis: `new`
    [b, t, width], the entries of tokens at positions `q_pos` [b, t],
    into `pool` [layers, n_blocks, block_len, width] at this layer; a
    token or a few as rows, a chunk as whole blocks."""
    _, n_blocks, bl, width = pool.shape
    b, t, _ = new.shape
    new = _pad_last(new.astype(pool.dtype), width)
    if t < bl:
        phys = jnp.take_along_axis(tables, q_pos // bl, axis=1)
        rows = (layer_idx * n_blocks + phys) * bl + q_pos % bl
        flat = pool.reshape(-1, width).at[rows.reshape(-1)].set(
            new.reshape(-1, width)
        )
        return flat.reshape(pool.shape)
    first, shift = q_pos[:, 0] // bl, q_pos[:, 0] % bl
    span = (t + bl - 2) // bl + 1
    phys = jnp.take_along_axis(
        tables, first[:, None] + jnp.arange(span), axis=1
    )
    held = jax.vmap(
        lambda old, rows, at: jax.lax.dynamic_update_slice(
            old, rows, (at, 0)
        )
    )(pool[layer_idx, phys].reshape(b, span * bl, width), new, shift)
    return pool.at[layer_idx, phys].set(
        held.reshape(b, span, bl, width)
    )


def _sortable(x):
    """float32 -> uint32 whose order is the floats' (-inf lowest;
    -0.0 and 0.0 one value, as they compare)."""
    i = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    i = jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)
    return jax.lax.bitcast_convert_type(i, jnp.uint32) ^ jnp.uint32(
        0x80000000
    )


def _kth_largest(keys, k: int):
    """keys [..., n] uint32 -> [...]: the k-th largest of each row,
    exactly, bit by bit from the top: 32 counting passes and no sort
    (a sort of a chunk's [queries, keys] scores is the slower by far)."""
    def bit(i, found):
        trial = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= trial[..., None], axis=-1) >= k
        return jnp.where(enough, trial, found)

    return jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.uint32)
    )


def _index_scores(qi, wi, index_pool, layer_idx, work, n_trips, keys):
    """The indexer over the work list: qi [b, index heads, t, dim]
    rotated, wi [b, index heads, t] float32, the pool's `index_k` ->
    I [b, t, keys] float32: sum_j wi_j ReLU(qi_j . kI_s) at every
    key s a query may see (inside its row's `valid_len`, not after
    it), -inf elsewhere. A trip scores b pairs' tiles, as
    `_paged_attention` walks them."""
    b, _, t, dim = qi.shape
    bl = index_pool.shape[2]
    tile = work["ids"].shape[1] * bl
    key_offsets = jnp.arange(tile)

    def one_trip(j, scores):
        pair = {
            name: jax.lax.dynamic_slice_in_dim(of_all, j * b, b)
            for name, of_all in work.items()
        }
        ids, (row, first_key, length) = pair["ids"], pair["at"].T
        with jax.named_scope("paged/gather_kv"):
            kt = index_pool[layer_idx, ids].reshape(b, tile, -1)[..., :dim]
        s = jnp.einsum(
            "bhtd,bkd->bhtk", qi[row], kt,
            preferred_element_type=jnp.float32,
        )
        s = jnp.sum(jax.nn.relu(s) * wi[row][..., None], axis=1)
        k_pos = (first_key[:, None] + key_offsets)[:, None]  # [b, 1, tile]
        seen = (k_pos <= pair["pos"][..., None]) & (
            k_pos < length[:, None, None]
        )
        s = jnp.where(seen, s, -jnp.inf)
        return _write_tiles(scores, s, row, first_key)

    return jax.lax.fori_loop(
        0, n_trips, one_trip,
        jnp.full((b, t, keys), -jnp.inf, jnp.float32),
    )


def _write_tiles(into, tiles, row, first_key):
    """`tiles` [pairs, ..., tile], pair i's slab of keys written into
    `into` [rows, ..., keys] at row `row[i]` from key `first_key[i]`
    on; a padding pair (`row[i]` past the rows) writes back what was
    there. One pair a trip (a chunk: one row) walks its live tiles
    alone and meets no padding pair."""
    rows, pairs = into.shape[0], tiles.shape[0]
    for i in range(pairs):
        at = [jnp.minimum(row[i], rows - 1)] + [0] * (into.ndim - 1)
        at[-1] = first_key[i]
        new = tiles[i][None].astype(into.dtype)
        if pairs > 1:
            old = jax.lax.dynamic_slice(into, at, new.shape)
            new = jnp.where(row[i] < rows, new, old)
        into = jax.lax.dynamic_update_slice(into, new, at)
    return into


def _expand_latent(latent_pool, layer_idx, tables, valid_len, wk, wv, tile):
    """A row's cache entries as every head's keys and values: -> (kn
    [rows, keys, heads x dn], kr [rows, keys, dr], v [rows, keys, heads
    x dv]) in the pool's dtype, the heads side by side as the matmul
    leaves them. `wk` [latent, heads x dn] and `wv` [latent, heads x
    dv] are Wkv_b's two parts (dn, dv whole lanes); dr lanes of an
    entry behind its latent are the rotary key all heads share. ONE
    gather of the row's table gives the entries (the null block's
    where the table is padding: finite), and the kernel writes each of
    the row's live tiles of `tile` keys once, where `selected_attention`
    reads it; kn and v past them are never written and hold anything
    (ops/latent_expand.py)."""
    rows = tables.shape[0]
    latent_width, width = wk.shape[0], latent_pool.shape[-1]
    with jax.named_scope("paged/gather_kv"):
        entries = latent_pool[layer_idx, tables].reshape(rows, -1, width)
    # (a latent narrower than its lanes: what else they hold meets
    # rows of zeros)
    rest = ((0, _lanes(latent_width) - latent_width), (0, 0))
    kn, v = latent_expand(
        entries, jnp.pad(wk, rest), jnp.pad(wv, rest),
        -(-valid_len // tile), block_k=tile,
    )
    return kn, entries[..., latent_width:], v


def _latent_attend(cfg: LlamaConfig, h, layer, cache, plan, layer_idx):
    """The attention half of a latent-attention layer: h [b, t, dim]
    the normed activation, `layer` its weights, `cache` the pool's
    cache leaves, `plan` the forward's (`_plans`; its work list's
    `pos` a row's q_pos), `layer_idx` its index into the pool -> (the
    attention's output [b, heads, t, v_head_dim] before `wo`, the
    cache, the layer's `dsa_counts` [2] where it has an indexer:
    visible pairs, attended pairs)."""
    b, t, _ = h.shape
    cos, sin, tables, q_pos, valid_len, work, n_trips = (
        plan[n] for n in (
            "cos", "sin", "tables", "q_pos", "valid_len", "work", "n_trips"
        )
    )
    heads, dt = cfg.n_heads, cfg.dtype
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    kvr, vd = cfg.kv_lora_rank, cfg.v_head_dim
    scale = (nope + rope) ** -0.5
    if cfg.rope_scaling is not None and cfg.rope_scaling[0] == "yarn":
        scale *= yarn_mscale(cfg.rope_scaling[1]) ** 2
    step = t == 1  # absorbed queries against cache entries, else heads

    def rotated(v):  # rotary on the rope dims, which lead
        return apply_rotary(v, cos, sin)

    wkv_b = layer["wkv_b"].reshape(kvr, heads, nope + vd)
    with jax.named_scope("mla/q"):
        cq = rms_norm(h @ layer["wq"], layer["q_norm"], eps=cfg.norm_eps)
        q = (cq @ layer["wq_b"]).reshape(b, t, heads, nope + rope)
        q = q.transpose(0, 2, 1, 3)
        q_nope, q_rope = q[..., :nope], apply_rotary(q[..., nope:], cos, sin)
        if step:
            # absorbed: [b, heads, 1, kvr + rope] against a cache entry
            q = _pad_last(jnp.concatenate([
                jnp.einsum("bhtn,chn->bhtc", q_nope, wkv_b[..., :nope]),
                q_rope,
            ], axis=-1), cache["latent"].shape[-1])
    with jax.named_scope("mla/kv_latent"):
        kv = h @ layer["wkv_a"]  # [b, t, kvr + rope]
        entry = jnp.concatenate([
            rms_norm(kv[..., :kvr], layer["kv_norm"], eps=cfg.norm_eps),
            apply_rotary(kv[:, None, :, kvr:], cos, sin)[:, 0],
        ], axis=-1)
        # Written BEFORE attention: a chunk attends to its own tokens.
        cache = dict(cache, latent=_latent_write(
            cache["latent"], layer_idx, tables, q_pos, entry
        ))
    latent = cache["latent"]
    keys = tables.shape[1] * latent.shape[2]
    counts = {}
    allowed = picked = None
    if cfg.index_topk:
        ih, idim = cfg.index_n_heads, cfg.index_head_dim
        with jax.named_scope("dsa/index"):
            qi = rotated(
                (cq @ layer["wiq"]).reshape(b, t, ih, idim)
                .transpose(0, 2, 1, 3)
            )
            ki = rotated(layer_norm(
                h @ layer["wik"], layer["ik_norm"], layer["ik_bias"]
            )[:, None])[:, 0]
            wi = (h @ layer["wiw"]).astype(jnp.float32).transpose(
                0, 2, 1
            ) * (ih ** -0.5 * idim ** -0.5)
            cache["index_k"] = _latent_write(
                cache["index_k"], layer_idx, tables, q_pos, ki
            )
            index = _index_scores(
                qi, wi, cache["index_k"], layer_idx, work, n_trips, keys
            )
        with jax.named_scope("dsa/select"):
            visible = index > -jnp.inf
            top = min(cfg.index_topk, keys)
            if step:
                best, picked = jax.lax.top_k(index[:, 0], top)  # [b, top]
                allowed = best > -jnp.inf
            else:
                ranks = _sortable(index)
                allowed = visible & (
                    ranks >= _kth_largest(ranks, top)[..., None]
                )
            counts["dsa_counts"] = jnp.stack(
                [visible.sum(), allowed.sum()]
            ).astype(jnp.int32)
    with jax.named_scope("mla/attend"):
        if picked is not None:
            # A step's rows each gather their own selected entries:
            # key s of row i lies in block tables[i, s // bl].
            n_blocks, bl, width = latent.shape[1:]
            rows = (
                layer_idx * n_blocks
                + jnp.take_along_axis(tables, picked // bl, axis=1)
            ) * bl + picked % bl
            with jax.named_scope("paged/gather_kv"):
                chosen = latent.reshape(-1, width)[rows]  # [b, top, width]
            s = jnp.einsum(
                "bhd,bkd->bhk", q[:, :, 0], chosen,
                preferred_element_type=jnp.float32,
            ) * scale
            s = jnp.where(allowed[:, None], s, -1e30)
            p = jnp.exp(s - s.max(axis=-1, keepdims=True))
            out = jnp.einsum(
                "bhk,bkc->bhc", p.astype(dt), chosen[..., :kvr],
                preferred_element_type=jnp.float32,
            ) / p.sum(axis=-1, keepdims=True)
            out = out[:, :, None]  # [b, heads, 1, kvr]
        elif step:
            out = _paged_attention(
                q, latent, latent, layer_idx,
                dict(work, pos=jnp.tile(work["pos"], (1, heads))),
                n_trips, scale=scale, v_width=kvr,
            )
        else:
            if allowed is None:  # no indexer: every visible key
                k_pos = jnp.arange(keys)
                allowed = (k_pos <= q_pos[..., None]) & (
                    k_pos < valid_len[:, None, None]
                )
            tile = work["ids"].shape[1] * latent.shape[2]
            kn, kr, v = _expand_latent(
                latent, layer_idx, tables, valid_len,
                _pad_last(wkv_b[..., :nope], _lanes(nope)).reshape(kvr, -1),
                _pad_last(wkv_b[..., nope:], _lanes(vd)).reshape(kvr, -1),
                tile,
            )
            out = selected_attention(
                _pad_last(q_nope, _lanes(nope)),
                _pad_last(q_rope, kr.shape[-1]), kn, kr, v,
                allowed.astype(jnp.int8), q_pos[:, 0], valid_len,
                scale=scale, block_k=tile,
            )[..., :vd]  # [b, heads, t, vd]: the heads' own values
    if step:
        with jax.named_scope("mla/out"):
            out = jnp.einsum(
                "bhtc,chv->bhtv", out.astype(dt), wkv_b[..., nope:]
            )
    return out, cache, counts


# ---------------------------------------------------------------------
# Gated short convolutions among attention layers (LFM2: `layer_types`
# "conv"). Such a layer has no keys. With n the normed activation:
# [B | C | u] = n W_in, z = B * u, c_t = sum_j w_j z_(t - (taps-1) + j)
# (a depthwise causal convolution over positions, `taps` of them, z zero
# before the row's first token), and the mixer's output is (C * c) W_out.
# All a later position needs of a row is z at its last `taps - 1`
# positions: a STATE of [taps - 1, dim] numbers a layer, whatever the
# row's length, kept in a slot of the pool's `conv_state` that belongs
# to the row (llm/kv_state.py has the bookkeeping). A forward reads the
# columns where it starts, convolves its chunk or its one position, and
# leaves the columns before the row's next position: at the row's last
# VALID position, not at a padded chunk's end.
# ---------------------------------------------------------------------


def _state_plan(table, tokens, q_pos):
    """What the conv layers of a paged forward are told, made once for
    all of them: -> dict of `read` [b] (the slot a row's columns are
    read from), `write` [b, 2] (the slots they are left in: the row's
    own and a snapshot's, the null slot 0 for none), `fresh` [b] (a row
    that starts at position 0 starts from zeros) and `n_valid` [b] (how
    many of the forward's positions are the row's).

    `table` is a `StateTables`, or a PLAIN table of pages [b, width]:
    the caller that keeps one id space for every cache
    (`init_block_pool` with a plain number), whose row's slot is its
    table's first block id; it keeps no snapshot and gives no length.
    A step's one position is always the row's (a dead row's slots are
    the null slot). Of a chunk, where no length is given, the row's
    tokens end at the last that is not 0: whoever hands a padded chunk
    over without its length (the benchmark's probe) pads with 0 and
    draws no 0, and the engine, whose prompts may hold any id, gives
    the length."""
    b, t = tokens.shape
    if isinstance(table, StateTables):
        read, own, snapshot, length = table.conv.T
    else:
        read = own = table[:, 0]
        snapshot, length = jnp.zeros_like(own), jnp.full_like(own, -1)
    if t == 1:
        n_valid = jnp.ones((b,), jnp.int32)
    else:
        by_tokens = jnp.max(
            jnp.where(tokens != 0, jnp.arange(1, t + 1), 0), axis=1
        )
        n_valid = jnp.clip(
            jnp.where(length >= 0, length - q_pos[:, 0], by_tokens), 0, t
        ).astype(jnp.int32)
    return dict(
        read=read, write=jnp.stack([own, snapshot], axis=1),
        fresh=q_pos[:, 0] == 0, n_valid=n_valid,
    )


def _conv_mix(cfg: LlamaConfig, h, layer, cache, plan, at, *, kind):
    """The mixer of a conv layer, where an attention layer has its
    attention half: h [b, t, dim] the normed activation, `layer` its
    weights (`wq` `wc` `wu` the thirds B, C, u of W_in, `taps`), `cache`
    the pool's cache leaves, `plan` the forward's (`_state_plan`), `at`
    its index into `conv_state` -> (C * c as [b, 1, t, dim], one head
    as wide as the model before `wo` = W_out, the cache, no counts)."""
    b, t, dim = h.shape
    held = kind.conv - 1
    state = cache[STATE_LEAF]  # [conv layers, held, slots, dim]
    slots = state.shape[2]
    # A slot's column j is row (at * held + j) * slots + slot of the
    # state seen as rows of `dim`: read and written as rows, the form
    # `_paged_write` scatters a token in.
    flat, columns = state.reshape(-1, dim), at * held + jnp.arange(held)
    f32 = jnp.float32
    with jax.named_scope("layer/conv_in"):
        gate = h @ layer["wc"]
        z = (h @ layer["wq"]) * (h @ layer["wu"])
    with jax.named_scope("layer/conv"):
        before = jnp.where(
            plan["fresh"][:, None, None], 0,
            flat[columns * slots + plan["read"][:, None]],
        ).astype(z.dtype)
        zz = jnp.concatenate([before, z], axis=1)  # [b, held + t, dim]
        taps = layer["taps"].astype(f32)
        c = sum(
            taps[j] * zz[:, j:j + t].astype(f32) for j in range(kind.conv)
        )
        out = gate.astype(f32) * c
        # What the row's next position needs: the `held` columns up to
        # its last valid one (column i of `zz` is position start - held
        # + i), into the row's slot and the snapshot's.
        keep = jax.vmap(
            lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, held, axis=0)
        )(zz, plan["n_valid"]).astype(state.dtype)
        rows = columns * slots + plan["write"][..., None]  # [b, 2, held]
        state = flat.at[rows.reshape(-1)].set(
            jnp.broadcast_to(keep[:, None], (b, 2, held, dim)).reshape(-1, dim)
        ).reshape(state.shape)
    return out[:, None], {**cache, STATE_LEAF: state}, {}


def _serve_block(
    cfg: LlamaConfig,
    x: jax.Array,  # [b, t, dim]
    layer: Dict[str, jax.Array],  # its weights; experts as whole stacks
    cache: Dict[str, jax.Array],  # the pool's cache leaves
    spec: _Cache,  # its cache's: which attention half it runs
    plan,  # what its cache's layers walk in this forward (`_plans`)
    at,  # [] its index into its cache's leaves
    ffn_idx,  # [] and into its FFN's stack
    live,  # [b] rows that are real (None: all)
    counted,  # the counters the pool has leaves for
):
    """THE layer of a paged forward, whatever the family: norm, an
    attention half (which writes the layer's cache and returns the
    attention's output before `wo`), the residual through `wo`, the
    FFN -> (x, cache, counts): of `counted`, the layer's `moe_counts`
    [E held], `moe_routed` [] and `moe_spilled` [] (an expert layer:
    its picks per expert, the picks over all the router's outputs, a
    dead row picking none, and 1 where the held picks passed
    `ops/moe.py`'s `held_row_budget`, so the layer took every row)
    and what its attention counted (`dsa_counts`)."""
    b, t, _ = x.shape
    with jax.named_scope("layer/attn_qkv"):
        h = model_norm(cfg, x, layer["attn_norm"])
    out, cache, counts = spec.attend(cfg, h, layer, cache, plan, at)
    with jax.named_scope(spec.out_scope):
        out = out.astype(cfg.dtype).transpose(0, 2, 1, 3).reshape(b, t, -1)
        x = x + out @ layer["wo"]
    with jax.named_scope("paged/mlp"):
        # (a leading dense layer of an expert model has no router)
        x, _, picks = _mlp(
            cfg, x, layer, live=live,
            layer_idx=ffn_idx if "router" in layer else None,
        )
        if picks is not None:
            counts["moe_counts"] = picks
            if "moe_routed" in counted:
                rows_live = b if live is None else live.sum()
                counts["moe_routed"] = jnp.asarray(
                    rows_live * t * cfg.moe_top_k, jnp.int32
                )
                counts["moe_spilled"] = (picks.sum() > held_row_budget(
                    b * t * cfg.moe_top_k, cfg.moe_experts,
                    layer["router"].shape[-1],
                )).astype(jnp.int32)
    return x, cache, counts


def _plans(caches, pool, tables, q_pos, valid_len, alive, tokens):
    """What the layers of each of a model's caches (`_pool_plan`) walk
    in one paged forward, made once for all of them: -> {cache: plan},
    a plan holding the cache's `tables`, `q_pos`, `valid_len` and what
    its attention walks (`_paged_plan`: the work list, or for a step's
    kernel each row's tiles). `tables` holds a row's table in
    each kind's pool (`KindTables`), or is one [b, width] table for
    every cache. A window layer's plan is made from its VIEW of the
    row (`_window_view`), so it walks the tiles that hold a key some
    query of this forward can see and no other: for a step the last
    block or two of tiles, for a chunk its own and the window before
    it. A cache of STATE (conv layers) has no pages and no work list:
    its plan is the rows' slots and how many of the forward's `tokens`
    are each row's (`_state_plan`)."""
    plans = {}
    for name, cache in caches.items():
        if cache.state:
            plans[name] = _state_plan(tables, tokens, q_pos)
            continue
        shape = pool[next(iter(cache.leaves))].shape
        n_blocks, bl = shape[1], shape[-2]
        # (a plain table serves every kind: a ring as wide as the row)
        table = tables
        if isinstance(tables, (KindTables, StateTables)):
            table = getattr(tables, name)
        pos, valid = q_pos, valid_len
        if cache.window:
            table, first_key = _window_view(
                table, q_pos[:, 0], cache.window, bl, q_pos.shape[1]
            )
            pos, valid = q_pos - first_key[:, None], valid_len - first_key
        # (the queries of a kv head's group lie side by side, as
        # `_paged_attention` lays them)
        plans[name] = _paged_plan(
            table, pos, valid, alive, n_blocks, bl, cache.groups,
            in_place=q_pos.shape[1] == 1 and _reads_in_place(cache, pool),
        )
    return plans


def _paged_forward(
    params, cfg: LlamaConfig, tokens, pool, tables, q_pos, valid_len,
    alive=True, row=None,
):
    """tokens [b, t] at absolute positions q_pos [b, t] (consecutive
    along a row) -> (logits [b, t, vocab], new pool). `tables` maps
    each row's logical blocks to pool blocks and `valid_len` [b]
    bounds what attention may see.
    Which positions the head is run for is the caller's to say: with
    `row` [b] (traced, a position of the forward a row) the final norm
    and the head see that one position of each row and the logits are
    [b, vocab]; the walk over the layers, the pool and the counters
    are the same.
    `alive` [b] names the rows whose key tiles attention walks (a
    dead row still computes, and sees no key). The pool is carried
    through the walk over the layers and written in place. The new
    pool also holds what this forward counted, in the leaves the pool
    has for it (`COUNTER_LEAVES`; overwritten, not summed: the engine
    adds them up): `moe_counts`, `moe_routed` and `moe_spilled` an
    entry an expert layer, `dsa_counts` one a layer.

    ONE walk for every family. What differs between them is what
    `_pool_plan` read off the configuration: which caches there are,
    which attention half reads each, and which stacks hold the layers
    (`dense_layers`, where the model has leading dense layers, then
    `layers`; what a kind changes of the attention in a stack of its
    own, `attn_full` / `attn_window` / `attn_conv`, beside them)."""
    q_pos = jnp.asarray(q_pos, jnp.int32)
    valid_len = jnp.asarray(valid_len, jnp.int32)
    live = None if alive is True else alive
    caches, counters = _pool_plan(cfg)
    plans = _plans(caches, pool, tables, q_pos, valid_len, alive, tokens)
    with jax.named_scope("embed"):
        x = embed_tokens(cfg, params, tokens)
    for name, spec in caches.items():
        if spec.state:
            continue  # (a conv layer turns nothing)
        plans[name]["cos"], plans[name]["sin"] = rotary_embedding(
            q_pos, *spec.rotary, cfg.rope_scaling
        )
    #: model layer -> (its cache, its index into that cache's leaves)
    where = {
        layer: (name, at) for name, spec in caches.items()
        for at, layer in enumerate(spec.layers)
    }

    def block(x, cache, layer, name, at, ffn_idx):
        return _serve_block(
            cfg, x, layer, cache, caches[name], plans[name], at, ffn_idx,
            live, tuple(counters),
        )

    cache = cache_leaves(pool)
    counted: Dict[str, list] = {name: [] for name in counters}
    #: what a kind changes of the attention, where it lies in a stack
    #: of its own over that kind's layers (`llama.kinds_layer_shapes`)
    apart = {n: params[f"attn_{n}"] for n in caches if f"attn_{n}" in params}
    stacks = [params[n] for n in ("dense_layers", "layers") if n in params]
    first = 0  # the stack's first layer, of the model's
    for layers in stacks:
        depth = layers["attn_norm"].shape[0]
        # A layer's weights are sliced out of their stacks, all but a
        # MoE layer's experts: a slice of those would be copied
        # before the grouped-matmul kernel (805 MB a layer at OLMoE's
        # widths), so they stay whole and the expert layer finds its
        # own in them (ops/moe.py). A dense model has none. The other
        # slices are read inside their matmuls, where the stack holds
        # them, as long as the product comes out row-major: the
        # projections that feed a head split are held so
        # (`_row_major`), or the compiler would fold the split's
        # transposition into `wq` / `wk` and copy them out a layer.
        experts = {n: layers[n] for n in EXPERT_LEAVES if n in layers}
        sliced = {n: w for n, w in layers.items() if n not in experts}
        if not apart:
            # The stack's layers are alike: one body, scanned.
            name, _ = where[first]

            def body(carry, inputs):
                layer, i = inputs
                # (a model of one stack counts its layers as the scan
                # does: no `0 +` in the program it has had)
                *carry, counts = block(
                    *carry, {**layer, **experts}, name,
                    first + i if len(stacks) > 1 else i, i,
                )
                return tuple(carry), counts

            (x, cache), counts = jax.lax.scan(
                body, (x, cache), (sliced, jnp.arange(depth))
            )
            parts = [counts]
        else:
            # They alternate in kind, each layer with its kind's plan
            # and its place in its kind's stacks. Where the stack
            # holds two or more whole PERIODS of the pattern (LFM2's
            # conv, conv, attention, conv: ten of them) they are ONE
            # body, a period long, scanned; what is left behind them,
            # and a stack of under two periods (MiMo's six layers),
            # is unrolled. A period of one layer is the scan above.
            names = [where[first + i][0] for i in range(depth)]
            period = next(
                p for p in range(1, depth + 1)
                if all(names[i] == names[i + p] for i in range(depth - p))
            )
            whole = depth // period if depth >= 2 * period else 0
            #: layers of a kind in a period: how far a kind's index
            #: into its stacks moves from one period to the next
            per = {n: names[:period].count(n) for n in apart}

            def one(carry, i, trip=0):
                """The stack's layer `i`, `trip` periods on."""
                name, at = where[first + i]
                at, i = at + trip * per[name], i + trip * period
                layer = {
                    **{n: w[at] for n, w in apart[name].items()},
                    **{n: w[i] for n, w in sliced.items()},
                    **experts,
                }
                return block(*carry, layer, name, at, i)

            def stacked(per_layer):
                return {
                    n: jnp.stack([c[n] for c in per_layer])
                    for n in per_layer[0]
                }

            def trip_body(carry, trip):
                per_layer = []
                for i in range(period):
                    *carry, layer_counts = one(carry, i, trip)
                    per_layer.append(layer_counts)
                return tuple(carry), stacked(per_layer)

            parts = []
            if whole:
                (x, cache), scanned = jax.lax.scan(
                    trip_body, (x, cache), jnp.arange(whole)
                )
                parts.append({
                    n: v.reshape(-1, *v.shape[2:]) for n, v in scanned.items()
                })
            per_layer = []
            for i in range(whole * period, depth):
                x, cache, layer_counts = one((x, cache), i)
                per_layer.append(layer_counts)
            if per_layer:
                parts.append(stacked(per_layer))
        for part in parts:
            for name, value in part.items():
                counted[name].append(value)
        first += depth
    if row is not None:
        x = jax.vmap(
            lambda h, at: jax.lax.dynamic_index_in_dim(h, at, keepdims=False)
        )(x, row)
    with jax.named_scope("final_norm"):
        x = model_norm(cfg, x, params["final_norm"])
    with jax.named_scope("lm_head"):
        logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, {
        **cache, **{n: jnp.concatenate(v) for n, v in counted.items()},
    }


def _paged_prefill_impl(
    params, cfg: LlamaConfig, tokens, pool, table, offset, valid_len,
    row=None,
):
    b, t = tokens.shape
    q_pos = (
        jnp.asarray(offset, jnp.int32)
        + jnp.broadcast_to(jnp.arange(t), (b, t))
    )

    def a_row(value):
        return jnp.broadcast_to(jnp.asarray(value, jnp.int32), (b,))

    return _paged_forward(
        params, cfg, tokens, pool, table, q_pos, a_row(valid_len),
        row=None if row is None else a_row(row),
    )


_paged_prefill_jit = None


def paged_prefill(
    params, cfg: LlamaConfig, tokens, pool, table, offset, valid_len,
    row=None,
):
    """Jitted chunked prefill straight into the block pool: one
    forward over `tokens` [1, chunk] at positions [offset, offset +
    chunk) of the sequence whose block table is `table` [1, nb].
    Because the chunk shape and the table width are static while
    `offset` is traced, this compiles ONCE per (chunk, nb, model) —
    not once per prompt bucket — and a prefix-cache hit simply starts
    at a later offset with the shared blocks already in the pool.
    -> (logits [1, chunk, vocab], pool), or with `row` (traced: a
    position of the chunk, 0 its first) the logits [1, vocab] of that
    one position, the only ones such a program computes: what a caller
    that reads one row asks for (the engine: a prompt's last token).
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
        params, cfg, tokens, pool, table, offset, valid_len, row
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
    tables = jax.tree.map(
        lambda table: jnp.where(alive[:, None], table, 0), tables
    )
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
    block pool: sample one token per row from `last_logits`, scatter
    its k/v into each row's current block in place, and attend over
    each alive row's own tiles of block-table entries, a batch of
    (row, tile) pairs a trip (`paged_tiles_read`). Compiles once per
    (batch, pool, table) shape. `pool` and `last_logits` are donated
    on accelerator backends — treat them as consumed."""
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
    tables = state["tables"]
    if "window_rings" in state:  # a row's table in each kind's pool
        tables = KindTables(tables, state["window_rings"])
    if "state_slots" in state:
        tables = step_state_tables(tables, state["state_slots"])
    token, pool, last_logits = _paged_decode_step_impl(
        params, cfg, pool, tables, last_logits,
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
    fetch = {
        "token": token, "step": state["step"],
        **jax.tree.map(lambda c: c + 0, counter_leaves(pool) or {}),
    }
    return fetch, pool, last_logits, state


def step_state_tables(tables, state_slots):
    """Every slot's pages and its own state slot (`state_slots` [slots,
    1]) as a step takes them: a row reads and leaves its columns in its
    own slot, keeps no snapshot, and its one position is its own."""
    own = state_slots.astype(jnp.int32)
    return StateTables(tables, jnp.concatenate(
        [own, own, jnp.zeros_like(own), jnp.full_like(own, -1)], axis=1
    ))


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
    rows = {"tables": table_row}
    if isinstance(table_row, KindTables):
        rows = {"tables": table_row.full, "window_rings": table_row.window}
    if isinstance(table_row, StateTables):  # (the slot the row writes)
        rows = {"tables": table_row.full, "state_slots": table_row.conv[:, 1:2]}
    return {
        **state,
        **{n: state[n].at[slot].set(row[0]) for n, row in rows.items()},
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


def _copy_window_pages_impl(pool, src, dst):
    return {
        **pool,
        **{  # (k and v, or the one leaf that holds both: `_joint_kv`)
            name: pool[name].at[:, dst].set(pool[name][:, src])
            for name in pool if name.startswith("window_")
        },
    }


_copy_window_pages_jit = None


def copy_window_pages(pool, src, dst):
    """-> the pool with the window pages `src` [n] copied over the
    pages `dst` [n], every window layer's k and v: how the keys at a
    chunk boundary outlive the ring that overwrites them (to pages of
    the prefix cache), and how a prefix hit finds them again (into its
    row's ring). llm/kv_window.py says when. `pool` is donated on
    accelerator backends."""
    global _copy_window_pages_jit
    if _copy_window_pages_jit is None:
        _copy_window_pages_jit = compile_watch.instrument(
            "generate.copy_window_pages",
            jax.jit(
                _copy_window_pages_impl, donate_argnums=accel_donate(0)
            ),
        )
    return _copy_window_pages_jit(pool, src, dst)


def _finish_chunk_impl(
    state, last_logits, logits, moe_counts, slot, last, position, budget,
    eos,
):
    row = logits[0]
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
    fence = row[:1] if moe_counts is None else jax.tree.map(
        lambda counted: counted + 0, moe_counts
    )
    return state, last_logits, fence


_finish_chunk_jit = None


def finish_chunk(
    state, last_logits, logits, moe_counts, slot, last, position, budget,
    eos,
):
    """What follows every `paged_prefill` chunk of the engine, in one
    small program whose scalars are all traced. If the chunk was the
    prompt's `last`, the row starts: `last_logits[slot]` becomes
    `logits` [1, vocab], the one position the chunk ran its head for
    (`paged_prefill`'s `row`: the prompt's last token), and the
    state's row gets its `position`, `budget` and `eos` and is alive.
    Otherwise nothing changes. -> (state, last_logits, fence): the
    fence is ready when the chunk is; for a MoE config it is a copy of
    the chunk's `moe_counts` (the pool's own is donated to the next
    program), else the row's first logit. `last_logits` is donated on
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
        state, last_logits, logits, moe_counts, slot, last, position,
        budget, eos,
    )
