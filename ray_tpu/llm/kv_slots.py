"""Paged KV cache for the continuous-batching engine.

PR 10's fixed slot arenas shaped [layers, slots, kv_heads, max_len,
head_dim] made every request — a 6-token chat turn included — reserve
`max_len` positions of KV for its whole lifetime, so slot count (the
decode batch width) was hard-coupled to worst-case sequence memory.
This module replaces them with a PAGED cache (ISSUE 11 / ROADMAP
item 1c):

* one shared pool of `block_len`-sized KV blocks,

      k, v: [layers, n_blocks, kv_heads, block_len, head_dim]

  (models/generate.init_block_pool);
* a per-request PAGE TABLE mapping logical block j -> physical block
  id; a forward writes its new k/v into the pool in place and
  attention walks the live rows' tables a tile of entries at a time,
  each row as far as its own length reaches and a dead row not at all
  (models/generate._paged_attention), so the math — and the greedy
  token stream — is that of plain causal attention over the keys
  inside `valid_len`;
* a refcounted `BlockAllocator` (the plasma-style ownership model of
  the reference object plane: pin/refcount, free-list reuse, nothing
  zeroed) with PREFIX CACHING: full prompt blocks register under the
  exact token prefix they hold, and a later request whose prompt
  starts with the same tokens shares those blocks — its prefill
  SKIPS them entirely (shared system prompts become nearly free).

Shapes stay STATIC: the decode step runs over the full slot batch
with full-width [slots, max_blocks] tables (dead rows ride along
pointing at the reserved null block 0), and a prompt runs as whole
prefill chunks and a last one padded to the chunk, its half or its
quarter, whichever holds the tokens left (`bucket_for`) — XLA
compiles the decode step ONCE per engine geometry and the paged
prefill once a shape, three at most (the per-bucket scratch caches
of the arena design are gone).

Junk-is-masked contract (unchanged from the arenas): a freed block is
NOT zeroed. Attention masks positions >= valid_len, and every visible
position is rewritten by its owning request before valid_len covers
it — so alloc/free is pure host bookkeeping, O(1) per block.

Immutability contract for shared blocks: only FULL blocks of prompt
tokens register in the prefix table, decode writes always land at
positions >= len(prompt) (never inside a full prompt block), and a
registered block is only ever written again after eviction
unregisters it — so a cache hit can never observe a block mid-rewrite.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Dict, Hashable, List, Sequence

from ..models.llama import LlamaConfig
from ..models.generate import STATE_LEAF, cache_leaves, init_block_pool


def chunk_shapes(chunk: int, block_len: int) -> tuple:
    """Token counts a prompt's LAST chunk may have, ascending: the
    prefill chunk, its half and its quarter, each offered only where
    it is a whole number of KV blocks (a chunk writes whole blocks).
    Derived from the engine's geometry alone: every engine of one
    geometry compiles, and warms, the same set."""
    return tuple(
        chunk // parts for parts in (4, 2, 1)
        if chunk % (parts * block_len) == 0
    )


def bucket_for(n: int, chunk: int, max_len: int, block_len: int) -> int:
    """Where the chunks of an `n`-token prompt end: whole chunks of
    `chunk` cover [0, last), last = (ceil(n / chunk) - 1) * chunk, and
    the last chunk starts there and is the smallest of
    `chunk_shapes` that holds the n - last tokens left, padded to that
    shape. A function of the prompt's length alone: not of the other
    traffic, the slot or a prefix hit (a hit skips whole chunks and
    never the last). Raises when it exceeds the per-request
    capacity."""
    if n < 1:
        raise ValueError("empty prompt")
    last = (n - 1) // chunk * chunk
    bucket = last + next(
        shape for shape in chunk_shapes(chunk, block_len)
        if shape >= n - last
    )
    if bucket > max_len:
        raise ValueError(
            f"prompt of {n} tokens needs a {bucket}-token bucket but "
            f"requests are capped at max_len={max_len}"
        )
    return bucket


def default_block_len(prefill_chunk: int, cap: int = 16) -> int:
    """Auto block length: the largest divisor of the prefill chunk at
    most `cap` — chunks must cover whole blocks so a chunked prefill
    never splits a block write across dispatches."""
    for cand in range(min(cap, prefill_chunk), 0, -1):
        if prefill_chunk % cand == 0:
            return cand
    return 1


class BlocksExhausted(RuntimeError):
    """The pool has fewer free (or evictable cached) blocks than the
    reservation needs."""


#: The reserved scratch block every dead slot's table points at; its
#: contents are garbage by design and never gathered for a live row.
NULL_BLOCK = 0


class BlockAllocator:
    """Refcounted physical-block bookkeeping plus the prefix-reuse
    table. Pure host-side Python — no JAX — so its invariants are
    unit-testable in microseconds (tests/test_kv_blocks.py).

    Block states:

    * free        — on the free list, contents meaningless;
    * pinned      — refcount >= 1, owned by one or more live requests
                    (shared only via a prefix-cache hit);
    * cached-free — refcount 0 but still registered under its prompt
                    prefix in an LRU: reusable by a future prefix hit,
                    evictable (oldest first) when a reservation
                    outgrows the free list.

    Prefix keys are opaque hashables minted by the cache owner
    (PagedKVCache chains SHA-256 digests over the token prefix a
    block completes — O(prompt) to build, and a cross-prompt
    collision would be a SHA-256 collision).
    """

    def __init__(self, n_blocks: int, reserved: int = 1):
        if n_blocks <= reserved:
            raise ValueError(
                f"pool needs > {reserved} blocks, got {n_blocks}"
            )
        self.n_blocks = int(n_blocks)
        self.reserved = int(reserved)
        # LIFO free list: a just-freed (cache-warm) block is reused
        # first, same as the arena slot free list.
        self._free: List[int] = list(
            range(n_blocks - 1, reserved - 1, -1)
        )
        self._refcount: Dict[int, int] = {}
        self._prefix_to_block: Dict[Hashable, int] = {}
        self._block_prefix: Dict[int, Hashable] = {}
        #: refcount-0 blocks still holding a registered prefix, oldest
        #: first (eviction order).
        self._cached: "OrderedDict[int, None]" = OrderedDict()

    # -- capacity ------------------------------------------------------
    def capacity(self) -> int:
        """Blocks a single reservation could ever obtain."""
        return self.n_blocks - self.reserved

    def available(self) -> int:
        """Blocks obtainable right now (free + evictable cached)."""
        return len(self._free) + len(self._cached)

    def used(self) -> int:
        """Blocks pinned by live requests."""
        return len(self._refcount)

    def cached(self) -> int:
        """Refcount-0 blocks retained for prefix reuse."""
        return len(self._cached)

    # -- allocation ----------------------------------------------------
    def reserve(self, n: int) -> List[int]:
        """Claim `n` blocks at refcount 1. Free blocks first, then
        LRU-evict cached-free blocks (their prefix entries drop).
        Raises BlocksExhausted — the caller sheds or keeps the request
        queued — without handing out a partial set."""
        if n < 0:
            raise ValueError(f"reserve({n})")
        if n > self.available():
            raise BlocksExhausted(
                f"need {n} KV blocks, only {self.available()} "
                f"available (pool {self.capacity()})"
            )
        out: List[int] = []
        for _ in range(n):
            if self._free:
                block = self._free.pop()
            else:
                block, _ = self._cached.popitem(last=False)
                del self._prefix_to_block[self._block_prefix.pop(block)]
            self._refcount[block] = 1
            out.append(block)
        return out

    def release(self, blocks: Sequence[int]) -> None:
        """Drop one reference per block. A block reaching refcount 0
        goes to the cached-free LRU if it still holds a registered
        prefix, else back to the free list. Double-free raises — the
        engine-killing class of bug the arena design hit once
        (PR 10's mid-prefill cancel) must be loud here too."""
        for block in blocks:
            count = self._refcount.get(block)
            if count is None:
                raise ValueError(
                    f"double free of KV block {block}"
                )
            if count > 1:
                self._refcount[block] = count - 1
                continue
            del self._refcount[block]
            if block in self._block_prefix:
                self._cached[block] = None
            else:
                self._free.append(block)

    # -- prefix cache --------------------------------------------------
    def peek_prefix(self, keys: Sequence[Hashable]) -> int:
        """Length of the longest cached run of `keys` (no pinning) —
        the admission gate's lookahead."""
        hits = 0
        for key in keys:
            if key not in self._prefix_to_block:
                break
            hits += 1
        return hits

    def peek_cached(self, keys: Sequence[Hashable], limit: int) -> int:
        """Among the first `limit` blocks of the longest cached run of
        `keys`, how many are currently refcount-0 (cached-free)?
        Pinning THOSE removes them from `available()`; hit blocks
        already pinned by a live request cost nothing to share — the
        distinction the admission gate needs to budget a reservation
        exactly (no pinning here)."""
        cached = 0
        for key in keys[: max(0, limit)]:
            block = self._prefix_to_block.get(key)
            if block is None:
                break
            if self._refcount.get(block, 0) == 0:
                cached += 1
        return cached

    def match_prefix(self, keys: Sequence[Hashable]) -> List[int]:
        """Pin and return the blocks of the longest cached run of
        `keys`. Pinning removes a cached-free block from the eviction
        LRU, so a reservation made after this call cannot steal a
        matched block."""
        out: List[int] = []
        for key in keys:
            block = self._prefix_to_block.get(key)
            if block is None:
                break
            count = self._refcount.get(block, 0)
            if count == 0:
                self._cached.pop(block, None)
            self._refcount[block] = count + 1
            out.append(block)
        return out

    def register(self, block: int, key: Hashable) -> bool:
        """Publish a pinned block as the cache of prompt prefix `key`.
        First writer wins: if the prefix (or the block) is already
        registered the call is a no-op — the caller's copy simply
        stays private."""
        if self._refcount.get(block) is None:
            raise ValueError(
                f"register of unpinned KV block {block}"
            )
        if key in self._prefix_to_block or block in self._block_prefix:
            return False
        self._prefix_to_block[key] = block
        self._block_prefix[block] = key
        return True

    def stats(self) -> Dict[str, int]:
        return {
            "kv_blocks_total": self.capacity(),
            "kv_blocks_used": self.used(),
            "kv_blocks_cached": self.cached(),
            "kv_blocks_free": len(self._free),
        }


class _TwoPools:
    """`PagedKVCache.alloc` of a model that keeps two kinds of cache:
    a row's blocks are {"full": ids, "window": ids}, reserved and
    released together; whoever carries them treats them as opaque."""

    def __init__(self, full: BlockAllocator, window):
        self.full, self.window = full, window

    def reserve(self, n: int) -> Dict[str, List[int]]:
        """A row of `n` blocks: its full pages and its ring of window
        pages (llm/kv_window.py), both or neither."""
        ring = self.window.ring_for(n)
        if ring > self.window.alloc.available():
            raise BlocksExhausted(
                f"need {ring} window pages, only "
                f"{self.window.alloc.available()} available"
            )
        return {
            "full": self.full.reserve(n),
            "window": self.window.alloc.reserve(ring),
        }

    def release(self, blocks: Dict[str, List[int]]) -> None:
        self.full.release(blocks["full"])
        self.window.alloc.release(blocks["window"])


class _PagesAndState:
    """`PagedKVCache.alloc` of a model with conv layers: a row's blocks
    are {"full": its pages, "state": [its state slot]}, reserved and
    released together; whoever carries them treats them as opaque."""

    def __init__(self, full: BlockAllocator, state):
        self.full, self.state = full, state

    def reserve(self, n: int) -> Dict[str, List[int]]:
        """A row of `n` blocks and its one state slot
        (llm/kv_state.py), both or neither."""
        if self.state.alloc.available() < 1:
            raise BlocksExhausted("no state slot available")
        return {
            "full": self.full.reserve(n),
            "state": self.state.alloc.reserve(1),
        }

    def release(self, blocks: Dict[str, List[int]]) -> None:
        self.full.release(blocks["full"])
        self.state.alloc.release(blocks["state"])


class PagedKVCache:
    """The engine's shared block pool plus its geometry: block length,
    per-request logical-table width, and prompt-length buckets.

    What a row's cache IS belongs to this class alone: the engine, the
    benchmark's probe and its compile rehearsal get the cache from
    `for_engine`, a row's `table` argument from `row_table` and a
    step's `state` from `step_state`, and carry a row's blocks (what
    `alloc.reserve(blocks_for(tokens))` returned) as they come. For a
    model whose layers are all of one kind that is one pool, one
    allocator (`alloc` is `full`), a list of block ids a row and the
    six-leaf state. A model with `layer_kinds` (window and full
    attention mixed) keeps a second pool for its window layers
    (`window`, llm/kv_window.py): a row's blocks are then {"full",
    "window"}, its table a table in each pool and the state has one
    more leaf, `window_rings`. A model with conv layers keeps a STATE
    slot a row beside its pages (`state`, llm/kv_state.py): a row's
    blocks are {"full", "state"}, its table `generate.StateTables`
    and the state's one more leaf is `state_slots`."""

    def __init__(
        self,
        cfg: LlamaConfig,
        n_blocks: int,
        block_len: int,
        max_len: int,
        prefill_chunk: int,
        slots: int = 0,
    ):
        if prefill_chunk < 1 or prefill_chunk > max_len:
            raise ValueError(
                f"prefill_chunk {prefill_chunk} outside [1, {max_len}]"
            )
        if block_len < 1 or prefill_chunk % block_len != 0:
            raise ValueError(
                f"kv_block_len {block_len} must divide the prefill "
                f"chunk {prefill_chunk} (chunks write whole blocks)"
            )
        if max_len % block_len != 0:
            raise ValueError(
                f"max_len {max_len} must be a multiple of "
                f"kv_block_len {block_len}"
            )
        self.cfg = cfg
        self.block_len = int(block_len)
        self.max_len = int(max_len)
        self.prefill_chunk = int(prefill_chunk)
        #: Logical table width: the block count a max_len sequence
        #: needs; every request's table pads to it (static shapes).
        self.max_blocks = self.max_len // self.block_len
        #: The allocator of the pages every kind of model has: a row's
        #: whole length, the prefix cache's blocks.
        self.full = BlockAllocator(n_blocks, reserved=1)
        self.alloc = self.full
        #: The window layers' pages, where the model has such layers.
        self.window = None
        pool_blocks = int(n_blocks)
        windows = {k.window for k in cfg.layer_kinds if k.window}
        if windows:
            from .kv_window import WindowPages

            self.window = WindowPages(
                windows.pop(), self.block_len, self.prefill_chunk,
                slots, int(n_blocks),
            )
            self.alloc = _TwoPools(self.full, self.window)
            pool_blocks = {
                "full": pool_blocks, "window": self.window.alloc.n_blocks
            }
        #: The conv layers' state slots, where the model has such layers.
        self.state = None
        if any(k.conv for k in cfg.layer_kinds):
            from .kv_state import StateSlots

            if self.window is not None:
                raise ValueError(
                    "window layers and conv layers in one model: no cache "
                    "holds both a ring and a state slot a row"
                )
            self.state = StateSlots(
                self.block_len, self.prefill_chunk, slots, int(n_blocks)
            )
            self.alloc = _PagesAndState(self.full, self.state)
            # (the leaf's slot axis in whole 16-row tiles, so that its
            # rows are the leaf as it lies: `init_block_pool`)
            pool_blocks = {
                "full": pool_blocks,
                "conv": -(-self.state.alloc.n_blocks // 16) * 16,
            }
        self._pool = init_block_pool(cfg, pool_blocks, self.block_len)
        if self.state is not None:
            states = self._pool[STATE_LEAF]
            self.state.slot_bytes = states.nbytes // states.shape[2]
        #: Bytes of one block of pages over every leaf that holds pages
        #: (for the engine's `kv_bytes_in_use`).
        self.block_bytes = sum(
            leaf.nbytes // leaf.shape[1]
            for name, leaf in cache_leaves(self._pool).items()
            if name != STATE_LEAF
        )

    @classmethod
    def for_engine(
        cls, cfg: LlamaConfig, *, slots: int, max_len: int,
        prefill_chunk: int, kv_block_len: int = 0, kv_blocks: int = 0,
    ) -> "PagedKVCache":
        """The cache of an engine's settings (`EngineConfig`'s keys of
        the same names; 0 is auto: the largest block up to 16 that
        divides the chunk, `slots x max_len` worth of blocks). A
        model's window pool is sized from these (kv_window.py)."""
        block_len = kv_block_len or default_block_len(prefill_chunk)
        n_blocks = kv_blocks or slots * (max_len // block_len) + 1
        return cls(cfg, n_blocks, block_len, max_len, prefill_chunk, slots)

    # -- what a row's cache is, for the programs -----------------------
    def host_rows(self, blocks: Sequence) -> Dict[str, "object"]:
        """Every slot's block ids as numpy tables, `blocks[i]` what
        `alloc.reserve` gave the row in slot i (None or empty: no row):
        `tables` [n, max_blocks], the null block past a row's end and
        in a slot that holds none; with a window pool `window_rings`
        [n, ring] beside it, logical block j at entry j mod ring; with
        state slots `state_slots` [n, 1], a row's own."""
        import numpy as np

        rows = {"tables": (self.max_blocks, "full")}
        if self.window is not None:
            rows["window_rings"] = (self.window.ring, "window")
        if self.state is not None:
            rows["state_slots"] = (1, "state")
        out = {}
        for name, (width, part) in rows.items():
            table = np.full((len(blocks), width), NULL_BLOCK, np.int32)
            for slot, ids in enumerate(blocks):
                if ids:
                    ids = ids[part] if isinstance(ids, dict) else ids
                    table[slot, :len(ids)] = ids
            out[name] = table
        return out

    def row_table(
        self, slot: int, blocks, read=None, snapshot: int = NULL_BLOCK,
        length: int = -1, pages=None,
    ):
        """What `paged_prefill` takes as `table` and `patch_step_slot`
        as `table_row` for the row in `slot` (one program serves every
        slot, so it is not read) whose blocks are `blocks`: [1,
        max_blocks], or with a window pool `generate.KindTables`. With
        state slots `generate.StateTables`: the row starts from its own
        slot or the one given as `read` (a snapshot's), leaves its state
        in its own and a copy in `snapshot`, and `length` is its own
        length where the chunk may be padded (-1: not given); `pages`
        is the row's table of pages where the device has it already
        (an earlier `row_table(...).full`: a table a chunk then uploads
        four numbers)."""
        import jax.numpy as jnp

        rows = self.host_rows([blocks])
        if self.state is not None:
            import numpy as np

            from ..models.generate import StateTables

            own = int(rows["state_slots"][0, 0])
            return StateTables(
                jnp.asarray(rows["tables"]) if pages is None else pages,
                jnp.asarray(np.asarray(
                    [[own if read is None else read, own, snapshot, length]],
                    np.int32,
                )),
            )
        if self.window is None:
            return jnp.asarray(rows["tables"])
        from ..models.generate import KindTables

        return KindTables(
            jnp.asarray(rows["tables"]), jnp.asarray(rows["window_rings"])
        )

    def step_state(self, blocks: Sequence, positions, alive, eos, budget, step):
        """What `paged_engine_step` takes as `state` (layout in
        models/generate.py), from every slot's blocks and the per-slot
        arrays."""
        import jax.numpy as jnp
        import numpy as np

        return {
            **{n: jnp.asarray(t) for n, t in self.host_rows(blocks).items()},
            "positions": jnp.asarray(positions, jnp.int32),
            "alive": jnp.asarray(alive, bool),
            "eos": jnp.asarray(eos, jnp.int32),
            "budget": jnp.asarray(budget, jnp.int32),
            "step": jnp.asarray(np.int32(step)),
        }

    def publish(self, blocks, n_shared: int, prefix_keys: Sequence) -> None:
        """Register a row's full prompt blocks past the `n_shared` it
        took from the cache itself, for later prefix hits; first writer
        wins on races."""
        ids = blocks["full"] if isinstance(blocks, dict) else blocks
        for i in range(n_shared, len(prefix_keys)):
            self.full.register(ids[i], prefix_keys[i])

    # -- pool ----------------------------------------------------------
    @property
    def pool(self) -> Dict[str, object]:
        """The {"k", "v"} block pool the jitted paged kernels consume
        and (on accelerator backends, via donation) update in place;
        for a MoE config it also carries `moe_counts`, the last
        forward's picks per layer and expert. ONE pytree whatever the
        model caches: a second pool's leaves lie in it too."""
        return self._pool

    @pool.setter
    def pool(self, new: Dict[str, object]) -> None:
        self._pool = new

    # -- geometry ------------------------------------------------------
    def bucket_for(self, prompt_len: int) -> int:
        return bucket_for(
            prompt_len, self.prefill_chunk, self.max_len, self.block_len
        )

    def chunk_shapes(self) -> tuple:
        return chunk_shapes(self.prefill_chunk, self.block_len)

    def blocks_for(self, total_tokens: int) -> int:
        """Blocks a sequence of `total_tokens` positions occupies."""
        return -(-int(total_tokens) // self.block_len)

    def prefix_keys(self, prompt: Sequence[int]) -> List[bytes]:
        """Prefix-cache keys for every FULL block of `prompt`: key i
        is an incremental SHA-256 chain digest(i-1) || block-i tokens,
        so building all keys is O(prompt_len) in time AND memory
        (materializing the exact prefix per key would be quadratic),
        while the chain still binds each key to the ENTIRE token
        prefix — a cross-prompt key collision is a SHA-256 collision.
        The final PARTIAL block (if any) never gets a key — decode
        writes into it, and shared blocks must stay immutable."""
        import hashlib

        bl = self.block_len
        keys: List[bytes] = []
        digest = b"rt-paged-kv-prefix"
        for i in range(len(prompt) // bl):
            chained = hashlib.sha256(digest)
            chained.update(
                ",".join(map(str, prompt[i * bl:(i + 1) * bl])).encode()
            )
            digest = chained.digest()
            keys.append(digest)
        return keys

    def nbytes(self) -> int:
        """Bytes of the pool's cache leaves, whatever a configuration
        names them (k and v; a latent and an indexer's keys)."""
        return int(sum(
            leaf.nbytes for leaf in cache_leaves(self._pool).values()
        ))
