"""A row's WINDOW pages: the second page pool of a model whose layers
are of two kinds (`LlamaConfig.layer_kinds`: window and full attention
mixed). Host bookkeeping only, like `kv_slots.BlockAllocator`, which it
uses for its own pool.

A full-attention layer keeps every key of a row, so a row holds full
pages for its whole length (`kv_slots.PagedKVCache`, as every model). A
window layer's query sees the `window` keys up to itself, so of such a
layer a row keeps a RING of `ring` pages: logical block j lives at entry
`j mod ring` of the row's window table, and a page whose last key has
left every later query's window is overwritten in place by the block
`ring` after it. The forward finds its keys by that rule alone
(`models/generate._window_view`), so one table serves a row's every
chunk and step and nothing is patched between them.

  * `ring` is what the widest forward sees (`generate.window_view_blocks`
    at the prefill chunk): the blocks of the `window - 1` keys before a
    chunk, the chunk's own, and the one more block its write reads. A
    row reserves `min(ring, its blocks)` window pages at admission and
    holds exactly those until it is released: it never asks for another,
    so the window pool cannot run dry under a row that was admitted, and
    the admission gate that covers both pools (`llm/engine.py`
    `_gate_locked`) still means that a gated admission cannot fail its
    reservation one line later.

  * The prefix cache and the ring. A prefix hit that skips `S` tokens
    needs, of the window layers, the keys `[S - (window - 1), S)`: the
    TAIL of the boundary `S`, `tail_blocks` pages. The row that computed
    them will overwrite them, so they are COPIED out of its ring when
    the chunk that completes them has been dispatched
    (`generate.copy_window_pages`; `keep_tail` says from where to
    where), into pages of this pool registered under the same prefix
    keys the full pool uses and released at once: refcount 0, kept while
    nobody needs the page, evicted oldest first. A later hit copies them
    into its own ring (`admit`). EVERY whole-chunk boundary of a prompt
    is kept (`_skip_for` skips whole chunks, so those are the boundaries
    a hit can use), at `tail_blocks` pages each: `window - 1` tokens of
    window cache a chunk, an eighth of what the window layers would hold
    of the prompt without a window at a chunk of 1,024 and a window of
    128. Where the tail of the longest boundary the full pool could
    serve is gone, `usable_skip` falls back to the next shorter boundary
    whose tail is whole, or to a miss: a row never reads a page it does
    not own.

The pool is sized by `blocks_for_engine`: every slot's ring, the tail of
every whole-chunk boundary the FULL pool can hold, and the null block.
"""

from __future__ import annotations

from typing import Hashable, List, Optional, Sequence, Tuple

from ..models.generate import window_view_blocks
from .kv_slots import BlockAllocator


class WindowPages:
    def __init__(
        self, window: int, block_len: int, prefill_chunk: int,
        slots: int, full_blocks: int,
    ):
        if prefill_chunk < window:
            raise ValueError(
                f"prefill_chunk {prefill_chunk} is shorter than the window "
                f"{window}: a chunk boundary's tail would span chunks"
            )
        self.window = int(window)
        self.block_len = int(block_len)
        self.prefill_chunk = int(prefill_chunk)
        #: Pages that hold the `window - 1` keys before a block-aligned
        #: boundary.
        self.tail_blocks = -(-(self.window - 1) // self.block_len)
        #: A row's ring, in pages (the width of its window table).
        self.ring = window_view_blocks(window, block_len, prefill_chunk)
        boundaries = full_blocks * self.block_len // self.prefill_chunk
        self.alloc = BlockAllocator(
            max(1, slots) * self.ring + boundaries * self.tail_blocks + 1,
            reserved=1,
        )

    def ring_for(self, total_blocks: int) -> int:
        """Window pages a row of `total_blocks` blocks reserves."""
        return min(self.ring, int(total_blocks))

    def _tail(self, skip: int) -> slice:
        """The logical blocks of boundary `skip`'s tail."""
        end = skip // self.block_len
        return slice(end - self.tail_blocks, end)

    # -- admission -----------------------------------------------------
    def usable_skip(self, prefix_keys: Sequence[Hashable], skip: int) -> int:
        """The longest whole-chunk boundary at most `skip` (what the
        full pool can serve) whose tail this pool still holds whole; 0
        (a miss) where none is."""
        while skip > 0:
            keys = prefix_keys[self._tail(skip)]
            if self.alloc.peek_prefix(keys) == self.tail_blocks:
                return skip
            skip -= self.prefill_chunk
        return 0

    def gate(
        self, prefix_keys: Sequence[Hashable], skip: int, total_blocks: int
    ) -> bool:
        """Can a row of `total_blocks` blocks that skips `skip` tokens
        get its ring NOW? A hit's tail is pinned while the ring is
        reserved (so the reservation cannot evict it), which takes its
        cached-free pages out of `available()` for that moment."""
        pinned = 0
        if skip:
            pinned = self.alloc.peek_cached(
                prefix_keys[self._tail(skip)], self.tail_blocks
            )
        return self.alloc.available() - pinned >= self.ring_for(total_blocks)

    def admit(
        self, prefix_keys: Sequence[Hashable], skip: int, total_blocks: int
    ) -> Tuple[List[int], Optional[Tuple[List[int], List[int]]]]:
        """Reserve a row's ring -> (its pages, the copy that brings a
        hit's tail into it: (from pages, to pages), or None for a
        miss). The caller dispatches the copy before the row's first
        chunk; the tail's pages are unpinned here already, since
        whatever may overwrite them is dispatched after that copy."""
        tail = self.alloc.match_prefix(prefix_keys[self._tail(skip)]) if skip else []
        try:
            ring = self.alloc.reserve(self.ring_for(total_blocks))
        finally:
            self.alloc.release(tail)
        if not skip:
            return ring, None
        return ring, (tail, self.entries(ring, skip))

    def entries(self, ring: Sequence[int], skip: int) -> List[int]:
        """The pages of `ring` that hold boundary `skip`'s tail."""
        blocks = self._tail(skip)
        return [ring[j % self.ring] for j in range(blocks.start, blocks.stop)]

    # -- the prefix cache's tails ---------------------------------------
    def keep_tail(
        self, prefix_keys: Sequence[Hashable], ring: Sequence[int],
        boundary: int,
    ) -> Optional[Tuple[List[int], List[int]]]:
        """The keys before the whole-chunk `boundary` of a row are
        complete in its `ring`: keep them for a later hit -> the copy to
        dispatch (from pages, to pages), or None where the pool already
        holds that tail, the prompt has no keys for it (a prefix cache
        switched off), or every page is pinned by a ring (keeping a tail
        is never worth refusing a row)."""
        blocks = self._tail(boundary)
        keys = prefix_keys[blocks]
        if blocks.start < 0 or len(keys) < self.tail_blocks:
            return None
        if self.alloc.peek_prefix(keys) == self.tail_blocks:
            return None
        if self.alloc.available() < self.tail_blocks:
            return None
        kept = self.alloc.reserve(self.tail_blocks)
        for page, key in zip(kept, keys):
            self.alloc.register(page, key)
        self.alloc.release(kept)
        return self.entries(ring, boundary), kept

    def stats(self) -> dict:
        return {
            "window_blocks_total": self.alloc.capacity(),
            "window_blocks_used": self.alloc.used(),
            "window_blocks_cached": self.alloc.cached(),
        }
