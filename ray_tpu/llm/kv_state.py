"""A row's STATE slot: what a conv layer keeps of a row (a model whose
`LlamaConfig.layer_kinds` hold gated short convolutions among its
attention layers). Host bookkeeping only, like `kv_window.WindowPages`,
and with a `kv_slots.BlockAllocator` of its own over the slots.

A conv layer needs of a row the mixer's `taps - 1` columns before the
row's next position, whatever the row's length
(`models/generate._conv_mix`): [taps - 1, dim] numbers a layer. The
pool's `conv_state` leaf holds them in SLOTS, [conv layers, taps - 1,
slots, dim], slot 0 the null slot. A slot belongs to a row and not
to a page: no table walks it, nothing of it is shared between rows, and
a forward is told three ids a row (`generate.StateTables`): the slot it
starts from, the slot it leaves the row's state in, and one more that
takes a copy.

  * A row reserves ONE slot at admission, under the gate that covers the
    page pool (`llm/engine.py` `_gate_locked`), and gives it back with
    its pages. Nothing zeroes it: a row that starts at position 0 starts
    from zeros whatever the slot holds (the junk-is-masked contract of
    the pages), and a row that starts later starts from a snapshot.

  * The prefix cache when most layers hold state. A prefix hit that
    skips `S` tokens needs every conv layer's columns at `S`, which no
    page holds. So each whole-chunk boundary a prompt's prefill passes
    leaves a SNAPSHOT: the chunk that ends there writes the columns it
    leaves in the row's slot into a second slot as well (`keep` says
    which), registered under the boundary's prefix key (the key of the
    block that ends at `S`, the full pool's own) and released at once:
    refcount 0, kept while nobody needs the slot, evicted oldest first.
    `_skip_for` skips whole chunks, so those are the boundaries a hit
    can use. A later hit's first chunk READS the snapshot's slot and
    writes the row's own (`admit` says from where): no copy program.
    Where the snapshot of the longest boundary the pages could serve is
    gone, `usable_skip` falls back to the next shorter boundary that
    has one, or to a miss: a row never reads a slot it does not own
    unless it is a snapshot registered under its own prefix.

The slots: one a row the engine can hold, one a whole chunk the page
pool can hold (`blocks x block_len // prefill_chunk` snapshots), and
the null slot. (The pool's leaf holds them rounded up to a whole
number of 16-row tiles, `kv_slots.PagedKVCache`: the program then
writes a slot as rows of the leaf where it lies,
`generate.init_block_pool`.)
"""

from __future__ import annotations

from typing import Hashable, List, Optional, Sequence, Tuple

from .kv_slots import NULL_BLOCK, BlockAllocator


class StateSlots:
    def __init__(
        self, block_len: int, prefill_chunk: int, slots: int,
        full_blocks: int, slot_bytes: int = 0,
    ):
        self.block_len = int(block_len)
        self.prefill_chunk = int(prefill_chunk)
        #: Bytes of one slot over all the conv layers (for the gauges).
        self.slot_bytes = int(slot_bytes)
        boundaries = int(full_blocks) * self.block_len // self.prefill_chunk
        self.alloc = BlockAllocator(
            max(1, slots) + boundaries + 1, reserved=1
        )
        #: Snapshots kept, snapshots that made room for a newer slot,
        #: and admissions that started from one.
        self.written = self.evicted = self.restored = 0

    def _key(self, prefix_keys: Sequence[Hashable], boundary: int):
        """The prefix key of the block-aligned `boundary`: that of the
        block that ends there (None where the prompt has none)."""
        index = boundary // self.block_len - 1
        if boundary % self.block_len or not 0 <= index < len(prefix_keys):
            return None
        return prefix_keys[index]

    def _reserve(self, n: int) -> List[int]:
        cached = self.alloc.cached()
        slots = self.alloc.reserve(n)
        self.evicted += cached - self.alloc.cached()
        return slots

    # -- admission -----------------------------------------------------
    def usable_skip(self, prefix_keys: Sequence[Hashable], skip: int) -> int:
        """The longest whole-chunk boundary at most `skip` (what the
        pages can serve) whose snapshot is still held; 0 (a miss) where
        none is."""
        while skip > 0:
            key = self._key(prefix_keys, skip)
            if key is not None and self.alloc.peek_prefix([key]):
                return skip
            skip -= self.prefill_chunk
        return 0

    def gate(self, prefix_keys: Sequence[Hashable], skip: int) -> bool:
        """Can a row that skips `skip` tokens get its slot NOW? A hit's
        snapshot is pinned while the slot is reserved (so the
        reservation cannot evict it), which takes it out of
        `available()` for that moment if nobody else holds it."""
        pinned = 0
        if skip:
            pinned = self.alloc.peek_cached(
                [self._key(prefix_keys, skip)], 1
            )
        return self.alloc.available() - pinned >= 1

    def admit(
        self, prefix_keys: Sequence[Hashable], skip: int
    ) -> Tuple[List[int], Optional[int]]:
        """Reserve a row's slot -> (its slot, as the one-entry list the
        allocator gave; the snapshot's slot its first chunk reads, or
        None for a row that starts at position 0). The snapshot is
        unpinned here already: whatever may overwrite it is dispatched
        after the chunk that reads it."""
        held = (
            self.alloc.match_prefix([self._key(prefix_keys, skip)])
            if skip else []
        )
        try:
            own = self._reserve(1)
        finally:
            self.alloc.release(held)
        if not skip:
            return own, None
        self.restored += 1
        return own, held[0]

    # -- the prefix cache's snapshots -----------------------------------
    def keep(self, prefix_keys: Sequence[Hashable], boundary: int) -> int:
        """The chunk about to be dispatched ends on the whole-chunk
        `boundary` of its prompt: -> the slot its state is to be copied
        to for later hits, or the null slot where the pool already
        holds that snapshot, the prompt has no key for it (a prefix
        cache switched off), or every slot is a row's (keeping a
        snapshot is never worth refusing a row)."""
        key = self._key(prefix_keys, boundary)
        if key is None or self.alloc.peek_prefix([key]):
            return NULL_BLOCK
        if self.alloc.available() < 1:
            return NULL_BLOCK
        kept = self._reserve(1)
        self.alloc.register(kept[0], key)
        self.alloc.release(kept)
        self.written += 1
        return kept[0]

    def stats(self) -> dict:
        held = self.alloc.used() + self.alloc.cached()
        return {
            "conv_snapshots_written": self.written,
            "conv_snapshots_evicted": self.evicted,
            "conv_hits_restored": self.restored,
            "conv_state_slots_total": self.alloc.capacity(),
            "conv_state_slots_used": self.alloc.used(),
            "conv_state_slots_cached": self.alloc.cached(),
            "conv_state_bytes_in_use": held * self.slot_bytes,
        }
