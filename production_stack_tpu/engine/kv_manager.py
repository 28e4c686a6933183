"""Paged KV-cache block manager with content-hash prefix caching.

The reference gets this from vLLM's PagedAttention block manager plus
LMCache's chunk-hash dedup (`SURVEY.md` §2.4 "KV-cache tiering"). Here the
manager is host-side bookkeeping only — device pages live in the stacked
``[L, nb, bs, KH, hd]`` cache arrays owned by the runner; this class decides
*which page index* each sequence writes/reads, and which full pages are
shareable across requests via the prefix-committing block hashes of
:mod:`production_stack_tpu.kvcache.hashing` (the same scheme the router's
KV-aware policy and the remote cache tier speak, so routing and reuse agree).

Eviction is LRU over reusable pages (refcount 0 but content intact). An
``on_evict`` hook lets the tiering layer capture pages on their way out
(HBM → host DRAM → remote, LMCache-style).
"""

from __future__ import annotations

import collections
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..kvcache.hashing import block_hashes
from ..logging_utils import init_logger

logger = init_logger(__name__)


class NoFreeBlocksError(RuntimeError):
    pass


class BlockAllocator:
    """Reference-counted page allocator with hash-addressed reuse."""

    def __init__(
        self,
        num_blocks: int,
        block_size: int,
        enable_prefix_caching: bool = True,
        on_evict: Optional[Callable[[int, int], None]] = None,
        state_slots: int = 0,
        window_blocks: int = 0,
        window_tokens: int = 0,
    ):
        # Recurrent-state slots (a model with state-space layers): one a
        # sequence from its first scheduling to the release of its pages,
        # owned here with them. 0 for a model that keeps pages alone.
        self.state_slots = state_slots
        self._free_slots: List[int] = list(range(state_slots - 1, -1, -1))
        self.state_slot_waits = 0  # admissions that found no slot free
        # A second group of pages, for a model's sliding-window layers
        # (``window_tokens`` the window): a sequence drops its reference to
        # a page there as it advances past it, so it never holds more than
        # ``window_bound`` whatever its length. 0 pages: no such group. The
        # group is an allocator of its own: with prefix caching on its full
        # pages are committed under the same block hashes as the global
        # group's, an unreferenced one waits in its LRU, and ``match_prefix``
        # ends a match where either group ends it; with it off no hash is
        # kept and a dropped page is free at once. The group is sized for
        # ``max_num_seqs`` such residencies
        # (``engine/config.py::window_block_count``), so admission, which
        # stops at that many sequences, has counted it; running out is
        # ``NoFreeBlocksError`` all the same (preemption by recompute).
        self.window_blocks = window_blocks
        self.window_tokens = window_tokens
        self.window: Optional[BlockAllocator] = None
        if window_blocks:
            self.window = BlockAllocator(
                window_blocks, block_size, enable_prefix_caching,
                on_evict=self._count_window_eviction)
        self.window_pages_released = 0
        self.window_pages_evicted = 0
        # Tokens a match was cut back by because the window group had lost
        # the pages under the last window before the match point.
        self.window_prefix_tokens_lost = 0
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.enable_prefix_caching = enable_prefix_caching
        self.on_evict = on_evict
        self._refcount = [0] * num_blocks
        self._hash_of_block: Dict[int, int] = {}
        self._block_of_hash: Dict[int, int] = {}
        # refcount-0 blocks with intact, hash-addressed content (LRU order).
        self._reusable: "collections.OrderedDict[int, int]" = collections.OrderedDict()
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        # Prefix-cache KPIs exported as vllm:gpu_prefix_cache_* gauges.
        self.hit_tokens = 0
        self.query_tokens = 0

    # -- capacity ---------------------------------------------------------

    @property
    def num_free(self) -> int:
        return len(self._free) + len(self._reusable)

    @property
    def usage(self) -> float:
        return 1.0 - self.num_free / max(self.num_blocks, 1)

    # -- allocation -------------------------------------------------------

    def allocate(self) -> int:
        """Take one writable page (evicting the LRU reusable page if needed)."""
        if self._free:
            blk = self._free.pop()
            self._refcount[blk] = 1
            return blk
        if self._reusable:
            blk, h = self._reusable.popitem(last=False)
            del self._block_of_hash[h]
            del self._hash_of_block[blk]
            if self.on_evict is not None:
                self.on_evict(blk, h)
            self._refcount[blk] = 1
            return blk
        raise NoFreeBlocksError("out of KV blocks")

    def acquire_cached(self, h: int) -> Optional[int]:
        """Reuse the page holding hash ``h``, if resident. Increfs."""
        if not self.enable_prefix_caching:
            return None
        blk = self._block_of_hash.get(h)
        if blk is None:
            return None
        if blk in self._reusable:
            del self._reusable[blk]
        self._refcount[blk] += 1
        return blk

    def incref(self, blk: int) -> None:
        self._refcount[blk] += 1

    def acquire_resident(self, h: int) -> Optional[int]:
        """Reacquire the page holding hash ``h`` from wherever it survives.
        Base allocator: HBM residency only; the tiered allocator overrides
        this to also fault pages back up from host DRAM / the remote store.
        Used by the swap path to resurrect a parked sequence's committed
        prefix without copying bytes that never left."""
        return self.acquire_cached(h)

    def commit(self, blk: int, h: int, allow_swap: bool = True) -> int:
        """Mark a freshly-written full page as content-addressed by ``h``.

        If another request concurrently committed the same content, dedup to
        the existing page: the caller must swap to the returned id.
        ``allow_swap=False`` suppresses that (and the release of the
        duplicate) — required while the page is referenced by an in-flight
        pipelined decode burst, whose device block table still points at it.
        """
        if not self.enable_prefix_caching:
            return blk
        existing = self._block_of_hash.get(h)
        if existing is not None and existing != blk:
            if not allow_swap:
                return blk  # keep our copy un-addressed; existing stays owner
            self.release(blk)
            self.incref(existing)
            if existing in self._reusable:
                del self._reusable[existing]
            return existing
        self._hash_of_block[blk] = h
        self._block_of_hash[h] = blk
        return blk

    def release(self, blk: int, cold: bool = False) -> None:
        """Drop a reference. An unreferenced page with a hash waits in the
        LRU as its youngest, or with ``cold`` as its oldest: the next to be
        evicted."""
        self._refcount[blk] -= 1
        assert self._refcount[blk] >= 0, f"double free of block {blk}"
        if self._refcount[blk] == 0:
            h = self._hash_of_block.get(blk)
            if h is not None:
                self._reusable[blk] = h  # keep content for future hits
                if cold:
                    self._reusable.move_to_end(blk, last=False)
            else:
                self._free.append(blk)

    def release_all(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            self.release(b)

    # -- everything a sequence holds --------------------------------------

    def take_state_slot(self, seq) -> bool:
        """Give ``seq`` a recurrent-state slot if it needs one and has none.
        False when every slot is taken (the caller leaves it queued)."""
        if not self.state_slots or seq.state_slot is not None:
            return True
        if not self._free_slots:
            self.state_slot_waits += 1
            return False
        seq.state_slot = self._free_slots.pop()
        return True

    @property
    def state_slots_in_use(self) -> int:
        return self.state_slots - len(self._free_slots)

    # -- the window group -------------------------------------------------

    def _count_window_eviction(self, blk: int, h: int) -> None:
        self.window_pages_evicted += 1

    @property
    def window_pages_in_use(self) -> int:
        """Pages of the window group some sequence references."""
        return self.window.num_blocks - self.window.num_free if self.window else 0

    @property
    def window_pages_cached(self) -> int:
        """Unreferenced pages of the window group kept for their hashes."""
        return len(self.window._reusable) if self.window else 0

    @property
    def window_steady(self) -> int:
        """Pages of the window group a sequence holds between steps: the
        window's, one more where it straddles a page, one for the token
        being written."""
        return -(-self.window_tokens // self.block_size) + 2

    def window_bound(self, chunk_tokens: int) -> int:
        """The most pages of the window group one sequence holds while a
        chunk of ``chunk_tokens`` is written."""
        return self.window_steady + -(-chunk_tokens // self.block_size)

    def _window_first(self, computed_tokens: int) -> int:
        """The first page of the window group a query at
        ``computed_tokens`` or later can see (a query at position ``t`` sees
        ``t - window + 1 .. t``)."""
        return max(computed_tokens - self.window_tokens, 0) // self.block_size

    def trim_window(self, seq) -> None:
        """Drop ``seq``'s references to the window-group pages that lie
        wholly below its window: no later query of its own sees them (the
        next is at ``num_computed_tokens`` or later), and the kernels
        neither fetch nor fold them. The table entry reads 0 from then on.
        A page that was committed under its hash stays matchable in the
        group's LRU until its room is needed. The pages under the last window
        before the point where ``seq`` left the cached chain it was matched
        on (``window_parted`` pages in: sequences part there, and the next
        may) keep an LRU page's place; any other page it passes is worth a
        match only to a sequence that parts within a window of it, and goes
        first, or one long prompt's pages would push every waiting
        conversation's last window out of the group."""
        ids = seq.window_block_ids
        below = self._window_first(seq.num_computed_tokens)
        kept = self._window_first(seq.window_parted * self.block_size)
        for j in range(seq.window_released, min(below, len(ids))):
            self.window.release(
                ids[j], cold=not (kept <= j < seq.window_parted))
            ids[j] = 0
            seq.window_released = j + 1
            self.window_pages_released += 1

    def advance_window(self, seq, up_to_tokens: int) -> None:
        """Window-group pages for ``seq`` up to ``up_to_tokens``, after
        dropping what fell below its window: from the free list first, then
        the LRU's oldest. All or nothing."""
        if not self.window_blocks:
            return
        self.trim_window(seq)
        need = -(-up_to_tokens // self.block_size) - len(seq.window_block_ids)
        if need > self.window.num_free:
            raise NoFreeBlocksError("out of window-group KV blocks")
        for _ in range(need):
            seq.window_block_ids.append(self.window.allocate())

    def commit_window(self, seq, i: int, h: int, allow_swap: bool = True) -> None:
        """Content-address ``seq``'s window-group page ``i`` by the hash of
        its global page (the same tokens under the same prefix): a no-op
        where it was trimmed already, or with prefix caching off."""
        if (self.window is None or i < seq.window_released
                or i >= len(seq.window_block_ids)):
            return
        seq.window_block_ids[i] = self.window.commit(
            seq.window_block_ids[i], h, allow_swap=allow_swap)

    def match_window(
        self, seq, blocks: List[int], hashes: List[int]
    ) -> Tuple[List[int], List[int]]:
        """Cut the global group's match ``(blocks, hashes)`` back to the
        longest point ``m`` the window group covers too: it has to hold the
        pages under the last window before ``m`` (what ``trim_window``
        leaves a sequence that has computed ``m`` pages), since the window
        layers' keys cannot be recomputed for a tail alone. Takes the
        references in the window group for ``seq``, gives back the global
        pages past ``m``, and counts the tokens so lost."""
        if self.window is None or not blocks:
            return blocks, hashes
        seq.window_parted = len(blocks)
        held = self.window._block_of_hash

        def covered(n: int) -> bool:  # the last window before page n
            first = self._window_first(n * self.block_size)
            return all(h in held for h in hashes[first:n])

        m = next((n for n in range(len(blocks), 0, -1) if covered(n)), 0)
        lost = len(blocks) - m
        if lost:
            self.release_all(blocks[m:])
            self.hit_tokens -= lost * self.block_size
            self.window_prefix_tokens_lost += lost * self.block_size
        first = self._window_first(m * self.block_size)
        seq.window_block_ids = [0] * first + [
            self.window.acquire_cached(h) for h in hashes[first:m]]
        seq.window_released = first
        return blocks[:m], hashes[:m]

    def release_sequence(self, seq) -> None:
        """Give back what ``seq`` holds: its references in both groups and
        its state slot."""
        self.release_all(seq.block_ids)
        seq.block_ids = []
        if self.window is not None:
            self.window.release_all(seq.window_block_ids[seq.window_released:])
        seq.window_block_ids = []
        seq.window_released = seq.window_parted = 0
        if seq.state_slot is not None:
            self._free_slots.append(seq.state_slot)
            seq.state_slot = None

    # -- prefix lookup ----------------------------------------------------

    def match_prefix(
        self,
        token_ids: Sequence[int],
        salt: int = 0,
        deadline: Optional[float] = None,
    ) -> Tuple[List[int], List[int]]:
        """Longest resident prefix of ``token_ids`` at block granularity.

        ``salt`` seeds the hash chain (LoRA adapters salt by adapter name so
        base-model KV never serves adapter requests and vice versa).
        ``deadline`` (monotonic; used by the tiered allocator) bounds
        lower-tier fetches to the request's remaining budget — the base
        allocator is HBM-only and ignores it.
        Returns (matched block ids — increfed, their hashes). Callers start
        computing at ``len(matched) * block_size``.
        """
        self.query_tokens += len(token_ids)
        if not self.enable_prefix_caching:
            return [], []
        hashes = block_hashes(token_ids, self.block_size, parent=salt)
        matched: List[int] = []
        matched_hashes: List[int] = []
        for h in hashes:
            blk = self.acquire_cached(h)
            if blk is None:
                break
            matched.append(blk)
            matched_hashes.append(h)
        self.hit_tokens += len(matched) * self.block_size
        return matched, matched_hashes

    @property
    def hit_rate(self) -> float:
        return self.hit_tokens / self.query_tokens if self.query_tokens else 0.0

    def reset_metrics(self) -> None:
        self.hit_tokens = 0
        self.query_tokens = 0
