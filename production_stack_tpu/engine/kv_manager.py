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
        # (``window_tokens`` the window): a sequence's pages there are
        # released as it advances past them, so it never holds more than
        # ``window_bound`` whatever its length. 0 pages: no such group. The
        # group is sized for ``max_num_seqs`` such residencies
        # (``engine/config.py::window_block_count``), so admission, which
        # stops at that many sequences, has counted it; running out is
        # ``NoFreeBlocksError`` all the same (preemption by recompute).
        self.window_blocks = window_blocks
        self.window_tokens = window_tokens
        self._free_window: List[int] = list(range(window_blocks - 1, -1, -1))
        self.window_pages_released = 0
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

    def release(self, blk: int) -> None:
        self._refcount[blk] -= 1
        assert self._refcount[blk] >= 0, f"double free of block {blk}"
        if self._refcount[blk] == 0:
            h = self._hash_of_block.get(blk)
            if h is not None:
                self._reusable[blk] = h  # keep content for future hits
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

    @property
    def window_pages_in_use(self) -> int:
        return self.window_blocks - len(self._free_window)

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

    def trim_window(self, seq) -> None:
        """Release ``seq``'s window-group pages that lie wholly below its
        window: no later query sees them (a query at position ``t`` sees
        ``t - window + 1 .. t``, and the next is at ``num_computed_tokens``
        or later), and the kernels neither fetch nor fold them. The table
        entry reads 0 from then on."""
        ids = seq.window_block_ids
        below = max(seq.num_computed_tokens - self.window_tokens, 0) // self.block_size
        for j in range(seq.window_released, min(below, len(ids))):
            self._free_window.append(ids[j])
            ids[j] = 0
            seq.window_released = j + 1
            self.window_pages_released += 1

    def advance_window(self, seq, up_to_tokens: int) -> None:
        """Window-group pages for ``seq`` up to ``up_to_tokens``, after
        releasing what fell below its window. All or nothing."""
        if not self.window_blocks:
            return
        self.trim_window(seq)
        need = -(-up_to_tokens // self.block_size) - len(seq.window_block_ids)
        if need > len(self._free_window):
            raise NoFreeBlocksError("out of window-group KV blocks")
        for _ in range(need):
            seq.window_block_ids.append(self._free_window.pop())

    def release_sequence(self, seq) -> None:
        """Give back what ``seq`` holds: its pages of both groups and its
        state slot."""
        self.release_all(seq.block_ids)
        seq.block_ids = []
        self._free_window.extend(seq.window_block_ids[seq.window_released:])
        seq.window_block_ids = []
        seq.window_released = 0
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
