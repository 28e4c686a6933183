"""Continuous-batching scheduler: admission, chunked prefill, preemption.

The reference's engines get this behavior from vLLM (`--enable-chunked-prefill`,
`--max-num-seqs` pass-throughs in `helm/values.yaml:71-81`); here it is native.
Each call to :meth:`Scheduler.schedule` emits one device step: either a set of
prefill chunks (token-budget bounded) or one decode batch over all running
sequences. Out-of-pages decode preempts the youngest sequence (free its pages,
recompute later) — same policy family as vLLM's recompute preemption.

Static-shape discipline: the scheduler emits *logical* work; the runner pads
each step into a small set of compiled bucket shapes, so nothing here needs to
care about XLA.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, List, Optional

from ..logging_utils import init_logger
from .kv_manager import BlockAllocator, NoFreeBlocksError
from .sequence import Sequence, SequenceStatus

logger = init_logger(__name__)


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    max_num_seqs: int = 64
    max_prefill_tokens: int = 2048  # per-step chunked-prefill token budget
    max_model_len: int = 4096
    num_decode_steps: int = 1  # decode burst length per device call
    # Bursts of page reservation per decode pass. 2 when the engine
    # pipelines bursts (the in-flight continuation writes one burst past
    # what the host has seen, so its pages must exist at dispatch time).
    decode_lookahead: int = 1
    # Extra per-sequence page reservation for speculative decoding: a verify
    # step writes KV at up to spec_tokens positions past the committed
    # length, so those pages must exist before dispatch.
    spec_tokens: int = 0
    # The model drafts its own next token (``--speculative-mtp``): its draft
    # layer's entry for a position is stored one slot ahead, so a prefill
    # chunk needs the page of the token after its last, and a prefix hit
    # recomputes its last cached token (whose state makes the first slot
    # past the hit).
    mtp: bool = False
    # Fair timeslicing when more live users than HBM holds (needs a
    # swapper): after a running sequence has decoded this many tokens since
    # its last (re)admission, it may rotate out in favor of a parked or
    # waiting one. 0 = rotate only under allocation pressure.
    swap_quantum: int = 0
    # Deadline shedding: drop sequences whose end-to-end budget
    # (Sequence.deadline, monotonic) expired — queued ones before they
    # consume a prefill step, running ones between decode steps.
    deadline_shedding: bool = True
    # Tenant-aware scheduling (docs/multi-tenancy.md): admit the waiting
    # queue weighted-fair across tenants with strict tier priority
    # (interactive before batch) and preempt batch-tier sequences first
    # — swap/shed — when an interactive tenant is waiting for pages.
    # With homogeneous traffic (one tenant/tier) behavior is identical
    # to plain FIFO.
    tenant_fairness: bool = True


@dataclasses.dataclass
class PrefillItem:
    seq: Sequence
    start: int  # first token index processed this step
    end: int  # one past the last token index


@dataclasses.dataclass
class SchedulerOutput:
    prefills: List[PrefillItem] = dataclasses.field(default_factory=list)
    decodes: List[Sequence] = dataclasses.field(default_factory=list)
    preempted: List[Sequence] = dataclasses.field(default_factory=list)
    # Sequences shed this pass because their deadline expired (pages
    # already released): the engine must surface finish_reason="deadline"
    # to their waiting clients.
    expired: List[Sequence] = dataclasses.field(default_factory=list)
    n_decode_steps: int = 1
    # A locked (in-flight-burst) sequence needed pages it could not get
    # without evicting another locked sequence: the engine must drain the
    # burst and re-schedule.
    blocked_on_locked: bool = False

    @property
    def is_empty(self) -> bool:
        return not self.prefills and not self.decodes


class Scheduler:
    def __init__(
        self,
        config: SchedulerConfig,
        allocator: BlockAllocator,
        swapper=None,
    ):
        self.config = config
        self.allocator = allocator
        # Optional engine/swap.KVSwapper: preemption parks KV host-side and
        # resumes without recompute; quantum rotation timeslices more live
        # users than HBM holds.
        self.swapper = swapper
        self.waiting: Deque[Sequence] = deque()
        self.running: List[Sequence] = []
        self.swapped: Deque[Sequence] = deque()
        # Monotonic admission stamp: ``waiting`` and ``swapped`` form ONE
        # logical FIFO (else rotation would free pages for a waiting request
        # only for the rotated-out sequence to reclaim them — livelock).
        # Involuntary preemption/swap keeps the original stamp (front of
        # line); voluntary rotation takes a fresh one (back of line).
        self._stamp = 0
        # (request_id, num_free) of the last head-of-line admission failure:
        # until the free-page count changes there is no point re-running the
        # prefix match every step (it is O(prompt) hashing and would skew the
        # prefix-cache hit metrics with repeated counted hits).
        self._admit_blocked: Optional[tuple] = None
        # Deadline-shed counters (engine stats → pst:deadline_shed_*).
        self.deadline_sheds_queued = 0  # shed before any prefill step
        self.deadline_sheds_running = 0  # shed between decode steps
        # Tenant QoS (docs/multi-tenancy.md): DRR credit across tenant
        # classes for waiting-queue admission order, and counters/ages
        # the server exports as pst:tenant_* metrics.
        from ..resilience.tenancy import DeficitScheduler

        self._tenant_drr = DeficitScheduler()
        self.batch_preemptions = 0  # batch seqs preempted for interactive
        # admissions held back a step because a running row was computing
        # the page they need next (``_running_prefill_computes_next_page``)
        self.prefix_waits = 0

    # -- queue ops --------------------------------------------------------

    def prompt_fits(self, n_prompt_tokens: int) -> bool:
        """Whether a prompt (plus its first decode token) can EVER be
        scheduled in this pool. Shared by add() and the server's HTTP-layer
        400 precheck so the two cannot drift."""
        bs = self.allocator.block_size
        return (
            -(-(n_prompt_tokens + 1) // bs) <= self.allocator.num_blocks
        )

    def add(self, seq: Sequence) -> None:
        if seq.num_prompt_tokens >= self.config.max_model_len:
            raise ValueError(
                f"prompt of {seq.num_prompt_tokens} tokens exceeds "
                f"max_model_len={self.config.max_model_len}"
            )
        if not self.prompt_fits(seq.num_prompt_tokens):
            # Infeasible outright (prompt + its first decode token exceed
            # the whole pool): full-prompt admission would queue it forever,
            # and admitting it would self-preempt in a zero-progress loop.
            # Fail loudly (HTTP 400) instead. (Auto-sized pools always hold
            # a full max_model_len sequence plus one page —
            # config.resolve_num_kv_blocks — so this fires only on
            # explicitly undersized num_kv_blocks.)
            raise ValueError(
                f"prompt of {seq.num_prompt_tokens} tokens needs more KV "
                f"pages than the engine has ({self.allocator.num_blocks})"
            )
        seq.queue_stamp = self._next_stamp()
        self.waiting.append(seq)

    def _next_stamp(self) -> int:
        self._stamp += 1
        return self._stamp

    @staticmethod
    def _insert_by_stamp(dq: "Deque[Sequence]", seq: Sequence) -> None:
        """Insert keeping the deque ascending by queue_stamp. Involuntary
        preemption re-queues with the ORIGINAL stamp, and after rotate/
        resume cycles the running list is no longer stamp-ordered — a plain
        appendleft could put a newer victim in front of an older one,
        breaking the one-logical-FIFO invariant _admit relies on."""
        if not dq or dq[-1].queue_stamp <= seq.queue_stamp:
            dq.append(seq)
            return
        for i, s in enumerate(dq):
            if s.queue_stamp > seq.queue_stamp:
                dq.insert(i, seq)
                return

    def abort(self, request_id: str) -> Optional[Sequence]:
        for q in (self.waiting, self.running, self.swapped):
            for seq in list(q):
                if seq.request_id == request_id:
                    q.remove(seq)
                    self._finish(seq, "abort")
                    return seq
        return None

    def detach(self, request_id: str, reason: str = "abort") -> Optional[Sequence]:
        """Remove a sequence from the queues WITHOUT releasing its pages.

        For sequences referenced by an in-flight pipelined burst: the device
        is still writing through their block tables, so the pages must stay
        owned until the burst drains (the engine releases them then)."""
        for q in (self.waiting, self.running, self.swapped):
            for seq in list(q):
                if seq.request_id == request_id:
                    q.remove(seq)
                    seq.status = SequenceStatus.FINISHED
                    seq.finish_reason = reason
                    return seq
        return None

    def finish(self, seq: Sequence, reason: str) -> None:
        if seq in self.running:
            self.running.remove(seq)
        self._finish(seq, reason)

    def _finish(self, seq: Sequence, reason: str) -> None:
        seq.status = SequenceStatus.FINISHED
        seq.finish_reason = reason
        self.allocator.release_sequence(seq)
        if self.swapper is not None:
            self.swapper.drop(seq.request_id)

    @property
    def num_waiting(self) -> int:
        return len(self.waiting)

    @property
    def num_running(self) -> int:
        return len(self.running)

    @property
    def num_swapped(self) -> int:
        return len(self.swapped)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running or self.swapped)

    # -- the step ---------------------------------------------------------

    def schedule(
        self, locked: frozenset = frozenset(),
    ) -> SchedulerOutput:
        """``locked``: request ids whose pages an in-flight burst references;
        they must not be preempted this pass (the engine drains the burst
        and re-schedules when that constraint binds)."""
        self._locked = locked
        out = SchedulerOutput()
        # Deadline sweep FIRST: an expired sequence must never consume a
        # device step — not a prefill chunk, not a decode slot, not even an
        # admission that pins pages.
        self._shed_expired(out)
        if self.allocator.window_blocks:
            # A sequence that is not in this step still gives back the
            # window-group pages its last step moved past.
            for seq in self.running:
                self.allocator.trim_window(seq)
        self._admit(out)
        # Fair timeslicing: if parked/queued work remains after admission,
        # rotate out the running sequence with the most decode progress past
        # the quantum — next pass admits the beneficiary into its pages.
        if (
            self.swapper is not None
            and (self.swapped or self.waiting)
            and len(self.running) > 1
        ):
            self._rotate(out)

        # Phase 1: sequences needing prompt (or post-preemption recompute)
        # work get chunks, oldest first, bounded by the step token budget.
        # A preempted sequence that already has outputs recomputes KV up to
        # its last token exclusive — that token is re-processed by decode.
        budget = self.config.max_prefill_tokens
        for seq in list(self.running):
            if budget <= 0:
                break
            if seq not in self.running:  # evicted by an earlier _ensure_blocks
                continue
            target = (
                seq.num_prompt_tokens
                if not seq.output_token_ids
                else seq.num_tokens - 1
            )
            remaining = target - seq.num_computed_tokens
            if remaining <= 0:
                continue
            chunk = min(remaining, budget)
            start = seq.num_computed_tokens
            end = start + chunk
            if not self._ensure_blocks(seq, end + self.config.mtp, out):
                continue
            out.prefills.append(PrefillItem(seq=seq, start=start, end=end))
            budget -= chunk
        if out.prefills:
            return out

        # Phase 2: a decode burst for every running sequence. Burst length is
        # bounded so no sequence writes KV past max_model_len; early stops
        # are trimmed host-side (≤ n-1 wasted tokens per finishing request).
        n = max(self.config.num_decode_steps, 1)
        for seq in self.running:
            n = min(n, max(self.config.max_model_len - seq.num_tokens, 1))
            if seq.sampling.guided_choice:
                # Guided decoding needs its allowed-token mask rebuilt per
                # token host-side. (Penalty rows ride bursts at full depth:
                # the occurrence counts live in multi_step's scan carry —
                # ops/sampling.py apply_penalties_counts.)
                n = 1
        for seq in list(self.running):
            if seq not in self.running:  # lost pages to an earlier preemption
                continue
            if not self._reserve_decode(seq, n, out):
                continue
            out.decodes.append(seq)
        out.n_decode_steps = n
        return out

    def _reserve_decode(self, seq: Sequence, n: int, out) -> bool:
        """Pages for ``seq``'s next ``decode_lookahead`` bursts of ``n``."""
        look = max(self.config.decode_lookahead, 1)
        reserve = min(
            seq.num_tokens + max(look * n - 1, self.config.spec_tokens),
            self.config.max_model_len,
        )
        return self._ensure_blocks(seq, reserve, out, protect=seq)

    def reserve_chain(
        self, seqs: List[Sequence], n: int, out: SchedulerOutput
    ) -> bool:
        """Phase 2's reservation for ``seqs`` alone: the members of a chain
        the engine keeps across the prefill pass ``out`` (which returned
        before phase 2), those among them whose prompt that pass completes.
        All of them are locked meanwhile: only a sequence still in prefill
        can lose its pages (and its place in ``out.prefills``) to them.
        False, with ``out.blocked_on_locked`` set, if one cannot have its
        pages: the engine drains and schedules again."""
        self._locked = self._locked | {s.request_id for s in seqs}
        return all(self._reserve_decode(s, n, out) for s in seqs)

    # -- internals --------------------------------------------------------

    def _shed_expired(self, out: SchedulerOutput) -> None:
        """Drop sequences whose deadline budget is gone — the point of the
        whole deadline subsystem is that this happens *before* a TPU step
        is spent on them. Queued/parked sequences shed from the line
        (``deadline_sheds_queued``); running ones shed between decode
        steps (``deadline_sheds_running``). Sequences referenced by an
        in-flight pipelined burst are skipped (the device still writes
        through their pages) and caught on the post-drain pass."""
        if not self.config.deadline_shedding:
            return
        now = time.monotonic()
        locked = getattr(self, "_locked", frozenset())
        for q, running in ((self.waiting, False), (self.swapped, False),
                           (self.running, True)):
            for seq in [s for s in q if s.deadline_expired(now)]:
                if seq.request_id in locked:
                    continue
                q.remove(seq)
                self._finish(seq, "deadline")
                if running:
                    self.deadline_sheds_running += 1
                else:
                    self.deadline_sheds_queued += 1
                    self._admit_blocked = None  # free pages changed
                out.expired.append(seq)
                logger.info(
                    "shedding request %s (deadline exceeded while %s)",
                    seq.request_id, "running" if running else "queued",
                )

    def _rotate(self, out: SchedulerOutput) -> None:
        """Swap out at most ONE quantum-expired running sequence per pass
        (bounds thrash; steady state rotates every ``swap_quantum`` tokens)."""
        q = self.config.swap_quantum
        if q <= 0:
            return
        locked = getattr(self, "_locked", frozenset())
        best: Optional[Sequence] = None
        for seq in self.running:
            if seq.request_id in locked or seq.in_prefill:
                continue
            progress = seq.num_tokens - seq.resume_marker
            if progress >= q and (
                best is None
                or progress > best.num_tokens - best.resume_marker
            ):
                best = seq
        if best is not None and self.swapper.can_stash(best, self.allocator):
            self.running.remove(best)
            self.swapper.swap_out(best, self.allocator)
            best.queue_stamp = self._next_stamp()  # back of the line
            self.swapped.append(best)
            self._admit_blocked = None  # free pages changed

    def flight_depths(self) -> tuple:
        """(waiting, running, swapped, batch_tier_rows) for the flight
        recorder's per-step record (obs/flight.py). Called on the step
        thread right after a dispatch — the same thread that mutates the
        queues, so plain reads are safe; cost is O(running) over a list
        bounded by max_num_seqs."""
        running = self.running
        batch = sum(1 for s in running if s.tier_rank)
        return (len(self.waiting), len(running), len(self.swapped), batch)

    def queue_age_by_tier(self, now: Optional[float] = None) -> dict:
        """Oldest waiting sequence's queue age per tier (seconds) — the
        per-tenant starvation signal behind ``pst:tenant_queue_age_*``.
        The flood-isolation contract is asserted on these: batch pressure
        must never grow the interactive queue age."""
        now = now if now is not None else time.monotonic()
        ages = {"interactive": 0.0, "batch": 0.0}
        # list(deque) is a single C-level copy (atomic under the GIL):
        # this reader runs on the HTTP/stats thread while the step thread
        # mutates the queues, and iterating the live deque would raise
        # "deque mutated during iteration" mid-scrape.
        for q in (list(self.waiting), list(self.swapped)):
            for seq in q:
                tier = "batch" if seq.tier_rank else "interactive"
                ages[tier] = max(ages[tier], now - seq.arrival_time)
        return ages

    def _next_waiting_index(self) -> int:
        """Which waiting sequence admits next. Plain FIFO (index 0) when
        tenant fairness is off or the queue is homogeneous; otherwise the
        best tier admits first (interactive strictly before batch) and
        tenants within that tier take turns by deficit round robin —
        stamp order is preserved *within* each (tier, tenant) class, so
        no tenant's own requests ever reorder."""
        if not self.config.tenant_fairness or len(self.waiting) < 2:
            return 0
        keys = {(s.tier_rank, s.tenant) for s in self.waiting}
        if len(keys) == 1:
            return 0
        best_rank = min(rank for rank, _ in keys)
        heads: dict = {}
        for i, s in enumerate(self.waiting):
            if s.tier_rank == best_rank and s.tenant not in heads:
                heads[s.tenant] = i
        pick = self._tenant_drr.pick({t: 1.0 for t in heads})
        return heads.get(pick, 0)

    def _preempt_batch_for(self, seq: Sequence, out: SchedulerOutput) -> bool:
        """An interactive sequence is blocked on pages while batch-tier
        work holds them: preempt ONE batch-tier running sequence
        (swap-first — ``_preempt`` parks KV host-side when it can, sheds
        to recompute otherwise) and report whether pages were freed.
        Batch work is throughput-oriented by contract; trading its decode
        progress for interactive TTFT is the whole point of the tiers."""
        locked = getattr(self, "_locked", frozenset())
        victim: Optional[Sequence] = None
        for cand in reversed(self.running):  # youngest batch first
            if cand.request_id in locked or cand.tier_rank != 1:
                continue
            victim = cand
            break
        if victim is None:
            return False
        self._preempt(victim, out)
        self.batch_preemptions += 1
        self._admit_blocked = None  # free pages changed
        logger.info(
            "preempting batch-tier request %s for waiting interactive %s",
            victim.request_id, seq.request_id,
        )
        return True

    def _promised_pages(self) -> int:
        """Pages already-admitted sequences will still allocate to finish
        their prompts. Admission allocates nothing itself, so gating each
        candidate against raw ``num_free`` would admit several long prompts
        into the same pages — re-creating prefill thrash one level up."""
        bs = self.allocator.block_size
        return sum(
            s.blocks_needed(s.num_prompt_tokens, bs) for s in self.running
        )

    def _running_prefill_computes_next_page(
        self, seq: Sequence, toks: List[int], matched: int
    ) -> bool:
        """Whether ``seq`` should wait a step: some running row still in its
        prefill shares ``seq``'s tokens past the ``matched`` pages the cache
        gave it and has not computed those pages yet, so they will be
        committed under the hashes ``seq`` asks for within a step or a few,
        and computing them twice is waste. Worth a step's wait only where
        the pages in the making are a fair share of a step (an eighth of
        the prefill budget): two short prompts that happen to agree are
        computed side by side, as they always were. Same tokens and salt is
        the hash chain's own test, without hashing anything."""
        if not self.allocator.enable_prefix_caching:
            return False
        bs = self.allocator.block_size
        start = matched * bs
        worth = max(bs, self.config.max_prefill_tokens // 8)
        if start + worth > len(toks) - 1:
            return False
        salt = getattr(seq, "cache_salt", 0)
        for r in self.running:
            if (r.num_computed_tokens >= r.num_prompt_tokens
                    or getattr(r, "cache_salt", 0) != salt):
                continue
            theirs = r.all_token_ids[:r.num_prompt_tokens]
            if theirs[:start + bs] != toks[:start + bs]:
                continue
            common = next((n for n, (a, b) in enumerate(
                zip(theirs[start:], toks[start:len(toks) - 1])) if a != b),
                min(len(theirs), len(toks) - 1) - start)
            pages = common // bs  # whole pages in the making, past the match
            if pages * bs >= worth and r.num_computed_tokens < start + pages * bs:
                return True
        return False

    def _admit(self, out: SchedulerOutput) -> None:
        # ``swapped`` and ``waiting`` admit as one stamp-ordered FIFO.
        # Swap-in is gated by a worst-case page check so a blocked resume
        # does not churn fault-up I/O every pass; resume is nearly free
        # when the parked pages never left HBM.
        promised = self._promised_pages()
        while self.swapped and len(self.running) < self.config.max_num_seqs:
            seq = self.swapped[0]
            if self.waiting and (
                self.waiting[0].queue_stamp
                < getattr(seq, "queue_stamp", 0)
            ):
                break  # an older waiting request admits first
            # Headroom beyond the bare resume need: each running sequence
            # may grow a page within a few steps, and a resume that leaves
            # zero slack gets swapped right back out (I/O churn: resumed →
            # victim → resumed, downloading its tail every pass). With
            # NOTHING running the gate must not hold (a sequence that once
            # filled the whole pool has worst-case need == pool size, and
            # gating it forever would deadlock the engine) — attempt the
            # resume; swap_in itself degrades safely if pages are short.
            reserve = len(self.running) + 1
            if self.running and (
                self.swapper.blocks_needed(seq) + reserve + promised
                > self.allocator.num_free
            ):
                return  # no room for the line's head: nobody jumps it
            self.swapped.popleft()
            if not self.swapper.swap_in(seq, self.allocator):
                self._insert_by_stamp(self.swapped, seq)
                return
            if seq.status == SequenceStatus.RUNNING:
                seq.resume_marker = seq.num_tokens
                if seq.first_scheduled_time is None:
                    seq.first_scheduled_time = time.monotonic()
                self.running.append(seq)
            else:
                # Fallback: part of the committed chain was unrecoverable;
                # the sequence recomputes from its longest surviving prefix.
                self._insert_by_stamp(self.waiting, seq)
        while self.waiting and len(self.running) < self.config.max_num_seqs:
            idx = self._next_waiting_index()
            seq = self.waiting[idx]
            if self.swapped and (
                getattr(self.swapped[0], "queue_stamp", 0) < seq.queue_stamp
                and self.swapped[0].tier_rank <= seq.tier_rank
            ):
                # A parked sequence is older but could not resume (page
                # gate above): hold the line rather than jump it. A
                # waiting sequence of a STRICTLY better tier does jump a
                # parked batch one — interactive admission must not queue
                # behind preempted batch work.
                break
            if self._admit_blocked == (
                seq.request_id,
                self.allocator.num_free,
                self.config.max_prefill_tokens,
            ):
                break  # nothing changed since the last failed attempt
            # Prefix-cache lookup at admission; never match the full token
            # list — at least one token must be computed to produce logits.
            # (all_token_ids, not just the prompt: a preempted-with-outputs
            # sequence can re-match KV for its own generated tokens too.)
            if not seq.block_ids:
                toks = seq.all_token_ids
                matchable = toks[: len(toks) - 1]
                blocks, hashes = self.allocator.match_prefix(
                    matchable, salt=getattr(seq, "cache_salt", 0),
                    deadline=seq.deadline,
                )
                # a model with a window page group: the match ends where
                # either group ends it
                blocks, hashes = self.allocator.match_window(seq, blocks, hashes)
                if blocks:
                    seq.adopt_cached_prefix(blocks, hashes)
                    seq.num_computed_tokens = (
                        len(blocks) * self.allocator.block_size
                        - self.config.mtp)
                    seq.num_cached_prompt_tokens = seq.num_computed_tokens
                if self._running_prefill_computes_next_page(
                        seq, toks, len(blocks)):
                    # A running row is about to compute the page this
                    # sequence needs next (arrivals behind one uncached
                    # prefix): admitted now it would compute the same
                    # tokens again and hold a second copy of their pages.
                    # It waits for that row's commit and takes the pages
                    # from the cache, and nobody jumps the line meanwhile.
                    # The attempt is taken back whole, its counts too.
                    if seq.block_ids:
                        self.allocator.release_sequence(seq)
                        seq.reset_for_recompute()
                        seq.status = SequenceStatus.WAITING
                    self.allocator.query_tokens -= len(matchable)
                    self.allocator.hit_tokens -= (
                        len(blocks) * self.allocator.block_size)
                    self.prefix_waits += 1
                    break
            # Admission requires pages for the FULL prompt (vLLM-style), not
            # just the first chunk: chunk-level admission of a long prompt
            # overcommits the pool, and its later chunks then preempt
            # fully-prefilled sequences — which re-prefill and evict others
            # in turn (prefill thrash at near-capacity).
            need = seq.blocks_needed(
                seq.num_prompt_tokens, self.allocator.block_size
            )
            if need + promised > self.allocator.num_free:
                # Engine full; stays queued (vllm:num_requests_waiting). The
                # prefix blocks adopted above must be released: they are
                # refcounted and nothing in the preemption path reclaims
                # pages pinned by *waiting* sequences, so holding them here
                # could wedge admission permanently. Re-matched next attempt.
                if seq.block_ids:
                    self.allocator.release_sequence(seq)
                    seq.reset_for_recompute()
                    seq.status = SequenceStatus.WAITING
                # Batch-tier preemption (docs/multi-tenancy.md): before
                # declaring the pool full for a waiting INTERACTIVE
                # sequence, evict one running batch-tier sequence
                # (swap-first) and retry — batch work never starves
                # interactive prefills on pages.
                if (
                    self.config.tenant_fairness
                    and seq.tier_rank == 0
                    and self._preempt_batch_for(seq, out)
                ):
                    promised = self._promised_pages()
                    continue
                self._admit_blocked = (
                    seq.request_id,
                    self.allocator.num_free,
                    self.config.max_prefill_tokens,
                )
                break
            if not self.allocator.take_state_slot(seq):
                # Every recurrent-state slot is held (a burst's finished
                # members keep theirs until the drain): stays queued, and
                # the standing queue drains the burst.
                if seq.block_ids:
                    self.allocator.release_sequence(seq)
                    seq.reset_for_recompute()
                    seq.status = SequenceStatus.WAITING
                break
            del self.waiting[idx]
            self._admit_blocked = None
            self._tenant_drr.charge(seq.tenant)
            seq.status = SequenceStatus.RUNNING
            seq.resume_marker = seq.num_tokens
            # Queue-wait end marker (first admission only: a preempted
            # sequence's re-admission is not queue wait — its TTFT
            # decomposition keeps the original boundary).
            if seq.first_scheduled_time is None:
                seq.first_scheduled_time = time.monotonic()
            self.running.append(seq)
            promised += need  # this admission's unprefilled pages

    def _ensure_blocks(
        self,
        seq: Sequence,
        up_to_tokens: int,
        out: SchedulerOutput,
        protect: Optional[Sequence] = None,
    ) -> bool:
        """Allocate pages for ``seq`` up to ``up_to_tokens``, preempting the
        youngest other sequence on exhaustion. False if ``seq`` itself lost."""
        locked = getattr(self, "_locked", frozenset())
        while True:
            try:
                for _ in range(seq.blocks_needed(up_to_tokens, self.allocator.block_size)):
                    seq.block_ids.append(self.allocator.allocate())
                self.allocator.advance_window(seq, up_to_tokens)
                return True
            except NoFreeBlocksError:
                victim = self._pick_victim(exclude=protect or seq)
                if victim is None:
                    if seq.request_id in locked:
                        # Cannot self-preempt a sequence whose pages an
                        # in-flight burst still writes through: signal the
                        # engine to drain and retry.
                        out.blocked_on_locked = True
                        out.decodes[:] = [s for s in out.decodes if s is not seq]
                        return False
                    # Nothing left to evict but this sequence itself.
                    self._preempt(seq, out)
                    return False
                self._preempt(victim, out)

    def _pick_victim(self, exclude: Sequence) -> Optional[Sequence]:
        locked = getattr(self, "_locked", frozenset())
        if self.config.tenant_fairness:
            # Batch-tier sequences are preemptible first: an interactive
            # sequence only loses pages when no batch victim remains.
            for seq in reversed(self.running):  # youngest batch first
                if (
                    seq is not exclude
                    and seq.request_id not in locked
                    and seq.tier_rank == 1
                ):
                    return seq
        for seq in reversed(self.running):  # youngest first (vLLM policy)
            if seq is not exclude and seq.request_id not in locked:
                return seq
        return None

    def _preempt(self, seq: Sequence, out: SchedulerOutput) -> None:
        if seq in self.running:
            self.running.remove(seq)
        # The victim may already have been granted work this step — revoke it
        # (its pages are about to be surrendered).
        out.decodes[:] = [s for s in out.decodes if s is not seq]
        out.prefills[:] = [it for it in out.prefills if it.seq is not seq]
        if (
            self.swapper is not None
            and not seq.in_prefill
            and self.swapper.can_stash(seq, self.allocator)
        ):
            # Park KV instead of recompute: the committed prefix stays
            # content-addressed in place; only the tail pages move host-side.
            logger.info(
                "swapping out request %s (out of KV pages)", seq.request_id
            )
            self.swapper.swap_out(seq, self.allocator)
            # Involuntary: keeps its original (old) stamp, so the sorted
            # insert lands it at/near the front of the resume line.
            self._insert_by_stamp(self.swapped, seq)
            return
        logger.warning("preempting request %s (out of KV pages)", seq.request_id)
        # Pages and recurrent state both go: the recompute starts the state
        # from zeros at position 0.
        self.allocator.release_sequence(seq)
        seq.reset_for_recompute()
        self._insert_by_stamp(self.waiting, seq)
        out.preempted.append(seq)
