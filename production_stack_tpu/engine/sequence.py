"""Request-side state for the serving engine: sampling params + sequences.

Plays the role of vLLM's ``SamplingParams``/``Sequence`` (which the reference
stack drives over HTTP). A :class:`Sequence` owns its token ids, its KV page
list, and the prefix-cache commit cursor; all device state lives in the
runner's cache arrays.
"""

from __future__ import annotations

import dataclasses
import time
from enum import Enum
from typing import List, Optional, Sequence as Seq, Tuple, Union

from ..kvcache.hashing import block_hashes
from .kv_manager import BlockAllocator


@dataclasses.dataclass
class SamplingParams:
    max_tokens: int = 16
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = -1
    min_p: float = 0.0
    stop: Union[str, List[str], None] = None
    stop_token_ids: Tuple[int, ...] = ()
    ignore_eos: bool = False
    seed: Optional[int] = None
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    repetition_penalty: float = 1.0
    logprobs: Optional[int] = None
    # OpenAI logit_bias: additive per-token-id logit offsets, applied before
    # sampling (and before greedy argmax).
    logit_bias: Tuple[Tuple[int, float], ...] = ()
    # Guided choice (vLLM extra-body `guided_choice` analogue): the output
    # must be exactly one of these token-id sequences; each step's logits
    # are masked to the tokens that continue a still-viable choice.
    guided_choice: Tuple[Tuple[int, ...], ...] = ()

    @property
    def greedy(self) -> bool:
        return self.temperature <= 1e-5

    def guided_allowed(
        self, output_so_far: Seq[int], eos_ids: Seq[int] = ()
    ) -> Optional[List[int]]:
        """Token ids allowed next under guided_choice (None = unconstrained).
        A choice stays viable while the output equals its prefix. When the
        output already IS a complete choice, ``eos_ids`` are also allowed —
        otherwise a choice that is a strict prefix of another ("yes" vs
        "yes!") could never be produced: the mask would force continuation
        into the longer one."""
        if not self.guided_choice:
            return None
        out = tuple(output_so_far)
        n = len(out)
        allowed = []
        for c in self.guided_choice:
            if len(c) > n and c[:n] == out and c[n] not in allowed:
                allowed.append(c[n])
        if out in self.guided_choice:
            for e in eos_ids:
                if e not in allowed:
                    allowed.append(e)
        return allowed

    def guided_done(self, output_so_far: Seq[int]) -> bool:
        """True when no choice continuation remains — the completed-choice
        case, and also any dead end (e.g. EOS emitted under ignore_eos at a
        completed prefix choice): stopping beats serving a fully-masked
        logit row whose argmax would be garbage token 0."""
        if not self.guided_choice:
            return False
        return self.guided_allowed(output_so_far) == []

    @property
    def has_penalties(self) -> bool:
        return (
            self.presence_penalty != 0.0
            or self.frequency_penalty != 0.0
            or self.repetition_penalty != 1.0
        )

    def stop_strings(self) -> List[str]:
        if self.stop is None:
            return []
        return [self.stop] if isinstance(self.stop, str) else list(self.stop)


class SequenceStatus(Enum):
    WAITING = "waiting"
    RUNNING = "running"
    PREEMPTED = "preempted"
    SWAPPED = "swapped"  # live KV parked host-side (engine/swap.py)
    FINISHED = "finished"


class Sequence:
    """One request's lifecycle through the engine."""

    def __init__(
        self,
        request_id: str,
        prompt_token_ids: Seq[int],
        sampling: SamplingParams,
        arrival_time: Optional[float] = None,
        lora_idx: int = 0,
        lora_scale: float = 0.0,
        cache_salt: int = 0,
        deadline: Optional[float] = None,
        tenant: str = "default",
        tenant_class: str = "interactive",
        kv_transfer: Optional[dict] = None,
    ):
        self.request_id = request_id
        # The model's own draft of the token after the last one committed
        # (``--speculative-mtp``): made on the device by the step that
        # committed it; None where that step was none of those (a sequence
        # recomputed after preemption), and its next step then verifies none.
        self.mtp_draft: Optional[int] = None
        self.prompt_token_ids: List[int] = list(prompt_token_ids)
        self.output_token_ids: List[int] = []
        self.sampling = sampling
        self.status = SequenceStatus.WAITING
        # Queue/TTFT bookkeeping rides time.monotonic(), same clock as
        # `deadline` and the admission token bucket: stage durations
        # (queue wait, prefill, decode) must survive wall-clock steps —
        # an NTP adjustment mid-request would otherwise corrupt TTFT and
        # the per-stage decomposition.
        self.arrival_time = arrival_time or time.monotonic()
        self.first_scheduled_time: Optional[float] = None  # queue-wait end
        self.first_token_time: Optional[float] = None  # TTFT marker
        self.finish_reason: Optional[str] = None
        # LoRA bank slot serving this request (0 = base model) and its
        # alpha/r scaling; cache_salt seeds the block-hash chain so KV
        # produced under one adapter never serves as a prefix hit for
        # another (the KV itself differs).
        self.lora_idx = lora_idx
        self.lora_scale = lora_scale
        self.cache_salt = cache_salt
        # Monotonic (time.monotonic) expiry of the request's end-to-end
        # latency budget; None = no deadline. The scheduler sheds expired
        # sequences before they consume device steps.
        self.deadline = deadline
        # Tenant identity and tier, stamped by the router at admission
        # (X-PST-Tenant / X-PST-Tenant-Class). The scheduler admits
        # weighted-fair across tenants and preempts batch-tier work first.
        self.tenant = tenant
        self.tenant_class = (
            tenant_class if tenant_class == "batch" else "interactive"
        )

        # KV bookkeeping.
        self.block_ids: List[int] = []
        # Recurrent-state slot (models with state-space layers; the
        # allocator owns it with the pages): None until first scheduled.
        self.state_slot: Optional[int] = None
        # Pages of the window group (a model with sliding-window layers),
        # by the same logical index as ``block_ids``; the first
        # ``window_released`` entries were given back and read 0;
        # ``window_parted``: the pages of the cached chain it was matched on.
        self.window_block_ids: List[int] = []
        self.window_released = 0
        self.window_parted = 0
        self.num_computed_tokens = 0  # tokens whose KV is resident
        self.num_cached_prompt_tokens = 0  # prefix-cache hits at admission
        self.block_hashes: List[int] = []  # hash per committed block
        self._committed_blocks = 0
        self._last_hash = cache_salt
        # Chunk-hash cursor (controller registration granularity).
        self._chunk_cursor = 0
        self._chunk_last_hash = 0
        # Token count at admission / last swap-in: the scheduler's rotation
        # quantum measures decode progress since this marker.
        self.resume_marker = 0
        # Admission-FIFO stamp across waiting+swapped (scheduler._admit).
        self.queue_stamp = 0
        # Disagg KV handoff (docs/disagg.md): the router-stamped
        # kv_transfer_params for this request ({"request_id", "role"?}),
        # or None. On a producer engine the streamed publisher ships this
        # sequence's pages per prefill chunk under that id; the cursor
        # tracks how many committed blocks have been handed to it.
        self.kv_transfer = kv_transfer
        self.kv_published_cursor = 0

        # Per-request cost attribution (docs/observability.md "Cost
        # attribution"): device-seconds this request was charged — prefill
        # steps charge a token-weighted share, decode bursts/spec verifies
        # charge an active-row share (shares sum to the step wall, so a
        # mixed run's request costs sum to the device-busy wall and
        # pipelined continuations can never double-count). kv page-seconds
        # integrate len(block_ids) over wall time between charge points.
        self.cost_prefill_s = 0.0
        self.cost_decode_s = 0.0
        self.cost_kv_page_s = 0.0
        self._kv_cost_mark: Optional[float] = None

    # -- lengths ----------------------------------------------------------

    @property
    def tier_rank(self) -> int:
        """0 = interactive (served first), 1 = batch."""
        return 1 if self.tenant_class == "batch" else 0

    @property
    def num_prompt_tokens(self) -> int:
        return len(self.prompt_token_ids)

    @property
    def num_tokens(self) -> int:
        return len(self.prompt_token_ids) + len(self.output_token_ids)

    @property
    def all_token_ids(self) -> List[int]:
        return self.prompt_token_ids + self.output_token_ids

    @property
    def in_prefill(self) -> bool:
        return self.num_computed_tokens < self.num_prompt_tokens and not (
            self.output_token_ids
        )

    @property
    def is_finished(self) -> bool:
        return self.status == SequenceStatus.FINISHED

    def deadline_expired(self, now: Optional[float] = None) -> bool:
        if self.deadline is None:
            return False
        return (now if now is not None else time.monotonic()) >= self.deadline

    # -- cost attribution -------------------------------------------------

    def charge_kv_pages(self, now: Optional[float] = None) -> None:
        """Integrate KV residency since the last charge point:
        ``kv_page_s += pages_held * elapsed``. Called at every step that
        touches this sequence and once more at finish, so the integral
        tracks page-count changes at step granularity."""
        now = now if now is not None else time.monotonic()
        mark = self._kv_cost_mark
        if mark is not None and self.block_ids:
            self.cost_kv_page_s += len(self.block_ids) * max(now - mark, 0.0)
        self._kv_cost_mark = now

    def cost_snapshot(self, now: Optional[float] = None) -> dict:
        """The request's accumulated cost, for the ``X-PST-Cost`` header /
        usage extension and the tenant chip-time meter."""
        now = now if now is not None else time.monotonic()
        queue_s = (
            self.first_scheduled_time - self.arrival_time
            if self.first_scheduled_time is not None
            else now - self.arrival_time
        )
        return {
            "prefill_device_s": round(self.cost_prefill_s, 6),
            "decode_device_s": round(self.cost_decode_s, 6),
            "device_s": round(self.cost_prefill_s + self.cost_decode_s, 6),
            "kv_page_s": round(self.cost_kv_page_s, 3),
            "queue_s": round(max(queue_s, 0.0), 6),
        }

    # -- KV paging --------------------------------------------------------

    def blocks_needed(self, up_to_tokens: int, block_size: int) -> int:
        """How many new pages are needed to hold KV for ``up_to_tokens``."""
        want = -(-up_to_tokens // block_size)
        return max(0, want - len(self.block_ids))

    def commit_full_blocks(
        self, allocator: BlockAllocator, allow_swap: bool = True
    ) -> None:
        """Content-address every newly-filled page (enables prefix sharing).
        ``allow_swap=False`` while this sequence is part of an in-flight
        pipelined burst (the device still writes through these page ids)."""
        bs = allocator.block_size
        toks = self.all_token_ids
        n_full = self.num_computed_tokens // bs
        while self._committed_blocks < n_full:
            i = self._committed_blocks
            h = block_hashes(toks[i * bs : (i + 1) * bs], bs, parent=self._last_hash)[0]
            self.block_ids[i] = allocator.commit(
                self.block_ids[i], h, allow_swap=allow_swap
            )
            allocator.commit_window(self, i, h, allow_swap=allow_swap)
            self.block_hashes.append(h)
            self._last_hash = h
            self._committed_blocks += 1

    def commit_full_chunks(self, chunk_tokens: int) -> List[int]:
        """Chunk-granularity hashes of newly computed prefix (controller
        registration — the router's KV-aware lookup speaks these)."""
        toks = self.all_token_ids
        n_full = self.num_computed_tokens // chunk_tokens
        new: List[int] = []
        while self._chunk_cursor < n_full:
            i = self._chunk_cursor
            h = block_hashes(
                toks[i * chunk_tokens : (i + 1) * chunk_tokens],
                chunk_tokens,
                parent=self._chunk_last_hash,
            )[0]
            new.append(h)
            self._chunk_last_hash = h
            self._chunk_cursor += 1
        return new

    def adopt_cached_prefix(self, blocks: List[int], hashes: List[int]) -> None:
        """Install prefix-cache-hit pages found at admission time."""
        assert not self.block_ids
        self.block_ids = list(blocks)
        self.block_hashes = list(hashes)
        self._committed_blocks = len(blocks)
        self._last_hash = hashes[-1] if hashes else 0
        # caller sets num_computed_tokens (= len(blocks) * block_size)

    def reset_for_recompute(self) -> None:
        """Preemption: KV pages were surrendered; recompute from scratch."""
        # Close the KV cost clock: pages were charged up to the last
        # dispatch, and the preempted gap holds ZERO pages — leaving the
        # mark set would bill the post-recompute page count over the
        # whole wait (systematic overcharge of preempted tenants).
        self._kv_cost_mark = None
        self.mtp_draft = None
        self.block_ids = []
        self.num_computed_tokens = 0
        self.num_cached_prompt_tokens = 0
        self.block_hashes = []
        self._committed_blocks = 0
        self._last_hash = self.cache_salt
        self._chunk_cursor = 0
        self._chunk_last_hash = 0
        self.status = SequenceStatus.PREEMPTED
