"""Pure-logic scheduler/allocator regression tests (no device work).

Ring-1 strategy (SURVEY.md §4): stub-free unit tests over the admission and
preemption state machine alone.
"""

import dataclasses

import pytest

from production_stack_tpu.engine.kv_manager import BlockAllocator
from production_stack_tpu.engine.scheduler import Scheduler, SchedulerConfig
from production_stack_tpu.engine.sequence import (
    SamplingParams,
    Sequence,
    SequenceStatus,
)


def _sched(num_blocks=8, bs=4, **over):
    alloc = BlockAllocator(num_blocks, bs, enable_prefix_caching=True)
    kw = dict(max_num_seqs=4, max_prefill_tokens=64, max_model_len=256)
    kw.update(over)
    return Scheduler(SchedulerConfig(**kw), alloc), alloc


def test_admission_releases_pinned_prefix_on_capacity_shortfall():
    """A waiting seq whose prefix-cache hit pins pages must surrender them
    when the capacity check fails — otherwise admission can deadlock with
    nothing running and most pages pinned by un-admittable waiters."""
    sched, alloc = _sched(num_blocks=9, bs=4)

    # Request A computes 24 prompt tokens (6 pages) and finishes, leaving
    # those pages cached (refcount 0, reusable).
    a = Sequence("a", list(range(1, 25)), SamplingParams(max_tokens=1))
    sched.add(a)
    out = sched.schedule()
    assert out.prefills and out.prefills[0].seq is a
    a.num_computed_tokens = out.prefills[0].end
    a.commit_full_blocks(alloc)
    sched.finish(a, "stop")
    assert alloc.num_free == 9

    # Hog C takes the 2 untouched pages and stays running.
    c = Sequence("c", list(range(200, 208)), SamplingParams(max_tokens=64))
    sched.add(c)
    out = sched.schedule()
    assert out.prefills and out.prefills[0].seq is c
    c.num_computed_tokens = out.prefills[0].end

    # Request B shares A's 24-token prefix and needs 8 pages total — the
    # prefix match pins 6 reusable pages, but the 2 fresh pages it still
    # needs are held by C, so B cannot be admitted this round.
    b = Sequence("b", list(range(1, 25)) + list(range(100, 108)),
                 SamplingParams(max_tokens=1))
    sched.add(b)
    sched.schedule()
    assert b.status == SequenceStatus.WAITING
    # The regression: B must not keep the 6 matched pages pinned while
    # waiting — every page must be back in the reusable pool, and repeated
    # scheduling attempts must not leak pins either.
    assert b.block_ids == []
    assert alloc.num_free == 7
    for _ in range(3):
        sched.schedule()
        assert b.block_ids == [] and alloc.num_free == 7


def test_admission_matches_prefix_with_sharing():
    """Full-prompt admission accounts for shared pages: a request whose
    prefix pages are already resident admits into the remainder only."""
    sched, alloc = _sched(num_blocks=9, bs=4)
    a = Sequence("a", list(range(1, 25)), SamplingParams(max_tokens=1))
    sched.add(a)
    out = sched.schedule()
    a.num_computed_tokens = out.prefills[0].end
    a.commit_full_blocks(alloc)

    # B needs 8 pages total, but 6 are A's live committed pages (shared via
    # the prefix match) — only 2 fresh pages are required, which is exactly
    # what remains. Admits immediately, prefix hit established.
    b = Sequence("b", list(range(1, 25)) + list(range(100, 108)),
                 SamplingParams(max_tokens=1))
    sched.add(b)
    out = sched.schedule()
    assert any(item.seq is b for item in out.prefills)
    assert b.num_cached_prompt_tokens == 24
    assert alloc.num_free == 1  # 6 shared + 2 fresh of the 9-page pool


def test_infeasible_prompt_rejected_at_add():
    """Full-prompt admission makes an oversized prompt permanently
    unschedulable — it must 400 at add(), not queue forever."""
    sched, alloc = _sched(num_blocks=8, bs=4)
    with pytest.raises(ValueError, match="KV pages"):
        sched.add(
            Sequence("big", list(range(1, 41)), SamplingParams(max_tokens=1))
        )


def test_decode_depth_hint_overrides_and_clamps():
    """The burst depth is the configured ``num_decode_steps``, under the
    per-sequence clamps: a row near ``max_model_len`` shortens the burst to
    what its context has left, a guided row forces 1. Penalty rows ride at
    full depth — their state lives in multi_step's scan carry now."""
    sched, alloc = _sched(num_blocks=32, bs=4, num_decode_steps=16)
    a = Sequence("a", [1, 2, 3, 4, 5], SamplingParams(max_tokens=64))
    sched.add(a)
    out = sched.schedule()  # prefill pass
    a.num_computed_tokens = out.prefills[0].end
    a.commit_full_blocks(alloc)
    a.output_token_ids.append(7)

    out = sched.schedule()
    assert out.n_decode_steps == 16  # configured depth

    # Penalty rows keep the full depth (counts ride the scan carry).
    a.sampling = SamplingParams(max_tokens=64, repetition_penalty=1.2,
                                presence_penalty=0.5)
    out = sched.schedule()
    assert out.n_decode_steps == 16

    # The max_model_len margin: a burst never writes past the context.
    roomy = sched.config
    sched.config = dataclasses.replace(roomy, max_model_len=a.num_tokens + 5)
    out = sched.schedule()
    assert out.n_decode_steps == 5
    sched.config = roomy

    # Guided rows force n=1 regardless of the configured depth.
    a.sampling = SamplingParams(max_tokens=64, guided_choice=(("x", (9,)),))
    out = sched.schedule()
    assert out.n_decode_steps == 1


