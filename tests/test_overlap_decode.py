"""Overlapped decode pipeline (docs/engine.md "Overlapped decode pipeline").

The chained two-stage pipeline: burst N+1 dispatches as soon as burst N's
tokens are fetched, and burst N's host bookkeeping runs while N+1
executes. A chain starts whenever the decode batch can be chained, whether
or not requests are arriving. These tests pin the user-visible contract:

- the pipeline engages at the default configuration under a live arrival
  stream, and its outputs (token ids, text deltas, emission order, finish
  reasons) are IDENTICAL to the unpipelined loop — at most one burst of
  overshoot, trimmed before emission, never streamed;
- an arrival waits for the one burst in flight and no more, and joins
  the chain behind its own prefill: no drain, its token spliced into the
  chain's carry on the device, every request's tokens what the synchronous
  loop gives with many rows live; a finished member's pages come back a
  burst later, with no drain; what cannot join (no row, a wider table,
  another sampling program, a guided row, a standing queue) drains under
  its own reason; only the deepening past ``num_decode_steps`` waits for
  quiet;
- every decode dispatch is counted, chained or not, and every drained
  chain by its reason; a chained step of depth 1 is a
  ``jit_pst_decode_step`` program;
- stop strings and max_tokens are honored exactly; aborts mid-overlap
  cancel cleanly (no leaked pages);
- penalty/repetition rows are burst-eligible (multi_step's scan carry —
  ops/sampling.py apply_penalties_counts) and no longer cap the whole
  batch's depth to n=1;
- pst_engine_host_gap_seconds is recorded per batch bucket, declared in
  the metric registry, and documented.
"""

import os
import re
import time

import jax
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import CHAIN_BREAK_REASONS, LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.obs import ENGINE_TELEMETRY, ENGINE_TELEMETRY_REGISTRY


def _engine(**over):
    kw = dict(
        model="tiny-llama-debug",
        max_model_len=256,
        block_size=8,
        num_kv_blocks=128,
        max_num_seqs=8,
        max_prefill_tokens=64,
        attn_impl="gather",
        num_decode_steps=2,
        # Baseline: the pipeline off. Tests opt in explicitly.
        overlap_decode=False,
    )
    kw.update(over)
    return LLMEngine(EngineConfig(**kw))


def _overlap_engine(**over):
    """The pipeline as it is configured by default: ``overlap_decode`` is
    left to ``EngineConfig``."""
    return _engine(**over, overlap_decode=EngineConfig.overlap_decode)


def _run_stream(engine, requests):
    """Drive to completion; returns (per-request ordered event stream,
    per-request token ids). An event is what the SSE layer would frame:
    (text_delta, new_token_ids, finished, finish_reason)."""
    for rid, prompt, sp in requests:
        engine.add_request(rid, prompt_token_ids=prompt, sampling=sp)
    events = {rid: [] for rid, _, _ in requests}
    toks = {rid: [] for rid, _, _ in requests}
    steps = 0
    while engine.has_work():
        for out in engine.step():
            events[out.request_id].append(
                (out.text_delta, tuple(out.new_token_ids), out.finished,
                 out.finish_reason)
            )
            toks[out.request_id].extend(out.new_token_ids)
        steps += 1
        assert steps < 1000
    return events, toks


def _reqs(lengths, max_tokens, temperature=0.0, **sp):
    rng = np.random.default_rng(11)
    return [
        (
            f"r{i}",
            rng.integers(1, 500, size=n).tolist(),
            SamplingParams(max_tokens=mt, temperature=temperature,
                           ignore_eos=True, **sp),
        )
        for i, (n, mt) in enumerate(zip(lengths, max_tokens))
    ]


# ----------------------------------------------------------------------
# Engagement + equivalence
# ----------------------------------------------------------------------


def test_overlap_engages_and_streams_identically():
    """With the gates open the pipeline must actually engage, and the
    full event stream (SSE framing input: deltas, ids, finish order) must
    equal the unpipelined loop's."""
    ref_events, ref_toks = _run_stream(
        _engine(), _reqs((17, 33, 9, 25), (12, 20, 7, 16))
    )
    eng = _overlap_engine()
    got_events, got_toks = _run_stream(
        eng, _reqs((17, 33, 9, 25), (12, 20, 7, 16))
    )
    assert eng.pipelined_bursts_total > 0, "pipeline never engaged"
    assert got_toks == ref_toks
    # Per-request frame streams are identical: same deltas, same token
    # grouping is NOT required across modes, so compare the concatenation
    # and the terminal frame.
    for rid in ref_events:
        assert "".join(e[0] for e in got_events[rid]) == "".join(
            e[0] for e in ref_events[rid]
        )
        assert got_events[rid][-1][2:] == ref_events[rid][-1][2:]
        # No frame after the finished one, and none empty-after-finish.
        assert all(not e[2] for e in got_events[rid][:-1])


def _sp(max_tokens, **kw):
    return SamplingParams(max_tokens=max_tokens, temperature=0.0,
                          ignore_eos=True, **kw)


def _run_with_arrivals(engine, first, later, every=3):
    """Drive ``first`` to completion while one request of ``later``
    arrives every ``every`` engine steps. Returns (token ids by request,
    engine steps taken)."""
    for rid, prompt, sp in first:
        engine.add_request(rid, prompt_token_ids=prompt, sampling=sp)
    later = list(later)
    toks = {rid: [] for rid, _, _ in list(first) + later}
    steps = 0
    while engine.has_work() or later:
        for out in engine.step():
            toks[out.request_id].extend(out.new_token_ids)
        steps += 1
        if later and steps % every == 0:
            rid, prompt, sp = later.pop(0)
            engine.add_request(rid, prompt_token_ids=prompt, sampling=sp)
        assert steps < 1000
    return toks, steps


def _arrival_stream():
    rng = np.random.default_rng(23)
    mk = lambda rid, n, mt: (  # noqa: E731
        rid, rng.integers(1, 500, size=n).tolist(), _sp(mt))
    first = [mk("a0", 17, 40), mk("a1", 9, 40)]
    later = [mk(f"b{i}", 11 + i, 12) for i in range(5)]
    return first, later


def test_chain_engages_at_default_config_while_requests_arrive():
    """Under a stream of arrivals, as a busy server has one, most decode
    dispatches are chained, token for token what the synchronous loop
    gives: a chain asks for no quiet."""
    ref, _ = _run_with_arrivals(_engine(), *_arrival_stream())
    eng = _overlap_engine(min_decode_bucket=8)
    assert eng.cfg.overlap_decode
    got, _ = _run_with_arrivals(eng, *_arrival_stream())
    assert got == ref
    # arrivals landed mid-chain, and the chain went on behind each
    assert eng.chain_kept_prefills_total >= 3
    assert eng.pipeline_breaks["prefill"] == 0
    assert eng.pipelined_bursts_total == eng.decode_dispatches_total


def test_arrival_mid_chain_prefills_behind_the_burst_in_flight():
    """An arrival's prefill is launched behind the burst in flight, before
    that burst is drained, and its first token comes no later than one
    burst after the synchronous loop gives it."""
    rng = np.random.default_rng(4)
    p0 = rng.integers(1, 500, 21).tolist()
    p1 = rng.integers(1, 500, 15).tolist()

    def run(eng, after=8):
        """r1 arrives once r0 has ``after`` tokens out: how many r0 had
        when r1's first token came, and whether its prefill was launched
        with a burst in flight."""
        behind = []
        dispatch = eng.runner.prefill_dispatch
        eng.runner.prefill_dispatch = lambda items, **kw: (
            behind.append(eng.runner.burst_in_flight),
            dispatch(items, **kw))[1]
        eng.add_request("r0", prompt_token_ids=p0, sampling=_sp(40))
        n0, sent = 0, False
        while eng.has_work():
            for out in eng.step():
                if out.request_id == "r1":
                    return n0, behind
                n0 += len(out.new_token_ids)
            if not sent and n0 >= after:
                eng.add_request("r1", prompt_token_ids=p1, sampling=_sp(6))
                sent = True
        raise AssertionError("r1 never answered")

    sync_n0, _ = run(_engine())
    eng = _overlap_engine()
    n0, behind = run(eng)
    assert behind == [True], "the prefill must not wait for a drain"
    assert n0 <= sync_n0 + eng.cfg.num_decode_steps


@pytest.mark.parametrize("swap", [True, False])
def test_standing_queue_costs_no_more_steps_than_the_synchronous_loop(swap):
    """More requests than ``max_num_seqs``: while the queue stands the
    loop is the synchronous one (no start/drain pairs, two engine steps a
    token), and the chain takes over once it is gone."""
    reqs = lambda: _reqs((17, 33, 9, 25, 13, 21), (14, 20, 9, 16, 11, 18))  # noqa: E731
    steps = {}
    for name, overlap in (("sync", False),
                          ("chain", EngineConfig.overlap_decode)):
        eng = _engine(max_num_seqs=2, kv_swap=swap, overlap_decode=overlap)
        for rid, prompt, sp in reqs():
            eng.add_request(rid, prompt_token_ids=prompt, sampling=sp)
        n, toks, chained_under_queue = 0, {}, 0
        while eng.has_work():
            queued = eng.scheduler.num_waiting
            before = eng.pipelined_bursts_total
            for out in eng.step():
                toks.setdefault(out.request_id, []).extend(out.new_token_ids)
            if queued and eng.scheduler.num_waiting:
                chained_under_queue += eng.pipelined_bursts_total - before
            n += 1
            assert n < 1000
        steps[name] = (n, toks)
        assert chained_under_queue == 0
    assert steps["chain"][1] == steps["sync"][1]
    assert eng.pipelined_bursts_total > 0  # the last two run with no queue
    # that one chain costs its start, which returns no token, and the
    # drain of the burst launched before its last member was seen to finish
    assert sum(eng.pipeline_breaks.values()) == 1
    assert steps["chain"][0] <= steps["sync"][0] + 2


def test_decode_counters_add_up():
    """Chained and synchronous dispatches are all the decode dispatches,
    every chain that started was drained, and every drain has a reason."""
    eng = _overlap_engine(num_decode_steps=1)
    calls = {"burst_start": 0, "burst_continue": 0, "burst_drain": 0,
             "execute_decode_multi": 0}

    def counted(name):
        fn = getattr(eng.runner, name)

        def wrapper(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        setattr(eng.runner, name, wrapper)

    for name in calls:
        counted(name)
    first, later = _arrival_stream()
    # a guided row keeps its batches synchronous
    later.insert(2, ("g", [3, 4, 5], SamplingParams(
        max_tokens=6, temperature=0.0, guided_choice=((5, 9), (5, 12, 13)))))
    _run_with_arrivals(eng, first, later)
    chained = calls["burst_start"] + calls["burst_continue"]
    assert chained == eng.pipelined_bursts_total > 0
    assert calls["execute_decode_multi"] > 0
    assert (chained + calls["execute_decode_multi"]
            == eng.decode_dispatches_total)
    assert set(eng.pipeline_breaks) == set(CHAIN_BREAK_REASONS)
    assert (calls["burst_start"] == calls["burst_drain"]
            == sum(eng.pipeline_breaks.values()))
    assert eng.pipeline_breaks["prefill"] == 0
    assert eng.pipeline_breaks["row_bucket"] > 0  # chains of 2 and 4 rows
    assert eng.pipeline_breaks["not_eligible"] > 0  # the guided row
    assert eng.pipeline_breaks["decode_set"] > 0  # the last member finished
    assert eng.chain_kept_prefills_total > 0
    stats = eng.stats()
    assert stats["decode_dispatches_total"] == eng.decode_dispatches_total
    assert stats["pipeline_breaks_total"] == eng.pipeline_breaks
    assert stats["chain_kept_prefills_total"] == eng.chain_kept_prefills_total


def test_decode_counters_are_exported():
    from prometheus_client import generate_latest

    from production_stack_tpu.engine.server import EngineMetrics

    eng = _overlap_engine()
    _run_with_arrivals(eng, *_arrival_stream())
    metrics = EngineMetrics("m")
    metrics.refresh(eng.stats())
    text = generate_latest(metrics.registry).decode()

    def value(series):
        return float(re.search(
            re.escape(series) + r" (\S+)", text).group(1))

    assert (value('pst:decode_dispatches_total{model_name="m"}')
            == eng.decode_dispatches_total)
    assert (value('pst:pipelined_bursts_total{model_name="m"}')
            == eng.pipelined_bursts_total)
    for why, n in eng.pipeline_breaks.items():
        assert value('pst:pipeline_breaks_total{model_name="m",reason="%s"}'
                     % why) == n
    assert (value('pst:chain_kept_prefills_total{model_name="m"}')
            == eng.chain_kept_prefills_total > 0)


@pytest.mark.parametrize("depth, name", [
    (1, "jit_pst_decode_step"), (4, "jit_pst_decode_burst")])
def test_chained_program_is_named_by_its_depth(depth, name):
    """The device trace knows a program by its module name: a chained
    step of depth 1 is found with the synchronous decode step, a deeper
    burst is a burst. Live traffic and the warm-up's ``b{B}xn{depth}``
    bucket reach the same jitted object."""
    from production_stack_tpu.engine.precompile import (
        Bucket, table_width_buckets)

    eng = _overlap_engine(num_decode_steps=depth)
    jitted = eng.runner._burst_fn(depth)
    lowered = []

    def spy(*args):
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
            if hasattr(x, "shape") else x, args)
        lowered.append(jitted.lower(*shapes).as_text())
        return jitted(*args)

    eng.runner._burst_fn = lambda n: spy if n == depth else None
    _run_stream(eng, _reqs((9,), (3 * depth,)))
    assert eng.pipelined_bursts_total > 0 and lowered
    modules = {re.search(r"module @(\w+)", text).group(1) for text in lowered}
    assert len(modules) == 1 and modules.pop().startswith(name)
    del eng.runner._burst_fn  # the class's own again
    before = jitted._cache_size()
    eng.runner.warmup_bucket(Bucket(
        "decode_burst", rows=1, width=table_width_buckets(eng.cfg)[0],
        n_steps=depth, greedy=True))
    assert jitted._cache_size() == before, "the warm-up compiled another"


def test_chain_lets_go_of_a_row_whose_deadline_passed():
    """The scheduler sheds only rows that no burst in flight writes
    through, so a chain must not hold a row past its deadline."""
    eng = _overlap_engine(num_decode_steps=1, deadline_shedding=True)
    eng.add_request("d", prompt_token_ids=list(range(5, 20)),
                    sampling=_sp(200), deadline=time.monotonic() + 3600)
    seq = eng._seqs["d"]
    outs = []
    while eng.has_work() and len(outs) < 200:
        outs += eng.step()
        if len(seq.output_token_ids) == 5:
            seq.deadline = time.monotonic() - 1.0
    assert outs[-1].finish_reason == "deadline"
    assert len(seq.output_token_ids) <= 5 + 2  # the burst in flight, no more
    assert eng.pipeline_breaks["not_eligible"] == 1
    assert eng.allocator.num_free == eng.allocator.num_blocks


def test_overlap_max_tokens_exact_with_overshoot_trimmed():
    """Burst depth 4 + pipelining: a request whose max_tokens is not a
    multiple of the depth still emits EXACTLY max_tokens (the burst's
    speculative tail is trimmed before emission)."""
    eng = _overlap_engine(num_decode_steps=4)
    _, toks = _run_stream(eng, _reqs((15, 21), (9, 13)))
    assert eng.pipelined_bursts_total > 0
    assert [len(toks[f"r{i}"]) for i in range(2)] == [9, 13]


def test_overlap_stop_strings_honored_and_never_streamed():
    """Stop strings under the pipeline: the emitted text ends exactly
    where the unpipelined loop's does — overshot tokens decoded past the
    stop are trimmed before any frame is emitted."""
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, 200, size=12).tolist()

    def run(engine):
        engine.add_request(
            "s", prompt_token_ids=prompt,
            sampling=SamplingParams(max_tokens=40, temperature=0.0,
                                    ignore_eos=True),
        )
        # Discover the greedy text, then stop on a substring of it.
        text = ""
        while engine.has_work():
            for out in engine.step():
                text += out.text_delta
        return text

    full = run(_engine())
    assert len(full) > 8
    stop = full[5:8]

    def run_stop(engine):
        engine.add_request(
            "s", prompt_token_ids=prompt,
            sampling=SamplingParams(max_tokens=40, temperature=0.0,
                                    ignore_eos=True, stop=[stop]),
        )
        text, reason = "", None
        while engine.has_work():
            for out in engine.step():
                text += out.text_delta
                assert stop not in text, "stop string leaked into a frame"
                if out.finished:
                    reason = out.finish_reason
        return text, reason

    ref = run_stop(_engine())
    eng = _overlap_engine(num_decode_steps=4)
    got = run_stop(eng)
    assert got == ref
    assert got[1] == "stop"


def test_abort_mid_overlap_cancels_cleanly():
    """Aborting an in-flight member under auto-engaged overlap defers its
    page release to the drain; the survivor's tokens are unchanged and the
    allocator balances afterwards."""
    rng = np.random.default_rng(5)
    p0 = rng.integers(1, 500, size=19).tolist()
    p1 = rng.integers(1, 500, size=27).tolist()
    ref = _run_stream(
        _engine(),
        [("keep", p0, SamplingParams(max_tokens=20, temperature=0.0,
                                     ignore_eos=True))],
    )[1]["keep"]

    eng = _overlap_engine()
    eng.add_request("keep", prompt_token_ids=p0,
                    sampling=SamplingParams(max_tokens=20, temperature=0.0,
                                            ignore_eos=True))
    eng.add_request("gone", prompt_token_ids=p1,
                    sampling=SamplingParams(max_tokens=50, temperature=0.0,
                                            ignore_eos=True))
    kept, steps, aborted = [], 0, False
    while eng.has_work():
        for out in eng.step():
            assert not (aborted and out.request_id == "gone"), (
                "aborted request kept emitting"
            )
            if out.request_id == "keep":
                kept.extend(out.new_token_ids)
        steps += 1
        if steps == 4:
            assert eng.abort_request("gone")
            aborted = True
    assert eng.pipelined_bursts_total > 0
    assert kept == ref
    assert not eng._burst_deferred
    assert not eng.runner.burst_in_flight
    assert eng.allocator.num_free == eng.allocator.num_blocks


def test_overlap_late_arrival_joins_without_a_drain():
    """A request arriving mid-pipeline prefills behind the burst in flight
    and takes a row of the chain behind that: nothing drains until the last
    member has finished; everyone finishes with exact lengths and the
    allocator balances afterwards."""
    eng = _overlap_engine(num_decode_steps=4, min_decode_bucket=2)
    rng = np.random.default_rng(4)
    eng.add_request("r0", prompt_token_ids=rng.integers(1, 500, 21).tolist(),
                    sampling=SamplingParams(max_tokens=24, temperature=0.0,
                                            ignore_eos=True))
    toks = {"r0": [], "r1": []}
    steps = 0
    while eng.has_work():
        for out in eng.step():
            toks[out.request_id].extend(out.new_token_ids)
        steps += 1
        if steps == 3:
            assert eng.runner.burst_in_flight, "arrival must land mid-pipeline"
            eng.add_request(
                "r1", prompt_token_ids=rng.integers(1, 500, 15).tolist(),
                sampling=SamplingParams(max_tokens=10, temperature=0.0,
                                        ignore_eos=True),
            )
        if steps == 5:
            assert eng.runner.burst_in_flight
            assert [s.request_id for s in eng._burst_seqs] == ["r0", "r1"]
            assert sum(eng.pipeline_breaks.values()) == 0
        assert steps < 1000
    assert len(toks["r0"]) == 24
    assert len(toks["r1"]) == 10
    assert eng.chain_kept_prefills_total == 1
    assert eng.pipeline_breaks["decode_set"] == 1 == sum(
        eng.pipeline_breaks.values())
    assert not eng._burst_deferred
    assert not eng.runner.burst_in_flight
    assert eng.allocator.num_free == eng.allocator.num_blocks


# ----------------------------------------------------------------------
# A chain kept across prefills: rows join on the device
# ----------------------------------------------------------------------


def _session_stream(temperature, n_first=5, n_later=9, **sp):
    """``n_first`` requests at once, then one more every other step while
    they decode: different lengths, and outputs short enough that members
    finish (and their rows are taken again) while others arrive."""
    rng = np.random.default_rng(31)

    def mk(rid, n, mt):
        return (rid, rng.integers(1, 500, size=n).tolist(), SamplingParams(
            max_tokens=mt, temperature=temperature, ignore_eos=True,
            seed=None if temperature == 0.0 else 1000 + n, **sp))

    first = [mk(f"a{i}", 9 + 4 * i, 30 + 3 * i) for i in range(n_first)]
    later = [mk(f"b{i}", 7 + 3 * i, 6 + (5 * i) % 11) for i in range(n_later)]
    return first, later


@pytest.mark.parametrize("temperature, depth", [
    (0.0, 1), (0.0, 2), (0.9, 1), (0.9, 3)])
def test_rows_joining_a_live_chain_get_the_synchronous_loops_tokens(
        temperature, depth):
    """Many rows live, arrivals every other step, members finishing in
    between: every request's tokens are those of the same requests with
    ``overlap_decode`` off, greedy and seeded-sampled, at depth 1 and
    deeper. The chain is never drained by a prefill: each arrival's first
    token is spliced into the carry on the device, its seed counted from
    the chain's own step offset, and a finished member's row is taken by
    a later arrival."""
    kw = dict(max_num_seqs=16, min_decode_bucket=16, num_decode_steps=depth,
              num_kv_blocks=256)
    ref, _ = _run_with_arrivals(
        _engine(**kw), *_session_stream(temperature), every=2)
    eng = _overlap_engine(**kw)
    rows_used = []
    cont = eng.runner.burst_continue
    eng.runner.burst_continue = lambda members, joins=(): (
        rows_used.extend(row for row, _, _ in joins),
        cont(members, joins))[1]
    got, _ = _run_with_arrivals(eng, *_session_stream(temperature), every=2)
    assert got == ref
    assert all(len(t) > 0 for t in got.values())
    assert eng.pipeline_breaks["prefill"] == 0
    # the first arrival comes while the five are still in prefill
    assert eng.chain_kept_prefills_total == len(rows_used) == 8
    assert len(set(rows_used)) < len(rows_used), "a dead row was taken again"
    assert sum(eng.pipeline_breaks.values()) == 1  # the last member's end
    assert eng.allocator.num_free == eng.allocator.num_blocks


def test_inner_chunks_of_a_long_prompt_keep_the_chain():
    """A prompt of five chunks arrives under a running chain: the chain
    goes on behind every chunk (four that complete nothing and change no
    membership, then the one that joins), and the tokens are the
    synchronous loop's."""
    rng = np.random.default_rng(8)
    first = [("a", rng.integers(1, 500, 12).tolist(), _sp(40))]
    later = [("long", rng.integers(1, 500, 75).tolist(), _sp(9))]
    kw = dict(max_prefill_tokens=16, min_decode_bucket=4, num_decode_steps=1)
    ref, _ = _run_with_arrivals(_engine(**kw), first, later)
    eng = _overlap_engine(**kw)
    fetched = []
    fetch = eng.runner.prefill_fetch
    eng.runner.prefill_fetch = lambda h, n: (fetched.append(n), fetch(h, n))[1]
    got, _ = _run_with_arrivals(eng, first, later)
    assert got == ref
    assert eng.chain_kept_prefills_total == 5
    assert fetched == [1], "only the completing chunk's token is read"
    assert sum(eng.pipeline_breaks.values()) == 1  # the end


def test_a_joining_cycle_is_the_prefills_in_the_flight_recorder():
    """The cycle in which a row joins waits for the prefill program: it is
    carried by the prefill's dispatch (recorded at its fetch, after the
    chained step's) and held against prefill cycles, not against plain
    decode steps a third as long, which would make every arrival a stall.
    An inner chunk's cycle waits for a decode step and is a decode cycle."""
    rng = np.random.default_rng(8)
    first = [("a", rng.integers(1, 500, 12).tolist(), _sp(40))]
    later = [("long", rng.integers(1, 500, 40).tolist(), _sp(5))]
    eng = _overlap_engine(max_prefill_tokens=16, min_decode_bucket=4,
                          num_decode_steps=1)
    _run_with_arrivals(eng, first, later)
    assert eng.chain_kept_prefills_total == 3  # two inner chunks, the last
    rows = eng.flight.records()
    cycles = [(rows[i - 1]["kind"], r["kind"]) for i, r in enumerate(rows)
              if i and r["cycle_s"] is not None
              and rows[i - 1]["cycle_s"] is None]
    assert cycles.count(("prefill", "decode")) == 2  # the inner chunks
    assert cycles.count(("decode", "prefill")) == 1  # the join


def test_finished_members_give_their_pages_back_with_no_drain():
    """One long request keeps the chain alive while sixty short ones come
    and go through a pool that holds six of them: a member's pages are
    free again one burst after the host saw it finish, the chain never
    drains, and nothing is left held at the end."""
    eng = _overlap_engine(num_decode_steps=1, max_num_seqs=4,
                          min_decode_bucket=4, num_kv_blocks=40,
                          max_model_len=256)
    rng = np.random.default_rng(12)
    eng.add_request("long", prompt_token_ids=rng.integers(1, 500, 10).tolist(),
                    sampling=_sp(200))
    done, sent, steps, low = 0, 0, 0, eng.allocator.num_blocks
    while eng.has_work():
        for out in eng.step():
            if out.finished and out.request_id != "long":
                done += 1
        steps += 1
        if sent < 60 and eng.scheduler.num_running < 3 and steps % 2 == 0:
            eng.add_request(
                f"s{sent}", sampling=_sp(3 + sent % 4),
                prompt_token_ids=rng.integers(1, 500, 30).tolist())
            sent += 1
        if sent == 60 and done == 60 and eng.runner.burst_in_flight:
            # two bursts after the last short one finished, with the chain
            # still running: only the long request holds pages
            held = len(eng._seqs["long"].block_ids) if "long" in eng._seqs else 0
            low = min(low, eng.allocator.num_blocks - eng.allocator.num_free
                      - held)
        assert steps < 2000
    assert done == 60
    assert eng.chain_kept_prefills_total == 60
    assert sum(eng.pipeline_breaks.values()) == 1, eng.pipeline_breaks
    assert low == 0, "a finished member's pages were still held"
    assert not eng._burst_deferred
    assert eng.allocator.num_free == eng.allocator.num_blocks


def test_a_finished_turns_pages_are_hit_by_the_sessions_next_turn():
    """A chain that never drains commits every page while a burst is in
    flight (``allow_swap=False``): the pages are registered all the same,
    and a session's next turn, arriving under the same chain, finds its
    whole history in the prefix cache as it does in the synchronous loop."""
    rng = np.random.default_rng(3)
    history = rng.integers(1, 500, 40).tolist()
    cached = {}
    for name, eng in (("sync", _engine(num_decode_steps=1)),
                      ("chain", _overlap_engine(num_decode_steps=1,
                                                min_decode_bucket=4))):
        eng.add_request("keeper", prompt_token_ids=history[:11],
                        sampling=_sp(120))
        eng.add_request("t1", prompt_token_ids=history, sampling=_sp(17))
        answer, turn2, steps = [], None, 0
        while eng.has_work():
            for out in eng.step():
                if out.request_id == "t1":
                    answer.extend(out.new_token_ids)
                    if out.finished:
                        turn2 = eng.add_request(
                            "t2", prompt_token_ids=history + answer + [7, 8, 9],
                            sampling=_sp(5))
            steps += 1
            assert steps < 1000
        cached[name] = turn2.num_cached_prompt_tokens
        if name == "chain":
            assert sum(eng.pipeline_breaks.values()) == 1
            assert eng.chain_kept_prefills_total >= 1
    # 40 + 17 tokens of history, the last sampled token's KV never written:
    # seven whole pages of eight
    assert cached["chain"] == cached["sync"] == 56


def _fallback(name):
    """-> (engine overrides, the running chain's request, the arrival)."""
    rng = np.random.default_rng(21)
    p = lambda n: rng.integers(1, 500, n).tolist()  # noqa: E731
    cases = {
        "row_bucket": (dict(), _sp(30), (p(9), _sp(5))),
        "table_width": (dict(max_model_len=2048, num_kv_blocks=256,
                             max_prefill_tokens=1024, min_decode_bucket=4),
                        _sp(30), (p(600), _sp(5))),
        "sampling_variant:penalties": (
            dict(min_decode_bucket=4), _sp(30),
            (p(9), _sp(5, presence_penalty=0.5))),
        "sampling_variant:logprobs": (
            dict(min_decode_bucket=4), _sp(30), (p(9), _sp(5, logprobs=2))),
        "sampling_variant:sampled": (
            dict(min_decode_bucket=4), _sp(30),
            (p(9), SamplingParams(max_tokens=5, temperature=0.8, seed=1,
                                  ignore_eos=True))),
        "not_eligible": (
            dict(min_decode_bucket=4), _sp(30),
            ([3, 4, 5], SamplingParams(
                max_tokens=6, temperature=0.0,
                guided_choice=((5, 9), (5, 12, 13))))),
        "queue": (dict(min_decode_bucket=4, max_num_seqs=1), _sp(30),
                  (p(9), _sp(5))),
    }
    over, first_sp, (prompt, sp) = cases[name]
    return over, ("a", p(12), first_sp), ("b", prompt, sp)


@pytest.mark.parametrize("name", [
    "row_bucket", "table_width", "sampling_variant:penalties",
    "sampling_variant:logprobs", "sampling_variant:sampled", "not_eligible",
    "queue"])
def test_what_cannot_join_drains_the_chain_under_its_reason(name):
    """No free row in the chain's bucket, a table wider than the chain's,
    penalties (their counts need the first token), log-probabilities or
    sampling the chain's program was not compiled with, a guided row, a
    queue left standing: the chain drains as it did, the reason is counted,
    and the tokens are the synchronous loop's."""
    over, first, later = _fallback(name)
    kw = dict(num_decode_steps=1, **over)
    ref, _ = _run_with_arrivals(_engine(**kw), [first], [later])
    eng = _overlap_engine(**kw)
    got, _ = _run_with_arrivals(eng, [first], [later])
    assert got == ref
    why = name.split(":")[0]
    assert eng.pipeline_breaks[why] == 1, eng.pipeline_breaks
    assert eng.pipeline_breaks["prefill"] == 0
    assert eng.chain_kept_prefills_total == 0
    assert eng.allocator.num_free == eng.allocator.num_blocks


def test_overlap_sampled_rows_match_sync():
    """Seeded sampling through the pipeline: the on-device seed chain
    (base + step offset) must reproduce the synchronous loop exactly."""
    reqs = lambda: _reqs((13, 22), (10, 10), temperature=0.9, seed=42)  # noqa: E731
    _, ref = _run_stream(_engine(), reqs())
    eng = _overlap_engine()
    _, got = _run_stream(eng, reqs())
    assert eng.pipelined_bursts_total > 0
    assert got == ref


# ----------------------------------------------------------------------
# Penalties ride bursts (multi_step scan carry)
# ----------------------------------------------------------------------


PENALTY_SP = dict(presence_penalty=0.8, frequency_penalty=0.5,
                  repetition_penalty=1.3)


def test_penalties_ride_bursts_and_match_single_step():
    """A penalized batch decodes at full burst depth (no n=1 forcing) and
    reproduces the single-step penalty path token for token — the scan
    carry's on-device counts equal the host-rebuilt arrays."""
    reqs = lambda: _reqs((14, 23), (16, 16), **PENALTY_SP)  # noqa: E731
    ref_eng = _engine(num_decode_steps=1)
    _, ref = _run_stream(ref_eng, reqs())

    eng = _engine(num_decode_steps=4)
    steps = 0
    for rid, prompt, sp in reqs():
        eng.add_request(rid, prompt_token_ids=prompt, sampling=sp)
    toks = {"r0": [], "r1": []}
    while eng.has_work():
        for out in eng.step():
            toks[out.request_id].extend(out.new_token_ids)
        steps += 1
    assert toks == ref
    # 16 tokens at depth 4 ≈ prefill steps + ~4 decode bursts: far fewer
    # engine steps than the 16+ the old n=1 forcing produced.
    assert steps <= 10, f"penalized batch still stepping token-by-token ({steps})"


def test_penalties_ride_pipelined_bursts():
    """Penalty state chains ACROSS pipelined continuations on device: a
    pipelined penalized run equals the single-step reference."""
    reqs = lambda: _reqs((14, 23), (18, 18), **PENALTY_SP)  # noqa: E731
    _, ref = _run_stream(_engine(num_decode_steps=1), reqs())
    eng = _overlap_engine(num_decode_steps=4)
    _, got = _run_stream(eng, reqs())
    assert eng.pipelined_bursts_total > 0, (
        "penalized rows must be pipeline-eligible now"
    )
    assert got == ref


def test_mixed_penalized_and_plain_batch_matches():
    """One penalized row must not perturb its plain batchmates (neutral
    penalty rows are identity), nor cap their depth."""
    rng = np.random.default_rng(3)
    p0 = rng.integers(1, 500, size=12).tolist()
    p1 = rng.integers(1, 500, size=18).tolist()

    def run(engine, with_peer):
        engine.add_request(
            "plain", prompt_token_ids=p0,
            sampling=SamplingParams(max_tokens=12, temperature=0.0,
                                    ignore_eos=True),
        )
        if with_peer:
            engine.add_request(
                "pen", prompt_token_ids=p1,
                sampling=SamplingParams(max_tokens=12, temperature=0.0,
                                        ignore_eos=True, **PENALTY_SP),
            )
        toks = {"plain": [], "pen": []}
        while engine.has_work():
            for out in engine.step():
                toks[out.request_id].extend(out.new_token_ids)
        return toks

    alone = run(_engine(num_decode_steps=4), with_peer=False)["plain"]
    both = run(_engine(num_decode_steps=4), with_peer=True)
    assert both["plain"] == alone
    # And the penalized row still matches its own single-step reference.
    ref = run(_engine(num_decode_steps=1), with_peer=True)["pen"]
    assert both["pen"] == ref


def test_guided_rows_still_force_single_step_and_stay_unpipelined():
    """Guided-choice masks are host-rebuilt per token: the scheduler must
    keep n=1 for them and the pipeline must not engage."""
    eng = _overlap_engine(num_decode_steps=4)
    choice = ((5, 9), (5, 12, 13))
    eng.add_request(
        "g", prompt_token_ids=[3, 4, 5],
        sampling=SamplingParams(max_tokens=8, temperature=0.0,
                                guided_choice=choice),
    )
    toks = []
    while eng.has_work():
        for out in eng.step():
            toks.extend(out.new_token_ids)
    assert eng.pipelined_bursts_total == 0
    assert tuple(toks) in choice


# ----------------------------------------------------------------------
# Host-gap metric
# ----------------------------------------------------------------------


def _host_gaps() -> dict:
    """pst_engine_host_gap_seconds as it stands (the registry is the
    process's, so tests read it before and after): {batch_bucket:
    {"count", "sum", "under": {le: cumulative count}}}."""
    out: dict = {}
    for metric in ENGINE_TELEMETRY_REGISTRY.collect():
        if metric.name != "pst_engine_host_gap_seconds":
            continue
        for smp in metric.samples:
            b = out.setdefault(smp.labels["batch_bucket"],
                               {"count": 0.0, "sum": 0.0, "under": {}})
            if smp.name.endswith("_bucket"):
                b["under"][float(smp.labels["le"])] = smp.value
            elif smp.name.endswith(("_count", "_sum")):
                b[smp.name.rsplit("_", 1)[1]] = smp.value
    return out


def _new_host_gaps(before: dict, le: float = float("inf")) -> dict:
    """{batch_bucket: observations at most ``le`` seconds} since ``before``."""
    out = {}
    for bucket, b in _host_gaps().items():
        was = before.get(bucket, {"under": {}})["under"].get(le, 0.0)
        if b["under"][le] > was:
            out[bucket] = b["under"][le] - was
    return out


def test_host_gap_recorded_per_bucket_and_declared():
    ENGINE_TELEMETRY.reset_for_tests()
    before = _host_gaps()
    eng = _engine(num_decode_steps=2)
    _run_stream(eng, _reqs((9, 9), (8, 8)))
    new = _new_host_gaps(before)
    assert new, "no host-gap samples recorded"
    # Synchronous loop: every decode→decode gap is real host bookkeeping.
    bucket, count = next(iter(new.items()))
    assert bucket.startswith("b")
    assert count >= 1
    after = _host_gaps()[bucket]
    assert after["sum"] >= before.get(bucket, {"sum": 0.0})["sum"]
    # Exposition: the histogram series exists per bucket.
    from prometheus_client import generate_latest

    text = generate_latest(ENGINE_TELEMETRY_REGISTRY).decode()
    assert "pst_engine_host_gap_seconds_bucket" in text
    assert f'batch_bucket="{bucket}"' in text
    # Registry + docs contract (the metric-registry pstlint triangle).
    from production_stack_tpu.obs.metric_registry import BY_NAME

    assert "pst_engine_host_gap_seconds" in BY_NAME
    docs = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "observability.md",
    )
    with open(docs, encoding="utf-8") as f:
        assert "pst_engine_host_gap_seconds" in f.read()


def test_host_gap_zero_under_pipeline():
    """Pipelined continuations record 0-valued gaps: the device ran the
    bursts back-to-back, so nothing host-side sat on the critical path."""
    ENGINE_TELEMETRY.reset_for_tests()
    before = _host_gaps()
    eng = _overlap_engine(num_decode_steps=2)
    _run_stream(eng, _reqs((9,), (24,)))
    assert eng.pipelined_bursts_total >= 2
    new = {b: n for b, n in _new_host_gaps(before).items()
           if "xn" in b and n >= 2}
    assert new, "no pipelined-bucket gaps recorded"
    # Continuations record exactly 0: in some pipelined bucket at least
    # half of the new observations sit in the histogram's lowest bucket.
    lowest = _new_host_gaps(before, le=0.0005)
    assert any(lowest.get(b, 0) * 2 >= n for b, n in new.items()), (new, lowest)


def test_host_gap_not_polluted_by_prefill():
    """A prefill between decode steps cancels the open gap: the wall a
    new arrival's prefill spends must never read as decode host gap."""
    ENGINE_TELEMETRY.reset_for_tests()
    before = _host_gaps()
    eng = _engine(num_decode_steps=2)
    eng.add_request(
        "a", prompt_token_ids=list(range(5, 14)),
        sampling=SamplingParams(max_tokens=30, temperature=0.0,
                                ignore_eos=True),
    )
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
        if steps == 5:
            import time as _t

            _t.sleep(0.05)  # a fat would-be gap...
            eng.add_request(  # ...interrupted by an arrival's prefill
                "b", prompt_token_ids=list(range(30, 45)),
                sampling=SamplingParams(max_tokens=6, temperature=0.0,
                                        ignore_eos=True),
            )
    new = _new_host_gaps(before)
    assert new
    # No gap reached the 50 ms the arrival interrupted.
    assert _new_host_gaps(before, le=0.05) == new
