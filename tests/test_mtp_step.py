"""The verify-and-draft step (``--speculative-mtp``; docs/engine.md "Verify
and draft") on the CPU at a tiny size: token for token what the draft-off
engine emits, through both branches of the accept test, the prefix cache of
both page groups and the draft layer's pages, the window group's page
boundaries, rows with log-probabilities, sampled rows; and what it is
refused with."""

import dataclasses
import functools

import numpy as np
import pytest

from production_stack_tpu.engine import config as engine_config
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.models import registry
from production_stack_tpu.models.registry import PRESETS

from . import model_contract as contract
from .model_contract import assert_same, run

BASE = PRESETS["tiny-exaone-moe-debug"]
# A vocabulary small enough that a random draft layer agrees with a random
# model often: both branches of the accept test are taken.
SMALL = "tiny-exaone-moe-v12"
registry.PRESETS[SMALL] = dataclasses.replace(BASE, vocab_size=12, name=SMALL)
# (log-probabilities report the 20 likeliest ids: a vocabulary that has them)
WIDER = "tiny-exaone-moe-v24"
registry.PRESETS[WIDER] = dataclasses.replace(BASE, vocab_size=24, name=WIDER)
KW = dict(max_model_len=512, num_kv_blocks=160, max_prefill_tokens=32, seed=3)

make = functools.partial(contract.make_engine, SMALL, **KW)
run = functools.partial(run, logprobs=None)


def prompts(n, lo=9, step=7, seed=0, vocab=12):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, vocab, lo + step * i)]
            for i in range(n)]


@pytest.fixture(scope="module")
def plain():
    """The draft off, the chained pipeline on: what every run must equal."""
    return make()


@pytest.fixture(scope="module", params=["chained", "synchronous"])
def drafted(request):
    """The draft on, its step chained (the default: the next step launched
    from the device's accept counts before anything is fetched) and with
    ``--no-overlap-decode`` (one launch and one fetch a step)."""
    return make(speculative_mtp=1,
                overlap_decode=request.param == "chained")


def test_both_branches_emit_what_the_draft_off_engine_emits(plain, drafted):
    """Four rows, 100 tokens each (a few hundred steps): drafts are accepted
    and rejected, rows finish at different steps, and every token and
    log-probability is the draft-off engine's, the window sliding past six
    pages meanwhile."""
    ps = prompts(4)
    want = run(plain, ps, 100)
    before = drafted.stats()
    got = run(drafted, ps, 100, stagger=2)
    for a, b in zip(got, want):
        assert_same(a, b, tol=2e-4)
    stats = {k: v - before.get(k, 0) for k, v in drafted.stats().items()
             if isinstance(v, float)}
    drafts = stats["spec_decode_num_draft_tokens_total"]
    accepted = stats["spec_decode_num_accepted_tokens_total"]
    assert 0 < accepted < drafts  # both branches
    assert stats["mtp_tokens_emitted_total"] == (
        stats["mtp_row_steps_total"] + accepted)
    # every decode dispatch was a verify-and-draft step; chained, all but
    # what an arrival's prefill or a finished row's last fetch breaks
    assert stats["mtp_steps_total"] == stats["decode_dispatches_total"] > 50
    chained = stats["pipelined_bursts_total"] if drafted.cfg.overlap_decode else 0
    assert chained == (
        stats["decode_dispatches_total"] if drafted.cfg.overlap_decode else 0)
    # a step carried two tokens a row where it accepted: fewer steps than
    # tokens (a chain launches one step past every row's end)
    assert stats["mtp_steps_total"] < 99 + 3 * 2 + 4
    # five expert layers a step: the draft module's among those counted
    assert stats["moe_layer_steps_total"] % 5 == 0
    if drafted.cfg.overlap_decode:
        # the staggered arrivals joined the chain behind their prefills,
        # token and first draft spliced into the carry on the device
        assert stats["chain_kept_prefills_total"] > 0
    assert stats["window_pages_released_total"] > 0
    assert drafted.stats()["kv_pages_in_use"] == 0


def test_a_prefix_hit_whose_next_token_differs_drafts_as_a_cold_prefill(drafted):
    """The second request shares three pages with the first and parts at the
    token after them. Its drafts come from the draft layer's pages of the
    first request and its own beyond: under the slot rule they are a cold
    engine's, so the same steps accept the same drafts."""
    shared = prompts(1, lo=24)[0]
    first, second = shared + [1, 2, 3, 4, 5], shared + [7, 8, 9, 10, 11]
    cold = make(speculative_mtp=1, enable_prefix_caching=False,
                overlap_decode=drafted.cfg.overlap_decode)
    want = run(cold, [second], 40)[0]
    steps_cold = cold.stats()["mtp_steps_total"]
    accepted_cold = cold.stats()["spec_decode_num_accepted_tokens_total"]
    run(drafted, [first], 8)
    before = drafted.stats()
    got = run(drafted, [second], 40)[0]
    assert got["seq"].num_cached_prompt_tokens == 23  # three pages less one
    assert_same(got, want, tol=2e-4)
    after = drafted.stats()
    assert after["mtp_steps_total"] - before["mtp_steps_total"] == steps_cold
    assert (after["spec_decode_num_accepted_tokens_total"]
            - before["spec_decode_num_accepted_tokens_total"]) == accepted_cold


def test_a_rejected_draft_at_a_window_pages_boundary_keeps_every_page(plain):
    """Pages of 8 tokens under a 16-token window: every eighth step's draft
    position opens a page of both groups. The window group gives pages back
    by the committed length alone, so whatever the accept test says no page
    is freed early or written after it went: the output is the draft-off
    engine's over 20 page boundaries, and the pool is whole afterwards."""
    ps = prompts(2, lo=15, step=1, seed=5)
    eng = make(speculative_mtp=1, enable_prefix_caching=False)
    seen = {"held": 0, "early": 0}

    def watch(seq):
        live = len(seq.window_block_ids) - seq.window_released
        seen["held"] = max(seen["held"], live)
        # pages wholly below the window of the committed length, no further
        first = max(seq.num_computed_tokens - 16, 0) // 8
        seen["early"] += seq.window_released > first

    got = run(eng, ps, 160, watch=watch)
    for a, b in zip(got, run(plain, ps, 160)):
        assert_same(a, b, tol=2e-4)
    stats = eng.stats()
    assert 0 < stats["spec_decode_num_accepted_tokens_total"] < stats[
        "spec_decode_num_draft_tokens_total"]
    assert seen["early"] == 0
    assert 0 < seen["held"] <= eng.allocator.window_bound(2)
    assert stats["window_pages_in_use"] == 0 and stats["kv_pages_in_use"] == 0
    assert eng.allocator.window.num_free == eng.allocator.window.num_blocks


def test_a_row_with_logprobs_rides_the_step_and_reports_the_verifys_rows():
    """One row asks for log-probabilities, one does not: both ride the same
    verify-and-draft steps; the first reports, for one token or two a step,
    the log-probabilities of the positions emitted."""
    ps = prompts(2, seed=9, vocab=24)
    plain = contract.make_engine(WIDER, **KW)
    drafted = contract.make_engine(WIDER, **KW, speculative_mtp=1)
    sp = lambda lp: SamplingParams(  # noqa: E731
        max_tokens=60, temperature=0.0, ignore_eos=True, logprobs=lp)

    def go(eng):
        eng.add_request("a", prompt_token_ids=ps[0], sampling=sp(3))
        eng.add_request("b", prompt_token_ids=ps[1], sampling=sp(None))
        out = {"a": [], "b": []}
        while eng.has_work():
            for o in eng.step():
                for j, t in enumerate(o.new_token_ids):
                    lp = o.logprobs[j] if o.logprobs else None
                    out[o.request_id].append((t, lp and lp["logprob"], lp and tuple(
                        i for i, _ in lp["top"])))
        return out

    before = drafted.stats()["spec_decode_num_accepted_tokens_total"]
    got, want = go(drafted), go(plain)
    assert drafted.stats()["spec_decode_num_accepted_tokens_total"] > before
    assert [t for t, _, _ in got["b"]] == [t for t, _, _ in want["b"]]
    assert all(lp is None for _, lp, _ in got["b"])
    assert len(got["a"]) == 60
    for (t, lp, top), (t2, lp2, top2) in zip(got["a"], want["a"]):
        assert t == t2 and top == top2 and abs(lp - lp2) < 2e-4


def test_a_sampled_row_rides_undrafted_and_a_greedy_row_beside_it_is_drafted(
        plain, drafted):
    """A row at temperature 1 is sampled at position 0 as a decode step
    samples it (the same seed history) and never drafted for; the greedy row
    of the same steps is."""
    ps = prompts(2, seed=11)

    def go(eng):
        eng.add_request("s", prompt_token_ids=ps[0], sampling=SamplingParams(
            max_tokens=30, temperature=1.0, seed=7, ignore_eos=True))
        eng.add_request("g", prompt_token_ids=ps[1], sampling=SamplingParams(
            max_tokens=30, temperature=0.0, ignore_eos=True))
        out = {"s": [], "g": []}
        while eng.has_work():
            for o in eng.step():
                out[o.request_id].extend(o.new_token_ids)
        return out

    before = drafted.stats()
    got = go(drafted)
    assert got == go(make(overlap_decode=False))
    after = drafted.stats()
    drafts = (after["spec_decode_num_draft_tokens_total"]
              - before["spec_decode_num_draft_tokens_total"])
    rows = after["mtp_row_steps_total"] - before["mtp_row_steps_total"]
    assert 0 < drafts < rows  # the sampled row's steps carried no draft


def test_a_row_near_max_model_len_ends_at_the_limit():
    """Rows that run into ``max_model_len``: the draft is not verified
    where two tokens would pass the limit, nothing is written past the last
    page, and the tokens are the draft-off engine's."""
    ps = prompts(2, lo=20, step=3, seed=13)
    kw = dict(KW, max_model_len=64, num_kv_blocks=24)
    want = run(contract.make_engine(SMALL, **kw), ps, 80)
    got = run(contract.make_engine(SMALL, **kw, speculative_mtp=1), ps, 80)
    for a, b in zip(got, want):
        assert a["tokens"] == b["tokens"]
        assert len(a["seq"].prompt_token_ids) + len(a["tokens"]) == 64


def test_preemption_by_recompute_returns_the_same_tokens(plain):
    """Eight global pages under a 40- and a 24-token prompt: one must lose
    its pages of both groups (its draft layer's with them) while decoding;
    it starts again, its first step back verifies no draft, and the tokens
    are the draft-off engine's."""
    ps = [prompts(1, lo=40, seed=17)[0], prompts(1, lo=24, seed=18)[0]]
    eng = contract.make_engine(
        SMALL, speculative_mtp=1, num_kv_blocks=8, max_model_len=128, seed=3,
        enable_prefix_caching=False)
    got = run(eng, ps, 10)
    assert eng.num_preempted_total > 0, "the test must exercise preemption"
    for p, a in zip(ps, got):
        assert a["tokens"] == run(plain, [p], 10)[0]["tokens"]
    assert eng.allocator.window_pages_in_use == 0


@pytest.mark.parametrize("model,over,match", [
    ("tiny-mellum-debug", {}, "has no multi-token-prediction module"),
    ("tiny-llama-debug", {}, "has no multi-token-prediction module"),
    (SMALL, dict(speculative_mtp=2), "the module's depth is 1"),
    (SMALL, dict(speculative_ngram=2), "--speculative-ngram"),
    (SMALL, dict(num_decode_steps=4), "--num-decode-steps"),
])
def test_refused_at_start_up(model, over, match):
    kw = dict(model=model, kv_swap=False, speculative_mtp=1)
    kw.update(over)
    with pytest.raises(ValueError, match=match):
        engine_config.refuse_unserved(EngineConfig(**kw), PRESETS[model])


def test_the_server_takes_the_flag_and_exports_the_counters(drafted):
    from prometheus_client import generate_latest

    from production_stack_tpu.engine.server import EngineMetrics, parse_engine_args

    args = parse_engine_args(["--model", SMALL, "--speculative-mtp", "1"])
    assert args.speculative_mtp == 1
    assert parse_engine_args(["--model", SMALL]).speculative_mtp == 0
    run(drafted, prompts(1), 12)
    metrics, stats = EngineMetrics("m"), drafted.stats()
    metrics.refresh(stats)
    text = generate_latest(metrics.registry).decode()
    for name, key in (
            ("vllm:spec_decode_num_draft_tokens_total",
             "spec_decode_num_draft_tokens_total"),
            ("vllm:spec_decode_num_accepted_tokens_total",
             "spec_decode_num_accepted_tokens_total"),
            ("pst:mtp_steps_total", "mtp_steps_total"),
            ("pst:mtp_row_steps_total", "mtp_row_steps_total"),
            ("pst:mtp_tokens_emitted_total", "mtp_tokens_emitted_total")):
        line = next(ln for ln in text.splitlines()
                    if ln.startswith(f'{name}{{model_name="m"}}'))
        assert float(line.split()[-1]) == stats[key] > 0


@pytest.mark.parametrize("overlap", [False, True],
                         ids=["synchronous", "chained"])
def test_a_verify_step_reads_the_pages_its_rows_share_once(overlap, monkeypatch):
    """(PR 54) A verify step's two positions a row ride the decode stream
    (``paged_attn_short``), whose shared phase reads the rows' common
    leading pages once a call: the step's ``pst.step_info`` carries
    ``shared_kv_tokens`` / ``shared_rows`` and the spared-reads counter
    moves by what `shared_prefix_run` counts below each row's *first* query
    position, which is the run the kernel's first cell finds; and the
    tokens are the gather reference's."""
    from production_stack_tpu.engine.runner import shared_prefix_run
    from production_stack_tpu.obs.engine_telemetry import ENGINE_TELEMETRY

    from .test_paged_attention import program_run

    eng = make(speculative_mtp=1, attn_impl="pallas", overlap_decode=overlap)
    bs = eng.cfg.block_size
    prefix = prompts(1, lo=3 * bs, seed=21)[0]  # three whole pages
    tails = [[1, 2, 3], [4, 5, 6, 7, 8], [9], [10, 11, 3, 4, 5, 6, 7]]
    ps = [prefix + t for t in tails]
    run(eng, [prefix + [11]], 1)  # leaves the prefix's pages in the cache
    seen, batches = [], []
    monkeypatch.setattr(
        ENGINE_TELEMETRY, "step_info",
        lambda kind, **meta: seen.append((kind, meta)))
    build = eng.runner._mtp_batch
    monkeypatch.setattr(
        eng.runner, "_mtp_batch",
        lambda seqs: batches.append(build(seqs)) or batches[-1])
    before = eng.stats()
    got = run(eng, ps, 6)
    after = eng.stats()
    steps = [m for k, m in seen if k == "decode"]
    assert steps and all(m["step"] == "mtp_verify" for m in steps)
    full = [m for m in steps if m["rows"] == 4 and m["shared_rows"] == 4]
    assert full, "four rows behind the prefix were verified together"
    assert all(m["shared_kv_tokens"] >= 2 * bs for m in full)
    spared = (after["decode_shared_tokens_spared_total"]
              - before["decode_shared_tokens_spared_total"])
    assert spared == sum(
        max(m["shared_rows"] - 1, 0) * m["shared_kv_tokens"] for m in steps)
    context = (after["decode_context_tokens_total"]
               - before["decode_context_tokens_total"])
    assert 0.2 < spared / context < 0.75
    if not overlap:
        # each synchronous step's record is its batch's run, and the host's
        # twin agrees with the kernel's first cell on where the run ends
        assert len(batches) == len(steps)
        for b, m in zip(batches, steps):
            n = m["rows"]
            tables, lens = b["block_tables"][:n], b["kv_lens"][:n]
            pages, rows = shared_prefix_run(tables, lens, bs, 2)
            assert (pages * bs, rows) == (
                m["shared_kv_tokens"], m["shared_rows"])
            found = program_run(tables, lens, bs, b["positions"][:n, 0])
            assert found[0] == pages
            assert pages <= int(b["positions"][:n, 0].min()) // bs
    want = run(make(speculative_mtp=1, overlap_decode=overlap), ps, 6)
    for a, b in zip(got, want):
        assert_same(a, b, tol=2e-4)
