"""The traffic generators: deterministic in the seed, the same multiset of
sizes and gaps for every seed, lengths and sharing as the mix file says,
and open-loop timing from the due time."""

import json
import math
import os

import pytest

from perf import client
from perf.generators import closed_loop, common, sessions

DATA = os.path.join(os.path.dirname(__file__), "data")
VOCAB = 512
BIG_SEED = 2**31 + 12345  # the driver's seeds do not fit 32 signed bits


def _mix(name):
    with open(os.path.join(DATA, "traffic", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("gen,mix", [
    (sessions, "tiny-sessions"), (sessions, "tiny-sessions-closed"),
    (closed_loop, "tiny-closed")])
def test_plan_is_deterministic_in_seed(gen, mix):
    a = gen.plan(_mix(mix), BIG_SEED, 10.0, VOCAB)
    b = gen.plan(_mix(mix), BIG_SEED, 10.0, VOCAB)
    c = gen.plan(_mix(mix), BIG_SEED + 1, 10.0, VOCAB)
    assert a == b
    assert a != c


@pytest.mark.parametrize("gen,mix", [
    (sessions, "tiny-sessions"), (sessions, "tiny-sessions-closed"),
    (closed_loop, "tiny-closed")])
def test_every_seed_offers_the_same_set_of_sizes(gen, mix):
    def sizes(seed):
        p = gen.plan(_mix(mix), seed, 10.0, VOCAB)
        body = sorted(len(r.get("prompt", r.get("append"))) for r in p["requests"])
        out = sorted(r["max_tokens"] for r in p["requests"])
        hist = sorted(len(s) for s in p["sessions"])
        return body, out, hist

    assert sizes(1) == sizes(BIG_SEED)


def test_poisson_schedule_fills_the_window_with_the_same_gaps():
    a = common.poisson_due_times(200, 40.0, common.rng_for(1, "due"))
    b = common.poisson_due_times(200, 40.0, common.rng_for(2, "due"))
    assert a[0] == 0.0 and a == sorted(a) and a[-1] < 40.0
    gaps = lambda d: sorted(round(y - x, 9) for x, y in zip(d, d[1:] + [40.0]))
    assert gaps(a) == gaps(b) and a != b
    # exponential quantiles: the mean gap is the window over the count
    assert math.isclose(sum(gaps(a)) / 200, 40.0 / 200, rel_tol=1e-9)


def test_lengths_follow_the_mix_file():
    mix = _mix("tiny-closed")
    p = closed_loop.plan(mix, 3, 10.0, VOCAB)
    lo, hi = mix["prompt_tokens"]["lo"], mix["prompt_tokens"]["hi"]
    assert len(p["requests"]) == mix["pool"] and p["clients"] == mix["clients"]
    for r in p["requests"]:
        assert lo <= len(r["prompt"]) <= hi and r["due"] == 0.0
        assert mix["output_tokens"]["lo"] <= r["max_tokens"] <= mix["output_tokens"]["hi"]
        assert all(3 <= t < VOCAB for t in r["prompt"])


def test_session_turns_are_as_long_as_the_mix_file_says():
    """No length is bent to the engine's pages: questions and answers span
    the whole of their ranges, and contexts start anywhere in a page."""
    mix = _mix("tiny-sessions-closed")
    p = sessions.plan(mix, BIG_SEED, 10.0, VOCAB)
    assert p["mode"] == "closed" and p["clients"] == mix["sessions"]
    q = [len(r["append"]) for r in p["requests"]]
    o = [r["max_tokens"] for r in p["requests"]]
    assert (min(q), max(q)) == (mix["question_tokens"]["lo"], mix["question_tokens"]["hi"])
    assert (min(o), max(o)) == (mix["output_tokens"]["lo"], mix["output_tokens"]["hi"])
    assert len({(a + b) % 16 for a, b in zip(q, o)}) > 4


def test_sessions_share_the_system_prompt_and_have_their_own_history():
    mix = _mix("tiny-sessions")
    p = sessions.plan(mix, 7, 10.0, VOCAB)
    n = mix["shared_prefix_tokens"]
    assert len(p["sessions"]) == mix["sessions"]
    assert all(s[:n] == p["shared_prefix"] for s in p["sessions"])
    assert len({tuple(s[n:]) for s in p["sessions"]}) == mix["sessions"]
    assert all("append" in r for r in p["requests"])


def test_ttft_is_timed_from_the_due_time_not_from_sending():
    rec = client.Record(due=1.0, sent=1.4, events=[1.5, 1.6, 1.9],
                        event_tokens=[1, 1, 1], want_tokens=3, done=1.9)
    late = client.Record(due=9.0, sent=9.0, events=[], want_tokens=3)
    failed = client.Record(due=2.0, sent=2.0, want_tokens=3, error="HTTP 503")
    s = client.summarize([rec, late, failed], 10.0)
    assert s["ttft_ms"] == [pytest.approx(500.0)]  # 1.5 - due, not 1.5 - sent
    assert s["gap_ms"] == [pytest.approx(100.0), pytest.approx(300.0)]
    assert s["generator_late_ms"][0] == pytest.approx(400.0)
    assert (s["attempted"], s["failed"], s["in_flight_at_close"]) == (2, 1, 1)
    assert s["output_tokens_completed"] == 3


def test_percentile_interpolates():
    assert client.percentile([1, 2, 3, 4, 5], 50) == 3
    assert client.percentile([0, 10], 95) == pytest.approx(9.5)


def test_warm_up_probes_are_fixed_work_from_the_mix_file():
    """The probes do not follow ``--seed``'s order of turns: each is a
    session's context cut at a page boundary plus the listed number of fresh
    tokens, a group of them sent together behind a fresh blocker prompt, at
    the shortest and the longest session."""
    from perf import warmup

    mix = _mix("tiny-sessions-closed")
    spec = dict(mix["warmup"]["probes"], groups=[[6], [20, 20, 5]])
    p = sessions.plan(mix, BIG_SEED, 10.0, VOCAB)
    groups = warmup.probe_groups(spec, p, VOCAB)
    assert [len(g) for _, g in groups] == [1, 3, 1, 3]
    assert all(len(b) == spec["blocker_tokens"] for b, _ in groups)
    by_len = sorted(p["sessions"], key=len)
    page = spec["page_tokens"]
    for base, half in ((by_len[0], groups[:2]), (by_len[-1], groups[2:])):
        cut = len(base) // page * page
        assert [len(q) - cut for _, g in half for q in g] == [6, 20, 20, 5]
        assert all(q[:cut] == base[:cut] for _, g in half for q in g)
    fresh = [tuple(q[-5:]) for _, g in groups for q in g] + [
        tuple(b[:5]) for b, _ in groups]
    assert len(set(fresh)) == len(fresh)  # no probe repeats another's tokens
    again = warmup.probe_groups(spec, sessions.plan(mix, BIG_SEED, 10.0, VOCAB), VOCAB)
    assert again == groups
    # without sessions the shared prefix is the cached context
    flat = warmup.probe_groups(
        dict(spec, contexts=[0.0]),
        {"sessions": [], "shared_prefix": list(range(3, 40))}, VOCAB)
    assert all(q[:32] == list(range(3, 35)) for _, g in flat for q in g)


def _pow2(n):
    return 1 << max(n - 1, 0).bit_length()


def test_an_unshared_closed_loop_mix_and_its_probes_behind_the_empty_prefix():
    """A saturated mix of unrelated single prompts as the generator plans it
    (``data/traffic/tiny-chat-saturated.json``: a client for every row, no
    shared prefix): prompts over the whole of the range and outputs too, the
    same multiset for every seed in an order and with contents drawn from
    the seed. Its probes stand behind the empty prefix and need no
    ``contexts``: each group is one packed prefill step inside the budget,
    as long as the file says, and between them they touch every program a
    packed step of this traffic can be padded to (the program pads to a
    power of two of rows and of the longest chunk)."""
    from perf import warmup

    mix = _mix("tiny-chat-saturated")
    p = closed_loop.plan(mix, BIG_SEED, 50.0, VOCAB)
    q = closed_loop.plan(mix, 7, 50.0, VOCAB)
    assert (p["mode"], p["clients"], len(p["requests"])) == ("closed", 8, 96)
    assert p["shared_prefix"] == [] and p["setup_prompts"] == [] == p["sessions"]
    lens = [len(r["prompt"]) for r in p["requests"]]
    outs = [r["max_tokens"] for r in p["requests"]]
    # the (i + 0.5)/n quantiles: the ends of the ranges to within a token
    assert (min(lens), max(lens), min(outs), max(outs)) == (8, 63, 4, 12)
    assert sorted(lens) == sorted(len(r["prompt"]) for r in q["requests"])
    assert sorted(outs) == sorted(r["max_tokens"] for r in q["requests"])
    # the seed draws the order as well as the contents: a window consumes
    # only a prefix of the pool, so a cell on such a mix samples the orders
    assert lens != [len(r["prompt"]) for r in q["requests"]]
    assert outs != [r["max_tokens"] for r in q["requests"]]
    assert len({tuple(r["prompt"][:8]) for r in p["requests"]}) == 96  # unshared

    spec = mix["warmup"]["probes"]
    assert "contexts" not in spec
    groups = warmup.probe_groups(spec, p, VOCAB)
    assert all(len(b) == spec["blocker_tokens"] for b, _ in groups)
    assert [[len(x) for x in g] for _, g in groups] == spec["groups"]  # no prefix
    assert all(sum(g) <= spec["blocker_tokens"] and len(g) <= mix["clients"]
               for g in spec["groups"])
    touched = {(_pow2(len(g)), _pow2(max(g))) for g in spec["groups"]}
    assert touched == {(1, 64), (1, 8), (1, 1), (2, 32), (4, 32), (4, 16),
                       (8, 32), (8, 8)}
