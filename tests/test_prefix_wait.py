"""Arrivals behind one uncached prefix compute it once
(``engine/scheduler.py::_running_prefill_computes_next_page``): a sequence
whose next page a running row is about to compute waits for that row's
commit and takes the page from the cache, instead of computing the same
tokens again beside it and holding a second copy of their pages."""

import functools

import pytest

from . import model_contract as contract
from .model_contract import assert_same, run

make_engine = functools.partial(contract.make_engine, "tiny-llama-debug")
SHARED = [(5 * i + 2) % 500 + 1 for i in range(40)]  # five pages of 8


def prompts(n):
    return [SHARED + [(11 * i + 7 * j) % 500 + 1 for j in range(5 + 3 * i)]
            for i in range(n)]


def test_rows_behind_one_uncached_prefix_wait_for_its_first_owner():
    """Four prompts share 40 uncached tokens and arrive together under a
    prefill budget of 16: the first computes the prefix in three steps while
    the others wait, then each takes its five pages from the cache. Tokens
    and log-probabilities are those of the same prompts sent one at a time,
    and the counts of the cache say what happened: no attempt that was
    taken back is counted."""
    eng = make_engine()
    got = run(eng, prompts(4), 6)
    assert [g["seq"].num_cached_prompt_tokens for g in got] == [0, 40, 40, 40]
    assert eng.scheduler.prefix_waits >= 2  # a step for each chunk it waited
    lone = make_engine(enable_prefix_caching=False)
    for p, g in zip(prompts(4), got):
        assert_same(g, run(lone, [p], 6)[0])
    stats = eng.stats()
    assert stats["prefix_cache_hits_total"] == 3 * 40
    assert stats["prefix_cache_queries_total"] == sum(
        len(p) - 1 for p in prompts(4))
    assert stats["kv_pages_in_use"] == 0
    assert stats["prefix_waits_total"] == eng.scheduler.prefix_waits


def test_an_identical_prompt_waits_and_then_hits_all_but_its_last_page():
    eng = make_engine()
    a, b = run(eng, [prompts(1)[0]] * 2, 4)
    assert a["tokens"] == b["tokens"]
    assert b["seq"].num_cached_prompt_tokens == 40  # 45 tokens: five pages
    assert eng.scheduler.prefix_waits > 0


@pytest.mark.parametrize("why,over", [
    ("no prefix cache", {"enable_prefix_caching": False}),
    ("nothing shared", {}),
])
def test_nobody_waits_without_a_shared_uncached_page(why, over):
    eng = make_engine(**over)
    ps = prompts(3) if over else [[(13 * i + j) % 500 + 1 for j in range(30)]
                                  for i in range(3)]
    run(eng, ps, 4)
    assert eng.scheduler.prefix_waits == 0, why


def test_a_cached_prefix_is_taken_at_once():
    """Once the prefix is committed, later arrivals behind it admit
    together: the wait is for a page in the making, not for a page held."""
    eng = make_engine()
    run(eng, prompts(1), 2)
    waits = eng.scheduler.prefix_waits
    got = run(eng, prompts(4)[1:], 4)
    assert [g["seq"].num_cached_prompt_tokens for g in got] == [40, 40, 40]
    assert eng.scheduler.prefix_waits == waits
