"""Tail-latency request hedging tests (docs/resilience.md).

Ring 1: HedgePolicy units (delay derivation, outstanding-ratio cap,
eligibility).
Ring 2: real router app + in-process fake engines — a slow engine's
requests complete fast via the hedge path (hedge-won counter > 0), losers
are cancelled upstream, hedges never fire at open breakers, and streaming
requests are never hedged.
"""

import asyncio
import time

import aiohttp
import pytest

from production_stack_tpu.resilience.deadline import HedgePolicy
from production_stack_tpu.router.services.request_service import hedge_eligible

from .router_utils import reset_router_singletons
from .test_resilience_e2e import MODEL, Cluster, _completion, _router_metrics

HEDGE_ARGS = [
    "--proxy-retries", "2",
    "--retry-backoff", "0.01",
    "--breaker-failure-threshold", "2",
    "--breaker-recovery-time", "60",
    "--hedge-enabled",
    "--hedge-delay-ms", "80",
]


@pytest.fixture(autouse=True)
def _reset():
    reset_router_singletons()
    yield
    reset_router_singletons()


# ---------------------------------------------------------------------------
# Ring 1 — policy units
# ---------------------------------------------------------------------------


def test_hedge_eligibility_table():
    assert hedge_eligible("/v1/completions", {"stream": False})
    assert hedge_eligible("/v1/completions", {})
    assert hedge_eligible("/v1/chat/completions", {})
    assert hedge_eligible("/v1/embeddings", None)
    assert hedge_eligible("/v1/rerank", None)
    assert hedge_eligible("/v1/score", None)
    # Streams are committed to one upstream after the first byte.
    assert not hedge_eligible("/v1/completions", {"stream": True})
    assert not hedge_eligible("/v1/chat/completions", {"stream": True})
    # Non-generation endpoints are out of scope.
    assert not hedge_eligible("/tokenize", None)
    assert not hedge_eligible("/detokenize", None)


def test_hedge_delay_fixed_and_quantile():
    fixed = HedgePolicy(enabled=True, delay_ms=120.0)
    assert fixed.delay_s() == pytest.approx(0.12)
    adaptive = HedgePolicy(enabled=True, delay_ms=0.0, quantile=0.9,
                           min_samples=4, fallback_delay_ms=100.0)
    # Too few samples: fixed fallback.
    assert adaptive.delay_s() == pytest.approx(0.1)
    for v in (0.01, 0.02, 0.03, 0.04, 0.05):
        adaptive.observe_latency(v)
    # Tracks the p90 of observed latencies.
    assert adaptive.delay_s() == pytest.approx(0.05)
    # ... bounded below so it never hedges on noise.
    fast = HedgePolicy(enabled=True, delay_ms=0.0, min_samples=2,
                       min_delay_ms=10.0)
    fast.observe_latency(0.001)
    fast.observe_latency(0.001)
    assert fast.delay_s() == pytest.approx(0.01)


def test_hedge_outstanding_ratio_cap():
    p = HedgePolicy(enabled=True, max_outstanding_ratio=0.5)
    # Floor of 1: a lone slow request can always hedge.
    p.note_request_start()
    assert p.try_acquire_hedge()
    # cap = ceil(0.5 * 1) = 1: the second concurrent hedge is refused.
    assert not p.try_acquire_hedge()
    p.release_hedge()
    assert p.try_acquire_hedge()
    p.release_hedge()
    p.note_request_end()
    # 8 primaries at ratio 0.5 → up to 4 concurrent hedges.
    for _ in range(8):
        p.note_request_start()
    granted = sum(1 for _ in range(8) if p.try_acquire_hedge())
    assert granted == 4


# ---------------------------------------------------------------------------
# Ring 2 — router e2e
# ---------------------------------------------------------------------------


def _metric_value(text: str, name: str, label: str = "") -> float:
    for line in text.splitlines():
        if line.startswith(name) and (not label or label in line):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


async def test_hedge_rescues_request_from_slow_engine():
    """Acceptance: one engine in `slow` mode + hedging enabled →
    non-streaming requests complete within budget via the hedge path
    (hedge-won counter > 0) and the slow loser is cancelled upstream."""
    async with Cluster(extra_args=HEDGE_ARGS) as c:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"{c.engine_urls[0]}/admin/fail",
                json={"mode": "slow", "delay": 3.0},
            ) as resp:
                assert resp.status == 200
            t0 = asyncio.get_event_loop().time()
            results = []
            for i in range(6):  # round-robin lands on the slow engine twice
                status, by, _ = await _completion(
                    s, c.router_url, prompt=f"h{i}", max_tokens=2
                )
                results.append((status, by))
            elapsed = asyncio.get_event_loop().time() - t0
            assert [r[0] for r in results] == [200] * 6
            # Every response came from a healthy engine — the slow one
            # never won a race.
            assert all(by != "engine-0" for _, by in results)
            # ... and nothing waited out the 3s injected latency.
            assert elapsed < 2.5, elapsed
            text = await _router_metrics(s, c.router_url)
            assert _metric_value(text, "pst_hedge_fired_total") >= 2
            assert _metric_value(text, "pst_hedge_won_total") >= 2
            # The losing (slow) attempts were cancelled upstream: the slow
            # engine's in-flight count drains to zero.
            for _ in range(40):
                if c.engine_state(0).num_running == 0:
                    break
                await asyncio.sleep(0.1)
            assert c.engine_state(0).num_running == 0


async def test_hedge_cancelled_when_primary_wins():
    """A hedge fired against a healthy-but-briefly-busy primary loses the
    race and is cancelled (pst_hedge_cancelled_total)."""
    args = HEDGE_ARGS[:-1] + ["20"]  # hedge after 20ms
    async with Cluster(extra_args=args, speed=30.0) as c:
        # speed=30 tok/s → 2 tokens ≈ 66ms > 20ms hedge delay: every
        # request hedges, and with identical engines the primary usually
        # wins (it has a head start).
        async with aiohttp.ClientSession() as s:
            base = await _router_metrics(s, c.router_url)
            base_fired = _metric_value(base, "pst_hedge_fired_total")
            base_cancelled = _metric_value(base, "pst_hedge_cancelled_total")
            base_won = _metric_value(base, "pst_hedge_won_total")
            for i in range(8):
                status, _, _ = await _completion(
                    s, c.router_url, prompt=f"c{i}", max_tokens=2
                )
                assert status == 200
            # The losing leg's cancellation is counted a moment after the
            # last response returns: poll (bounded) until the books close.
            deadline = time.monotonic() + 2.0
            while True:
                text = await _router_metrics(s, c.router_url)
                fired = _metric_value(text, "pst_hedge_fired_total") - base_fired
                cancelled = (
                    _metric_value(text, "pst_hedge_cancelled_total")
                    - base_cancelled
                )
                won = _metric_value(text, "pst_hedge_won_total") - base_won
                if cancelled + won == fired or time.monotonic() >= deadline:
                    break
                await asyncio.sleep(0.05)
            assert fired >= 1
            # Every fired hedge either won or was cancelled — none leaked.
            assert cancelled + won == fired


async def test_hedge_never_fires_at_open_breaker():
    """With both alternates' breakers OPEN, the hedge is suppressed
    (reason="breaker") instead of burning load on known-bad engines."""
    async with Cluster(extra_args=HEDGE_ARGS, speed=30.0) as c:
        async with aiohttp.ClientSession() as s:
            # Trip breakers on engines 1 and 2 (threshold 2, recovery 60s).
            for url in (c.engine_urls[1], c.engine_urls[2]):
                async with s.post(
                    f"{url}/admin/fail", json={"mode": "error"}
                ) as resp:
                    assert resp.status == 200
            for i in range(8):
                await _completion(s, c.router_url, prompt=f"t{i}", max_tokens=1)
            states = await s.get(f"{c.router_url}/engines")
            info = {e["url"]: e["breaker"] for e in await states.json()}
            assert info[c.engine_urls[1]] == "open"
            assert info[c.engine_urls[2]] == "open"
            before = _metric_value(
                await _router_metrics(s, c.router_url), "pst_hedge_fired_total"
            )
            # Slow enough to trigger the hedge delay (speed=30 → ~66ms for
            # 2 tokens; hedge delay 80ms... use 4 tokens ≈ 133ms).
            status, by, _ = await _completion(
                s, c.router_url, prompt="x", max_tokens=4
            )
            assert status == 200 and by == "engine-0"
            text = await _router_metrics(s, c.router_url)
            assert _metric_value(text, "pst_hedge_fired_total") == before
            assert _metric_value(
                text, "pst_hedge_suppressed_total", 'reason="breaker"'
            ) >= 1
            # The open-breaker engines saw no hedge traffic.
            assert all(
                not c.engine_state(i).requests_seen
                or all(
                    r.get("prompt", "").startswith("t")
                    for r in c.engine_state(i).requests_seen
                )
                for i in (1, 2)
            )


async def test_streaming_requests_never_hedge():
    async with Cluster(extra_args=HEDGE_ARGS, speed=30.0) as c:
        async with aiohttp.ClientSession() as s:
            before = _metric_value(
                await _router_metrics(s, c.router_url), "pst_hedge_fired_total"
            )
            async with s.post(
                f"{c.router_url}/v1/completions",
                json={"model": MODEL, "prompt": "x", "max_tokens": 8,
                      "stream": True},
            ) as resp:
                assert resp.status == 200
                payload = await resp.content.read()
            assert b"data: [DONE]" in payload
            text = await _router_metrics(s, c.router_url)
            assert _metric_value(text, "pst_hedge_fired_total") == before
            # Exactly one engine served it — no duplicate generation.
            served = sum(
                1 for i in range(3) if c.engine_state(i).requests_seen
            )
            assert served == 1


async def test_hedge_acts_as_failover_when_primary_fails_fast():
    """A primary that 500s before the hedge delay elapses is failed over
    immediately (plain retry semantics, not a hedge) — no client-visible
    error, no hedge counters."""
    async with Cluster(extra_args=HEDGE_ARGS) as c:
        async with aiohttp.ClientSession() as s:
            before_fired = _metric_value(
                await _router_metrics(s, c.router_url), "pst_hedge_fired_total"
            )
            before_failover = _metric_value(
                await _router_metrics(s, c.router_url),
                "pst_resilience_failovers_total",
            )
            async with s.post(
                f"{c.engine_urls[0]}/admin/fail",
                json={"mode": "error", "count": 1},
            ) as resp:
                assert resp.status == 200
            statuses = []
            for i in range(3):
                status, by, _ = await _completion(
                    s, c.router_url, prompt=f"f{i}", max_tokens=1
                )
                statuses.append(status)
            assert statuses == [200] * 3
            text = await _router_metrics(s, c.router_url)
            assert (
                _metric_value(text, "pst_resilience_failovers_total")
                >= before_failover + 1
            )
            assert _metric_value(text, "pst_hedge_fired_total") == before_fired
