"""Fleet SLO layer e2e (docs/observability.md "SLOs & alerting").

Real router + in-process fake engines: SLO counters against the TTFT
target, the canary prober's per-engine TTFT gauge with one engine
faulted slow, breaker feedback from probe outcomes, and the scraper's
parsing of the fake's pst_engine_* surface.
"""

import asyncio

import aiohttp
import pytest
from aiohttp import web

from production_stack_tpu.router.app import create_app
from production_stack_tpu.router.parser import parse_args
from production_stack_tpu.router.services.metrics_service import (
    configure_slo,
    observe_slo_failure,
    observe_slo_ttft,
    slo_requests_total,
    slo_ttft_within_target_total,
)
from production_stack_tpu.router.stats.engine_stats import EngineStats
from production_stack_tpu.testing.fake_engine import create_fake_engine_app

from .router_utils import reset_router_singletons


class Cluster:
    """Two fake engines + a router on ephemeral ports (slo/canary args)."""

    def __init__(self, extra_args=None, ttft=0.0):
        self.extra_args = extra_args or []
        self.ttft = ttft
        self.runners = []
        self.engine_urls = []
        self.router_url = None

    async def __aenter__(self):
        for _ in range(2):
            app = create_fake_engine_app(
                model="fake/model", speed=5000.0, ttft=self.ttft
            )
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = site._server.sockets[0].getsockname()[1]
            self.runners.append(runner)
            self.engine_urls.append(f"http://127.0.0.1:{port}")
        args = parse_args([
            "--service-discovery", "static",
            "--static-backends", ",".join(self.engine_urls),
            "--static-models", "fake/model,fake/model",
            "--routing-logic", "roundrobin",
            "--engine-stats-interval", "0.2",
            *self.extra_args,
        ])
        router_app = create_app(args)
        runner = web.AppRunner(router_app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        self.runners.append(runner)
        self.router_url = f"http://127.0.0.1:{port}"
        return self

    async def __aexit__(self, *exc):
        for runner in reversed(self.runners):
            await runner.cleanup()
        reset_router_singletons()


@pytest.fixture(autouse=True)
def _reset():
    reset_router_singletons()
    yield
    reset_router_singletons()


def _counter_value(counter, **labels) -> float:
    return counter.labels(**labels)._value.get()


# ---------------------------------------------------------------------------
# SLO counters (unit)
# ---------------------------------------------------------------------------


def test_slo_observation_against_target():
    configure_slo(200.0)
    base_req = _counter_value(slo_requests_total, model="m1")
    base_ok = _counter_value(slo_ttft_within_target_total, model="m1")
    observe_slo_ttft("m1", 0.05)   # within 200 ms
    observe_slo_ttft("m1", 0.95)   # miss
    observe_slo_failure("m1")      # no first byte: miss
    assert _counter_value(slo_requests_total, model="m1") == base_req + 3
    assert (
        _counter_value(slo_ttft_within_target_total, model="m1")
        == base_ok + 1
    )


def test_slo_disabled_counts_nothing():
    configure_slo(0.0)
    base = _counter_value(slo_requests_total, model="m2")
    observe_slo_ttft("m2", 0.01)
    observe_slo_failure("m2")
    assert _counter_value(slo_requests_total, model="m2") == base


# ---------------------------------------------------------------------------
# Router e2e: SLO counters + canary with one engine faulted slow
# ---------------------------------------------------------------------------


async def test_slo_counters_through_router():
    async with Cluster(extra_args=["--slo-ttft-ms", "5000"]) as c:
        async with aiohttp.ClientSession() as s:
            for _ in range(3):
                async with s.post(
                    f"{c.router_url}/v1/completions",
                    json={"model": "fake/model", "prompt": "hi",
                          "max_tokens": 2},
                ) as resp:
                    assert resp.status == 200
                    await resp.read()
            async with s.get(f"{c.router_url}/metrics") as resp:
                text = await resp.text()
        assert 'pst_slo_requests_total{model="fake/model"}' in text
        assert ('pst_slo_ttft_within_target_total{model="fake/model"}'
                in text)
        # All three fake-engine requests answer far inside 5 s.
        for line in text.splitlines():
            if line.startswith('pst_slo_requests_total{model="fake/model"}'):
                assert float(line.split()[-1]) >= 3.0


async def test_canary_exports_per_engine_ttft_with_one_slow_engine():
    async with Cluster(
        extra_args=["--canary-interval", "0.15", "--canary-timeout", "3"]
    ) as c:
        slow, fast = c.engine_urls
        async with aiohttp.ClientSession() as s:
            # Fault engine 0 slow: every generation (canary probes
            # included) takes >= 0.4 s.
            async with s.post(
                f"{slow}/admin/fail",
                json={"mode": "slow", "delay": 0.4, "count": -1},
            ) as resp:
                assert resp.status == 200
            # Let a few probe sweeps run.
            await asyncio.sleep(1.5)
            async with s.get(f"{c.router_url}/metrics") as resp:
                text = await resp.text()
        ttfts = {}
        for line in text.splitlines():
            if line.startswith("pst_canary_ttft_seconds{"):
                engine = line.split('engine="')[1].split('"')[0]
                ttfts[engine] = float(line.split()[-1])
        # Per-engine TTFT for BOTH engines, the slow one visibly slower.
        assert set(ttfts) == {slow, fast}, text
        assert ttfts[slow] >= 0.35
        assert ttfts[fast] < 0.35
        assert ttfts[slow] > ttfts[fast]


async def test_canary_failure_feeds_counter_and_breaker():
    async with Cluster(
        extra_args=["--canary-interval", "0.1", "--canary-timeout", "2"]
    ) as c:
        bad = c.engine_urls[0]
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"{bad}/admin/fail",
                json={"mode": "error", "status": 500, "count": -1},
            ) as resp:
                assert resp.status == 200
            await asyncio.sleep(1.0)
            async with s.get(f"{c.router_url}/metrics") as resp:
                text = await resp.text()
        failures = {
            line.split('engine="')[1].split('"')[0]: float(line.split()[-1])
            for line in text.splitlines()
            if line.startswith("pst_canary_failures_total{")
        }
        assert failures.get(bad, 0) >= 1
        # Repeated probe failures opened the engine's breaker
        # (pst_resilience_breaker_state 2 = open).
        breaker_lines = [
            line for line in text.splitlines()
            if line.startswith("pst_resilience_breaker_state{")
            and bad in line
        ]
        assert breaker_lines and float(breaker_lines[0].split()[-1]) == 2.0


async def test_canary_4xx_is_failure_but_never_feeds_breaker():
    """A misconfigured probe (bad key → 401, model mismatch → 404) is a
    failed probe, but must neither open a healthy engine's breaker nor
    close an open one via record_success."""
    async with Cluster(
        extra_args=["--canary-interval", "0.1", "--canary-timeout", "2"]
    ) as c:
        bad = c.engine_urls[0]
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"{bad}/admin/fail",
                json={"mode": "error", "status": 404, "count": -1},
            ) as resp:
                assert resp.status == 200
            await asyncio.sleep(0.8)
            async with s.get(f"{c.router_url}/metrics") as resp:
                text = await resp.text()
        failures = {
            line.split('engine="')[1].split('"')[0]: float(line.split()[-1])
            for line in text.splitlines()
            if line.startswith("pst_canary_failures_total{")
        }
        assert failures.get(bad, 0) >= 1
        # 404 < 500: the breaker stays closed (state 0).
        breaker_lines = [
            line for line in text.splitlines()
            if line.startswith("pst_resilience_breaker_state{")
            and bad in line
        ]
        assert breaker_lines and float(breaker_lines[0].split()[-1]) == 0.0
        # (The TTFT gauge may exist from a pre-fault sweep — the prober
        # starts with the router — but a 404 probe never updates it;
        # that's covered by the failure counter + closed breaker above.)


# ---------------------------------------------------------------------------
# Scraper ↔ fake-engine pst_engine_* contract
# ---------------------------------------------------------------------------


async def test_scraper_parses_fake_engine_telemetry():
    async with Cluster() as c:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{c.engine_urls[0]}/metrics") as resp:
                text = await resp.text()
    stats = EngineStats.from_scrape(text)
    # Deterministic fake values (testing/fake_engine.py): 3 prefill + 2
    # decode compiles, high watermark 0.55.
    assert stats.engine_compiles_total == 5
    assert stats.engine_kv_page_high_watermark == pytest.approx(0.55)


async def test_fake_engine_debug_profile_noop():
    async with Cluster() as c:
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"{c.engine_urls[0]}/debug/profile",
                json={"duration_ms": 123},
            ) as resp:
                assert resp.status == 200
                body = await resp.json()
    assert body["status"] == "skipped"
    assert body["duration_ms"] == 123
