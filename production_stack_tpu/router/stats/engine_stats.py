"""Scrape engine /metrics endpoints and keep a live per-engine snapshot.

Capability parity with the reference's ``src/vllm_router/stats/engine_stats.py``
(EngineStats.from_vllm_scrape :42-85, EngineStatsScraper :88-209). The
scraper is an asyncio task (not a daemon thread) and parses the same
``vllm:``-prefixed gauge names our TPU engine exports, so reference
dashboards keep working unchanged.

Ownership (router HA): the scraper is a plain class — no ``SingletonMeta``
— created by the app factory and *injected* per app (``create_app`` binds
it into request context via middleware), the same de-singletonization
``RequestStatsMonitor`` got in the HA PR. Two router apps in one process
(the multi-replica tests) each scrape into their OWN snapshot — zero
engine-stats bleed — while every existing ``get_engine_stats_scraper()``
call site keeps working via the per-request context binding with an
app-scope fallback (``router.appscope``; the module-default global died
with the app-scope pstlint check).
"""

# pstlint: disable-file=hop-contract(metrics scrapes are control-plane pulls on their own timer; no originating client request exists to propagate headers from)
from __future__ import annotations

import asyncio
import contextvars
from dataclasses import dataclass
from typing import Dict, Optional

import aiohttp
from prometheus_client.parser import text_string_to_metric_families

from ...logging_utils import init_logger
from ..service_discovery import get_service_discovery

logger = init_logger(__name__)

_METRIC_FIELDS = {
    "vllm:num_requests_running": "num_running_requests",
    "vllm:num_requests_waiting": "num_queuing_requests",
    "vllm:gpu_prefix_cache_hit_rate": "gpu_prefix_cache_hit_rate",
    "vllm:gpu_prefix_cache_hits_total": "gpu_prefix_cache_hits_total",
    "vllm:gpu_prefix_cache_queries_total": "gpu_prefix_cache_queries_total",
    "vllm:gpu_cache_usage_perc": "gpu_cache_usage_perc",
    # Engine telemetry (docs/observability.md "Engine telemetry").
    "pst_engine_compile_total": "engine_compiles_total",
    "pst_engine_kv_page_occupancy": "engine_kv_page_occupancy",
    "pst_engine_kv_page_high_watermark": "engine_kv_page_high_watermark",
    "pst_engine_warmup_coverage": "engine_warmup_coverage",
    # Remote-KV health (docs/kvserver.md): the disagg decode scorer
    # penalizes engines whose remote tier is degrading (fused-recompute
    # fallbacks, corrupt replica copies detected on read).
    "pst:kv_transfer_fallbacks_total": "kv_transfer_fallbacks_total",
    "pst_kv_integrity_failures_total": "kv_integrity_failures_total",
}

# Histogram whose p50 the scraper estimates from bucket counts (summed
# over label sets): the decode-loop host gap, so /engines and
# /debug/fleet surface the overlap-pipeline health without operators
# scraping engines directly.
_HOST_GAP_BUCKET = "pst_engine_host_gap_seconds_bucket"


def _bucket_quantile(buckets, q: float) -> float:
    """Estimate a quantile from cumulative ``{le: count}`` samples: the
    smallest upper bound covering q of the observations (the classic
    histogram_quantile upper-bound estimate, without interpolation —
    good enough for a health readout)."""
    if not buckets:
        return 0.0
    finite = sorted(
        (le, c) for le, c in buckets.items() if le != float("inf")
    )
    total = max(buckets.values())
    if total <= 0:
        return 0.0
    target = q * total
    for le, count in finite:
        if count >= target:
            return le
    return finite[-1][0] if finite else 0.0

# Labeled counters summed over their label sets (pst_engine_compile_total
# has one sample per {kind, shape_bucket}); everything else is a single
# sample and the last value wins.
_SUMMED_FIELDS = {
    "engine_compiles_total",
    # One sample per {source} (prefetch / match_prefix / restore).
    "kv_integrity_failures_total",
}


@dataclass
class EngineStats:
    num_running_requests: int = 0
    num_queuing_requests: int = 0
    gpu_prefix_cache_hit_rate: float = 0.0
    gpu_prefix_cache_hits_total: int = 0
    gpu_prefix_cache_queries_total: int = 0
    gpu_cache_usage_perc: float = 0.0
    engine_compiles_total: int = 0
    engine_kv_page_occupancy: float = 0.0
    engine_kv_page_high_watermark: float = 0.0
    engine_warmup_coverage: float = 0.0
    # Remote-KV tier health (docs/kvserver.md).
    kv_transfer_fallbacks_total: int = 0
    kv_integrity_failures_total: int = 0
    # Estimated from the pst_engine_host_gap_seconds bucket counts.
    engine_host_gap_p50: float = 0.0

    @staticmethod
    def from_scrape(text: str) -> "EngineStats":
        """Parse an engine's ``/metrics`` body into a snapshot.

        NEVER raises: a partially-written scrape (engine restarting
        mid-response) or a malformed line must degrade to whatever parsed
        before the damage, not kill the scrape sweep — a fleet-wide stats
        blackout because one engine emitted garbage would be worse than
        the garbage.
        """
        values: Dict[str, float] = {}
        host_gap_buckets: Dict[float, float] = {}
        try:
            for family in text_string_to_metric_families(text):
                for sample in family.samples:
                    if sample.name == _HOST_GAP_BUCKET:
                        try:
                            le = float(sample.labels.get("le", "inf"))
                            host_gap_buckets[le] = (
                                host_gap_buckets.get(le, 0.0)
                                + float(sample.value)
                            )
                        except (TypeError, ValueError):
                            pass
                        continue
                    field = _METRIC_FIELDS.get(sample.name)
                    if field is None:
                        continue
                    try:
                        v = float(sample.value)
                    except (TypeError, ValueError):
                        continue
                    if field in _SUMMED_FIELDS:
                        values[field] = values.get(field, 0.0) + v
                    else:
                        values[field] = v
        except Exception as e:  # noqa: BLE001 — keep what parsed so far
            logger.debug("partial engine scrape parse: %s", e)
        if host_gap_buckets:
            values["engine_host_gap_p50"] = _bucket_quantile(
                host_gap_buckets, 0.5
            )
        stats = EngineStats()
        for field, value in values.items():
            try:
                if field.startswith("num_") or field.endswith("_total"):
                    setattr(stats, field, int(value))
                else:
                    setattr(stats, field, float(value))
            except (TypeError, ValueError, OverflowError):
                continue  # one bad sample never poisons the snapshot
        return stats

    # Back-compat alias with the reference's classmethod name.
    from_vllm_scrape = from_scrape


class EngineStatsScraper:
    def __init__(self, scrape_interval: Optional[float] = None):
        if scrape_interval is None:
            raise ValueError("EngineStatsScraper needs a scrape_interval")
        self.scrape_interval = scrape_interval
        # Written only by the scrape task (_scrape_one fills, _loop
        # drops stale urls); readers get a copy via get_engine_stats().
        # pstlint: owned-by=task:_scrape_one,_loop
        self.engine_stats: Dict[str, EngineStats] = {}
        self._task: Optional[asyncio.Task] = None

    @classmethod
    def destroy(cls) -> None:
        """Drop the current scope's scraper (test/reconfiguration hook;
        the name survives from the SingletonMeta era so existing teardown
        helpers keep working)."""
        from .. import appscope

        appscope.scoped_set(_SCOPE_KEY, None)

    async def _scrape_one(self, session: aiohttp.ClientSession, url: str) -> None:
        try:
            async with session.get(
                f"{url}/metrics", timeout=aiohttp.ClientTimeout(total=self.scrape_interval)
            ) as resp:
                resp.raise_for_status()
                text = await resp.text()
            self.engine_stats[url] = EngineStats.from_scrape(text)
        except Exception as e:  # noqa: BLE001 — engine may be booting
            logger.debug("failed scraping %s: %s", url, e)

    async def _loop(self) -> None:
        async with aiohttp.ClientSession() as session:
            while True:
                try:
                    urls = [e.url for e in get_service_discovery().get_endpoint_info()]
                    await asyncio.gather(*(self._scrape_one(session, u) for u in urls))
                    for stale in set(self.engine_stats) - set(urls):
                        del self.engine_stats[stale]
                except asyncio.CancelledError:
                    raise
                except Exception as e:  # noqa: BLE001
                    logger.error("engine stats scrape sweep failed: %s", e)
                await asyncio.sleep(self.scrape_interval)

    async def start(self) -> None:
        if self._task is None:
            # pstlint: task-owner=_task
            self._task = asyncio.create_task(self._loop())

    def get_engine_stats(self) -> Dict[str, EngineStats]:
        return dict(self.engine_stats)

    def get_health(self) -> bool:
        return self._task is not None and not self._task.done()

    def close(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None


# Context binding: ``create_app`` injects its own scraper for the request
# tasks it serves; the app scope (``router.appscope``) covers bootstrap
# code and background loops — there is no module-level default left to
# bleed between apps (same contract as the request-stats monitor).
_bound_scraper: contextvars.ContextVar[Optional[EngineStatsScraper]] = (
    contextvars.ContextVar("pst_engine_stats_scraper", default=None)
)
_SCOPE_KEY = "engine_stats_scraper"


def initialize_engine_stats_scraper(scrape_interval: float) -> EngineStatsScraper:
    from .. import appscope

    return appscope.scoped_set(_SCOPE_KEY, EngineStatsScraper(scrape_interval))


def bind_engine_stats_scraper(
    scraper: EngineStatsScraper,
) -> contextvars.Token:
    """Bind ``scraper`` for the current context (one request's task tree);
    returns the token for ``unbind_engine_stats_scraper``."""
    return _bound_scraper.set(scraper)


def unbind_engine_stats_scraper(token: contextvars.Token) -> None:
    _bound_scraper.reset(token)


def get_engine_stats_scraper() -> EngineStatsScraper:
    from .. import appscope

    scraper = _bound_scraper.get()
    if scraper is not None:
        return scraper
    scraper = appscope.scoped_get(_SCOPE_KEY)
    if scraper is None:
        raise ValueError("EngineStatsScraper needs a scrape_interval")
    return scraper
