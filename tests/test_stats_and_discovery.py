"""Unit tests: request-stats lifecycle, engine-stats scrape parsing, static
discovery, hashtrie, parser validation."""

import asyncio

import pytest

from production_stack_tpu.router.parser import parse_args
from production_stack_tpu.router.routing.hashtrie import HashTrie
from production_stack_tpu.router.service_discovery import (
    ServiceDiscoveryType,
    StaticServiceDiscovery,
    initialize_service_discovery,
)
from production_stack_tpu.router.stats.engine_stats import EngineStats
from production_stack_tpu.router.stats.request_stats import RequestStatsMonitor

from .router_utils import reset_router_singletons


@pytest.fixture(autouse=True)
def _reset():
    reset_router_singletons()
    yield
    reset_router_singletons()


def test_engine_stats_from_scrape():
    text = "\n".join(
        [
            "# TYPE vllm:num_requests_running gauge",
            "vllm:num_requests_running 3",
            "# TYPE vllm:num_requests_waiting gauge",
            "vllm:num_requests_waiting 7",
            "# TYPE vllm:gpu_prefix_cache_hit_rate gauge",
            "vllm:gpu_prefix_cache_hit_rate 0.61",
            "# TYPE vllm:gpu_prefix_cache_hits_total counter",
            "vllm:gpu_prefix_cache_hits_total 100",
            "# TYPE vllm:gpu_prefix_cache_queries_total counter",
            "vllm:gpu_prefix_cache_queries_total 164",
            "# TYPE vllm:gpu_cache_usage_perc gauge",
            "vllm:gpu_cache_usage_perc 0.42",
            "",
        ]
    )
    stats = EngineStats.from_scrape(text)
    assert stats.num_running_requests == 3
    assert stats.num_queuing_requests == 7
    assert abs(stats.gpu_prefix_cache_hit_rate - 0.61) < 1e-9
    assert stats.gpu_prefix_cache_hits_total == 100
    assert stats.gpu_prefix_cache_queries_total == 164
    assert abs(stats.gpu_cache_usage_perc - 0.42) < 1e-9


def test_engine_stats_parses_engine_telemetry_names():
    """The pst_engine_* surface (docs/observability.md "Engine
    telemetry"): labeled compile counters SUM over their label sets."""
    text = "\n".join(
        [
            "# TYPE pst_engine_compile counter",
            'pst_engine_compile_total{kind="prefill",shape_bucket="b1xt64"} 3',
            'pst_engine_compile_total{kind="decode",shape_bucket="b8"} 4',
            "# TYPE pst_engine_kv_page_occupancy gauge",
            "pst_engine_kv_page_occupancy 0.8",
            "# TYPE pst_engine_kv_page_high_watermark gauge",
            "pst_engine_kv_page_high_watermark 0.93",
            "",
        ]
    )
    stats = EngineStats.from_scrape(text)
    assert stats.engine_compiles_total == 7
    assert abs(stats.engine_kv_page_occupancy - 0.8) < 1e-9
    assert abs(stats.engine_kv_page_high_watermark - 0.93) < 1e-9


def test_engine_stats_parses_warm_state_fields():
    """The /engines warm-state extension (docs/observability.md "Fleet
    debugging"): warmup coverage passes through, and the host-gap p50 is
    estimated from the histogram's cumulative buckets — summed across
    batch_bucket label sets — as the smallest upper bound covering half
    the observations."""
    text = "\n".join(
        [
            "# TYPE pst_engine_warmup_coverage gauge",
            "pst_engine_warmup_coverage 0.75",
            "# TYPE pst_engine_host_gap_seconds histogram",
            'pst_engine_host_gap_seconds_bucket{batch_bucket="b4",le="0.001"} 2',
            'pst_engine_host_gap_seconds_bucket{batch_bucket="b4",le="0.005"} 4',
            'pst_engine_host_gap_seconds_bucket{batch_bucket="b4",le="+Inf"} 5',
            'pst_engine_host_gap_seconds_sum{batch_bucket="b4"} 0.02',
            'pst_engine_host_gap_seconds_count{batch_bucket="b4"} 5',
            'pst_engine_host_gap_seconds_bucket{batch_bucket="b8",le="0.001"} 1',
            'pst_engine_host_gap_seconds_bucket{batch_bucket="b8",le="0.005"} 5',
            'pst_engine_host_gap_seconds_bucket{batch_bucket="b8",le="+Inf"} 5',
            'pst_engine_host_gap_seconds_sum{batch_bucket="b8"} 0.01',
            'pst_engine_host_gap_seconds_count{batch_bucket="b8"} 5',
            "",
        ]
    )
    stats = EngineStats.from_scrape(text)
    assert abs(stats.engine_warmup_coverage - 0.75) < 1e-9
    # Summed buckets: le=0.001 -> 3, le=0.005 -> 9, +Inf -> 10; half of
    # 10 observations is covered at le=0.005.
    assert abs(stats.engine_host_gap_p50 - 0.005) < 1e-9


def test_engine_stats_host_gap_absent_defaults_zero():
    stats = EngineStats.from_scrape("vllm:num_requests_running 1\n")
    assert stats.engine_host_gap_p50 == 0.0
    assert stats.engine_warmup_coverage == 0.0


@pytest.mark.parametrize("text", [
    "",                                         # empty scrape
    "complete garbage {{{ not prometheus",      # unparseable outright
    "vllm:num_requests_running not_a_number",   # malformed value
    # Truncated mid-line: an engine dying mid-response.
    "# TYPE vllm:num_requests_running gauge\n"
    "vllm:num_requests_running 3\n"
    'pst_engine_compile_total{kind="pre',
    # Unknown metrics only.
    "# TYPE something_else counter\nsomething_else_total 9\n",
])
def test_engine_stats_never_raises_on_partial_scrape(text):
    stats = EngineStats.from_scrape(text)
    assert isinstance(stats, EngineStats)


def test_engine_stats_partial_scrape_keeps_parsed_prefix():
    """Damage PAST the good lines must not discard what already parsed —
    the scrape sweep keeps serving stale-free values for the live part."""
    text = (
        "# TYPE vllm:num_requests_running gauge\n"
        "vllm:num_requests_running 5\n"
        "# TYPE vllm:gpu_cache_usage_perc gauge\n"
        "vllm:gpu_cache_usage_perc 0.5\n"
        "# TYPE broken gauge\n"
        "broken this-is-not-a-number\n"
    )
    stats = EngineStats.from_scrape(text)
    assert stats.num_running_requests == 5
    assert abs(stats.gpu_cache_usage_perc - 0.5) < 1e-9


def test_request_stats_lifecycle():
    mon = RequestStatsMonitor(sliding_window_size=60.0)
    url = "http://e0"
    mon.on_new_request(url, "r1", 100.0)
    stats = mon.get_request_stats(current_time=100.5)
    assert stats[url].in_prefill_requests == 1
    mon.on_request_response(url, "r1", 100.25)  # first token → TTFT 0.25
    mon.on_request_response(url, "r1", 100.35)  # second token → ITL 0.10
    mon.on_request_complete(url, "r1", 101.0)
    stats = mon.get_request_stats(current_time=101.0)
    s = stats[url]
    assert s.in_prefill_requests == 0
    assert s.in_decoding_requests == 0
    assert s.finished_requests == 1
    assert abs(s.ttft - 0.25) < 1e-9
    assert abs(s.avg_itl - 0.10) < 1e-9
    assert abs(s.avg_latency - 1.0) < 1e-9
    assert s.qps > 0


def test_static_discovery():
    sd = initialize_service_discovery(
        ServiceDiscoveryType.STATIC,
        urls=["http://e0", "http://e1"],
        models=["llama", "mistral"],
        aliases={"big": "llama"},
        model_labels=["a", "b"],
    )
    assert isinstance(sd, StaticServiceDiscovery)
    infos = sd.get_endpoint_info()
    assert len(infos) == 2
    assert infos[0].model_names == ["llama"]
    assert infos[1].model_label == "b"
    assert sd.aliases == {"big": "llama"}
    assert infos[0].has_model("llama") and not infos[0].has_model("mistral")


def test_static_discovery_length_mismatch():
    with pytest.raises(ValueError):
        StaticServiceDiscovery(urls=["http://a"], models=["m1", "m2"])


def test_static_discovery_warming_flag():
    """set_warming flips the endpoint's warming flag (reconciled by the
    /ready probes, exactly like draining)."""
    sd = StaticServiceDiscovery(
        urls=["http://e0", "http://e1"], models=["llama", "llama"]
    )
    sd.set_warming("http://e1", True)
    infos = {e.url: e for e in sd.get_endpoint_info()}
    assert infos["http://e0"].warming is False
    assert infos["http://e1"].warming is True
    sd.set_warming("http://e1", False)
    assert all(not e.warming for e in sd.get_endpoint_info())


def test_warming_from_ready_interpretation():
    from production_stack_tpu.router.service_discovery import (
        warming_from_ready,
    )

    assert warming_from_ready(503, {"ready": False, "reason": "warming"})
    assert not warming_from_ready(200, {"ready": True})
    assert not warming_from_ready(404, None)  # pre-warmup engine
    assert not warming_from_ready(503, None)  # non-JSON 5xx
    assert not warming_from_ready(503, {"reason": "draining"})


def test_filter_routable_excludes_warming():
    from production_stack_tpu.router.routing.logic import filter_routable
    from production_stack_tpu.router.service_discovery import EndpointInfo

    def ep(url, **kw):
        return EndpointInfo(
            url=url, model_names=["m"], Id=url, added_timestamp=0.0,
            model_label="default", **kw,
        )

    eps = [
        ep("http://ok"),
        ep("http://warming", warming=True),
        ep("http://draining", draining=True),
    ]
    routable = filter_routable(eps, apply_breakers=False)
    assert [e.url for e in routable] == ["http://ok"]


def test_canary_skips_warming_engines(event_loop):
    """A warming engine must be skipped, not probed: a probe would queue
    behind the precompile pass and feed the breaker a spurious failure."""
    from production_stack_tpu.router.service_discovery import EndpointInfo
    from production_stack_tpu.router.services.canary import CanaryProber

    prober = CanaryProber(interval=1.0)
    warming_ep = EndpointInfo(
        url="http://nowhere.invalid:1", model_names=["m"], Id="x",
        added_timestamp=0.0, model_label="default", warming=True,
    )
    # _probe_one returns before touching the (absent) client session —
    # probing a warming engine would raise here.
    event_loop.run_until_complete(prober._probe_one(warming_ep))
    assert prober.probes_total == 0
    assert prober.failures_total == 0


def test_hashtrie(event_loop):
    trie = HashTrie(chunk_size=4)
    event_loop.run_until_complete(trie.insert("abcdefgh", "e1"))
    event_loop.run_until_complete(trie.insert("abcdxxxx", "e2"))
    matched, eps = event_loop.run_until_complete(trie.longest_prefix_match("abcdefgh"))
    assert matched == 8 and eps == {"e1"}
    matched, eps = event_loop.run_until_complete(trie.longest_prefix_match("abcdzzzz"))
    assert matched == 4 and eps == {"e1", "e2"}
    matched, eps = event_loop.run_until_complete(trie.longest_prefix_match("zzzz"))
    assert matched == 0 and eps == set()
    # availability filter
    matched, eps = event_loop.run_until_complete(
        trie.longest_prefix_match("abcdefgh", {"e2"})
    )
    assert matched == 4 and eps == {"e2"}
    # endpoint removal
    event_loop.run_until_complete(trie.remove_endpoint("e1"))
    matched, eps = event_loop.run_until_complete(trie.longest_prefix_match("abcdefgh"))
    assert "e1" not in eps


def test_parser_static_ok(tmp_path):
    args = parse_args(
        [
            "--service-discovery", "static",
            "--static-backends", "http://localhost:9101",
            "--static-models", "m",
        ]
    )
    assert args.port == 8001
    assert args.static_aliases_parsed == {}


def test_parser_validation_errors():
    with pytest.raises(ValueError):
        parse_args(["--service-discovery", "static"])  # missing backends
    with pytest.raises(ValueError):
        parse_args(
            [
                "--service-discovery", "static",
                "--static-backends", "http://a:1,http://b:2",
                "--static-models", "only-one",
            ]
        )
    with pytest.raises(ValueError):
        parse_args(
            [
                "--service-discovery", "static",
                "--static-backends", "http://a:1",
                "--static-models", "m",
                "--routing-logic", "session",
            ]
        )


def test_parser_config_file(tmp_path):
    cfg = tmp_path / "router.yaml"
    cfg.write_text(
        "port: 9999\nstatic-backends: http://localhost:9101\nstatic-models: m\n"
    )
    args = parse_args(["--config", str(cfg)])
    assert args.port == 9999
    assert args.static_backends == "http://localhost:9101"
