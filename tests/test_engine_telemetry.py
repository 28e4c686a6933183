"""Engine telemetry ring (docs/observability.md "Engine telemetry").

Ring 1: the EngineTelemetry sink — first-call-per-bucket compile
detection, step-duration routing, throughput (appended at a dispatch,
summed at a scrape), stats refresh, collections timed in the program,
every cause of a stall at 0 from the start.
Ring 2: a real tiny CPU engine — a forced recompile (new prefill shape
bucket) increments pst_engine_compile_total, records
pst_engine_compile_seconds, and rides RequestOutput.compile_events.
Ring 3: the engine HTTP server — the compile event lands on the
in-flight request's trace (/debug/requests), the first token's way to
the socket is its ``deliver`` span, streamed or not, /metrics carries
the pst_engine_* surface, and POST /debug/profile is guarded + a
graceful CPU no-op.
Ring 4: the generated observability/prometheus-rules.yaml passes an
offline schema check (promtool-equivalent) and the metric-docs lint
passes.
"""

import asyncio
import gc
import json
import pathlib
import re
import subprocess
import sys

import aiohttp
import pytest
import yaml
from aiohttp import web

from production_stack_tpu.engine.async_engine import AsyncLLMEngine
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.engine.server import create_engine_app
from production_stack_tpu.obs import (
    ENGINE_TELEMETRY,
    EngineTelemetry,
    render_engine_telemetry,
)

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _reset_telemetry():
    ENGINE_TELEMETRY.reset_for_tests()
    yield
    ENGINE_TELEMETRY.reset_for_tests()


# ---------------------------------------------------------------------------
# Ring 1 — the sink
# ---------------------------------------------------------------------------


def test_first_call_per_bucket_counts_one_compile():
    tel = EngineTelemetry()
    key = (0, "decode", ((1, 8),), (False, True))
    assert tel.record_dispatch("decode", key, 1.5, batch_bucket="b8") is True
    # Same signature again: steady-state step, not a compile.
    assert tel.record_dispatch("decode", key, 0.01, batch_bucket="b8") is False
    assert tel.compile_count() == 1
    # A different signature compiles again.
    key2 = (0, "decode", ((1, 16),), (False, True))
    assert tel.record_dispatch("decode", key2, 2.0, batch_bucket="b16") is True
    assert tel.compile_count() == 2


def test_compile_events_drain_once():
    tel = EngineTelemetry()
    tel.record_dispatch("prefill", ("k1",), 3.0, batch_bucket="b1xt128")
    events = tel.drain_compile_events()
    assert events == [
        {"kind": "prefill", "shape_bucket": "b1xt128", "seconds": 3.0}
    ]
    assert tel.drain_compile_events() == []


def _gauge(text: str, series: str) -> float:
    return float(
        re.search("^" + re.escape(series) + r" (\S+)", text, re.M).group(1))


def test_throughput_update(monkeypatch):
    """A dispatch appends its sample and walks nothing; the scrape
    (``refresh_from_stats``) sums what the last ten seconds hold."""
    tel = EngineTelemetry()
    clock = [1000.0]
    monkeypatch.setattr(
        "production_stack_tpu.obs.engine_telemetry.time.monotonic",
        lambda: clock[0])
    series = 'pst_engine_tokens_per_second{kind="decode"}'
    tel.refresh_from_stats({})
    tel.record_dispatch("decode", ("a",), 0.1, batch_bucket="b8", tokens=100)
    clock[0] += 2.0
    tel.record_dispatch("decode", ("a",), 0.1, batch_bucket="b8", tokens=100)
    clock[0] += 2.0
    # Gauges live in the shared registry; the values themselves are
    # asserted through exposition text (the public contract).
    tel.refresh_from_stats({})
    assert _gauge(render_engine_telemetry().decode(), series) == 200 / 4.0
    # nothing is read between two scrapes, so nothing is computed there
    tel.record_dispatch("decode", ("a",), 0.1, batch_bucket="b8", tokens=400)
    assert _gauge(render_engine_telemetry().decode(), series) == 50.0
    # samples older than the window go at the next dispatch, scrape or none
    clock[0] += 9.0
    tel.record_dispatch("decode", ("a",), 0.1, batch_bucket="b8", tokens=100)
    assert [toks for _, _, toks in tel._tok_samples] == [400, 100]
    tel.refresh_from_stats({})
    assert _gauge(render_engine_telemetry().decode(), series) == 500 / 9.0
    clock[0] += 11.0  # an idle engine reads 0, not its last burst
    tel.refresh_from_stats({})
    assert _gauge(render_engine_telemetry().decode(), series) == 0.0


def test_refresh_from_stats_tracks_high_watermark():
    tel = EngineTelemetry()
    tel.refresh_from_stats({"kv_cache_usage_perc": 0.6,
                            "num_preemptions_total": 2})
    tel.refresh_from_stats({"kv_cache_usage_perc": 0.3,
                            "num_preemptions_total": 5})
    text = render_engine_telemetry().decode()
    assert "pst_engine_kv_page_occupancy 0.3" in text
    assert "pst_engine_kv_page_high_watermark 0.6" in text


def test_startup_phase_gate():
    tel = EngineTelemetry()
    tel.startup_enabled = False
    tel.record_startup_phase("load", 12.0)  # must be a no-op
    tel.startup_enabled = True
    tel.record_startup_phase("load", 12.0)
    assert 'pst_engine_startup_seconds{phase="load"} 12.0' in (
        render_engine_telemetry().decode()
    )


def test_every_cause_of_a_stall_reads_zero_from_the_start():
    """In a process that has stalled nowhere yet, /metrics' telemetry part
    carries both families with all seven causes at 0: a window without a
    stall reads 0, not nothing."""
    out = subprocess.run(
        [sys.executable, "-c",
         "from production_stack_tpu.obs import render_engine_telemetry;"
         "print(render_engine_telemetry().decode())"],
        capture_output=True, text=True, cwd=REPO, check=True).stdout
    for cause in ("compile", "gc", "device", "machine", "interpreter",
                  "host_work", "unknown"):
        for family in ("pst_engine_stalls_total", "pst_engine_stall_seconds_total"):
            assert f'{family}{{cause="{cause}"}} 0.0' in out, (family, cause)
    assert "pst_engine_gc_pause_seconds_total 0.0" in out
    assert len(re.findall(r'^pst_engine_stalls_total\{', out, re.M)) == 7


def test_collections_are_timed_in_the_program():
    tel = ENGINE_TELEMETRY  # the process's one sink: its counter is global
    series = "pst_engine_gc_pause_seconds_total"
    tel.watch_collections()
    tel.watch_collections()  # one entry however often it is asked for
    assert gc.callbacks.count(tel._on_collection) == 1
    # Automatic collection is held off: between two readings of the counter
    # only the collections asked for here run (a full one that an allocation
    # trips in between is timed too, and moved it under the driver's load).
    was_on = gc.isenabled()
    gc.disable()
    try:
        graph = [[i] for i in range(200_000)]
        before = _gauge(render_engine_telemetry().decode(), series)
        gc.collect(0)  # the young generation is not timed
        assert _gauge(render_engine_telemetry().decode(), series) == before
        with tel.phase("step"):  # a collection adds to the open cycle
            gc.collect()
            cycle = tel._open_cycle()
        del graph
        grown = _gauge(render_engine_telemetry().decode(), series) - before
        assert grown > 0 and cycle.gc_s == pytest.approx(grown)
    finally:
        if was_on:
            gc.enable()


def test_a_collection_that_reports_late_is_counted_once_and_where_it_fell():
    """A collection's callbacks are Python: the step thread, having waited
    it out, takes the interpreter lock at their first instruction and may
    close its cycle before "stop" runs (seen on the chip). The cycle counts
    the collection then, and the late "stop" adds it nowhere else."""
    import threading
    import time

    from production_stack_tpu.obs.flight import FlightRecorder

    tel = ENGINE_TELEMETRY
    rec = FlightRecorder(capacity=8)
    tel.attach_flight(rec)
    series = "pst_engine_gc_pause_seconds_total"
    before = _gauge(render_engine_telemetry().decode(), series)

    def elsewhere(phase):
        t = threading.Thread(
            target=tel._on_collection, args=(phase, {"generation": 2}))
        t.start()
        t.join()

    with tel.phase("step"):
        elsewhere("start")
        time.sleep(0.02)
    elsewhere("stop")
    with tel.phase("step"):
        pass
    stalled, after = rec.records()[-2:]
    assert stalled["gc_s"] >= 0.02 and after["gc_s"] == 0
    grown = _gauge(render_engine_telemetry().decode(), series) - before
    assert grown == pytest.approx(stalled["gc_s"], abs=5e-3)


# ---------------------------------------------------------------------------
# Ring 2 — real tiny CPU engine: forced recompile
# ---------------------------------------------------------------------------


def _tiny_cfg(**over):
    kw = dict(
        model="tiny-llama-debug", max_model_len=256, block_size=8,
        num_kv_blocks=256, max_num_seqs=8, max_prefill_tokens=64,
        # These tests count the synchronous loop's compiles against exact
        # expectations (the pipeline decodes through its own multi-step
        # executables). Overlap's own compile story is covered by the
        # lattice tests in test_precompile.py.
        overlap_decode=False,
    )
    kw.update(over)
    return EngineConfig(**kw)


def _run_to_completion(engine, rid, prompt_ids, max_tokens=2):
    engine.add_request(
        rid, prompt_token_ids=prompt_ids,
        sampling=SamplingParams(max_tokens=max_tokens),
    )
    outs = []
    while engine.has_work():
        outs += engine.step()
    return outs


def test_forced_recompile_counts_and_rides_outputs():
    engine = LLMEngine(_tiny_cfg())
    # Startup phases were recorded during construction.
    text = render_engine_telemetry().decode()
    for phase in ("load", "shard", "warmup"):
        assert f'pst_engine_startup_seconds{{phase="{phase}"}}' in text

    _run_to_completion(engine, "warm", [1, 2, 3, 4, 5])
    warm = ENGINE_TELEMETRY.compile_count()
    assert warm >= 2  # at least one prefill + one decode bucket

    # Steady state: the same shapes again compile nothing.
    _run_to_completion(engine, "steady", [9, 8, 7, 6, 5])
    assert ENGINE_TELEMETRY.compile_count() == warm

    # A 33-token prompt pads to a NEW prefill chunk bucket (t64 vs t8):
    # the forced recompile of the acceptance criterion.
    outs = _run_to_completion(engine, "victim", list(range(1, 34)))
    assert ENGINE_TELEMETRY.compile_count() == warm + 1
    carried = [o for o in outs if o.compile_events]
    assert carried, "the victim request's outputs must carry the event"
    ev = carried[0].compile_events[0]
    assert ev["kind"] == "prefill"
    assert ev["shape_bucket"] == "b1xt64"
    assert ev["seconds"] >= 0.0

    text = render_engine_telemetry().decode()
    assert ('pst_engine_compile_total{kind="prefill",shape_bucket="b1xt64"}'
            in text)
    assert 'pst_engine_compile_seconds_count{kind="prefill"}' in text
    assert 'pst_engine_batch_fill_ratio_count{kind="prefill"}' in text


# ---------------------------------------------------------------------------
# Ring 3 — engine HTTP server
# ---------------------------------------------------------------------------


class EngineServer:
    def __init__(self, **app_over):
        self.app_over = app_over
        self.url = None

    async def __aenter__(self):
        self.engine = AsyncLLMEngine(_tiny_cfg())
        app = create_engine_app(self.engine, **self.app_over)
        self.runner = web.AppRunner(app)
        await self.runner.setup()
        site = web.TCPSite(self.runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        self.url = f"http://127.0.0.1:{port}"
        self.engine.start(asyncio.get_event_loop())
        return self

    async def __aexit__(self, *exc):
        self.engine.shutdown()
        await self.runner.cleanup()


async def test_server_metrics_and_compile_span_event():
    async with EngineServer() as server, aiohttp.ClientSession() as sess:
        # The very first request compiles its buckets: its trace must
        # carry the compile span event(s).
        payload = {"model": "tiny-llama-debug", "prompt": "hello world",
                   "max_tokens": 4, "temperature": 0.0}
        async with sess.post(f"{server.url}/v1/completions", json=payload) as r:
            assert r.status == 200

        async with sess.get(f"{server.url}/metrics") as r:
            text = await r.text()
        assert "pst_engine_compile_total" in text
        assert "pst_engine_step_duration_seconds" in text
        assert "pst_engine_kv_page_occupancy" in text
        assert "pst_engine_startup_seconds" in text
        for family in ("pst_engine_step_offcpu_seconds_count",
                       "pst_engine_stalls_total",
                       "pst_engine_stall_seconds_total",
                       "pst_engine_gc_pause_seconds_total"):
            assert family in text, family
        # The vllm: surface and the stage histograms still ride along.
        assert "vllm:num_requests_running" in text
        assert "pst_stage_duration_seconds" in text

        async with sess.get(f"{server.url}/debug/requests") as r:
            timelines = (await r.json())["requests"]
        assert timelines
        events = [
            ev for tl in timelines for sp in tl["spans"]
            for ev in sp["events"]
        ]
        compile_events = [ev for ev in events if ev["name"] == "compile"]
        assert compile_events, "compile must appear on the victim's trace"
        assert compile_events[0]["attributes"]["kind"] in (
            "prefill", "decode"
        )


@pytest.mark.parametrize("stream", [True, False],
                         ids=["streamed", "unstreamed"])
async def test_deliver_span_follows_prefill(stream):
    """The first token's way from the step thread's stamp to the return of
    the socket write that carried it, in /debug/requests: after prefill,
    never negative, and short of the whole request."""
    async with EngineServer() as server, aiohttp.ClientSession() as sess:
        payload = {"model": "tiny-llama-debug", "prompt": "hello world",
                   "max_tokens": 6, "temperature": 0.0, "stream": stream}
        async with sess.post(f"{server.url}/v1/completions", json=payload) as r:
            assert r.status == 200
            body = await r.read()
        if stream:
            assert body.rstrip().endswith(b"data: [DONE]")
        else:
            assert json.loads(body)["usage"]["completion_tokens"] == 6
        async with sess.get(f"{server.url}/debug/requests") as r:
            (timeline,) = (await r.json())["requests"]
        spans = {sp["name"]: sp for sp in timeline["spans"]}
        assert {"engine_queue", "prefill", "deliver", "decode"} <= set(spans)
        deliver, prefill = spans["deliver"], spans["prefill"]
        assert 0 <= deliver["duration_ms"] <= timeline["duration_ms"]
        assert deliver["start_ms"] == pytest.approx(
            prefill["start_ms"] + prefill["duration_ms"], abs=0.01)
        if not stream:  # the first token leaves with the last
            assert deliver["duration_ms"] >= spans["decode"]["duration_ms"]
        async with sess.get(f"{server.url}/metrics") as r:
            assert 'stage="deliver"' in await r.text()


async def test_debug_profile_guarded_and_cpu_noop():
    async with EngineServer() as server, aiohttp.ClientSession() as sess:
        # Disabled by default: 403, not silent success.
        async with sess.post(f"{server.url}/debug/profile") as r:
            assert r.status == 403
    async with EngineServer(profiling=True) as server, \
            aiohttp.ClientSession() as sess:
        async with sess.post(
            f"{server.url}/debug/profile", json={"duration_ms": 50}
        ) as r:
            assert r.status == 200
            body = await r.json()
        # CPU backend: graceful no-op with an explanation.
        assert body["status"] == "skipped"
        assert "cpu" in body["reason"]
        async with sess.post(
            f"{server.url}/debug/profile", json={"duration_ms": "bogus"}
        ) as r:
            assert r.status == 400


async def test_debug_profile_requires_api_key_when_configured():
    async with EngineServer(profiling=True, api_key="sekrit") as server, \
            aiohttp.ClientSession() as sess:
        async with sess.post(f"{server.url}/debug/profile") as r:
            assert r.status == 401
        async with sess.post(
            f"{server.url}/debug/profile",
            headers={"Authorization": "Bearer sekrit"},
        ) as r:
            assert r.status == 200


# ---------------------------------------------------------------------------
# Ring 4 — generated rules + docs lint
# ---------------------------------------------------------------------------

_DURATION_RE = re.compile(r"^\d+(s|m|h|d|w|y)$")


def test_prometheus_rules_offline_schema_check():
    """promtool-equivalent structural validation of the generated rules
    (the acceptance criterion's offline alternative to
    `promtool check rules`)."""
    path = REPO / "observability" / "prometheus-rules.yaml"
    data = yaml.safe_load(path.read_text())
    assert set(data) == {"groups"}
    names = set()
    n_record = n_alert = 0
    for group in data["groups"]:
        assert group["name"] and group["name"] not in names
        names.add(group["name"])
        if "interval" in group:
            assert _DURATION_RE.match(group["interval"])
        assert group["rules"]
        for rule in group["rules"]:
            assert ("record" in rule) != ("alert" in rule)
            assert isinstance(rule["expr"], str) and rule["expr"].strip()
            # Balanced parens = the cheapest PromQL sanity check that
            # still catches generator typos.
            assert rule["expr"].count("(") == rule["expr"].count(")")
            if "record" in rule:
                n_record += 1
                assert re.match(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$", rule["record"])
                assert "for" not in rule
            else:
                n_alert += 1
                assert re.match(r"^[a-zA-Z_]\w*$", rule["alert"])
                if "for" in rule:
                    assert _DURATION_RE.match(rule["for"])
                assert rule["labels"]["severity"] in ("page", "ticket")
                assert rule["annotations"]["summary"]
                assert rule["annotations"]["description"]
    # The burn-rate design: one recording rule per window, page+ticket.
    assert n_record >= 5
    assert n_alert >= 2
    alerts = {
        r["alert"] for g in data["groups"] for r in g["rules"] if "alert" in r
    }
    assert {"PstTtftSloBurnRatePage", "PstTtftSloBurnRateTicket"} <= alerts


def test_rules_match_generator_output():
    """The committed rules file must equal the generator's output (the
    CI drift check, runnable locally)."""
    sys.path.insert(0, str(REPO / "observability"))
    try:
        import gen_dashboards
    finally:
        sys.path.pop(0)
    generated = gen_dashboards._dump_rules_yaml(
        gen_dashboards.prometheus_rules()
    )
    committed = (REPO / "observability" / "prometheus-rules.yaml").read_text()
    assert generated == committed


def test_metric_docs_lint_passes():
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "check_metric_docs.py")],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
