"""Evidence plane: forensics bundles, regression verdicts, and the
self-budgeting driver (docs/benchmarking.md "Driver mode, verdicts &
evidence bundles", docs/observability.md "Forensics bundles").

- Bundle mechanics: tail-bar triggers, per-series /metrics deltas,
  worst-trace selection, live harvest against a stalled fake engine,
  and the post-mortem path (a SIGKILLed engine's persisted snapshots).
- Flight snapshot persistence: naming contract, bounded oldest-first
  disk eviction, restart load-back via ``?snapshots=1``.
- Verdicts: the pass/fail claim matrix over synthetic rounds, plus a
  capture of a run killed mid-sweep — its tail point must be flagged and
  its missing phases surfaced as unevaluable, never silently passed.
- Driver mode: the budget gate admits exactly one engine bring-up when
  the wall is nearly spent, the watchdog force-emits a verdict-bearing
  partial at T−lead, and the final stdout line is parseable JSON even
  when a SIGALRM lands mid-run.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import aiohttp
import pytest
from aiohttp import web

from production_stack_tpu.obs.flight import FlightRecorder, load_snapshot_dir
from production_stack_tpu.obs.forensics import (
    BUNDLE_SCHEMA,
    ForensicsCollector,
    crosses_tail_bar,
    evidence_dir_for,
    metrics_delta,
    worst_traces,
)
from production_stack_tpu.testing.fake_engine import create_fake_engine_app

sys.path.insert(0, ".")
import bench  # noqa: E402
from benchmarks import bench_engine  # noqa: E402
from benchmarks import verdicts as V  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = "fake/model"


# ---------------------------------------------------------------------------
# Trigger + delta + trace-selection units
# ---------------------------------------------------------------------------


@pytest.mark.fast
def test_crosses_tail_bar_matrix():
    # The sweep's own shape bar: p99 > factor x p50.
    assert crosses_tail_bar(100.0, 301.0) == "tail_outlier"
    assert crosses_tail_bar(100.0, 300.0) is None
    # An absolute SLO bar outranks the relative shape.
    assert crosses_tail_bar(100.0, 150.0, abs_bar_ms=120.0) == "slo_bar"
    assert crosses_tail_bar(100.0, 110.0, abs_bar_ms=120.0) is None
    # Unmeasurable points never trigger.
    assert crosses_tail_bar(None, None) is None
    assert crosses_tail_bar(None, 500.0) is None
    assert crosses_tail_bar(0.0, 500.0) is None  # p50=0: no ratio


@pytest.mark.fast
def test_metrics_delta_per_series():
    before = {"a_total": 5.0, 'b{x="1"}': 2.0, "unchanged": 7.0}
    after = {"a_total": 9.0, 'b{x="1"}': 2.0, "unchanged": 7.0,
             "born_total": 3.0}
    d = metrics_delta(before, after)
    assert d == {"a_total": 4.0, "born_total": 3.0}  # unmoved series drop


@pytest.mark.fast
def test_worst_traces_selects_slowest():
    payload = {"requests": [
        {"request_id": "a", "duration_ms": 12.0},
        "not-a-dict",
        {"request_id": "b", "duration_ms": 900.0},
        {"request_id": "c"},  # no duration -> sorts last
        {"request_id": "d", "duration_ms": 55.0},
    ]}
    top = worst_traces(payload, n=2)
    assert [t["request_id"] for t in top] == ["b", "d"]
    assert worst_traces({}, n=3) == []


@pytest.mark.fast
def test_evidence_dir_beside_bench_out():
    assert evidence_dir_for("/tmp/bench.json") == "/tmp/bench.json.evidence"
    assert evidence_dir_for(None) == "/tmp/pst_bench.evidence"


# ---------------------------------------------------------------------------
# Flight snapshot persistence (the engine-side half of the post-mortem)
# ---------------------------------------------------------------------------


@pytest.mark.fast
def test_flight_snapshots_persist_and_restore(tmp_path):
    d = str(tmp_path / "snaps")
    rec = FlightRecorder(capacity=16, snapshot_dir=d)
    rec.record_step("decode", "b4xn8", 0.002, tokens=8)
    snap = rec.snapshot("tail_outlier", {"bucket": "b4xn8", "waiting": 3})
    assert snap["detail"]["bucket"] == "b4xn8"
    names = sorted(os.listdir(d))
    assert len(names) == 1
    # Naming contract: flight_<time_ns>_<seq>_<reason>.json, no .tmp left.
    assert names[0].startswith("flight_") and names[0].endswith(
        "_tail_outlier.json"
    )
    # A NEW recorder on the same dir (the restarted engine) restores it.
    rec2 = FlightRecorder(capacity=16, snapshot_dir=d)
    restored = rec2.restored_snapshots()
    assert len(restored) == 1
    assert restored[0]["detail"]["bucket"] == "b4xn8"
    payload = rec2.to_payload(include_restored=True)
    assert payload["snapshot_dir"] == d
    assert payload["restored_snapshots"][0]["detail"]["waiting"] == 3
    # Without the ?snapshots=1 flag the payload stays lean.
    assert "restored_snapshots" not in rec2.to_payload()


@pytest.mark.fast
def test_flight_snapshot_disk_eviction_oldest_first(tmp_path):
    d = str(tmp_path / "snaps")
    rec = FlightRecorder(capacity=8, snapshot_dir=d, snapshot_disk_keep=3)
    for i in range(5):
        rec.snapshot("tail_outlier", {"seq": i})
    names = sorted(os.listdir(d))
    assert len(names) == 3
    kept = [s["detail"]["seq"] for s in load_snapshot_dir(d)]
    assert kept == [2, 3, 4]  # oldest evicted, chronological order kept


@pytest.mark.fast
def test_load_snapshot_dir_skips_corrupt_files(tmp_path):
    d = tmp_path / "snaps"
    d.mkdir()
    (d / "flight_00000000000000000001_000001_tail_outlier.json").write_text(
        json.dumps({"reason": "tail_outlier", "detail": {"ok": True}})
    )
    # Half-written at SIGKILL: must not poison the post-mortem.
    (d / "flight_00000000000000000002_000002_tail_outlier.json").write_text(
        '{"reason": "tail_ou'
    )
    (d / "unrelated.txt").write_text("ignored")
    snaps = load_snapshot_dir(str(d))
    assert len(snaps) == 1
    assert snaps[0]["detail"]["ok"] is True
    assert snaps[0]["persisted_as"].endswith("_000001_tail_outlier.json")
    assert load_snapshot_dir(str(tmp_path / "missing")) == []


# ---------------------------------------------------------------------------
# Fake engine stall mode (the inducible BENCH_r05 signature)
# ---------------------------------------------------------------------------


async def _start_site(app, port=0):
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", port)
    await site.start()
    bound = site._server.sockets[0].getsockname()[1]
    return runner, f"http://127.0.0.1:{bound}"


async def test_fake_engine_stall_leaves_deterministic_snapshot(tmp_path):
    app = create_fake_engine_app(model=MODEL, speed=5000)
    app["state"].flight_snapshot_dir = str(tmp_path / "snaps")
    runner, url = await _start_site(app)
    try:
        async with aiohttp.ClientSession() as sess:
            async with sess.post(f"{url}/admin/fail", json={
                "mode": "nope"
            }) as r:
                assert r.status == 400
            async with sess.post(f"{url}/admin/fail", json={
                "mode": "stall", "delay": 0.05,
            }) as r:
                assert r.status == 200
            t0 = time.monotonic()
            async with sess.post(f"{url}/v1/completions", json={
                "model": MODEL, "prompt": "one two", "max_tokens": 4,
            }) as r:
                assert r.status == 200  # serves normally, just late
                await r.read()
            assert time.monotonic() - t0 >= 0.05
            async with sess.get(f"{url}/debug/flight?snapshots=1") as r:
                flight = await r.json()
            snaps = flight["snapshot_log"]
            assert len(snaps) == 1
            det = snaps[0]["detail"]
            assert snaps[0]["reason"] == "tail_outlier"
            assert det["injected"] == "stall"
            assert det["kind"] == "decode"
            assert det["bucket"].startswith("b")  # names the padded bucket
            assert det["device_s"] == pytest.approx(0.05)
            for key in ("waiting", "running", "swapped", "kv_occupancy"):
                assert key in det  # queue state rides the snapshot
            # Persisted too (same naming contract as the real recorder).
            assert flight["snapshot_dir"] == str(tmp_path / "snaps")
            on_disk = load_snapshot_dir(str(tmp_path / "snaps"))
            assert len(on_disk) == 1
            assert on_disk[0]["detail"]["bucket"] == det["bucket"]
            # One-shot: the default count=1 disarms after one stall.
            async with sess.post(f"{url}/v1/completions", json={
                "model": MODEL, "prompt": "three", "max_tokens": 4,
            }) as r:
                assert r.status == 200
                await r.read()
            async with sess.get(f"{url}/debug/flight") as r:
                flight2 = await r.json()
            assert len(flight2["snapshot_log"]) == 1
    finally:
        await runner.cleanup()


async def test_forensics_live_collection_from_stalled_engine(tmp_path):
    """The live half of the tentpole: a crossed tail bar harvests the
    engine flight dump + /debug/state + per-series metrics deltas into
    one bundle file; a healthy point costs nothing."""
    app = create_fake_engine_app(model=MODEL, speed=5000)
    runner, url = await _start_site(app)
    loop = __import__("asyncio").get_event_loop()
    try:
        collector = ForensicsCollector(str(tmp_path / "ev"), timeout_s=5.0)
        # Collector fetches are synchronous urllib (bench.py runs it in
        # a plain process); in this in-process test the server shares
        # the loop, so run them on a worker thread.
        baseline = await loop.run_in_executor(
            None, collector.mark, [url]
        )
        assert baseline[url]  # the fake engine serves /metrics
        async with aiohttp.ClientSession() as sess:
            await sess.post(f"{url}/admin/fail", json={
                "mode": "stall", "delay": 0.02,
            })
            async with sess.post(f"{url}/v1/completions", json={
                "model": MODEL, "prompt": "one two", "max_tokens": 4,
            }) as r:
                await r.read()
        # Healthy point: no trigger, no file.
        healthy = await loop.run_in_executor(None, lambda: (
            collector.maybe_collect("tenants", "warm", 100.0, 150.0,
                                    engines=[url], baseline=baseline)
        ))
        assert healthy is None
        assert collector.bundles == []
        path = await loop.run_in_executor(None, lambda: (
            collector.maybe_collect(
                "tenants", "baseline", 100.0, 1000.0,
                engines=[url], baseline=baseline,
                detail={"stall_injected": True},
            )
        ))
        assert path is not None and os.path.exists(path)
        assert os.path.basename(path) == "point_tenants_baseline.json"
        assert collector.bundles == [path]
        with open(path) as f:
            bundle = json.load(f)
        assert bundle["schema"] == BUNDLE_SCHEMA
        assert bundle["trigger"] == "tail_outlier"
        assert bundle["detail"]["p99_ms"] == 1000.0
        assert bundle["detail"]["stall_injected"] is True
        eng = bundle["engines"][url]
        snaps = eng["flight"]["snapshot_log"]
        assert snaps and snaps[0]["detail"]["injected"] == "stall"
        assert "ready" in eng["state"]
        # /debug/requests is best-effort: the fake engine 404s it and
        # the bundle records the error instead of dying.
        assert "error" in eng["worst_traces"][0]
        # The generation moved counters between mark() and collect().
        delta = bundle["metrics_delta"][url]
        assert isinstance(delta, dict) and delta
        assert all(isinstance(v, float) for v in delta.values())
    finally:
        await runner.cleanup()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post_json(url: str, body: dict, timeout: float = 10.0) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read().decode())


def test_forensics_postmortem_from_sigkilled_engine(tmp_path):
    """The after-death path: SIGKILL the engine, then build the bundle
    purely from what it persisted to --flight-snapshot-dir."""
    snap_dir = str(tmp_path / "snaps")
    port = _free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "production_stack_tpu.testing.fake_engine",
         "--port", str(port), "--flight-snapshot-dir", snap_dir],
        cwd=REPO_ROOT, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    url = f"http://127.0.0.1:{port}"
    try:
        deadline = time.monotonic() + 30
        while True:
            try:
                with urllib.request.urlopen(f"{url}/health", timeout=1):
                    break
            except OSError:
                if time.monotonic() > deadline:
                    raise RuntimeError("fake engine never came up")
                time.sleep(0.1)
        _post_json(f"{url}/admin/fail", {"mode": "stall", "delay": 0.05})
        _post_json(f"{url}/v1/completions", {
            "model": MODEL, "prompt": "one two", "max_tokens": 4,
        })
    finally:
        proc.kill()  # SIGKILL: no shutdown hooks, only the persisted files
        proc.wait(timeout=10)

    collector = ForensicsCollector(str(tmp_path / "ev"))
    path = collector.collect_postmortem(
        "engine_flagship", "qps0.5", snapshot_dirs=[snap_dir],
        detail={"trigger": "tail_outlier", "p99_ttft_ms": 120312.5},
    )
    assert path is not None
    with open(path) as f:
        bundle = json.load(f)
    assert bundle["trigger"] == "postmortem"
    snaps = bundle["postmortem_snapshots"]
    assert snaps and snaps[0]["detail"]["injected"] == "stall"
    assert snaps[0]["detail"]["bucket"].startswith("b")
    assert bundle["detail"]["p99_ttft_ms"] == 120312.5
    # An empty dir yields NO bundle — an empty post-mortem is noise.
    assert collector.collect_postmortem(
        "engine_flagship", "qps0.7",
        snapshot_dirs=[str(tmp_path / "nothing")],
    ) is None


# ---------------------------------------------------------------------------
# Verdicts: the claim matrix
# ---------------------------------------------------------------------------


def _passing_round() -> dict:
    return {
        "backend": "tpu",
        "compile_polluted": False,
        "roofline": {"achieved_fraction": 0.93},
        "sweep": [{"qps": 0.5, "p50_ttft_ms": 100.0, "p99_ttft_ms": 180.0}],
        "warm_restart": {"restart_to_ready_seconds": 12.0},
        "stack": {"replicas2": {"p50_delta_vs_single_ms": 1.2}},
        "fleet": {"fleet_hit_rate": 0.95, "churn_hit_rate": 0.92,
                  "rr_hit_rate": 0.40},
        "tenants": {"p99_delta_frac": 0.03, "victim_sheds": 0},
        "cost": {"unpipelined": {"attributed_fraction": 0.98},
                 "overlap": {"attributed_fraction": 1.01}},
        "disagg": {"p99_ttft_disagg_ms": 80.0, "p99_ttft_fused_ms": 150.0,
                   "overlap_fraction": 0.6, "fallbacks": 0,
                   "kvserver_kill": {"hit_rate_delta": 0.01,
                                     "meets_target": True,
                                     "requests_ok": True, "fallbacks": 0}},
        "autoscale": {"absorb_seconds": 4.0, "p99_during_absorb_ms": 180.0,
                      "cold_compiles_on_new_replicas": 0,
                      "failed_during_absorb": 0,
                      "wake_to_first_token_s": 0.4, "meets_target": True},
    }


@pytest.mark.fast
def test_verdicts_all_claims_pass_on_healthy_round():
    v = V.evaluate_round(_passing_round())
    assert v["ok"] is True
    assert v["n_pass"] == len(V.CLAIMS)
    assert v["n_fail"] == 0 and v["n_unevaluable"] == 0


def _set(d: dict, path, value) -> dict:
    node = d
    for key in path[:-1]:
        node = node[key]
    node[path[-1]] = value
    return d


@pytest.mark.fast
@pytest.mark.parametrize("path,value,claim", [
    (("compile_polluted",), True, "compile_polluted"),
    (("warm_restart", "restart_to_ready_seconds"), 45.0,
     "restart_to_ready"),
    (("roofline", "achieved_fraction"), 0.5, "roofline_fraction"),
    (("fleet", "fleet_hit_rate"), 0.3, "fleet_hit_rates"),
    (("stack", "replicas2", "p50_delta_vs_single_ms"), 9.0,
     "replicas2_overhead"),
    (("tenants", "p99_delta_frac"), 0.5, "tenant_isolation"),
    (("disagg", "p99_ttft_disagg_ms"), 200.0, "disagg_ttft"),
    (("cost", "overlap", "attributed_fraction"), 0.5, "cost_attribution"),
    (("disagg", "kvserver_kill", "meets_target"), False,
     "kvserver_kill_hold"),
    (("autoscale", "meets_target"), False, "autoscale_surge_absorb"),
    (("sweep",), [{"qps": 0.5, "p50_ttft_ms": 100.0,
                   "p99_ttft_ms": 1000.0}], "tail_shape"),
])
def test_verdicts_each_claim_fails_on_its_regression(path, value, claim):
    v = V.evaluate_round(_set(_passing_round(), path, value))
    assert v["ok"] is False and v["n_fail"] == 1
    failed = [c["claim"] for c in v["claims"] if c["status"] == "fail"]
    assert failed == [claim]


@pytest.mark.fast
def test_verdicts_missing_phases_are_unevaluable_not_passed():
    v = V.evaluate_round({"backend": "cpu"})
    assert v["n_pass"] == 0 and v["n_fail"] == 0
    assert v["n_unevaluable"] == len(V.CLAIMS)
    assert all(c["status"] == "unevaluable" and c["note"]
               for c in v["claims"])
    # No parseable result at all: ok=False with the provenance error.
    v2 = V.evaluate_round(None, {"error": "no parseable result"})
    assert v2["ok"] is False and v2["n_unevaluable"] == len(V.CLAIMS)


def _killed_capture(n: int) -> dict:
    """A synthetic driver capture of a run killed mid-sweep (rc 124,
    nothing parsed): only the per-point lines in the stderr tail survive,
    one of them with a tail two orders of magnitude over its median."""
    points = [(0.1, 250.0, 380.0), (0.3, 260.0, 400.0),
              (0.5, 300.0, 90_000.0), (0.7, 310.0, 450.0)]
    tail = "".join(
        f"[bench] llama-3-8b: qps {q}: {{'qps': {q}, 'n_requests': 24, "
        f"'p50_ttft_ms': {p50}, 'p99_ttft_ms': {p99}}}\n"
        for q, p50, p99 in points
    )
    return {"n": n, "rc": 124, "parsed": None, "tail": tail}


@pytest.mark.fast
def test_verdicts_flag_killed_run_tail_outlier(tmp_path):
    """A capture of a killed run (rc 124, parsed null) must recover its
    sweep from the tail's dict-literal lines and flag the qps-0.5 point's
    p99 as the tail_shape failure."""
    path = tmp_path / "BENCH_r05.json"
    path.write_text(json.dumps(_killed_capture(5)))
    parsed, meta = V.load_round(str(path))
    assert parsed is not None
    assert meta["rc"] == 124
    assert meta["recovered_from"] == "tail_sweep_lines"
    v = V.evaluate_round(parsed, meta)
    assert v["ok"] is False
    tail = next(c for c in v["claims"] if c["claim"] == "tail_shape")
    assert tail["status"] == "fail"
    outlier_qps = [o["qps"] for o in tail["observed"]]
    assert outlier_qps == [0.5]
    assert tail["observed"][0]["p99_ttft_ms"] == 90_000.0
    # The phases the truncation ate are surfaced, not silently passed.
    assert v["n_unevaluable"] > 0


@pytest.mark.fast
def test_recover_from_tail_prefers_emitted_json():
    tail = (
        "[bench] llama-3-8b: qps 0.5: {'qps': 0.5, 'p50_ttft_ms': 300.0,"
        " 'p99_ttft_ms': 120312.5}\n"
        '{"backend": "tpu", "sweep": []}\n'
    )
    rec = V.recover_from_tail(tail)
    assert rec["backend"] == "tpu"
    assert rec["recovered_from"] == "tail_json"
    # Without an emit line, the per-point dict literals are salvaged.
    rec2 = V.recover_from_tail(tail.splitlines()[0])
    assert rec2["recovered_from"] == "tail_sweep_lines"
    assert rec2["sweep"][0]["p99_ttft_ms"] == 120312.5
    assert V.recover_from_tail('er_s": 4982.8}') is None  # r04: truncated


@pytest.mark.fast
def test_verdicts_trajectory_across_rounds(tmp_path):
    complete = {"backend": "tpu", "flagship": {
        "p50_ttft_ms": 200.0, "p99_ttft_ms": 300.0, "sweep": []}}
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps({"n": 1, "rc": 0, "parsed": complete, "tail": ""}))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(_killed_capture(2)))
    (tmp_path / "BENCH_notes.json").write_text("{}")  # not a round file
    paths = V.round_files(str(tmp_path))
    assert [os.path.basename(p) for p in paths] == [
        "BENCH_r01.json", "BENCH_r02.json"
    ]
    rows = V.trajectory(paths)
    assert [r["round"] for r in rows] == ["BENCH_r01.json", "BENCH_r02.json"]
    assert rows[0]["rc"] == 0 and rows[0]["parsed"] is True
    assert rows[1]["rc"] == 124
    assert rows[1]["recovered_from"] == "tail_sweep_lines"


# ---------------------------------------------------------------------------
# Self-budgeting driver
# ---------------------------------------------------------------------------


@pytest.mark.fast
def test_phase_estimate_prices_next_bringup(monkeypatch):
    monkeypatch.setattr(bench_engine, "_PHASE_WALLS", {})
    assert bench_engine.phase_estimate("flagship", 30.0) == 30.0
    monkeypatch.setattr(
        bench_engine, "_PHASE_WALLS", {"flagship": 148.7}
    )
    # 0.6 x the observed bring-up: a second bring-up started with less
    # than that left never begins.
    assert bench_engine.phase_estimate("warm_restart", 30.0) == \
        pytest.approx(89.22)
    monkeypatch.setattr(bench_engine, "_PHASE_WALLS", {"flagship": 10.0})
    assert bench_engine.phase_estimate("warm_restart", 30.0) == 30.0


def test_bench_engine_exhausted_budget_admits_exactly_one_bringup(
    tmp_path, monkeypatch, capsys
):
    """The r05 re-entry regression: with the budget nearly spent after
    the first model phase, NO further bring-up may start — and the
    final stdout line is still one parseable JSON object."""
    calls = []

    def fake_model_phase(model_name, **kwargs):
        calls.append(model_name)
        time.sleep(1.5)  # spends the wall past the warm-restart floor
        return {"sweep": [{"qps": 8.0, "p50_ttft_ms": 5.0,
                           "p99_ttft_ms": 9.0}],
                "compile_polluted": False}

    def forbidden_restart(*a, **k):
        raise AssertionError("second bring-up started past the budget")

    monkeypatch.setattr(bench_engine, "run_model_phase", fake_model_phase)
    monkeypatch.setattr(bench_engine, "warm_restart_phase",
                        forbidden_restart)
    monkeypatch.setattr(bench_engine, "_PHASE_WALLS", {})
    monkeypatch.setattr(bench_engine, "_BUDGET_T0", time.monotonic())
    monkeypatch.setattr(sys, "argv", ["bench_engine"])
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("PST_BENCH_ENGINE_BUDGET", "31")
    monkeypatch.setenv("PST_BENCH_ENGINE_OUT",
                       str(tmp_path / "partial.json"))
    for var in ("PST_BENCH_SKIP_RESTART", "PST_BENCH_REQUIRE_WARM"):
        monkeypatch.delenv(var, raising=False)
    old_term = signal.getsignal(signal.SIGTERM)
    try:
        bench_engine.main()
    finally:
        signal.signal(signal.SIGTERM, old_term)
    assert calls == ["tiny-llama-debug"]  # exactly one bring-up
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.strip()]
    result = json.loads(lines[-1])
    assert result["backend"] == "cpu"
    assert result["flagship"]["sweep"][0]["qps"] == 8.0
    assert result["warm_restart"]["skipped"] == "time budget exhausted"
    assert result["warm_restart"]["estimate_s"] >= 30.0
    assert result["compile_polluted"] is False
    # The skip was checkpointed too (the rc:124 survival path).
    partial = json.loads((tmp_path / "partial.json").read_text())
    assert partial["warm_restart"]["partial"] is True


def test_bench_engine_zero_budget_skips_every_phase(tmp_path):
    """`--time-budget` smaller than any phase floor: zero bring-ups,
    yet the child still exits 0 with a parseable final JSON."""
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env["PST_BENCH_ENGINE_OUT"] = str(tmp_path / "partial.json")
    env.pop("PST_BENCH_ENGINE_BUDGET", None)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_engine",
         "--time-budget", "5"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=180,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    result = json.loads(lines[-1])
    assert result["backend"] == "cpu"
    assert result["time_budget_s"] == 5.0
    assert result["flagship"]["skipped"] == "time budget exhausted"
    assert result["warm_restart"]["skipped"] == "time budget exhausted"
    assert "skipped" in proc.stderr  # the gate says so out loud


def test_bench_watchdog_force_emits_verdict_bearing_partial(monkeypatch):
    """T−lead with the run still going: the watchdog emits the partial
    (with its verdicts block) and SIGTERMs the main thread."""
    emitted = []
    killed = []
    done = threading.Event()

    def fake_emit(out):
        emitted.append(out)

    def fake_kill(pid, sig):
        killed.append((pid, sig))
        done.set()

    monkeypatch.setattr(bench, "emit", fake_emit)
    monkeypatch.setattr(bench.os, "kill", fake_kill)
    state = {"engine": {"backend": "cpu"}, "stack": None, "fleet": None,
             "tenants": None, "cost": None, "disagg": None}
    budget = bench.TimeBudget(1.0)
    stop = bench.start_watchdog(budget, state, lead=0.5)
    try:
        assert done.wait(10.0), "watchdog never fired"
    finally:
        stop.set()
    assert killed == [(os.getpid(), signal.SIGTERM)]
    assert state["watchdog_fired"] is True
    out = emitted[-1]
    assert out["partial"] is True and out["watchdog_fired"] is True
    assert "claims" in out["verdicts"]  # the forced emit carries verdicts

    # The happy path: setting the stop event BEFORE T−lead means no
    # forced emit and no signal.
    emitted.clear()
    killed.clear()
    stop2 = bench.start_watchdog(bench.TimeBudget(1.0), dict(state),
                                 lead=0.5)
    stop2.set()
    time.sleep(0.8)
    assert emitted == [] and killed == []


def test_bench_finalize_always_carries_verdicts():
    state = {"engine": {"backend": "cpu", "flagship": {
        "p50_ttft_ms": 5.0, "sweep": [],
    }}, "stack": None, "fleet": None, "tenants": None, "cost": None,
        "disagg": None}
    out = bench.finalize(state, {"partial": True})
    assert out["partial"] is True
    assert out["backend"] == "cpu"
    assert isinstance(out["verdicts"]["claims"], list)
    assert out["verdicts"]["n_unevaluable"] > 0  # truncated, says so


def test_bench_stdout_last_line_contract_under_sigalrm(tmp_path):
    """The hard contract: even with a SIGALRM landing mid-run, the last
    stdout line is one complete JSON object bearing the verdicts block
    (and the $PST_BENCH_OUT mirror matches)."""
    env = os.environ.copy()
    for key in ("STACK", "FLEET", "TENANTS", "DISAGG", "COST"):
        env[f"PST_BENCH_SKIP_{key}"] = "1"
    env["PST_BENCH_SKIP_ENGINE"] = "1"  # probe_backend only (still slow
    # enough — a jax-importing child — for the alarm to land mid-phase)
    env["PST_BENCH_OUT"] = str(tmp_path / "out.json")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PST_BENCH_TINY", None)
    proc = subprocess.Popen(
        [sys.executable, "bench.py", "--time-budget", "300"],
        cwd=REPO_ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
    )
    time.sleep(1.2)  # past install_term_trap(), inside the engine probe
    try:
        proc.send_signal(signal.SIGALRM)
    except ProcessLookupError:
        pass  # already exited: the plain-run contract below still holds
    stdout, stderr = proc.communicate(timeout=180)
    assert proc.returncode == 0, stderr[-2000:]
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    final = json.loads(lines[-1])
    assert "verdicts" in final and "claims" in final["verdicts"]
    # Every emitted line upholds the contract, not just the last.
    for ln in lines:
        assert isinstance(json.loads(ln), dict)
    mirror = json.loads((tmp_path / "out.json").read_text())
    assert mirror["verdicts"]["n_fail"] == final["verdicts"]["n_fail"]
