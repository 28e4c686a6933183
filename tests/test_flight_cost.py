"""Flight recorder, per-request cost attribution, capacity signals
(docs/observability.md "Flight recorder" / "Cost attribution" /
"Capacity signals").

- Ring bounds under sustained load (no growth), the stall bar on a
  cycle's wall (``cycle_s``) with the stalled step's bucket, queue state
  and cause in the snapshot, the cause as a pure function of a record,
  compile snapshots, disabled/null behavior; snapshot persistence (naming
  contract, bounded oldest-first disk eviction, restart load-back via
  ``?snapshots=1``) and the fake engine's deterministic stall snapshot.
- Cost attribution parity: request device-seconds sum to the
  device-busy wall in BOTH pipeline modes (overlap shares must not
  double-count), the X-PST-Cost header / usage extension, and the
  per-tenant chip-time split under a flood (the PR 12 harness shape).
- /autoscale/signal: burn-window math against the gen_dashboards
  constants, queue slope, replica-hint transitions, and 2-replica
  gossip agreement on the fleet-derived fields.
"""

import asyncio
import importlib.util
import json
import os
import socket
import time

import aiohttp
import pytest
from aiohttp import web

from production_stack_tpu.engine.async_engine import AsyncLLMEngine
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.engine.server import create_engine_app
from production_stack_tpu.obs.engine_telemetry import (
    ENGINE_TELEMETRY,
    tenant_device_seconds,
)
from production_stack_tpu.obs.flight import (
    CYCLE_FIELDS,
    FIELDS,
    NULL_FLIGHT_RECORDER,
    STALL_CAUSES,
    FlightRecorder,
    load_snapshot_dir,
    stall_cause,
)
from production_stack_tpu.obs.top import render_frame
from production_stack_tpu.router.services import capacity as capacity_mod
from production_stack_tpu.router.services.capacity import (
    BURN_WINDOWS,
    CapacityMonitor,
    PAGE_BURN_RATE,
    SLO_OBJECTIVE,
    compute_signal,
)
from production_stack_tpu.testing.fake_engine import create_fake_engine_app
from tests.router_utils import reset_router_singletons

MODEL = "fake/model"


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


def test_flight_ring_bounds_under_sustained_load():
    """The ring is preallocated and NEVER grows: 10k records into a
    32-slot ring keep exactly 32 resident and the backing list at its
    construction size."""
    rec = FlightRecorder(capacity=32)
    for i in range(10_000):
        rec.record_step("decode", "b8", 0.001, tokens=8)
    stats = rec.stats()
    assert stats["capacity"] == 32
    assert stats["total_steps"] == 10_000
    assert stats["resident"] == 32
    assert len(rec._ring) == 32  # the backing store itself never grew
    rows = rec.records()
    assert len(rows) == 32
    # Chronological: the retained rows are the LAST 32.
    assert all(r["kind"] == "decode" for r in rows)


# A cycle's account with nothing in it, by field (obs/flight.py).
_ACCOUNT = dict.fromkeys(CYCLE_FIELDS[1:], 0.0)


def _cycle(rec, cycle_s, kind="decode", bucket="b8xn4", compiled=False,
           tokens=32, **account):
    """One cycle of one dispatch, which it fetched, through the recorder;
    the stall or None."""
    acc = {**_ACCOUNT, "wait_s": 0.001, **account}
    return rec.record_cycle(
        [(kind, bucket, cycle_s * 0.9, compiled, tokens)], cycle_s,
        tuple(acc[f] for f in CYCLE_FIELDS[1:]), kind=kind)


def test_flight_outlier_snapshot_names_bucket_and_queue_state():
    rec = FlightRecorder(capacity=64)
    state = {"waiting": 3, "running": 7, "swapped": 1,
             "batch_tier_rows": 2, "kv_occupancy": 0.83, "preemptions": 4}
    rec.set_probe(lambda: state)
    # Build the rolling baseline (p50 ~ 30ms, bar = 90ms).
    for _ in range(16):
        assert _cycle(rec, 0.03, wait_s=0.02) is None
    assert rec.snapshots() == []
    # The 120s-style stall: one cycle far past 3x the bucket median, all
    # of it in a wait that polled at its pace for a program that took it.
    stall = _cycle(rec, 1.5, wait_s=1.49, service_s=1.49, polls=4000,
                   poll_gap_max_s=0.0004)
    snaps = rec.snapshots()
    assert len(snaps) == 1
    snap = snaps[0]
    assert snap["reason"] == "tail_outlier" and snap["detail"] == stall
    assert snap["detail"]["kind"] == "decode"
    assert snap["detail"]["bucket"] == "b8xn4"
    assert snap["detail"]["cause"] == "device"
    assert snap["detail"]["phase"] == "wait"
    assert snap["detail"]["cycle_s"] == pytest.approx(1.5)
    assert snap["detail"]["excess_s"] == pytest.approx(1.47)
    assert snap["detail"]["median_s"] == pytest.approx(0.03)
    assert snap["detail"]["bar_s"] == pytest.approx(0.09)
    assert snap["detail"]["polls"] == 4000
    assert snap["detail"]["waiting"] == 3
    assert snap["detail"]["running"] == 7
    assert snap["detail"]["kv_occupancy"] == pytest.approx(0.83)
    # The snapshot's record tail ends with the stalled cycle itself, under
    # the documented fields.
    last = snap["records"][-1]
    assert last["cycle_s"] == pytest.approx(1.5)
    assert last["device_s"] == pytest.approx(1.35)
    assert last["batch_tier_rows"] == 2 and last["host_gap_s"] == 0.0


def test_the_bar_is_on_the_cycle_and_not_on_the_dispatch():
    """One bar: a dispatch whose host-timed wall is long inside a cycle of
    the usual length is no stall, and a long cycle of a short dispatch is."""
    rec = FlightRecorder(capacity=64)
    acc = tuple(_ACCOUNT.values())
    for _ in range(16):
        rec.record_cycle([("decode", "b8", 0.03, False, 8)], 0.03, acc)
    assert rec.record_cycle([("decode", "b8", 2.0, False, 8)], 0.03, acc) is None
    stall = rec.record_cycle([("decode", "b8", 0.001, False, 8)], 2.0, acc)
    assert stall["cause"] == "unknown" and stall["device_s"] == 0.001
    # a cycle that fetched is not held against those that launched and left:
    # a prompt's last chunk waits for the chunks before it
    waited = tuple(dict(_ACCOUNT, wait_s=0.5).values())
    for _ in range(9):
        assert rec.record_cycle(
            [("decode", "b8", 0.5, False, 8)], 0.5, waited) is None
    # a dispatch outside any cycle is held to no bar
    rec.record_step("encode", "t64", 9.0)
    assert rec.records()[-1]["cycle_s"] is None
    assert len(rec.snapshots()) == 1


def test_a_cycle_of_two_dispatches_rides_its_last_and_of_none_its_own():
    rec = FlightRecorder(capacity=64)
    acc = tuple(dict(_ACCOUNT, polls=7).values())
    rec.record_cycle([("decode", "b8xn1", 0.01, False, 8),
                      ("prefill", "b1xt64", 0.02, True, 64)], 0.04, acc)
    first, last = rec.records()[-2:]
    assert first["kind"] == "decode" and first["cycle_s"] is None
    assert last["kind"] == "prefill" and last["cycle_s"] == 0.04
    assert last["compiled"] is True and last["polls"] == 7
    rec.record_cycle([], 0.002, acc, kind="")
    own = rec.records()[-1]
    assert (own["kind"], own["bucket"], own["tokens"]) == ("none", "", 0)
    assert own["cycle_s"] == 0.002 and rec.stats()["total_steps"] == 3


# One record a cause, and the order between them: each row is the account
# of a cycle one second past its median, and what must be read from it.
_CAUSE_CASES = [
    ("compile", dict(compiled=True, gc_s=1.0, wait_s=1.0)),
    ("gc", dict(gc_s=0.5, wait_s=0.9, poll_gap_max_s=0.0004)),
    ("device", dict(wait_s=0.5, service_s=0.5, poll_gap_max_s=0.019,
                    thread_cpu_s=0.6)),
    # service time long: the program the cycle fetched took the excess itself
    ("device", dict(wait_s=1.0, service_s=0.98, poll_gap_max_s=0.0011)),
    # a wait that polled at its pace for a program that ran its usual time:
    # whatever the thread waited for, the device did not stand still
    ("unknown", dict(wait_s=0.9, service_s=0.017, poll_gap_max_s=0.0011)),
    ("machine", dict(wait_s=0.9, poll_gap_max_s=0.9, process_cpu_s=0.1)),
    ("machine", dict(postprocess_s=0.9, offcpu_s=0.5, process_cpu_s=0.48)),
    ("interpreter", dict(wait_s=0.9, poll_gap_max_s=0.8, process_cpu_s=0.9)),
    ("interpreter", dict(launch_s=0.9, offcpu_s=0.6, process_cpu_s=0.52,
                         thread_cpu_s=0.9)),
    # the process's clock within a tick of the line between the two: neither
    ("unknown", dict(wait_s=0.9, poll_gap_max_s=0.9, process_cpu_s=0.495)),
    ("unknown", dict(launch_s=0.9, offcpu_s=0.9, process_cpu_s=0.505)),
    ("host_work", dict(postprocess_s=1.0, thread_cpu_s=0.5,
                       process_cpu_s=0.6, gc_s=0.49, offcpu_s=0.49)),
    ("host_work", dict(wait_s=0.49, thread_cpu_s=0.9)),
    ("unknown", dict(intake_s=1.0, gc_s=0.4, wait_s=0.4, offcpu_s=0.4,
                     thread_cpu_s=0.4, poll_gap_max_s=0.4)),
]


@pytest.mark.parametrize("cause,account", _CAUSE_CASES,
                         ids=[f"{i}-{c}" for i, (c, _) in enumerate(_CAUSE_CASES)])
def test_stall_cause_is_a_pure_function_of_the_record(cause, account):
    rec = {"compiled": False, **_ACCOUNT, **account}
    assert stall_cause(rec, 1.0) == cause
    assert cause in STALL_CAUSES
    # through the recorder: the same record, the same name
    fr = FlightRecorder(capacity=32)
    for _ in range(8):
        _cycle(fr, 0.03)
    account = dict(account)
    compiled = account.pop("compiled", False)
    stall = _cycle(fr, 1.03, compiled=compiled, **account)
    assert stall["cause"] == cause
    assert fr.snapshots()[-1]["reason"] == (
        "compile" if compiled else "tail_outlier")


def test_queued_behind_a_long_program_is_no_stall():
    """A chained 17 ms decode step launched behind a 45 ms prefill program
    waits that program out: 62 ms a cycle at a median of 17, and nothing
    stalled. The bar is held to the cycle's wall less what the fetched
    program stood behind others inside it; the same wall with nothing
    queued is a stall of the device, and a record from before the clock
    (no ``service_s``) is read by its wait as it was."""
    rec = FlightRecorder(capacity=64)
    for _ in range(16):
        assert _cycle(rec, 0.017, bucket="b64xn1", wait_s=0.015,
                      service_s=0.017) is None
    assert _cycle(rec, 0.062, bucket="b64xn1", wait_s=0.060, service_s=0.017,
                  queued_s=0.045) is None
    assert rec.snapshots() == []
    # the median the next cycle is held to did not move either
    stall = _cycle(rec, 0.062, bucket="b64xn1", wait_s=0.060, service_s=0.062)
    assert stall["cause"] == "device" and stall["median_s"] == 0.017
    assert stall["excess_s"] == pytest.approx(0.045)
    assert (stall["service_s"], stall["queued_s"]) == (0.062, 0.0)
    old = {k: v for k, v in stall.items() if k != "service_s"}
    assert stall_cause(old, stall["excess_s"]) == "device"


def test_flight_outlier_bar_floors_small_steps():
    """3x a 2ms CPU cycle is noise: the 50ms floor keeps it silent, and a
    bucket of fewer than eight cycles has no bar yet."""
    rec = FlightRecorder(capacity=64)
    for _ in range(16):
        _cycle(rec, 0.002, bucket="b4")
    assert _cycle(rec, 0.02, bucket="b4") is None  # 10x the median
    for _ in range(7):
        _cycle(rec, 0.002, bucket="b2")
    assert _cycle(rec, 5.0, bucket="b2") is None
    assert rec.snapshots() == []


def test_flight_compile_snapshot_and_no_baseline_pollution():
    rec = FlightRecorder(capacity=64)
    # A live compile above the floor snapshots with reason "compile"...
    stall = _cycle(rec, 0.8, "prefill", "b1xt512", compiled=True)
    assert stall["cause"] == "compile" and stall["median_s"] is None
    assert stall["excess_s"] == pytest.approx(0.8)
    snaps = rec.snapshots()
    assert [s["reason"] for s in snaps] == ["compile"]
    # ...and never seeds the steady-state median (the next normal cycles
    # would otherwise need to be 3x the COMPILE wall to flag).
    for _ in range(16):
        _cycle(rec, 0.01, "prefill", "b1xt512")
    _cycle(rec, 0.2, "prefill", "b1xt512")
    assert [s["reason"] for s in rec.snapshots()] == [
        "compile", "tail_outlier"
    ]


def test_flight_window_and_n_filters():
    rec = FlightRecorder(capacity=16)
    for i in range(8):
        rec.record_step("decode", "b2", 0.001)
    assert len(rec.records(n=3)) == 3
    assert rec.records(window_s=60.0)  # everything is recent
    assert rec.records(window_s=1e-9) == []
    payload = rec.to_payload(n=2)
    assert set(payload) >= {"capacity", "records", "snapshot_log", "fields"}
    assert len(payload["records"]) == 2


def test_null_recorder_is_free():
    NULL_FLIGHT_RECORDER.record_step("decode", "b8", 1e9)
    assert _cycle(NULL_FLIGHT_RECORDER, 1e9) is None
    assert NULL_FLIGHT_RECORDER.records() == []
    assert NULL_FLIGHT_RECORDER.stats()["capacity"] == 0


def test_probe_failure_never_kills_the_step():
    rec = FlightRecorder(capacity=8)

    def bad_probe():
        raise RuntimeError("scheduler went away")

    rec.set_probe(bad_probe)
    rec.record_step("decode", "b2", 0.001)
    assert rec.records()[-1]["waiting"] == 0


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
# Cost attribution (in-process engine, CPU)
# ---------------------------------------------------------------------------


def _tiny_cfg(**over):
    kw = dict(
        model="tiny-llama-debug",
        max_model_len=256,
        block_size=16,
        num_kv_blocks=128,
        max_num_seqs=8,
        cost_attribution=True,
    )
    kw.update(over)
    return EngineConfig(**kw)


def _drive_mixed(eng, tag):
    """Mixed two-tenant workload; returns {rid: (tenant, cost)}."""
    tenants = {}
    for i in range(4):
        rid = f"{tag}-a{i}"
        eng.add_request(rid, prompt=f"question {i}",
                        sampling=SamplingParams(max_tokens=4, temperature=0.0),
                        tenant="acme", tenant_class="interactive")
        tenants[rid] = "acme"
    for i in range(3):
        rid = f"{tag}-b{i}"
        eng.add_request(rid, prompt=f"batch {i} " * (2 * i + 3),
                        sampling=SamplingParams(max_tokens=14, temperature=0.0),
                        tenant="batchcorp", tenant_class="batch")
        tenants[rid] = "batchcorp"
    costs = {}
    while eng.has_work():
        for out in eng.step():
            if out.finished and out.cost is not None:
                costs[out.request_id] = (tenants[out.request_id], out.cost)
    return costs


@pytest.mark.parametrize("overlap", [False, True],
                         ids=["unpipelined", "overlap"])
def test_cost_attribution_parity_vs_device_busy(overlap):
    """Finished requests' device-seconds sum to the device-busy wall
    within 10% in BOTH pipeline modes — overlap shares must neither drop
    wall segments nor double-count them."""
    ENGINE_TELEMETRY.reset_for_tests()
    eng = LLMEngine(_tiny_cfg(
        overlap_decode=overlap,
        num_decode_steps=4 if overlap else 1,
    ))
    _drive_mixed(eng, "warm")  # absorb compiles
    busy0 = ENGINE_TELEMETRY.device_busy_seconds()
    costs = _drive_mixed(eng, "run")
    busy = ENGINE_TELEMETRY.device_busy_seconds() - busy0
    assert len(costs) == 7
    attributed = sum(c["device_s"] for _, c in costs.values())
    assert busy > 0
    frac = attributed / busy
    assert 0.9 <= frac <= 1.1, (
        f"attributed {attributed:.4f}s vs busy {busy:.4f}s "
        f"(fraction {frac:.3f})"
    )
    # Cost payload shape: every field the header contract names.
    for _, c in costs.values():
        assert set(c) == {"prefill_device_s", "decode_device_s",
                          "device_s", "kv_page_s", "queue_s"}
        # Each field rounds to 6 decimals independently: allow the
        # worst-case 1.5 ulp of that rounding.
        assert c["device_s"] == pytest.approx(
            c["prefill_device_s"] + c["decode_device_s"], abs=2e-6
        )
        assert c["kv_page_s"] >= 0


def test_tenant_device_seconds_split_under_flood():
    """The PR 12 flood shape, billed in chip time: a flooding batch
    tenant with ~4x the decode tokens must be billed more device-seconds
    than the interactive victim — and the pst_tenant_device_seconds
    counter must agree with the per-request sums."""
    ENGINE_TELEMETRY.reset_for_tests()
    eng = LLMEngine(_tiny_cfg(tenant_fairness=True))
    _drive_mixed(eng, "warm")

    def counter_value(tenant):
        return tenant_device_seconds.labels(tenant=tenant)._value.get()

    v0 = {t: counter_value(t) for t in ("victim", "flooder")}
    tenants = {}
    for i in range(8):
        rid = f"fl-{i}"
        eng.add_request(rid, prompt=f"flood job {i} " * 4,
                        sampling=SamplingParams(max_tokens=16,
                                                temperature=0.0),
                        tenant="flooder", tenant_class="batch")
        tenants[rid] = "flooder"
    for i in range(4):
        rid = f"vi-{i}"
        eng.add_request(rid, prompt=f"victim {i}",
                        sampling=SamplingParams(max_tokens=4,
                                                temperature=0.0),
                        tenant="victim", tenant_class="interactive")
        tenants[rid] = "victim"
    sums = {"victim": 0.0, "flooder": 0.0}
    while eng.has_work():
        for out in eng.step():
            if out.finished and out.cost is not None:
                sums[tenants[out.request_id]] += out.cost["device_s"]
    assert sums["flooder"] > sums["victim"] > 0
    # The Prometheus meter moved by the per-request sums (the header
    # payload rounds to microseconds; the counter keeps full precision).
    for t in ("victim", "flooder"):
        assert counter_value(t) - v0[t] == pytest.approx(sums[t], abs=1e-4)


def test_cost_attribution_off_is_free():
    ENGINE_TELEMETRY.reset_for_tests()
    eng = LLMEngine(_tiny_cfg(cost_attribution=False))
    eng.add_request("r0", prompt="hello",
                    sampling=SamplingParams(max_tokens=4, temperature=0.0))
    finished = []
    while eng.has_work():
        for out in eng.step():
            if out.finished:
                finished.append(out)
    assert finished and finished[0].cost is None


def test_abort_still_bills_consumed_device_time():
    ENGINE_TELEMETRY.reset_for_tests()
    eng = LLMEngine(_tiny_cfg())

    def counter_value():
        return tenant_device_seconds.labels(tenant="aborter")._value.get()

    v0 = counter_value()
    eng.add_request("ab-1", prompt="work then abort",
                    sampling=SamplingParams(max_tokens=64, temperature=0.0),
                    tenant="aborter", tenant_class="interactive")
    for _ in range(3):
        eng.step()
    eng.abort_request("ab-1")
    assert counter_value() > v0


# ---------------------------------------------------------------------------
# Engine HTTP surface: /debug/flight + X-PST-Cost
# ---------------------------------------------------------------------------


class EngineServer:
    def __init__(self, **cfg_over):
        self.cfg = _tiny_cfg(max_prefill_tokens=64, **cfg_over)
        self.url = None

    async def __aenter__(self):
        ENGINE_TELEMETRY.reset_for_tests()
        self.engine = AsyncLLMEngine(self.cfg)
        app = create_engine_app(self.engine)
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


async def test_engine_debug_flight_and_cost_header():
    async with EngineServer() as server, aiohttp.ClientSession() as sess:
        payload = {"model": "tiny-llama-debug", "prompt": "hello world",
                   "max_tokens": 6, "temperature": 0.0}
        async with sess.post(f"{server.url}/v1/completions",
                             json=payload) as r:
            assert r.status == 200
            body = await r.json()
            # X-PST-Cost header and the usage extension carry one payload.
            cost = json.loads(r.headers["X-PST-Cost"])
            assert cost == body["usage"]["pst_cost"]
            assert cost["device_s"] > 0
            assert cost["device_s"] == pytest.approx(
                cost["prefill_device_s"] + cost["decode_device_s"], abs=2e-6
            )
        # The flight ring recorded the steps that served it.
        async with sess.get(f"{server.url}/debug/flight") as r:
            assert r.status == 200
            flight = await r.json()
        assert flight["total_steps"] > 0
        assert flight["records"]
        last = flight["records"][-1]
        assert {"kind", "bucket", "device_s", "waiting", "running",
                "kv_occupancy", "cycle_s", "wait_s", "offcpu_s",
                "process_cpu_s", "gc_s", "polls"} <= set(last)
        assert flight["fields"] == list(last)
        assert last["cycle_s"] > 0 and last["polls"] >= 0

        # Induced 120s-style stall: a cycle far past its bucket's rolling
        # median -> the ring auto-snapshots naming the stalled step's
        # bucket, queue state and cause, visible at GET /debug/flight
        # without any operator action.
        rec = server.engine.engine.flight
        await asyncio.sleep(0.1)  # the burst still in flight is drained
        for _ in range(12):
            _cycle(rec, 0.03, bucket="b8")
        _cycle(rec, 2.0, bucket="b8", wait_s=1.9, service_s=1.9,
               poll_gap_max_s=0.0004)
        async with sess.get(f"{server.url}/debug/flight?n=4") as r:
            flight = await r.json()
        assert len(flight["records"]) == 4
        snaps = [s for s in flight["snapshot_log"]
                 if s["reason"] == "tail_outlier"]
        assert snaps, "the induced stall left no snapshot"
        assert snaps[-1]["detail"]["bucket"] == "b8"
        assert snaps[-1]["detail"]["cause"] == "device"
        assert "waiting" in snaps[-1]["detail"]
        # /debug/state carries the ring stats for /debug/fleet cross-check.
        async with sess.get(f"{server.url}/debug/state") as r:
            state = await r.json()
        assert state["flight"]["total_steps"] == flight["total_steps"]


async def test_engine_streaming_cost_in_usage_chunk():
    async with EngineServer() as server, aiohttp.ClientSession() as sess:
        payload = {"model": "tiny-llama-debug", "prompt": "stream me",
                   "max_tokens": 4, "temperature": 0.0, "stream": True,
                   "stream_options": {"include_usage": True}}
        usages = []
        async with sess.post(f"{server.url}/v1/completions",
                             json=payload) as r:
            assert r.status == 200
            async for line in r.content:
                line = line.decode().strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                obj = json.loads(line[6:])
                if obj.get("usage"):
                    usages.append(obj["usage"])
        assert usages and "pst_cost" in usages[-1]
        assert usages[-1]["pst_cost"]["device_s"] > 0


# ---------------------------------------------------------------------------
# Fake engine determinism
# ---------------------------------------------------------------------------


async def _start_site(app, port=0):
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", port)
    await site.start()
    bound = site._server.sockets[0].getsockname()[1]
    return runner, f"http://127.0.0.1:{bound}"


async def test_fake_engine_flight_and_cost_deterministic():
    app = create_fake_engine_app(model=MODEL, speed=5000)
    runner, url = await _start_site(app)
    try:
        async with aiohttp.ClientSession() as sess:
            async with sess.post(
                f"{url}/v1/completions",
                json={"model": MODEL, "prompt": "one two three",
                      "max_tokens": 5},
            ) as r:
                assert r.status == 200
                cost = json.loads(r.headers["X-PST-Cost"])
                body = await r.json()
            # prompt_tokens=3, n=5: values are pure functions of counts.
            assert cost["prefill_device_s"] == pytest.approx(3e-4)
            assert cost["decode_device_s"] == pytest.approx(5e-3)
            assert body["usage"]["pst_cost"] == cost
            async with sess.get(f"{url}/debug/flight") as r:
                flight = await r.json()
            assert flight["total_steps"] == 2  # one prefill + one decode
            kinds = [rec["kind"] for rec in flight["records"]]
            assert kinds == ["prefill", "decode"]
            assert flight["records"][0]["bucket"] == "b1xt3"
            assert flight["records"][1]["tokens"] == 5
            # the real recorder's fields, the cycle's account among them
            assert flight["fields"] == list(FIELDS)
            assert all(set(r) == set(FIELDS) for r in flight["records"])
            decode = flight["records"][1]
            assert decode["cycle_s"] == pytest.approx(5e-3 + 5e-4)
            assert (decode["wait_s"], decode["polls"]) == (5e-3, 16)
            # Streams carry the header too (the fake knows its output
            # upfront).
            async with sess.post(
                f"{url}/v1/completions",
                json={"model": MODEL, "prompt": "s", "max_tokens": 2,
                      "stream": True},
            ) as r:
                assert "X-PST-Cost" in r.headers
                await r.read()
    finally:
        await runner.cleanup()


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
            # ... and the cause: a wait that polled at its pace
            assert (det["cause"], det["phase"]) == ("device", "wait")
            assert det["excess_s"] == pytest.approx(0.0505 - 0.004)
            assert stall_cause(det, det["excess_s"]) == "device"
            async with sess.get(f"{url}/metrics") as r:
                text = await r.text()
            assert 'pst_engine_stalls_total{cause="device"} 1' in text
            assert ('pst_engine_stall_seconds_total{cause="device"} 0.0465'
                    in text)
            for cause in STALL_CAUSES:  # every cause, stalled or not
                assert f'pst_engine_stalls_total{{cause="{cause}"}}' in text
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


# ---------------------------------------------------------------------------
# Capacity signals
# ---------------------------------------------------------------------------


def _load_gen_dashboards():
    spec = importlib.util.spec_from_file_location(
        "gen_dashboards_under_test", "observability/gen_dashboards.py"
    )
    mod = importlib.util.module_from_spec(spec)
    # Import executes only module-level defs + constants; generation
    # happens under __main__.
    spec.loader.exec_module(mod)
    return mod


def test_capacity_constants_match_gen_dashboards():
    """The in-process burn windows/objective must be the PR 5 constants
    the Prometheus rules are generated from — one reality, two renderers."""
    gd = _load_gen_dashboards()
    assert SLO_OBJECTIVE == gd.SLO_OBJECTIVE
    assert capacity_mod.SLO_ERROR_BUDGET == gd.SLO_ERROR_BUDGET
    # Same window set the recording rules cover.
    rules = open("observability/prometheus-rules.yaml").read()
    for label, _seconds in BURN_WINDOWS:
        assert f"ratio_rate{label}" in rules
    assert PAGE_BURN_RATE == 14.4


def test_burn_rates_windowed():
    mon = CapacityMonitor()
    now = time.time()
    # 40 failures 10 minutes ago: outside 5m, inside 30m+.
    for _ in range(40):
        mon.observe(False, now=now - 600)
    # 60 successes just now: the 5m window is clean.
    for _ in range(60):
        mon.observe(True, now=now)
    rates = mon.burn_rates(now=now)
    assert rates["5m"] == 0.0
    # 30m window: 40 errors / 100 requests = 0.4 ratio / 0.01 budget.
    assert rates["30m"] == pytest.approx(40.0, rel=0.01)
    assert rates["1h"] == rates["30m"]
    # An empty window burns nothing (idle fleets never page).
    assert CapacityMonitor().burn_rates()["3d"] == 0.0


def test_queue_slope_fit():
    mon = CapacityMonitor()
    t0 = time.time()
    for i in range(10):
        mon.sample_queue_depth(2 * i, now=t0 + i)  # +2 req/s
    assert mon.queue_slope() == pytest.approx(2.0, rel=0.05)
    mon2 = CapacityMonitor()
    for i in range(10):
        mon2.sample_queue_depth(5, now=t0 + i)
    assert mon2.queue_slope() == pytest.approx(0.0, abs=1e-6)


def test_signal_replica_hint_rises_on_page_burn():
    """Page-level burn must raise the hint even with no fleet context
    (bare scope: 0 engines discovered -> current floor 1)."""
    mon = CapacityMonitor()
    base = compute_signal(mon, None)
    assert base["replica_hint"] >= 1
    assert base["page_burning"] is False
    for _ in range(50):
        mon.observe(False)
    burned = compute_signal(mon, None)
    assert burned["burn_rates"]["5m"] >= PAGE_BURN_RATE
    assert burned["page_burning"] is True
    assert burned["replica_hint"] > base["replica_hint"]


def test_render_frame_capacity_pane():
    snap = {"replica": "r0", "replicas": {"r0": {"self": True}},
            "engines": {}, "routing": {}, "tenants": {}, "synced": True}
    signal = {"saturation": 0.61, "burn_rates": {"5m": 20.0, "1h": 3.5,
                                                 "6h": 0.1},
              "page_burning": True, "queue_depth": 9,
              "queue_depth_slope_per_s": 1.25, "kv_headroom": 0.4,
              "engines_ready": 3, "replica_hint": 5}
    frame = render_frame(snap, color=False, signal=signal)
    assert "capacity" in frame
    assert "hint=5" in frame
    assert "burn(5m/1h/6h)=20.00/3.50/0.10" in frame
    # Without a signal the pane is simply absent (old routers).
    assert "capacity" not in render_frame(snap, color=False)


# ---------------------------------------------------------------------------
# /autoscale/signal over HTTP: 2-replica gossip agreement
# ---------------------------------------------------------------------------


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


async def test_autoscale_signal_two_replica_agreement():
    """Both gossip replicas must serve the same fleet-derived signal
    fields (engines_ready, kv headroom, membership) — the inputs ride
    the gossip-merged fleet snapshot, so KEDA can scrape any replica."""
    from production_stack_tpu.router.app import create_app
    from production_stack_tpu.router.parser import parse_args

    engine_app = create_fake_engine_app(model=MODEL, speed=5000)
    engine_runner, engine_url = await _start_site(engine_app)
    ports = [_free_port(), _free_port()]
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    runners = []
    try:
        for i, port in enumerate(ports):
            app = create_app(parse_args([
                "--service-discovery", "static",
                "--static-backends", engine_url,
                "--static-models", MODEL,
                "--engine-stats-interval", "0.2",
                "--slo-ttft-ms", "200",
                "--state-backend", "gossip",
                "--state-peers",
                ",".join(u for j, u in enumerate(urls) if j != i),
                "--state-sync-interval", "0.1",
                "--state-peer-timeout", "1.0",
                "--state-replica-id", f"r{i}",
            ]))
            runner, _ = await _start_site(app, port)
            runners.append(runner)
        await asyncio.sleep(0.6)  # gossip convergence + one stats scrape
        async with aiohttp.ClientSession() as sess:
            for i in range(3):
                async with sess.post(
                    f"{urls[0]}/v1/completions",
                    json={"model": MODEL, "prompt": f"p{i}",
                          "max_tokens": 2},
                ) as resp:
                    assert resp.status == 200
                    await resp.read()
            await asyncio.sleep(0.4)
            signals = []
            for url in urls:
                async with sess.get(f"{url}/autoscale/signal") as resp:
                    assert resp.status == 200
                    signals.append(await resp.json())
        for sig in signals:
            assert sig["engines_total"] == 1
            assert sig["engines_ready"] == 1
            assert sig["replicas"] == 2  # both replicas see both replicas
            assert 0.0 <= sig["kv_headroom"] <= 1.0
            assert set(sig["burn_rates"]) == {w for w, _ in BURN_WINDOWS}
        # Fleet-derived fields agree across replicas (same merged view).
        keys = ("engines_total", "engines_ready", "replicas",
                "kv_occupancy_max")
        assert {k: signals[0][k] for k in keys} == \
            {k: signals[1][k] for k in keys}
    finally:
        await engine_runner.cleanup()
        for runner in reversed(runners):
            await runner.cleanup()
        reset_router_singletons()


async def test_autoscale_signal_hint_converges_on_disagreement():
    """The operator's max-merge depends on replicas NOT disagreeing for
    long: burn/queue evidence is replica-local (only the replica that
    proxied a slow request burns budget), so when one replica alone
    observes page-level burn, the other must still serve the same
    elevated ``replica_hint`` within one gossip sync interval — the
    evidence rides the fleet snapshot and compute_signal max-merges it."""
    from production_stack_tpu.router.app import create_app
    from production_stack_tpu.router.parser import parse_args

    engine_app = create_fake_engine_app(model=MODEL, speed=5000)
    engine_runner, engine_url = await _start_site(engine_app)
    ports = [_free_port(), _free_port()]
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    runners, apps = [], []
    try:
        for i, port in enumerate(ports):
            app = create_app(parse_args([
                "--service-discovery", "static",
                "--static-backends", engine_url,
                "--static-models", MODEL,
                "--engine-stats-interval", "0.2",
                "--slo-ttft-ms", "200",
                "--state-backend", "gossip",
                "--state-peers",
                ",".join(u for j, u in enumerate(urls) if j != i),
                "--state-sync-interval", "0.1",
                "--state-peer-timeout", "1.0",
                "--state-replica-id", f"r{i}",
            ]))
            runner, _ = await _start_site(app, port)
            runners.append(runner)
            apps.append(app)
        await asyncio.sleep(0.6)  # membership + first snapshot exchange

        async with aiohttp.ClientSession() as sess:
            # Baseline: both replicas idle, hints agree.
            base = []
            for url in urls:
                async with sess.get(f"{url}/autoscale/signal") as resp:
                    assert resp.status == 200
                    base.append(await resp.json())
            assert base[0]["replica_hint"] == base[1]["replica_hint"]

            # Disagreement: ONLY replica 0 observes page-level burn
            # (50 blown-TTFT events into ITS monitor; replica 1's
            # windows stay clean).
            for _ in range(50):
                apps[0]["capacity_monitor"].observe(False)
            local = compute_signal(apps[0]["capacity_monitor"], apps[0])
            assert local["page_burning"] is True

            # Within one sync interval the evidence gossips across and
            # replica 1 — which saw zero bad requests — serves the same
            # page-burning verdict and the same elevated hint.
            deadline = time.time() + 5.0
            signals = []
            while time.time() < deadline:
                await asyncio.sleep(0.15)
                signals = []
                for url in urls:
                    async with sess.get(f"{url}/autoscale/signal") as resp:
                        assert resp.status == 200
                        signals.append(await resp.json())
                if (signals[1]["page_burning"]
                        and signals[0]["replica_hint"]
                        == signals[1]["replica_hint"]):
                    break
            assert signals[1]["page_burning"] is True, signals[1]
            assert signals[1]["evidence_replicas"] == 2
            assert signals[0]["replica_hint"] == signals[1]["replica_hint"]
            assert signals[1]["replica_hint"] > base[1]["replica_hint"]
    finally:
        await engine_runner.cleanup()
        for runner in reversed(runners):
            await runner.cleanup()
        reset_router_singletons()


async def test_autoscale_signal_404_when_disabled():
    from production_stack_tpu.router.app import create_app
    from production_stack_tpu.router.parser import parse_args

    engine_app = create_fake_engine_app(model=MODEL, speed=5000)
    engine_runner, engine_url = await _start_site(engine_app)
    try:
        app = create_app(parse_args([
            "--service-discovery", "static",
            "--static-backends", engine_url,
            "--static-models", MODEL,
            "--no-capacity-signal",
        ]))
        runner, url = await _start_site(app)
        try:
            async with aiohttp.ClientSession() as sess:
                async with sess.get(f"{url}/autoscale/signal") as resp:
                    assert resp.status == 404
        finally:
            await runner.cleanup()
    finally:
        await engine_runner.cleanup()
        reset_router_singletons()
