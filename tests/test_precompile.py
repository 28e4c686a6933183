"""Warmup precompilation + persistent compile cache (engine/precompile.py).

The acceptance spine of the subsystem, on the CPU test model:

- lattice enumeration is provably complete: after a ``full`` warmup, a
  scripted traffic mix spanning prefill / decode / burst / spec / encode
  bucket shapes increments ``pst_engine_compile_total`` by **zero**;
- a warm restart against a populated persistent cache reaches ready with
  zero fresh XLA compiles and a strictly smaller precompile phase;
- ``/ready`` gates on warmup completion (warming → 503, done → 200) while
  ``/health`` stays green (liveness != readiness);
- the fake engine simulates the same story hermetically for router tests.
"""

import asyncio
import os
import threading
import time

import aiohttp
import pytest
from aiohttp import web

from production_stack_tpu.engine.async_engine import AsyncLLMEngine
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.precompile import (
    Bucket,
    Precompiler,
    compile_cache_key,
    decode_row_buckets,
    enumerate_lattice,
    lazy_core,
    prefill_shape_buckets,
    table_width_buckets,
)
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.engine.server import create_engine_app
from production_stack_tpu.models.registry import get_model_config
from production_stack_tpu.obs import ENGINE_TELEMETRY, ENGINE_TELEMETRY_REGISTRY

# Tiny but complete: two decode row buckets, one table bucket, four
# prefill chunk buckets, a 2-step burst — small enough that a full
# precompile stays in CI budget, rich enough to exercise every kind.
# The session's compile cache (tests/conftest.py). The tests below that place
# a cache of their own take the variable out first: it wins over the flag.
SESSION_CACHE_DIR = os.environ.get("JAX_COMPILATION_CACHE_DIR")

TINY = dict(
    model="tiny-llama-debug",
    max_model_len=64,
    block_size=16,
    num_kv_blocks=16,
    max_num_seqs=2,
    max_prefill_tokens=8,
    num_decode_steps=2,
    attn_impl="gather",
)


def _gauge(name: str, **labels) -> float:
    value = ENGINE_TELEMETRY_REGISTRY.get_sample_value(name, labels or None)
    assert value is not None, name
    return value


def _kind_compiles(kind: str) -> float:
    """Sum of pst_engine_compile_total over all shape buckets of ``kind``."""
    total = 0.0
    for metric in ENGINE_TELEMETRY_REGISTRY.collect():
        if metric.name == "pst_engine_compile":
            for s in metric.samples:
                if s.name.endswith("_total") and s.labels.get("kind") == kind:
                    total += s.value
    return total


# ----------------------------------------------------------------------
# Lattice enumeration (pure config)
# ----------------------------------------------------------------------


def test_lattice_enumerates_expected_buckets():
    cfg = EngineConfig(**TINY)
    assert decode_row_buckets(cfg) == [1, 2]
    # max_table_width = 64/16 = 4; the 64-wide floor clamps to the cap.
    assert table_width_buckets(cfg) == [4]
    pairs = prefill_shape_buckets(cfg)
    assert (1, 8) in pairs and (2, 8) in pairs and (1, 1) in pairs
    lattice = enumerate_lattice(cfg)
    labels = {(b.kind, b.label) for b in lattice}
    assert ("decode", "b1") in labels and ("decode", "b2") in labels
    assert ("decode_burst", "b1xn2") in labels
    assert ("decode_burst", "b2xn2") in labels
    # Penalized burst variants are enumerated (scheduler no longer clamps
    # penalty rows to n=1, so their executable must be warmable).
    assert any(
        b.kind == "decode_burst" and b.penalized for b in lattice
    )
    assert not any(
        b.kind != "decode_burst" and b.penalized for b in lattice
    )
    assert ("prefill", "b1xt8") in labels and ("prefill", "b2xt4") in labels
    assert ("encode", "t64") in labels
    # No spec shapes without speculative_ngram.
    assert not any(b.kind == "spec_verify" for b in lattice)
    # Both static-flag variants (greedy and sampled) for decode/prefill.
    assert any(b.kind == "decode" and not b.greedy for b in lattice)
    assert any(b.kind == "prefill" and b.greedy for b in lattice)


def test_lattice_respects_min_decode_bucket_and_spec():
    cfg = EngineConfig(**dict(TINY, min_decode_bucket=2, speculative_ngram=2,
                              num_decode_steps=1))
    assert decode_row_buckets(cfg) == [2]
    lattice = enumerate_lattice(cfg)
    assert any(
        b.kind == "spec_verify" and b.label == "b2xk2" for b in lattice
    )
    # Spec engines: overlap defers to speculation (engine._pipeline_ok),
    # so no depth-1 burst shapes are promised for them.
    assert not any(b.kind == "decode_burst" for b in lattice)
    # Default overlap_decode (no spec) pipelines through the multi-step
    # executable even at depth 1: b{B}xn1 must be enumerated or the first
    # pipelined burst would be a live-traffic compile.
    ov = EngineConfig(**dict(TINY, min_decode_bucket=2, num_decode_steps=1))
    assert any(
        b.kind == "decode_burst" and b.label == "b2xn1"
        for b in enumerate_lattice(ov)
    )
    # With every pipelining mode off, num_decode_steps=1 → no burst shapes.
    off = EngineConfig(**dict(TINY, num_decode_steps=1,
                              overlap_decode=False))
    assert not any(
        b.kind == "decode_burst" for b in enumerate_lattice(off)
    )


def test_prefill_pairs_respect_token_budget():
    cfg = EngineConfig(**dict(TINY, max_num_seqs=64, max_prefill_tokens=8))
    pairs = prefill_shape_buckets(cfg)
    # An 8-row batch needs ≥ 8 real tokens minimum — with the longest
    # chunk bucketing to 8 (min real 5), 7+5 > 8 is infeasible.
    assert (8, 8) not in pairs
    assert (8, 1) in pairs  # 8 one-token chunks fit exactly


def test_bucket_budget_and_lazy_selection():
    cfg = EngineConfig(**TINY)
    lattice = enumerate_lattice(cfg)
    pc = Precompiler(None, cfg, mode="full", bucket_budget=3)
    assert len(pc.select(lattice)) == 3
    # Budget walks most-likely-first: decode shapes lead.
    assert all(b.kind == "decode" for b in pc.select(lattice)[:2])
    core = lazy_core(lattice, cfg)
    assert 0 < len(core) <= 8
    assert all(b.greedy and not b.want_lp for b in core)
    assert Precompiler(None, cfg, mode="off").select(lattice) == []
    with pytest.raises(ValueError):
        Precompiler(None, cfg, mode="sometimes")


def test_compile_cache_key_stability():
    cfg = EngineConfig(**TINY)
    model_cfg = get_model_config(cfg.model)
    assert compile_cache_key(cfg, model_cfg) == compile_cache_key(
        EngineConfig(**TINY), model_cfg
    )
    # Anything that changes the compiled programs changes the key.
    assert compile_cache_key(
        EngineConfig(**dict(TINY, quantization="int8")), model_cfg
    ) != compile_cache_key(cfg, model_cfg)
    assert compile_cache_key(
        EngineConfig(**dict(TINY, block_size=32)), model_cfg
    ) != compile_cache_key(cfg, model_cfg)
    assert compile_cache_key(
        EngineConfig(**dict(TINY, tensor_parallel_size=2)), model_cfg
    ) != compile_cache_key(cfg, model_cfg)


# ----------------------------------------------------------------------
# Acceptance: full warmup → zero compiles on a spanning traffic mix
# ----------------------------------------------------------------------


def _drain(engine) -> None:
    for _ in range(400):
        if not engine.has_work():
            return
        engine.step()
    raise AssertionError("engine did not drain")


def test_full_warmup_then_zero_compiles_on_spanning_traffic():
    from production_stack_tpu.engine.engine import LLMEngine

    cfg = EngineConfig(**TINY)
    engine = LLMEngine(cfg)
    summary = engine.precompile(mode="full")
    assert summary["buckets_compiled"] == summary["buckets_total"] > 0
    assert _gauge("pst_engine_warmup_coverage") == 1.0
    assert (
        _gauge("pst_engine_warmup_buckets", state="compiled")
        == _gauge("pst_engine_warmup_buckets", state="total")
    )
    # The precompile phase is part of the startup decomposition.
    assert _gauge("pst_engine_startup_seconds", phase="precompile") > 0

    c0 = ENGINE_TELEMETRY.compile_count()

    # 1) Greedy single request: prefill chunks 8+2 (buckets t8, t2), then
    #    2-step decode bursts at row bucket 1.
    engine.add_request(
        "r1", prompt_token_ids=list(range(2, 12)),
        sampling=SamplingParams(max_tokens=3, temperature=0.0),
    )
    _drain(engine)

    # 2) Concurrent greedy + sampled: batched prefill rows (bucket 2),
    #    mixed-greedy decode bursts (the (want_lp=False, greedy=False)
    #    executable), single-row tail after the shorter one finishes.
    engine.add_request(
        "r2", prompt_token_ids=list(range(20, 26)),
        sampling=SamplingParams(max_tokens=4, temperature=1.0, seed=7),
    )
    engine.add_request(
        "r3", prompt_token_ids=list(range(30, 42)),
        sampling=SamplingParams(max_tokens=2, temperature=0.0),
    )
    _drain(engine)

    # 3) Two sampled rows (all-sampled batch), then encode shapes.
    engine.add_request(
        "r4", prompt_token_ids=list(range(2, 9)),
        sampling=SamplingParams(max_tokens=2, temperature=0.9, seed=1),
    )
    engine.add_request(
        "r5", prompt_token_ids=list(range(9, 16)),
        sampling=SamplingParams(max_tokens=2, temperature=0.8, seed=2),
    )
    _drain(engine)
    engine.runner.encode([1, 2, 3])
    engine.runner.encode(list(range(2, 50)))  # t64 bucket

    assert ENGINE_TELEMETRY.compile_count() == c0, (
        "live traffic after a full warmup must not compile anything"
    )

    # 3b) Disagg mix (docs/disagg.md): a producer-leg prefill
    #     (max_tokens=1, kv_transfer stamped) and a consumer-style
    #     request that adopts a cached prefix then decodes the tail.
    #     Both reuse warmed bucket families — the zero-live-compile
    #     invariant holds for the disagg fleet shape (publish/prefetch
    #     are host/DCN work, never new executables).
    engine.add_request(
        "r-dp", prompt_token_ids=list(range(3, 13)),
        sampling=SamplingParams(max_tokens=1, temperature=0.0,
                                ignore_eos=True),
        kv_transfer={"request_id": "xfer-span", "role": "producer"},
    )
    _drain(engine)
    engine.add_request(
        "r-dc", prompt_token_ids=list(range(3, 13)),
        sampling=SamplingParams(max_tokens=3, temperature=0.0),
        kv_transfer={"request_id": "xfer-span", "role": "consumer"},
    )
    _drain(engine)
    assert ENGINE_TELEMETRY.compile_count() == c0, (
        "disagg prefill/decode dispatches must reuse warmed bucket "
        "families"
    )

    # 4) Penalized row: its DECODE bursts ride the warmed with_pen variant
    #    (dense [B, V] penalty state — zero decode compiles). Its prefill
    #    is the documented exception: single-step/prefill penalty shapes
    #    carry pow2-length id arrays and are deliberately not warmed
    #    (docs/engine.md) — exactly one attributed compile.
    c_decode = _kind_compiles("decode")
    engine.add_request(
        "r6", prompt_token_ids=list(range(4, 11)),
        sampling=SamplingParams(max_tokens=3, temperature=0.0,
                                repetition_penalty=1.3,
                                presence_penalty=0.5),
    )
    _drain(engine)
    assert _kind_compiles("decode") == c_decode, (
        "penalized burst variant was not covered by warmup"
    )
    assert ENGINE_TELEMETRY.compile_count() <= c0 + 1


def test_full_warmup_covers_spec_verify():
    from production_stack_tpu.engine.engine import LLMEngine

    cfg = EngineConfig(**dict(
        TINY, max_num_seqs=1, speculative_ngram=2, num_decode_steps=1,
    ))
    engine = LLMEngine(cfg)
    engine.precompile(mode="full")
    c0 = ENGINE_TELEMETRY.compile_count()
    # A periodic prompt so the n-gram lookup proposes drafts and the
    # verify executable (b1xk2) actually runs.
    engine.add_request(
        "spec", prompt_token_ids=[5, 6, 7, 5, 6, 7, 5, 6],
        sampling=SamplingParams(max_tokens=6, temperature=0.0),
    )
    _drain(engine)
    assert engine.spec_proposed_total > 0, "spec path never engaged"
    assert ENGINE_TELEMETRY.compile_count() == c0


# ----------------------------------------------------------------------
# Persistent compile cache: warm restart e2e (real engine, CPU backend)
# ----------------------------------------------------------------------


def _disable_persistent_cache(jax) -> None:
    """Undo configure_compile_cache for the rest of the pytest process:
    put back the session's directory (``tests/conftest.py``) AND clear
    jax's latched cache object (which would otherwise keep serving the
    test's tmp directory)."""
    jax.config.update("jax_compilation_cache_dir", SESSION_CACHE_DIR)
    try:
        from jax._src import compilation_cache

        compilation_cache.reset_cache()
    except Exception:  # noqa: BLE001 — private API moved
        pass


def test_warm_restart_reuses_persistent_cache(tmp_path, monkeypatch):
    import gc

    import jax

    from production_stack_tpu.engine.engine import LLMEngine

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cfg_kw = dict(TINY, compile_cache_dir=str(tmp_path), warmup="full",
                  warmup_bucket_budget=8)
    try:
        h0, m0 = ENGINE_TELEMETRY.cache_stats()
        cold_engine = LLMEngine(EngineConfig(**cfg_kw))
        cold = cold_engine.precompile()
        h1, m1 = ENGINE_TELEMETRY.cache_stats()
        assert m1 - m0 > 0, "cold run must write cache entries"
        del cold_engine
        gc.collect()

        warm_engine = LLMEngine(EngineConfig(**cfg_kw))
        warm = warm_engine.precompile()
        h2, m2 = ENGINE_TELEMETRY.cache_stats()
        # Zero fresh compiles on the warm restart; every lookup hits.
        assert m2 - m1 == 0, "warm restart must not rebuild executables"
        assert h2 - h1 > 0
        # ... and the precompile phase is strictly faster.
        assert warm["seconds"] < cold["seconds"]
        del warm_engine
        gc.collect()
    finally:
        _disable_persistent_cache(jax)


def test_cache_key_partitions_cache_dir(tmp_path, monkeypatch):
    """Different configs must never share executables: the keyed
    subdirectory isolates them."""
    from production_stack_tpu.engine.precompile import configure_compile_cache

    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        cfg_a = EngineConfig(**dict(TINY, compile_cache_dir=str(tmp_path)))
        cfg_b = EngineConfig(**dict(
            TINY, compile_cache_dir=str(tmp_path), block_size=32,
        ))
        model_cfg = get_model_config(cfg_a.model)
        path_a = configure_compile_cache(cfg_a, model_cfg)
        path_b = configure_compile_cache(cfg_b, model_cfg)
        assert path_a != path_b
        assert path_a.startswith(str(tmp_path))
    finally:
        _disable_persistent_cache(jax)


# ----------------------------------------------------------------------
# /ready gating on the real engine server
# ----------------------------------------------------------------------


class EngineServer:
    def __init__(self, **cfg_over):
        kw = dict(TINY)
        kw.update(cfg_over)
        self.cfg = EngineConfig(**kw)
        self.url = None

    async def __aenter__(self):
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


async def test_ready_gates_on_warmup(monkeypatch):
    import production_stack_tpu.engine.engine as engine_mod

    entered = threading.Event()
    release = threading.Event()

    def slow_precompile(self, mode=None, bucket_budget=None):
        entered.set()
        assert release.wait(timeout=10)
        self.warmup_summary = {
            "mode": "full", "buckets_total": 4, "buckets_compiled": 4,
            "coverage": 1.0, "seconds": 0.01,
        }
        return self.warmup_summary

    monkeypatch.setattr(engine_mod.LLMEngine, "precompile", slow_precompile)
    async with EngineServer(warmup="full") as srv, aiohttp.ClientSession() as s:
        for _ in range(100):
            if entered.is_set():
                break
            await asyncio.sleep(0.05)
        assert entered.is_set()
        async with s.get(f"{srv.url}/ready") as r:
            assert r.status == 503
            body = await r.json()
            assert body["ready"] is False and body["reason"] == "warming"
            assert body["warmup"]["mode"] == "full"
        # Liveness stays green while warming: k8s must not kill the pod.
        async with s.get(f"{srv.url}/health") as r:
            assert r.status == 200
            assert (await r.json())["status"] == "warming"
        # Work endpoints reject with the tagged 503 while warming — the
        # marker the router keys warming reconciliation off (accepting
        # would queue the request behind the whole precompile pass).
        async with s.post(
            f"{srv.url}/v1/completions",
            json={"model": "tiny-llama-debug", "prompt": "hi",
                  "max_tokens": 1},
        ) as r:
            assert r.status == 503
            assert r.headers.get("X-PST-Warming") == "1"
        release.set()
        for _ in range(100):
            async with s.get(f"{srv.url}/ready") as r:
                if r.status == 200:
                    body = await r.json()
                    break
            await asyncio.sleep(0.05)
        assert body["ready"] is True
        assert body["warmup"]["buckets_compiled"] == 4
        # Draining flips readiness off again (the rolling-deploy pair).
        async with s.post(f"{srv.url}/drain") as r:
            assert r.status == 200
        async with s.get(f"{srv.url}/ready") as r:
            assert r.status == 503
            assert (await r.json())["reason"] == "draining"
        async with s.post(f"{srv.url}/undrain") as r:
            assert r.status == 200
        async with s.get(f"{srv.url}/ready") as r:
            assert r.status == 200


async def test_ready_immediate_when_warmup_off():
    async with EngineServer() as srv, aiohttp.ClientSession() as s:
        for _ in range(100):
            async with s.get(f"{srv.url}/ready") as r:
                if r.status == 200:
                    body = await r.json()
                    break
            await asyncio.sleep(0.05)
        assert body["ready"] is True
        assert body["warmup"]["mode"] == "off"


# ----------------------------------------------------------------------
# Fake engine: simulated warmup + warm-restart e2e (router-side story)
# ----------------------------------------------------------------------


async def test_fake_engine_warmup_and_warm_restart(tmp_path):
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.testing.fake_engine import (
        FAKE_WARMUP_BUCKETS,
        create_fake_engine_app,
    )

    cache = str(tmp_path / "cache")
    app = create_fake_engine_app(ready_delay=0.4, warmup_cache_dir=cache)
    t_cold = time.monotonic()
    async with TestClient(TestServer(app)) as c:
        r = await c.get("/ready")
        assert r.status == 503
        body = await r.json()
        assert body["reason"] == "warming"
        assert body["warmup"]["warm_start"] is False
        r = await c.get("/health")
        assert (await r.json())["status"] == "warming"
        while (await c.get("/ready")).status != 200:
            assert time.monotonic() - t_cold < 5
            await asyncio.sleep(0.05)
        cold_ready_s = time.monotonic() - t_cold
        text = await (await c.get("/metrics")).text()
        assert 'pst_engine_startup_seconds{phase="precompile"} 0.400' in text
        assert (
            f"pst_engine_compile_cache_misses_total {FAKE_WARMUP_BUCKETS}"
            in text
        )
        assert "pst_engine_compile_cache_hits_total 0" in text
        assert "pst_engine_warmup_coverage 1.0000" in text

    # Restart against the same cache dir: warm start — faster ready,
    # zero new compiles (all cache hits), smaller precompile phase.
    app2 = create_fake_engine_app(ready_delay=0.4, warmup_cache_dir=cache)
    t_warm = time.monotonic()
    async with TestClient(TestServer(app2)) as c:
        r = await c.get("/ready")
        body = await r.json()
        assert body["warmup"]["warm_start"] is True
        assert body["warmup"]["seconds"] < 0.4
        while (await c.get("/ready")).status != 200:
            assert time.monotonic() - t_warm < 5
            await asyncio.sleep(0.02)
        warm_ready_s = time.monotonic() - t_warm
        assert warm_ready_s < cold_ready_s
        text = await (await c.get("/metrics")).text()
        assert "pst_engine_compile_cache_misses_total 0" in text
        assert (
            f"pst_engine_compile_cache_hits_total {FAKE_WARMUP_BUCKETS}"
            in text
        )
        assert 'pst_engine_startup_seconds{phase="precompile"} 0.080' in text

        # /admin/warmup re-enters warming (for discovery tests).
        r = await c.post(
            "/admin/warmup",
            json={"ready_delay": 30.0, "reset_cache": True},
        )
        assert (await r.json())["status"] == "warming"
        r = await c.get("/ready")
        assert r.status == 503
        assert (await r.json())["reason"] == "warming"


async def test_static_discovery_probes_fake_engine_ready():
    """The router-side /ready probe against a live (fake) engine: warming
    while the simulated precompile runs, cleared once ready, last-known
    state kept when the engine is unreachable."""
    from aiohttp.test_utils import TestServer

    from production_stack_tpu.router.service_discovery import (
        StaticServiceDiscovery,
    )
    from production_stack_tpu.testing.fake_engine import create_fake_engine_app

    server = TestServer(create_fake_engine_app(ready_delay=0.35))
    await server.start_server()
    url = f"http://127.0.0.1:{server.port}"
    sd = StaticServiceDiscovery(urls=[url], models=["fake/model"])
    try:
        async with aiohttp.ClientSession() as session:
            assert await sd._probe_warming(session, url) is True
            t0 = time.monotonic()
            while await sd._probe_warming(session, url) is True:
                assert time.monotonic() - t0 < 5
                await asyncio.sleep(0.05)
            assert await sd._probe_warming(session, url) is False
            # Unreachable engine → tri-state None (keep last known).
            assert (
                await sd._probe_warming(session, "http://127.0.0.1:1")
            ) is None
    finally:
        await server.close()
