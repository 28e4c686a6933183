"""Ring-2 e2e for the real TPU engine server: tiny model, real HTTP surface.

The reference proves its stack against fake engines; the engine itself is
vLLM's problem. Here the engine is ours, so this ring drives the *real*
engine (tiny-llama-debug on the CPU mesh) through the same OpenAI surface
the router proxies: completions, chat, streaming, tokenize, metrics,
sleep/wake, LoRA admin. Tests are grouped per server instance (engine
construction + jit warmup dominates runtime).
"""

import asyncio
import json

import aiohttp
from aiohttp import web

from production_stack_tpu.engine.async_engine import AsyncLLMEngine
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.server import create_engine_app


class EngineServer:
    def __init__(self, cross_encoder=None, **cfg_over):
        kw = dict(
            model="tiny-llama-debug",
            max_model_len=256,
            block_size=8,
            num_kv_blocks=256,
            max_num_seqs=8,
            max_prefill_tokens=64,
        )
        kw.update(cfg_over)
        self.cfg = EngineConfig(**kw)
        self.cross_encoder = cross_encoder
        self.url = None

    async def __aenter__(self):
        self.engine = AsyncLLMEngine(self.cfg)
        app = create_engine_app(self.engine, cross_encoder=self.cross_encoder)
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


async def test_generation_surface():
    async with EngineServer() as server, aiohttp.ClientSession() as sess:
        # /v1/models + /version
        async with sess.get(f"{server.url}/v1/models") as r:
            assert r.status == 200
            assert (await r.json())["data"][0]["id"] == "tiny-llama-debug"
        async with sess.get(f"{server.url}/version") as r:
            assert "version" in await r.json()

        # Non-streaming completion.
        payload = {
            "model": "tiny-llama-debug",
            "prompt": "hello world",
            "max_tokens": 8,
            "temperature": 0.0,
        }
        async with sess.post(f"{server.url}/v1/completions", json=payload) as r:
            assert r.status == 200
            body = await r.json()
            assert body["object"] == "text_completion"
            assert body["usage"]["completion_tokens"] >= 1
            assert body["choices"][0]["finish_reason"] in ("stop", "length")

        # Streaming chat.
        payload = {
            "model": "tiny-llama-debug",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 6,
            "temperature": 0.0,
            "stream": True,
        }
        chunks = []
        async with sess.post(
            f"{server.url}/v1/chat/completions", json=payload
        ) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/event-stream")
            async for line in r.content:
                line = line.decode().strip()
                if line.startswith("data: "):
                    chunks.append(line[6:])
        assert chunks[-1] == "[DONE]"
        first = json.loads(chunks[0])
        assert first["choices"][0]["delta"].get("role") == "assistant"
        finals = [json.loads(c) for c in chunks[:-1]]
        assert any(c["choices"][0]["finish_reason"] for c in finals)

        # tokenize / detokenize round-trip.
        async with sess.post(f"{server.url}/tokenize", json={"prompt": "abc"}) as r:
            toks = (await r.json())["tokens"]
            assert len(toks) == 3
        async with sess.post(
            f"{server.url}/detokenize", json={"tokens": toks}
        ) as r:
            assert (await r.json())["prompt"] == "abc"

        # /metrics exposes the vllm:-named contract the router scrapes.
        async with sess.get(f"{server.url}/metrics") as r:
            text = await r.text()
        for name in (
            "vllm:num_requests_running",
            "vllm:num_requests_waiting",
            "vllm:gpu_prefix_cache_hit_rate",
            "vllm:gpu_cache_usage_perc",
            "vllm:time_to_first_token_seconds",
        ):
            assert name in text, f"missing {name} in /metrics"

        # Embeddings.
        async with sess.post(
            f"{server.url}/v1/embeddings",
            json={"model": "m", "input": ["hello", "world"]},
        ) as r:
            assert r.status == 200
            body = await r.json()
            assert len(body["data"]) == 2
            assert len(body["data"][0]["embedding"]) == 128  # hidden size


async def test_admin_surface(tmp_path):
    async with EngineServer(
        enable_lora=True, max_loras=2, max_lora_rank=8,
        lora_dir=str(tmp_path),
    ) as server, aiohttp.ClientSession() as sess:
        # health
        async with sess.get(f"{server.url}/health") as r:
            assert r.status == 200

        # sleep / wake cycle (level 2 drops + restores the KV cache).
        async with sess.get(f"{server.url}/is_sleeping") as r:
            assert (await r.json())["is_sleeping"] is False
        await sess.post(f"{server.url}/sleep?level=2")
        async with sess.get(f"{server.url}/is_sleeping") as r:
            assert (await r.json())["is_sleeping"] is True
        async with sess.post(
            f"{server.url}/v1/completions",
            json={"model": "m", "prompt": "a", "max_tokens": 1},
        ) as r:
            assert r.status == 503
        await sess.post(f"{server.url}/wake_up")
        async with sess.post(
            f"{server.url}/v1/completions",
            json={"model": "m", "prompt": "a", "max_tokens": 1},
        ) as r:
            assert r.status == 200

        # drain / undrain cycle: new generations 503 with the
        # X-PST-Draining marker (the router keys drain reconciliation —
        # vs breaker failure — off that header), probes report state.
        async with sess.get(f"{server.url}/is_draining") as r:
            assert (await r.json())["is_draining"] is False
        async with sess.post(f"{server.url}/drain") as r:
            assert (await r.json())["status"] == "draining"
        async with sess.post(
            f"{server.url}/v1/completions",
            json={"model": "m", "prompt": "a", "max_tokens": 1},
        ) as r:
            assert r.status == 503
            assert r.headers.get("X-PST-Draining") == "1"
        async with sess.get(f"{server.url}/health") as r:
            assert (await r.json())["status"] == "draining"
        async with sess.post(f"{server.url}/undrain") as r:
            assert (await r.json())["status"] == "accepting"
        async with sess.post(
            f"{server.url}/v1/completions",
            json={"model": "m", "prompt": "a", "max_tokens": 1},
        ) as r:
            assert r.status == 200

        # LoRA admin endpoints: a real PEFT checkpoint loads into a device
        # bank slot and reflects into /v1/models with parent set; a request
        # under the adapter name serves; a bogus path 404s.
        from tests.test_lora import _make_adapter_dir

        path = _make_adapter_dir(tmp_path, server.engine.engine.model_cfg)
        async with sess.post(
            f"{server.url}/v1/load_lora_adapter",
            json={"lora_name": "ad1", "lora_path": path},
        ) as r:
            assert r.status == 200
            assert (await r.json())["slot"] == 1
        async with sess.get(f"{server.url}/v1/models") as r:
            cards = (await r.json())["data"]
            by_id = {m["id"]: m for m in cards}
            assert by_id["ad1"]["parent"] == "tiny-llama-debug"
        async with sess.post(
            f"{server.url}/v1/completions",
            json={"model": "ad1", "prompt": "abc", "max_tokens": 2,
                  "temperature": 0.0},
        ) as r:
            assert r.status == 200
        async with sess.post(
            f"{server.url}/v1/load_lora_adapter",
            json={"lora_name": "nope", "lora_path": "/tmp/does-not-exist"},
        ) as r:
            assert r.status == 404
        await sess.post(
            f"{server.url}/v1/unload_lora_adapter", json={"lora_name": "ad1"}
        )
        async with sess.get(f"{server.url}/v1/models") as r:
            ids = [m["id"] for m in (await r.json())["data"]]
            assert "ad1" not in ids


async def test_api_key_auth():
    async with EngineServer() as server:
        # Rebuild app with an api key on a second port.
        from production_stack_tpu.engine.server import create_engine_app as mk

        app = mk(server.engine, api_key="sekrit")
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        url = f"http://127.0.0.1:{site._server.sockets[0].getsockname()[1]}"
        try:
            async with aiohttp.ClientSession() as sess:
                async with sess.get(f"{url}/v1/models") as r:
                    assert r.status == 401
                async with sess.get(
                    f"{url}/v1/models",
                    headers={"Authorization": "Bearer sekrit"},
                ) as r:
                    assert r.status == 200
                # Non-/v1 endpoints (health/metrics probes) stay open.
                async with sess.get(f"{url}/health") as r:
                    assert r.status == 200
                # Destructive/admin endpoints must also be guarded: /sleep
                # level 2 aborts all requests and drops the KV cache.
                for path in ("/sleep?level=2", "/wake_up",
                             "/v1/load_lora_adapter"):
                    async with sess.post(f"{url}{path}") as r:
                        assert r.status == 401, path
                for path in ("/rerank", "/score", "/tokenize", "/detokenize"):
                    async with sess.post(f"{url}{path}", json={}) as r:
                        assert r.status == 401, path
                assert not server.engine.sleeping
        finally:
            await runner.cleanup()


async def test_infeasible_prompt_400_not_hang():
    """A prompt whose pages can never fit must 400 at the HTTP layer
    (shared Scheduler.prompt_fits guard) — not queue forever or return an
    empty 200 stream (r5 advisor finding)."""
    async with EngineServer(
        num_kv_blocks=8, max_model_len=512, block_size=8
    ) as server, aiohttp.ClientSession() as sess:
        payload = {
            "model": "tiny-llama-debug",
            "prompt": list(range(1, 101)),  # 100 toks > 64-token pool
            "max_tokens": 4,
        }
        async with sess.post(f"{server.url}/v1/completions", json=payload) as r:
            assert r.status == 400
            body = await r.json()
            assert "KV pages" in body["message"]
        # The engine is still healthy and serves feasible prompts.
        ok = {
            "model": "tiny-llama-debug",
            "prompt": [1, 2, 3],
            "max_tokens": 4,
            "temperature": 0.0,
        }
        async with sess.post(f"{server.url}/v1/completions", json=ok) as r:
            assert r.status == 200


def test_exit_with_parent_ends_a_server_whose_starter_is_gone(tmp_path):
    """``--exit-with-parent``: a process starts the watcher's process in a
    session of its own (as a harness starts a server) and dies; the orphan
    takes SIGTERM from itself within a poll or two. Without the watcher it
    would sleep on."""
    import os
    import subprocess
    import sys
    import time

    orphan = (
        "import os, sys, time\n"
        "from production_stack_tpu.engine.server import exit_with_parent\n"
        "if sys.argv[2] == 'watch':\n"
        "    exit_with_parent(poll_s=0.05)\n"
        "open(sys.argv[1], 'w').write(str(os.getpid()))\n"
        "time.sleep(120)\n")
    starter = (
        "import subprocess, sys, time\n"
        "subprocess.Popen([sys.executable, '-c', sys.argv[1], sys.argv[2], "
        "sys.argv[3]], start_new_session=True)\n"
        "while not __import__('os').path.exists(sys.argv[2]):\n"
        "    time.sleep(0.05)\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=root, JAX_PLATFORMS="cpu")

    def alive(pid):
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return False
        with open(f"/proc/{pid}/stat") as f:  # a zombie is not alive
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"

    pids = {}
    for mode in ("watch", "plain"):
        pid_file = tmp_path / f"{mode}.pid"
        subprocess.run([sys.executable, "-c", starter, orphan, str(pid_file),
                        mode], env=env, check=True, timeout=120)
        pids[mode] = int(pid_file.read_text())
    try:
        deadline = time.monotonic() + 10
        while alive(pids["watch"]) and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not alive(pids["watch"])
        assert alive(pids["plain"])
    finally:
        for pid in pids.values():
            if alive(pid):
                os.kill(pid, 9)


async def test_profile_capture_is_no_longer_than_profile_max_ms(monkeypatch):
    """``--profile-max-ms``: ``POST /debug/profile`` takes the shorter of
    what it is asked for and the server's limit, and its answer says which
    (the backend's name is patched: on the CPU the endpoint skips)."""
    import jax

    from production_stack_tpu.engine import server

    engine = AsyncLLMEngine(EngineServer().cfg)
    app = create_engine_app(engine, profiling=True, profile_max_ms=40.0)
    took = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    try:
        async with aiohttp.ClientSession() as sess:
            for asked in (3000, 20):
                async with sess.post(
                        f"http://127.0.0.1:{port}/debug/profile",
                        json={"duration_ms": asked}) as r:
                    took.append((await r.json())["duration_ms"])
    finally:
        await runner.cleanup()
    assert took == [40.0, 20.0]
    assert server.parse_engine_args(
        ["--model", "tiny-llama-debug"]).profile_max_ms == 60_000.0
