#!/usr/bin/env python3
"""Chip smoke: the serving path, end to end, on one directly attached chip.

Starts the real engine server (``python -m production_stack_tpu.engine.server``)
as a child on the chip, serving the ``llama-3-8b`` preset at full width
(random weights from the seed) with int4 weights, an fp8 KV cache and the
Pallas attention kernels; answers a few requests through the OpenAI surface
— a short completion, a ~3k-token prompt, the same prompt again (prefix
cache), four concurrent streamed completions, one request through the real
router in front — and checks what comes back: status, token counts, the
device path the engine says it resolved (``GET /version``), ``/metrics``,
and that ``pst_engine_compile_total`` stops moving once the shapes have been
seen. Fails on the first thing that is wrong.

One process per chip: this parent never imports jax (a parent that touched
jax would hold the chip and the engine child would hang), the engine child
is the only process that does, and the router imports none. Every child is
stopped in a ``finally``.

No accelerator is a failure, not a smaller run: with ``JAX_PLATFORMS=cpu``
the script refuses at once; where jax silently finds no chip the engine
refuses at start-up (``production_stack_tpu/device.py``) and the dead child
fails the smoke.

The compile cache is shared with every child by the engine's own rule:
``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``.

Last line of stdout on success, and only then:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import concurrent.futures
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
MODEL = "llama-3-8b"
ENGINE_FLAGS = [
    "--model", MODEL,
    "--quantization", "int4",
    "--kv-cache-dtype", "float8_e4m3fn",
    "--attn-impl", "pallas",
    "--max-model-len", "32768",
    "--block-size", "128",
    "--max-num-batched-tokens", "1024",
    "--max-num-seqs", "16",
    "--min-decode-bucket", "4",
]
EXPECT_DEVICE_PATH = {
    "platform": "tpu",
    "attention_impl": "pallas",
    "int4_impl": "pallas",
    "pallas_interpret": False,
}
VOCAB = 128_256
LONG_PROMPT_TOKENS = 3000
# The contract allows 1200 s, compilation included; stop short of it so the
# failure is ours (with a message and the children stopped), not a kill.
DEADLINE_S = 1150.0
WARM_PASSES = 6
LOG_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

_T0 = time.monotonic()


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


def remaining() -> float:
    return DEADLINE_S - (time.monotonic() - _T0)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# Children
# ---------------------------------------------------------------------------


class Child:
    """One child process in its own session, logged to a file, killable as
    a group."""

    def __init__(self, name: str, argv: list, env: dict):
        os.makedirs(LOG_DIR, exist_ok=True)
        self.name = name
        self.log_path = os.path.join(LOG_DIR, f"{name}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            argv, cwd=HERE, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )

    def alive(self) -> bool:
        return self.proc.poll() is None

    def log_tail(self, n: int = 60) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            return b"\n".join(f.read().splitlines()[-n:]).decode(
                "utf-8", "replace"
            )

    def stop(self) -> None:
        if self.alive():
            for sig, wait in ((signal.SIGTERM, 20), (signal.SIGKILL, 10)):
                try:
                    os.killpg(self.proc.pid, sig)
                except ProcessLookupError:
                    break
                try:
                    self.proc.wait(timeout=wait)
                    break
                except subprocess.TimeoutExpired:
                    continue
        self._log.close()


def child_env() -> dict:
    env = dict(os.environ)
    # The package is not pip-installed: children import it from the checkout.
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONDONTWRITEBYTECODE"] = "1"
    return env


def start_engine(
    flags: list, children: list, env: dict = None, name: str = "engine"
) -> tuple:
    """Start the engine server child; returns (child, base url). The caller
    waits on ``/ready`` and stops ``children`` in a ``finally``."""
    port = free_port()
    child = Child(
        name,
        [sys.executable, "-m", "production_stack_tpu.engine.server",
         "--host", "127.0.0.1", "--port", str(port), *flags],
        env or child_env(),
    )
    children.append(child)
    say(f"engine child pid {child.proc.pid}: {' '.join(flags)}")
    return child, f"http://127.0.0.1:{port}"


def start_router(engine_base: str, children: list) -> str:
    """Start the real router (imports no jax) in front of one engine and
    wait until it answers; returns its base url."""
    port = free_port()
    child = Child(
        "router",
        [sys.executable, "-m", "production_stack_tpu.router.app",
         "--host", "127.0.0.1", "--port", str(port),
         "--service-discovery", "static",
         "--static-backends", engine_base, "--static-models", MODEL],
        child_env(),
    )
    children.append(child)
    base = f"http://127.0.0.1:{port}"
    wait_http_ok(f"{base}/health", child, "router /health", 60.0)
    return base


def wait_http_ok(url: str, child: Child, what: str, timeout: float) -> dict:
    """Poll ``url`` until 200; fail at once if the child dies."""
    t_end = time.monotonic() + min(timeout, max(remaining(), 1.0))
    last = "no answer yet"
    while time.monotonic() < t_end:
        if not child.alive():
            raise SmokeFailure(
                f"{child.name} exited with code {child.proc.returncode} "
                f"before {what}; last log lines:\n{child.log_tail()}"
            )
        try:
            with urllib.request.urlopen(url, timeout=5) as r:
                return json.loads(r.read() or b"{}")
        except urllib.error.HTTPError as e:
            last = f"HTTP {e.code}: {e.read()[:200]!r}"
        except (urllib.error.URLError, OSError, ValueError) as e:
            last = repr(e)
        time.sleep(1.0)
    raise SmokeFailure(
        f"timed out waiting for {what} at {url} ({last}); last log lines of "
        f"{child.name}:\n{child.log_tail()}"
    )


# ---------------------------------------------------------------------------
# HTTP helpers
# ---------------------------------------------------------------------------


def get_json(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=30) as r:
        if r.status != 200:
            raise SmokeFailure(f"GET {url} -> {r.status}")
        return json.loads(r.read())


def post_completion(base: str, body: dict, timeout: float) -> dict:
    req = urllib.request.Request(
        f"{base}/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            status, raw = r.status, r.read()
    except urllib.error.HTTPError as e:
        raise SmokeFailure(
            f"POST {base}/v1/completions -> {e.code}: {e.read()[:500]!r}"
        ) from e
    if status != 200:
        raise SmokeFailure(f"POST {base}/v1/completions -> {status}")
    return json.loads(raw)


def complete(base: str, prompt_ids: list, n_out: int, what: str) -> dict:
    """Non-streamed completion; checks the asked-for number of tokens."""
    out = post_completion(
        base,
        {"model": MODEL, "prompt": prompt_ids, "max_tokens": n_out,
         "temperature": 0.0, "ignore_eos": True},
        timeout=max(remaining(), 1.0),
    )
    usage = out.get("usage") or {}
    if usage.get("completion_tokens") != n_out:
        raise SmokeFailure(
            f"{what}: asked for {n_out} tokens, usage says {usage}"
        )
    if usage.get("prompt_tokens") != len(prompt_ids):
        raise SmokeFailure(
            f"{what}: sent {len(prompt_ids)} prompt tokens, usage says {usage}"
        )
    if not out.get("choices") or out["choices"][0].get("finish_reason") != "length":
        raise SmokeFailure(f"{what}: unexpected choices {out.get('choices')}")
    return out


def stream_complete(base: str, prompt_ids: list, n_out: int, what: str) -> int:
    """Streamed (SSE) completion; returns the number of data frames and
    checks the final usage frame reports ``n_out`` tokens."""
    req = urllib.request.Request(
        f"{base}/v1/completions",
        data=json.dumps(
            {"model": MODEL, "prompt": prompt_ids, "max_tokens": n_out,
             "temperature": 0.0, "ignore_eos": True, "stream": True,
             "stream_options": {"include_usage": True}}
        ).encode(),
        headers={"Content-Type": "application/json"},
    )
    frames, usage, done = 0, None, False
    try:
        with urllib.request.urlopen(req, timeout=max(remaining(), 1.0)) as r:
            if r.status != 200:
                raise SmokeFailure(f"{what}: stream status {r.status}")
            ctype = r.headers.get("Content-Type", "")
            if "text/event-stream" not in ctype:
                raise SmokeFailure(f"{what}: not SSE (Content-Type {ctype!r})")
            for line in r:
                line = line.strip()
                if not line.startswith(b"data:"):
                    continue
                payload = line[5:].strip()
                if payload == b"[DONE]":
                    done = True
                    break
                frame = json.loads(payload)
                frames += 1
                if frame.get("usage"):
                    usage = frame["usage"]
    except urllib.error.HTTPError as e:
        raise SmokeFailure(f"{what}: stream -> {e.code}: {e.read()[:500]!r}") from e
    if not done:
        raise SmokeFailure(f"{what}: stream ended without [DONE]")
    if not usage or usage.get("completion_tokens") != n_out:
        raise SmokeFailure(
            f"{what}: asked for {n_out} streamed tokens, usage says {usage}"
        )
    return frames


_METRIC_LINE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})?\s+(\S+)(\s+\S+)?$"
)


def scrape(base: str) -> dict:
    """Parse ``/metrics`` strictly: every sample line must be
    ``name[{labels}] value``. Returns {name: [(labels_text, value), ...]}."""
    with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
        if r.status != 200:
            raise SmokeFailure(f"GET /metrics -> {r.status}")
        text = r.read().decode()
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _METRIC_LINE.match(line)
        if not m:
            raise SmokeFailure(f"/metrics line does not parse: {line!r}")
        try:
            value = float(m.group(3))
        except ValueError as e:
            raise SmokeFailure(f"/metrics value does not parse: {line!r}") from e
        out.setdefault(m.group(1), []).append((m.group(2) or "", value))
    if not out:
        raise SmokeFailure("/metrics is empty")
    return out


def total(metrics: dict, name: str) -> float:
    return sum(v for _, v in metrics.get(name, []))


# ---------------------------------------------------------------------------
# The smoke
# ---------------------------------------------------------------------------


def prompt(seed: int, n: int) -> list:
    """Deterministic pseudo-random token ids (no numpy in the parent)."""
    x, ids = seed * 2654435761 % 2**32 or 1, []
    for _ in range(n):
        x = (1664525 * x + 1013904223) % 2**32
        ids.append(1 + x % (VOCAB - 2))
    return ids


def traffic(base: str, router_base: str, tag: str) -> None:
    """The five request kinds, once."""
    complete(base, prompt(1, 32), 8, f"{tag}: short completion")
    long_ids = prompt(2, LONG_PROMPT_TOKENS)
    complete(base, long_ids, 8, f"{tag}: {LONG_PROMPT_TOKENS}-token prompt")
    complete(base, long_ids, 8, f"{tag}: same prompt again")
    with concurrent.futures.ThreadPoolExecutor(4) as pool:
        futs = [
            pool.submit(stream_complete, base, prompt(10 + i, 32), 16,
                        f"{tag}: concurrent stream {i}")
            for i in range(4)
        ]
        frames = [f.result() for f in futs]
    if min(frames) < 2:
        raise SmokeFailure(f"{tag}: a stream carried {min(frames)} frames")
    complete(router_base, prompt(3, 32), 8, f"{tag}: via router")


def run() -> dict:
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        raise SmokeFailure(
            "JAX_PLATFORMS=cpu: this smoke exists to run on the chip; it "
            "does not pass at a small size on the CPU (tests/ covers that)"
        )
    if not os.path.isfile(
        os.path.join(HERE, "production_stack_tpu", "engine", "server.py")
    ):
        raise SmokeFailure(
            f"no production_stack_tpu package beside {__file__}: run from a "
            "checkout"
        )
    children: list = []
    try:
        engine, base = start_engine(ENGINE_FLAGS, children)
        wait_http_ok(f"{base}/ready", engine, "engine /ready", 900.0)
        t_ready = time.monotonic() - _T0
        say(f"engine ready after {t_ready:.1f}s")

        # What the engine says it resolved — asserted, not guessed.
        dev = get_json(f"{base}/version").get("device") or {}
        say(f"device path: {json.dumps(dev)}")
        for key, want in EXPECT_DEVICE_PATH.items():
            if dev.get(key) != want:
                raise SmokeFailure(
                    f"engine resolved {key}={dev.get(key)!r}, smoke needs "
                    f"{want!r} (device path: {dev})"
                )
        if dev.get("device_count") != 1 or len(dev.get("mesh_device_ids", [])) != 1:
            raise SmokeFailure(f"expected one chip, one-device mesh: {dev}")
        if not dev.get("kv_pages", 0) > 32768 // 128:
            raise SmokeFailure(f"KV pool too small for one sequence: {dev}")

        router_base = start_router(base, children)

        m0 = scrape(base)
        startup = dict(m0.get("pst_engine_startup_seconds", []))
        say(f"startup seconds by phase: {startup}")

        # Cold pass: every shape compiles on first use.
        t0 = time.monotonic()
        traffic(base, router_base, "cold")
        m1 = scrape(base)
        say(f"cold traffic pass {time.monotonic() - t0:.1f}s, "
            f"{total(m1, 'pst_engine_compile_total'):.0f} compiles so far")
        if not total(m1, "vllm:gpu_prefix_cache_hits_total") > 0:
            raise SmokeFailure(
                "prefix-cache hit counter did not move on the repeated prompt"
            )
        if not total(m1, "pst_engine_compile_total") > 0:
            raise SmokeFailure("no compile was counted on a cold engine")

        # Warm passes: the same traffic again. Concurrent arrivals may batch
        # differently from pass to pass (row buckets 1, 2, 4), so allow a few
        # passes for the finite shape set to be seen — then a pass with any
        # compile in it is a live compile after warm traffic.
        prev = m1
        for i in range(WARM_PASSES):
            t0 = time.monotonic()
            traffic(base, router_base, f"warm{i}")
            cur = scrape(base)
            delta = (total(cur, "pst_engine_compile_total")
                     - total(prev, "pst_engine_compile_total"))
            say(f"warm pass {i}: {time.monotonic() - t0:.1f}s, "
                f"{delta:.0f} new compiles")
            prev = cur
            if delta == 0:
                break
        else:
            raise SmokeFailure(
                f"pst_engine_compile_total kept moving over {WARM_PASSES} "
                "warm passes"
            )
        for child in children:
            if not child.alive():
                raise SmokeFailure(
                    f"{child.name} died during traffic:\n{child.log_tail()}"
                )
        report = {
            "model": MODEL,
            "engine_flags": ENGINE_FLAGS,
            "device_path": dev,
            "engine_ready_s": round(t_ready, 1),
            "startup_seconds": startup,
            "compiles": total(prev, "pst_engine_compile_total"),
            "compile_cache_hits": total(
                prev, "pst_engine_compile_cache_hits_total"),
            "compile_cache_misses": total(
                prev, "pst_engine_compile_cache_misses_total"),
            "prefix_cache_hit_tokens": total(
                prev, "vllm:gpu_prefix_cache_hits_total"),
            "wall_s": round(time.monotonic() - _T0, 1),
        }
        say("smoke observations (not speeds): " + json.dumps(report))
        return dev
    finally:
        for child in reversed(children):
            child.stop()


def main() -> int:
    try:
        dev = run()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev["platform"],
            "kind": dev["device_kind"],
            "count": dev["device_count"],
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
