"""Benchmark: the reference multi-round-QA protocol on the real chip.

Orchestrates three phases as separate processes (the engine phases need
sole chip ownership) and prints ONE JSON line:

  1. Engine phase (`benchmarks/bench_engine.py`): Llama-3-8B, int4
     group-wise weights (Pallas streaming matmul) + fp8 KV on one 16 GiB
     v5e chip. Two sub-phases: a 4-user TTFT sweep (6 QPS points
     0.1-1.1, ≥300 measured requests, per-point p50/p99 + the
     dispatch→fetch round trip — the workload must FIT so TTFT measures
     the engine, not eviction thrash) and an 8-users-×-20k CONCURRENCY phase
     (more live KV than HBM holds; live-KV swap rotates the overflow)
     ending in a pipelined-deep-burst saturated decode probe; then
     llama-1b for round-over-round comparability.
  2. Stack phase: a REAL engine server + the REAL router as subprocesses;
     router overhead as the mean ± 95% CI of PAIRED per-request deltas
     (same warm prompt direct vs via-router, order alternating) over
     ≥200 pairs (reference: `router-e2e-test.yml:49-74`).
  3. Fleet phase: multi-round QA through the real router over FOUR fake
     engines, fleet KV hit rate read via the router's own scrape parser —
     the fused `fleet` policy vs the paired round-robin baseline, plus a
     churn leg (one engine SIGKILLed mid-phase) against the ≥0.9 target.

Headline `value` = p50 TTFT over every measured flagship request across the
sweep; `vs_baseline` = (200 ms north star) / value, >1.0 beats it.
`rpc_floor_ms` records one trivial dispatch→fetch round trip at run time,
beside TTFT and never subtracted from it.

This file deliberately never imports jax: the chip is acquired and released
by the child processes.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

TTFT_TARGET_S = 0.200  # north-star p50 TTFT (BASELINE.md)
REPO = os.path.dirname(os.path.abspath(__file__))

# A run with NO budget is how r05 died: the driver's `timeout` landed
# mid-bring-up with nothing flushed. Every run is budgeted now — an
# explicit --time-budget wins, else these defaults (just under the
# historical 3600 s driver wall; --tiny is the CPU smoke profile).
DEFAULT_TIME_BUDGET_S = 3300.0
TINY_TIME_BUDGET_S = 240.0
WATCHDOG_LEAD_S = 30.0


class BenchInterrupted(BaseException):
    """Raised by the SIGTERM/SIGALRM handlers so an externally imposed
    wall (the driver's `timeout`, or --time-budget) unwinds the current
    phase THROUGH its cleanup finallys and still reaches the final
    emit(). BaseException on purpose: the per-phase `except Exception`
    guards must not swallow it into an ordinary phase error."""


class TimeBudget:
    """Total wall budget carved into per-phase walls (ROADMAP 5a: the
    r05 run died on rc:124 with nothing parseable — a budgeted run
    truncates phases deliberately instead of being killed mid-write).

    ``phase_wall(weight, weights_left)`` hands the next phase its share
    of whatever remains; a phase that finishes early donates the slack
    to the rest. 0/None = unbudgeted (the historical behavior)."""

    def __init__(self, total: float = 0.0) -> None:
        self.total = max(float(total or 0.0), 0.0)
        self.t0 = time.monotonic()

    @property
    def enabled(self) -> bool:
        return self.total > 0

    def remaining(self) -> float:
        return max(self.total - (time.monotonic() - self.t0), 0.0)

    def phase_wall(self, weight: float, weights_left: float) -> float:
        """Seconds granted to the next phase: its weight share of the
        remaining budget."""
        return self.remaining() * weight / max(weights_left, weight)

    def exhausted(self, floor: float = 20.0) -> bool:
        """Too little budget left to produce a meaningful phase."""
        return self.enabled and self.remaining() < floor


def install_term_trap() -> None:
    """SIGTERM (the driver's `timeout` sends it before SIGKILL) raises
    BenchInterrupted in the main thread: the current phase unwinds
    through its process-cleanup finallys and main() flushes the final
    JSON — an rc:124 run still yields a parseable result."""
    def _raise(signum, frame):
        raise BenchInterrupted(f"signal {signum}")

    signal.signal(signal.SIGTERM, _raise)
    signal.signal(signal.SIGALRM, _raise)


def phase_alarm(seconds: float) -> None:
    """Arm the per-phase wall (0 disarms): SIGALRM -> BenchInterrupted."""
    signal.setitimer(signal.ITIMER_REAL, max(seconds, 0.0))


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def force_cpu() -> bool:
    return (
        os.environ.get("PST_BENCH_CPU") == "1"
        or os.environ.get("JAX_PLATFORMS") == "cpu"
    )


def child_env() -> dict:
    """Environment for chip-owning children: as inherited, or pinned to
    the CPU (kernels interpreted) for the CPU smoke profile."""
    env = dict(os.environ)
    if force_cpu():
        env["JAX_PLATFORMS"] = "cpu"
        env["PST_FORCE_PALLAS_INTERPRET"] = "1"
    return env


def read_partial(path: str) -> dict:
    """Best-effort read of an incrementally-written partial result file
    (bench_engine.write_partial); {} when absent or unparseable."""
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def run_engine_phase() -> dict:
    """Run the engine benchmark subprocess.

    The child checkpoints its cumulative result to $PST_BENCH_ENGINE_OUT
    after every qps point and phase, so a timeout (BENCH_r05: rc=124 with
    nothing parseable) or crash degrades to the partial result instead of
    losing the whole run — recompile-heavy sweeps stay attributable.
    """
    partial_path = os.environ.get(
        "PST_BENCH_ENGINE_OUT", "/tmp/pst_bench_engine_partial.json"
    )
    env = child_env()
    env["PST_BENCH_ENGINE_OUT"] = partial_path
    # The child persists flight snapshots here so a tail outlier stays
    # explainable even when the child is SIGKILLed (post-mortem path).
    env["PST_BENCH_FLIGHT_SNAPSHOT_DIR"] = engine_snapshot_dir()
    try:
        os.remove(partial_path)  # never serve a previous run's partial
    except OSError:
        pass
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "benchmarks", "bench_engine.py")],
            stdout=subprocess.PIPE,
            text=True,
            env=env,
            timeout=int(os.environ.get("PST_BENCH_ENGINE_TIMEOUT", "4200")),
        )
    except subprocess.TimeoutExpired:
        partial = read_partial(partial_path)
        if partial:
            log("engine phase timed out; continuing with its partial result")
            partial["partial"] = True
            partial["error"] = "engine phase timed out"
            return partial
        raise
    lines = proc.stdout.strip().splitlines()
    if lines:
        try:
            parsed = json.loads(lines[-1])
        except ValueError:
            parsed = None
        if not isinstance(parsed, dict) or "backend" not in parsed:
            # Stray non-object JSON, or a JSON-ish log line that is not the
            # bench result (every real result carries "backend"): fall
            # through to the partial checkpoint.
            parsed = None
        if parsed is not None:
            if proc.returncode != 0:
                # A complete result with a nonzero rc is deliberate
                # (--require-warm failing on compile pollution): keep the
                # data, surface the verdict.
                parsed["engine_rc"] = proc.returncode
            return parsed
    partial = read_partial(partial_path)
    if partial:
        log(f"engine phase failed (rc={proc.returncode}); "
            "continuing with its partial result")
        partial["partial"] = True
        partial["failed"] = True  # not a budget cut: fails the run
        partial["error"] = f"engine phase rc={proc.returncode}"
        return partial
    raise RuntimeError(
        f"engine benchmark phase failed (rc={proc.returncode}); "
        "its stderr is above"
    )


def run_cost_phase() -> dict:
    """Cost-attribution audit (benchmarks/bench_cost.py): per-request
    device-seconds must sum to within 10% of the device-busy wall in
    BOTH pipeline modes, and the heavy tenant must be billed more chip
    time (docs/observability.md "Cost attribution"). Runs the tiny model
    in a subprocess — the attribution math is share-exact and therefore
    backend-independent, so this phase never needs the chip."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "bench_cost.py")],
        stdout=subprocess.PIPE,
        text=True,
        env=child_env(),
        timeout=int(os.environ.get("PST_BENCH_COST_TIMEOUT", "600")),
    )
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        raise RuntimeError(f"cost phase failed (rc={proc.returncode})")
    return json.loads(lines[-1])


def ensure_port_free(port: int) -> None:
    import socket

    with socket.socket() as s:
        try:
            s.bind(("127.0.0.1", port))
        except OSError as e:
            raise RuntimeError(
                f"port {port} is already bound (stale bench process?); "
                "kill it before benchmarking — a leftover server would be "
                "silently measured instead of the fresh stack"
            ) from e


def wait_http(url: str, timeout: float, proc=None, log_path=None) -> bool:
    t0 = time.time()
    while time.time() - t0 < timeout:
        if proc is not None and proc.poll() is not None:
            tail = ""
            if log_path and os.path.exists(log_path):
                with open(log_path) as f:
                    tail = "".join(f.readlines()[-15:])
            raise RuntimeError(
                f"server exited early (rc={proc.returncode}):\n{tail}"
            )
        try:
            with urllib.request.urlopen(url, timeout=2) as r:
                if r.status == 200:
                    return True
        except Exception:
            time.sleep(1.0)
    return False


def paired_router_overhead(
    direct_url: str,
    router_url,
    model: str,
    sys_len: int,
    hist_len: int,
    n_pairs: int = 220,
) -> dict:
    """Mean ± 95% CI of per-request router overhead over paired requests.

    Each pair streams the SAME (warm, prefix-cached) prompt once direct to
    the engine and once through the router, back to back, order alternating
    pair to pair; TTFT is client-measured time to the first SSE byte. The
    per-pair delta cancels engine compute and slow drift (both legs of a
    pair see the same window), isolating the router hop —
    reference methodology: router-e2e-test.yml's direct-vs-router compare,
    upgraded from aggregate medians to a paired design.

    ``router_url`` may be a list of replica URLs (the ``replicas: 2``
    variant): via-router legs round-robin across them, the way an LB
    spreads clients, so the measured overhead includes the shared-state
    backend's cost on the hot path.
    """
    import statistics

    import aiohttp

    router_urls = (
        list(router_url) if isinstance(router_url, (list, tuple))
        else [router_url]
    )

    rng = __import__("random").Random(11)
    prompts = [
        " ".join(
            "w%d" % rng.randrange(5000) for _ in range(sys_len + hist_len)
        )
        for _ in range(16)
    ]

    async def ttft(session: "aiohttp.ClientSession", base: str, prompt: str) -> float:
        t0 = time.perf_counter()
        async with session.post(
            f"{base}/v1/completions",
            json={
                "model": model, "prompt": prompt, "max_tokens": 4,
                "temperature": 0.0, "stream": True,
            },
        ) as resp:
            resp.raise_for_status()
            async for _ in resp.content.iter_any():
                return time.perf_counter() - t0
        raise RuntimeError("empty stream")

    async def run() -> dict:
        deltas: list = []
        async with aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=120)
        ) as session:
            for p in prompts:  # warm both paths everywhere
                await ttft(session, direct_url, p)
                for r in router_urls:
                    await ttft(session, r, p)
            for i in range(n_pairs):
                p = prompts[i % len(prompts)]
                via = router_urls[i % len(router_urls)]
                if i % 2 == 0:
                    d = await ttft(session, direct_url, p)
                    v = await ttft(session, via, p)
                else:
                    v = await ttft(session, via, p)
                    d = await ttft(session, direct_url, p)
                deltas.append((v - d) * 1e3)
        mean = statistics.fmean(deltas)
        sd = statistics.stdev(deltas)
        ci = 1.96 * sd / (len(deltas) ** 0.5)
        return {
            "router_overhead_ms": round(mean, 2),
            "router_overhead_ci95_ms": round(ci, 2),
            "router_overhead_median_ms": round(statistics.median(deltas), 2),
            "n_pairs": len(deltas),
            "overhead_significant": bool(abs(mean) > ci),
        }

    return asyncio.run(run())


def run_stack_phase(on_tpu: bool) -> dict:
    """Engine server + router subprocesses; multi_round_qa over HTTP,
    engine-direct then via-router (same warm workload → delta = router)."""
    from benchmarks.multi_round_qa import WorkloadConfig, run_benchmark, summarize

    # NOTE on lengths: preset models use the byte-fallback tokenizer, so a
    # "word" of synth text is ~6 tokens — the word counts below are ~6x
    # smaller than the intended token counts.
    if on_tpu:
        model = "llama-1b"
        engine_args = [
            "--model", model, "--max-model-len", "8192",
            "--block-size", "64", "--num-kv-blocks", "1024",
            "--max-num-seqs", "16", "--max-num-batched-tokens", "1024",
            "--attn-impl", "pallas", "--kv-cache-dtype", "float8_e4m3fn",
            # One decode width + no adaptive variant: every compiled shape
            # must exist after the warm-up legs — a stray XLA compile
            # during a measured leg would read as seconds of fake "TTFT".
            "--num-decode-steps", "4", "--min-decode-bucket", "4",
        ]
        # Light load on purpose: this phase isolates ROUTER OVERHEAD (the
        # p50 delta). Engine server + router + client share one host core;
        # a saturating workload measures host contention, not the router.
        sys_len, hist_len, answer_len = 120, 300, 16  # ≈ 700+1.8k byte toks
        start_timeout = 420.0
    else:
        model = "tiny-llama-debug"
        engine_args = [
            "--model", model, "--max-model-len", "2048", "--block-size", "8",
            "--num-kv-blocks", "2100", "--max-num-seqs", "8",
            "--max-num-batched-tokens", "128", "--attn-impl", "gather",
            "--num-decode-steps", "4", "--min-decode-bucket", "4",
        ]
        sys_len, hist_len, answer_len = 32, 64, 8  # ≈ 200+400 byte tokens
        start_timeout = 180.0

    eport, rport = 18200, 18201
    ensure_port_free(eport)
    ensure_port_free(rport)
    elog, rlog = "/tmp/pst_bench_engine.log", "/tmp/pst_bench_router.log"
    engine = subprocess.Popen(
        [sys.executable, "-m", "production_stack_tpu.engine.server",
         "--port", str(eport), *engine_args],
        stdout=open(elog, "w"), stderr=subprocess.STDOUT,
        cwd=REPO, env=child_env(),
    )
    router = None
    replicas = []
    try:
        if not wait_http(
            f"http://127.0.0.1:{eport}/health", start_timeout,
            proc=engine, log_path=elog,
        ):
            raise RuntimeError("engine server did not become healthy")
        router = subprocess.Popen(
            [sys.executable, "-m", "production_stack_tpu.router.app",
             "--port", str(rport),
             "--service-discovery", "static",
             "--static-backends", f"http://127.0.0.1:{eport}",
             "--static-models", model,
             "--routing-logic", "roundrobin"],
            stdout=open(rlog, "w"), stderr=subprocess.STDOUT,
            cwd=REPO,
        )
        if not wait_http(
            f"http://127.0.0.1:{rport}/health", 60,
            proc=router, log_path=rlog,
        ):
            raise RuntimeError("router did not become healthy")

        def drive(base_url: str, tag: str, rounds: int) -> dict:
            cfg = WorkloadConfig(
                num_users=4, num_rounds=rounds, qps=1.0,
                system_prompt_len=sys_len, chat_history_len=hist_len,
                answer_len=answer_len, model=model, base_url=base_url,
                seed=7,  # same histories both legs: second leg runs warm
            )
            t0 = time.time()
            records = asyncio.run(run_benchmark(cfg))
            s = summarize(records, time.time() - t0)
            log(f"stack[{tag}]: {s}")
            return s

        # One short leg sanity-checks the stack end to end (and compiles
        # the decode buckets its concurrency hits); the paired phase warms
        # its OWN prompts before measuring, so no further warm-up is
        # needed for the delta to be unbiased.
        drive(f"http://127.0.0.1:{eport}", "sanity", rounds=1)
        # Paired per-request deltas (r4 verdict: the leg-median sandwich
        # produced a negative, noise-dominated number): each PAIR sends the
        # SAME warm prompt direct and via the router back-to-back, with the
        # order alternating pair to pair so slow drift cancels within each
        # pair; the statistic is the mean per-pair delta with
        # a 95% CI over >=200 pairs.
        pairs = paired_router_overhead(
            f"http://127.0.0.1:{eport}", f"http://127.0.0.1:{rport}",
            model, sys_len, hist_len,
            n_pairs=int(os.environ.get("PST_BENCH_PAIRS", "220")),
        )

        # replicas: 2 variant (ROADMAP item 5's ≤ +5 ms p50 gate): the
        # same paired design against TWO router replicas coordinating
        # over the gossip state backend, clients alternating replicas
        # like an LB would. The single-replica router is stopped first —
        # three routers contending for the shared host core would measure
        # scheduling noise, not the replication cost.
        router.send_signal(signal.SIGTERM)
        try:
            router.wait(timeout=10)
        except subprocess.TimeoutExpired:
            router.kill()
        router = None
        r2ports = [rport + 1, rport + 2]
        for p in r2ports:
            ensure_port_free(p)
        r2logs = []
        for i, p in enumerate(r2ports):
            lg = f"/tmp/pst_bench_router_r2_{i}.log"
            r2logs.append(lg)
            replicas.append(subprocess.Popen(
                [sys.executable, "-m", "production_stack_tpu.router.app",
                 "--port", str(p),
                 "--service-discovery", "static",
                 "--static-backends", f"http://127.0.0.1:{eport}",
                 "--static-models", model,
                 "--routing-logic", "roundrobin",
                 "--state-backend", "gossip",
                 "--state-peers",
                 f"http://127.0.0.1:{r2ports[1 - i]}",
                 "--state-sync-interval", "0.25",
                 "--state-replica-id", f"bench-replica-{i}"],
                stdout=open(lg, "w"), stderr=subprocess.STDOUT,
                cwd=REPO,
            ))
        for p, proc, lg in zip(r2ports, replicas, r2logs):
            if not wait_http(f"http://127.0.0.1:{p}/ready", 60,
                             proc=proc, log_path=lg):
                raise RuntimeError(f"router replica :{p} not ready")
        pairs2 = paired_router_overhead(
            f"http://127.0.0.1:{eport}",
            [f"http://127.0.0.1:{p}" for p in r2ports],
            model, sys_len, hist_len,
            n_pairs=int(os.environ.get("PST_BENCH_PAIRS_R2", "120")),
        )
        delta_p50 = round(
            pairs2["router_overhead_median_ms"]
            - pairs["router_overhead_median_ms"], 2,
        )
        replicas2 = {
            "replicas": 2,
            **pairs2,
            "p50_delta_vs_single_ms": delta_p50,
            "target_ms": 5.0,
            "meets_target": bool(delta_p50 <= 5.0),
        }
        if not replicas2["meets_target"]:
            log(f"replicas:2 router overhead p50 delta {delta_p50}ms "
                "exceeds the +5ms target")
        return {"model": model, **pairs, "replicas2": replicas2}
    finally:
        for proc in [router, engine] + replicas:
            if proc is not None:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()


def run_fleet_phase() -> dict:
    """Fleet routing hit rate THROUGH the routing path (ROADMAP item 3's
    acceptance): multi-round QA through the real router over FOUR fake
    engines, hit rate read from each engine's /metrics via the router's
    own scrape parser. Fake engines (with the derived KV/prefix-cache
    simulation) — the ROUTING POLICY, not chip speed, is under test; four
    of them make affinity-vs-spread differences visible in a way two real
    CPU engines never were. Three paired legs in the SAME run:

      fleet_hit_rate  — --routing-logic fleet, no faults (≥ 0.9 target)
      rr_hit_rate     — naive roundrobin baseline (fleet must beat it)
      churn_hit_rate  — fleet again with one engine SIGKILLed mid-phase;
                        breakers fence the corpse, failover re-homes its
                        sessions, the trie relearns — hit rate must stay
                        ≥ 0.9 (the churn-tolerance acceptance gate)
    """
    from benchmarks.multi_round_qa import WorkloadConfig, run_benchmark
    from production_stack_tpu.router.stats.engine_stats import EngineStats

    model = "fake/model"
    n_engines = 4
    env = dict(os.environ, PYTHONPATH=REPO)

    def measure(policy: str, base_port: int, churn_kill_after: float = 0.0) -> dict:
        eports = [base_port + i for i in range(n_engines)]
        rport = base_port + n_engines
        for p in eports + [rport]:
            ensure_port_free(p)
        procs = []
        logs = []
        try:
            for i, p in enumerate(eports):
                lg = f"/tmp/pst_fleet_engine_{p}.log"
                logs.append(lg)
                procs.append(subprocess.Popen(
                    [sys.executable, "-m",
                     "production_stack_tpu.testing.fake_engine",
                     "--port", str(p), "--model", model,
                     "--speed", "120", "--ttft", "0.02",
                     "--name", f"fleet-{i}",
                     # Small enough that roundrobin (every conversation
                     # cached on every engine, ~21k tokens) thrashes,
                     # while affinity (2-3 conversations per engine,
                     # ~5-7k tokens) fits comfortably.
                     "--kv-capacity-tokens", "12000"],
                    stdout=open(lg, "w"), stderr=subprocess.STDOUT,
                    cwd=REPO, env=env,
                ))
            for p, proc, lg in zip(eports, procs, logs):
                if not wait_http(f"http://127.0.0.1:{p}/health", 60,
                                 proc=proc, log_path=lg):
                    raise RuntimeError(f"fleet fake engine :{p} not healthy")
            rlog = f"/tmp/pst_fleet_router_{policy}_{base_port}.log"
            router = subprocess.Popen(
                [sys.executable, "-m", "production_stack_tpu.router.app",
                 "--port", str(rport),
                 "--service-discovery", "static",
                 "--static-backends",
                 ",".join(f"http://127.0.0.1:{p}" for p in eports),
                 "--static-models", ",".join([model] * n_engines),
                 "--routing-logic", policy,
                 "--engine-stats-interval", "1",
                 "--proxy-retries", "3", "--retry-backoff", "0.01",
                 "--breaker-failure-threshold", "2",
                 "--breaker-recovery-time", "60"],
                stdout=open(rlog, "w"), stderr=subprocess.STDOUT,
                cwd=REPO, env=env,
            )
            procs.append(router)
            if not wait_http(f"http://127.0.0.1:{rport}/health", 60,
                             proc=router, log_path=rlog):
                raise RuntimeError("fleet router not healthy")
            cfg = WorkloadConfig(
                num_users=8, num_rounds=32, qps=4.0,
                system_prompt_len=24, chat_history_len=800, answer_len=8,
                model=model, base_url=f"http://127.0.0.1:{rport}", seed=13,
            )

            killed_port = None

            async def drive() -> list:
                nonlocal killed_port
                bench_task = asyncio.ensure_future(run_benchmark(cfg))
                if churn_kill_after > 0:
                    done, _ = await asyncio.wait(
                        [bench_task], timeout=churn_kill_after
                    )
                    if not done:
                        # SIGKILL, no drain, no goodbye: the churn leg.
                        procs[0].kill()
                        killed_port = eports[0]
                        log(f"fleet[{policy}]: killed engine :{killed_port} "
                            f"mid-phase at t={churn_kill_after:.1f}s")
                return await bench_task

            t0 = time.time()
            records = asyncio.run(drive())
            wall = time.time() - t0
            ok = sum(1 for r in records if r.status == 200)
            hits = queries = 0.0
            per_engine = []
            for p in eports:
                if p == killed_port:
                    continue  # the corpse serves no /metrics
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{p}/metrics", timeout=10
                ) as r:
                    # The router's own scrape parser — the path KV-aware
                    # routing relies on in production.
                    st = EngineStats.from_vllm_scrape(r.read().decode())
                hits += st.gpu_prefix_cache_hits_total
                queries += st.gpu_prefix_cache_queries_total
                per_engine.append({
                    "engine": p,
                    "hit_rate": round(st.gpu_prefix_cache_hit_rate, 3),
                })
            rate = hits / queries if queries else 0.0
            out = {"policy": policy, "fleet_hit_rate": round(rate, 3),
                   "requests_ok": ok, "requests_total": len(records),
                   "wall_seconds": round(wall, 1),
                   "per_engine": per_engine}
            if churn_kill_after > 0:
                out["killed_engine"] = killed_port
            return out
        finally:
            for proc in procs:
                if proc.poll() is None:
                    proc.send_signal(signal.SIGTERM)
            for proc in procs:
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()

    fleet = measure("fleet", 18300)
    rr = measure("roundrobin", 18310)
    # Kill one engine mid-phase: halfway through the no-churn leg's wall.
    churn = measure("fleet", 18320,
                    churn_kill_after=max(fleet["wall_seconds"] * 0.55, 2.0))
    return {
        "fleet_hit_rate": fleet["fleet_hit_rate"],
        "rr_hit_rate": rr["fleet_hit_rate"],
        "churn_hit_rate": churn["fleet_hit_rate"],
        "fleet": fleet,
        "roundrobin": rr,
        "churn": churn,
        "engines": n_engines,
        "target_hit_rate": 0.9,
        # Churn tolerance is BOTH numbers: the survivors' hit rate AND
        # near-zero client-visible failures (a broken failover path must
        # not pass just because the corpse's metrics are excluded).
        "meets_target": (
            fleet["fleet_hit_rate"] >= 0.9
            and churn["fleet_hit_rate"] >= 0.9
            and churn["requests_ok"] >= 0.98 * churn["requests_total"]
        ),
        "beats_roundrobin": (
            fleet["fleet_hit_rate"] > rr["fleet_hit_rate"]
            and churn["fleet_hit_rate"] > rr["fleet_hit_rate"]
        ),
    }


def run_autoscale_phase() -> dict:
    """Closed-loop autoscale under surge (docs/autoscaling.md): the REAL
    router (k8s discovery against the in-process fake API server) + the
    REAL pst-operator actuator + fake engines, with offered load DOUBLED
    mid-run. Measures how long the loop takes to absorb the surge, the
    client p99 while absorbing, that the new replica comes up with ZERO
    fresh compiles (warm-start path), and the wake→first-token bound of a
    scaled-to-zero pool. Kill-surviving like every stack phase: the
    subprocess fleet dies in the finally, partial numbers ride the emit."""
    from production_stack_tpu.testing.fake_k8s import PST, FakeK8s

    operator_dir = os.path.join(REPO, "operator")
    operator_bin = os.path.join(operator_dir, "build", "pst-operator")
    build = subprocess.run(["make"], cwd=operator_dir,
                           capture_output=True, text=True)
    if build.returncode != 0 or not os.path.exists(operator_bin):
        return {"error": f"operator build failed: {build.stderr[-400:]}"}

    model = "fake/model"
    slo_ms = float(os.environ.get("PST_BENCH_AUTOSCALE_SLO_MS", "1500"))
    env = dict(os.environ, PYTHONPATH=REPO)

    def operator_tick(api: str) -> None:
        proc = subprocess.run(
            [operator_bin, "--api-server", api, "--namespace", "default",
             "--once"],
            capture_output=True, text=True, timeout=120)
        if proc.returncode != 0:
            raise RuntimeError(f"operator tick failed: {proc.stderr[-300:]}")

    def get_json(url: str) -> dict:
        with urllib.request.urlopen(url, timeout=10) as r:
            return json.loads(r.read().decode())

    def compile_total(eng_url: str) -> float:
        with urllib.request.urlopen(f"{eng_url}/metrics", timeout=5) as r:
            text = r.read().decode()
        return sum(float(line.rsplit(" ", 1)[1])
                   for line in text.splitlines()
                   if line.startswith("pst_engine_compile_total"))

    def pct(vals, q):
        if not vals:
            return None
        vals = sorted(vals)
        return vals[min(int(round(q * (len(vals) - 1))), len(vals) - 1)]

    def seed_runtime(k8s, autoscale):
        k8s.seed(PST, "tpuruntimes", {
            "apiVersion": "pst.production-stack.io/v1alpha1",
            "kind": "TPURuntime",
            "metadata": {"name": "base", "namespace": "default"},
            "spec": {"model": model, "replicas": 1, "engineConfig": {},
                     "kvCache": {}, "autoscale": autoscale},
        })

    def start_engine(k8s, procs, engines, idx, eport, ip_base):
        ip = f"127.0.0.{ip_base + idx}"
        name = f"base-engine-{idx}"
        lg = f"/tmp/pst_autoscale_engine_{ip_base + idx}.log"
        p = subprocess.Popen(
            [sys.executable, "-m",
             "production_stack_tpu.testing.fake_engine",
             "--host", ip, "--port", str(eport), "--model", model,
             "--speed", "2000", "--name", name],
            stdout=open(lg, "w"), stderr=subprocess.STDOUT,
            cwd=REPO, env=env)
        procs.append(p)
        url = f"http://{ip}:{eport}"
        if not wait_http(f"{url}/health", 60, proc=p, log_path=lg):
            raise RuntimeError(f"autoscale fake engine {name} not healthy")
        engines[name] = url
        k8s.seed_engine_pod(name, eport, ip=ip)
        return name

    def start_router(k8s, procs, eport, rport, tag):
        lg = f"/tmp/pst_autoscale_router_{tag}.log"
        p = subprocess.Popen(
            [sys.executable, "-m", "production_stack_tpu.router.app",
             "--host", "127.0.0.1", "--port", str(rport),
             "--service-discovery", "k8s",
             "--k8s-label-selector", "model=base",
             "--k8s-port", str(eport),
             "--routing-logic", "roundrobin",
             "--engine-stats-interval", "1",
             "--slo-ttft-ms", "40", "--admission-rate", "400",
             "--proxy-retries", "0", "--breaker-failure-threshold", "100"],
            stdout=open(lg, "w"), stderr=subprocess.STDOUT, cwd=REPO,
            env=dict(env, PST_K8S_API_SERVER=k8s.url))
        procs.append(p)
        if not wait_http(f"http://127.0.0.1:{rport}/health", 60,
                         proc=p, log_path=lg):
            raise RuntimeError("autoscale router not healthy")
        k8s.seed_router_replica("pst-router", rport)
        return f"http://127.0.0.1:{rport}"

    def wait_signal(router_url, pred, timeout_s, what):
        deadline = time.time() + timeout_s
        sig = None
        while time.time() < deadline:
            sig = get_json(f"{router_url}/autoscale/signal")
            if pred(sig):
                return sig
            time.sleep(0.3)
        raise RuntimeError(f"autoscale signal never converged ({what}): {sig}")

    # ---- surge leg: offered load doubles against a saturating pool ------
    eport, rport = 18400, 18409
    for p in (eport, rport):
        ensure_port_free(p)
    k8s = FakeK8s().start()
    procs = []
    engines = {}
    records = []  # (t_done, latency_ms, served_by, ok)
    rec_lock = threading.Lock()
    stop_load = threading.Event()
    workers = []
    out = {"slo_ms": slo_ms}
    try:
        start_engine(k8s, procs, engines, 0, eport, ip_base=2)
        router_url = start_router(k8s, procs, eport, rport, "surge")
        seed_runtime(k8s, {"minReplicas": 1, "maxReplicas": 3,
                           "scaleDownStabilizationS": 3600,
                           "idleVerdicts": 3})
        wait_signal(router_url, lambda s: s["engines_ready"] == 1, 30,
                    "initial discovery")

        def worker(idx):
            i = 0
            while not stop_load.is_set():
                t0 = time.time()
                try:
                    req = urllib.request.Request(
                        f"{router_url}/v1/completions",
                        data=json.dumps({
                            "model": model, "prompt": f"load-{idx}-{i}",
                            "max_tokens": 2}).encode(),
                        headers={"Content-Type": "application/json"},
                        method="POST")
                    with urllib.request.urlopen(req, timeout=30) as resp:
                        by = resp.headers.get("X-Served-By")
                        resp.read()
                    ok = True
                except Exception:  # noqa: BLE001 — shed/failure is a datum
                    by, ok = None, False
                with rec_lock:
                    records.append(
                        (time.time(), (time.time() - t0) * 1e3, by, ok))
                i += 1
                time.sleep(0.05)

        def add_workers(n):
            for _ in range(n):
                t = threading.Thread(target=worker, args=(len(workers),),
                                     daemon=True)
                workers.append(t)
                t.start()

        add_workers(2)          # baseline offered load
        time.sleep(3.0)
        # Surge: the lone engine saturates (120ms >> the 40ms objective)
        # AND the offered load doubles.
        req = urllib.request.Request(
            f"{engines['base-engine-0']}/admin/fail",
            data=json.dumps({"mode": "slow", "delay": 0.12,
                             "count": -1}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=5):
            pass
        surge_start = time.time()
        add_workers(2)
        sig = wait_signal(router_url, lambda s: s["replica_hint"] >= 2, 45,
                          "surge hint")
        out["surge_hint"] = sig["replica_hint"]
        operator_tick(k8s.url)
        st = k8s.bucket(PST, "tpuruntimes")["base"].get("status", {})
        if st.get("lastAutoscaleAction") != "scale_up":
            raise RuntimeError(f"operator never scaled up: {st}")
        want = int(st["desiredReplicas"])
        new_names = [
            start_engine(k8s, procs, engines, i, eport, ip_base=2)
            for i in range(1, want)
        ]
        compile_before = {n: compile_total(engines[n]) for n in new_names}
        # Absorbed: a new replica serves live traffic.
        absorb_deadline = time.time() + 60
        absorb_end = None
        while absorb_end is None and time.time() < absorb_deadline:
            with rec_lock:
                tail = records[-20:]
            if any(by in new_names for _, _, by, _ in tail):
                absorb_end = time.time()
            else:
                time.sleep(0.2)
        if absorb_end is None:
            raise RuntimeError("new replica never took traffic")
        time.sleep(2.0)         # post-absorb sample window
        stop_load.set()
        for t in workers:
            t.join(timeout=30)
        cold = sum(compile_total(engines[n]) - compile_before[n]
                   for n in new_names)
        with rec_lock:
            absorb_window = [r for r in records if r[0] >= surge_start]
        p99 = pct([ms for _, ms, _, ok in absorb_window if ok], 0.99)
        failed = sum(1 for *_, ok in absorb_window if not ok)
        out.update({
            "absorb_seconds": round(absorb_end - surge_start, 2),
            "p99_during_absorb_ms": round(p99, 1) if p99 else None,
            "cold_compiles_on_new_replicas": cold,
            "replicas_after": want,
            "requests_during_absorb": len(absorb_window),
            "failed_during_absorb": failed,
        })
    finally:
        stop_load.set()
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        k8s.stop()

    # ---- wake leg: a fresh pool parks slept, first arrival wakes it -----
    # Fresh fleet on purpose: the surge leg's burn windows keep its hint
    # high for minutes, which is exactly the anti-flap conservatism the
    # actuator encodes — waiting them out would blow the phase wall.
    eport2, rport2 = 18410, 18419
    for p in (eport2, rport2):
        ensure_port_free(p)
    k8s = FakeK8s().start()
    procs = []
    engines = {}
    try:
        start_engine(k8s, procs, engines, 0, eport2, ip_base=21)
        router_url = start_router(k8s, procs, eport2, rport2, "wake")
        seed_runtime(k8s, {"minReplicas": 1, "maxReplicas": 2,
                           "scaleDownStabilizationS": 0, "idleVerdicts": 1,
                           "scaleToZero": True})
        wait_signal(router_url, lambda s: s["engines_ready"] == 1
                    and s["in_flight_total"] == 0, 30, "wake-leg discovery")
        operator_tick(k8s.url)
        st = k8s.bucket(PST, "tpuruntimes")["base"].get("status", {})
        if st.get("lastAutoscaleAction") != "sleep":
            raise RuntimeError(f"pool never parked slept: {st}")
        t0 = time.time()
        req = urllib.request.Request(
            f"{router_url}/v1/completions",
            data=json.dumps({"model": model, "prompt": "wake",
                             "max_tokens": 4, "stream": True}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=60) as resp:
            resp.read(16)       # first streamed token bytes
            wake_s = time.time() - t0
            resp.read()
        out["wake_to_first_token_s"] = round(wake_s, 3)
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        k8s.stop()

    out["meets_target"] = bool(
        out.get("absorb_seconds") is not None
        and out.get("p99_during_absorb_ms") is not None
        and out["p99_during_absorb_ms"] <= slo_ms
        and out.get("cold_compiles_on_new_replicas") == 0
        and out.get("failed_during_absorb") == 0
        and out.get("wake_to_first_token_s") is not None
        and out["wake_to_first_token_s"] < 10.0
    )
    return out


def run_tenant_phase() -> dict:
    """Tenant flood isolation (docs/multi-tenancy.md): the real router
    with --tenant-isolation over two fake engines; a victim tenant paces
    steady traffic while a flooder offers ~10x its admitted share. The
    headline numbers are the victim's p50/p99 with and without the flood
    and the isolation delta — the ≤10% guarantee BENCH rounds capture as
    driver evidence (per-point, kill-surviving, like the fleet phase).
    """
    model = "fake/model"
    env = dict(os.environ, PYTHONPATH=REPO)
    base_port = 18400
    eports = [base_port, base_port + 1]
    rport = base_port + 2
    for p in eports + [rport]:
        ensure_port_free(p)
    tenant_file = "/tmp/pst_bench_tenants.json"
    with open(tenant_file, "w") as f:
        json.dump({"tenants": {
            "victim": {"weight": 1, "tier": "interactive"},
            "flooder": {"weight": 1, "tier": "interactive"},
        }}, f)
    procs = []
    try:
        for i, p in enumerate(eports):
            lg = f"/tmp/pst_tenant_engine_{p}.log"
            procs.append(subprocess.Popen(
                [sys.executable, "-m",
                 "production_stack_tpu.testing.fake_engine",
                 "--port", str(p), "--model", model,
                 "--speed", "40", "--ttft", "0.02",
                 "--name", f"tenant-{i}"],
                stdout=open(lg, "w"), stderr=subprocess.STDOUT,
                cwd=REPO, env=env,
            ))
            if not wait_http(f"http://127.0.0.1:{p}/health", 60,
                             proc=procs[-1], log_path=lg):
                raise RuntimeError(f"tenant fake engine :{p} not healthy")
        rlog = "/tmp/pst_tenant_router.log"
        router = subprocess.Popen(
            [sys.executable, "-m", "production_stack_tpu.router.app",
             "--port", str(rport),
             "--service-discovery", "static",
             "--static-backends",
             ",".join(f"http://127.0.0.1:{p}" for p in eports),
             "--static-models", ",".join([model] * len(eports)),
             "--routing-logic", "roundrobin",
             "--engine-stats-interval", "1",
             "--tenant-isolation",
             "--tenant-config", tenant_file,
             "--admission-rate", "30",
             "--admission-queue-timeout", "0.3"],
            stdout=open(rlog, "w"), stderr=subprocess.STDOUT,
            cwd=REPO, env=env,
        )
        procs.append(router)
        if not wait_http(f"http://127.0.0.1:{rport}/health", 60,
                         proc=router, log_path=rlog):
            raise RuntimeError("tenant router not healthy")

        import aiohttp

        base = f"http://127.0.0.1:{rport}"
        engine_urls = [f"http://127.0.0.1:{p}" for p in eports]
        collector = forensics_collector()
        stall_injected = os.environ.get("PST_BENCH_INJECT_STALL") == "1"
        if stall_injected:
            # CI's induced r05 signature: a one-shot N-ms decode stall on
            # the first engine — the victim leg's p99 blows past 3x its
            # p50 and the collector below must harvest a bundle naming
            # the stalled bucket + queue state.
            stall_s = float(os.environ.get("PST_BENCH_STALL_S", "1.5"))
            req = urllib.request.Request(
                f"{engine_urls[0]}/admin/fail",
                data=json.dumps({"mode": "stall", "delay": stall_s,
                                 "count": 1}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=5) as resp:
                resp.read()
            log(f"tenants: armed one-shot {stall_s}s stall on engine 0")
        metrics_baseline = collector.mark(engine_urls + [base])

        async def one(session, tenant, max_tokens=4):
            t0 = time.monotonic()
            async with session.post(
                f"{base}/v1/completions",
                json={"model": model, "prompt": f"{tenant} q",
                      "max_tokens": max_tokens},
                headers={"X-PST-Tenant": tenant},
            ) as resp:
                await resp.read()
                return resp.status, time.monotonic() - t0

        async def victim_phase(session, n=40, pace=0.05):
            lat, shed = [], 0
            for _ in range(n):
                status, dt = await one(session, "victim")
                if status == 200:
                    lat.append(dt)
                else:
                    shed += 1
                await asyncio.sleep(pace)
            return lat, shed

        async def drive() -> dict:
            async with aiohttp.ClientSession() as session:
                baseline, base_shed = await victim_phase(session)
                stop = asyncio.Event()

                async def flood():
                    tasks = []
                    while not stop.is_set():
                        tasks.append(asyncio.create_task(
                            one(session, "flooder", max_tokens=1)
                        ))
                        await asyncio.sleep(0.01)  # ~100 rps offered
                    done = await asyncio.gather(
                        *tasks, return_exceptions=True
                    )
                    return [d[0] for d in done if isinstance(d, tuple)]

                flood_task = asyncio.create_task(flood())
                await asyncio.sleep(0.3)
                flooded, flood_shed = await victim_phase(session)
                stop.set()
                statuses = await flood_task
                return {
                    "baseline": baseline, "flooded": flooded,
                    "victim_sheds": base_shed + flood_shed,
                    "flood_offered": len(statuses),
                    "flood_shed": sum(1 for s in statuses if s == 429),
                }

        res = asyncio.run(drive())

        def pct(samples, q):
            if not samples:
                return None
            ordered = sorted(samples)
            return ordered[min(int(len(ordered) * q), len(ordered) - 1)]

        base_p99 = pct(res["baseline"], 0.99)
        flood_p99 = pct(res["flooded"], 0.99)
        delta = (
            (flood_p99 - base_p99) / base_p99
            if base_p99 and flood_p99 else None
        )
        # Tail forensics while the stack is still alive: a leg whose p99
        # blows past 3x its p50 (the injected stall, or a real isolation
        # failure) harvests flight snapshots, worst traces, fleet state
        # and metrics deltas into the run's evidence dir.
        evidence = []
        for leg, samples in (("baseline", res["baseline"]),
                             ("flooded", res["flooded"])):
            p50_s, p99_s = pct(samples, 0.5), pct(samples, 0.99)
            bundle = collector.maybe_collect(
                "tenants", leg,
                p50_s * 1e3 if p50_s else None,
                p99_s * 1e3 if p99_s else None,
                engines=engine_urls, router=base,
                baseline=metrics_baseline,
                detail={"stall_injected": stall_injected},
            )
            if bundle:
                evidence.append(bundle)
                log(f"forensics: tenants/{leg} tail bar crossed "
                    f"-> {bundle}")
        return {
            "evidence_bundles": evidence,
            "victim_p50_ms": round(pct(res["baseline"], 0.5) * 1e3, 1),
            "victim_p99_ms": round(base_p99 * 1e3, 1),
            "flood_victim_p50_ms": round(pct(res["flooded"], 0.5) * 1e3, 1),
            "flood_victim_p99_ms": round(flood_p99 * 1e3, 1),
            "p99_delta_frac": round(delta, 4) if delta is not None else None,
            "victim_sheds": res["victim_sheds"],
            "flood_offered": res["flood_offered"],
            "flood_shed": res["flood_shed"],
            "target_delta_frac": 0.10,
            # The guarantee: victim p99 moved <= 10%, no victim sheds,
            # and the flood really was over its share (mostly 429s).
            "meets_target": bool(
                delta is not None and delta <= 0.10
                and res["victim_sheds"] == 0
                and res["flood_shed"] > res["flood_offered"] * 0.5
            ),
        }
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()


def run_disagg_phase() -> dict:
    """Disaggregated P/D pools vs the fused fleet (docs/disagg.md): the
    SAME four fake engines under the chip queueing model
    (--chip-ms-per-ktok: prefill slices and decode slices serialize per
    engine — the head-of-line interference disagg removes), driven at the
    same offered qps through the real router twice — once fused, once as
    2 prefill + 2 decode pools with the streamed KV handoff over a real
    kvserver. Headline: p99 TTFT paired delta at the high-qps point while
    holding tokens/s/chip, plus the overlap fraction and the fallback
    count (must be zero on a healthy run)."""
    import aiohttp

    import socket

    model = "fake/model"
    env = dict(os.environ, PYTHONPATH=REPO)
    # Env-tunable so --tiny (and CI's bench-smoke) can shrink the load
    # without forking the protocol.
    n_requests = int(os.environ.get("PST_BENCH_DISAGG_REQUESTS", "150"))
    offered_qps = float(os.environ.get("PST_BENCH_DISAGG_QPS", "24.0"))
    # Mixed workload: heavy prefills (the head-of-line blockers) and
    # light TTFT-sensitive requests, Poisson arrivals — the tail of the
    # light class is where fused interference shows.
    heavy_prompt = "payload words " * 250    # ~500 fake tokens
    light_prompt = "payload words " * 50     # ~100 fake tokens
    heavy_tokens, light_tokens = 64, 8

    def free_port() -> int:
        # Ephemeral allocation instead of the fixed-port + ensure_port_free
        # pattern: this phase runs two back-to-back stacks and the first
        # one's TIME_WAIT sockets would trip the fixed check; a port the
        # kernel just handed out cannot hide a stale server.
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def measure(tag: str, pools, kv_url, mid_load=None) -> dict:
        ports = [free_port() for _ in range(5)]
        rport = ports[-1]
        procs = []
        try:
            for i, p in enumerate(ports[:-1]):
                lg = f"/tmp/pst_disagg_engine_{tag}_{p}.log"
                procs.append(subprocess.Popen(
                    [sys.executable, "-m",
                     "production_stack_tpu.testing.fake_engine",
                     "--port", str(p), "--model", model,
                     "--speed", "200", "--name", f"{tag}-{i}",
                     "--chip-ms-per-ktok", "60",
                     "--kv-url", kv_url],
                    stdout=open(lg, "w"), stderr=subprocess.STDOUT,
                    cwd=REPO, env=env,
                ))
            for p in ports[:-1]:
                if not wait_http(f"http://127.0.0.1:{p}/health", 60):
                    raise RuntimeError(f"disagg fake engine :{p} not healthy")
            rlog = f"/tmp/pst_disagg_router_{tag}.log"
            args = [
                sys.executable, "-m", "production_stack_tpu.router.app",
                "--port", str(rport),
                "--service-discovery", "static",
                "--static-backends",
                ",".join(f"http://127.0.0.1:{p}" for p in ports[:-1]),
                "--static-models", ",".join([model] * len(ports[:-1])),
                "--routing-logic", "roundrobin",
                "--engine-stats-interval", "1",
            ]
            if pools:
                args += ["--static-pools", ",".join(pools)]
            procs.append(subprocess.Popen(
                args, stdout=open(rlog, "w"), stderr=subprocess.STDOUT,
                cwd=REPO, env=env,
            ))
            if not wait_http(f"http://127.0.0.1:{rport}/health", 60,
                             log_path=rlog):
                raise RuntimeError(f"disagg router ({tag}) not healthy")
            base = f"http://127.0.0.1:{rport}"

            async def one(session, i: int) -> dict:
                heavy = i % 2 == 0
                t0 = time.monotonic()
                ttft = None
                tokens = 0
                async with session.post(
                    f"{base}/v1/completions",
                    json={"model": model,
                          "prompt": heavy_prompt if heavy else light_prompt,
                          "max_tokens": (heavy_tokens if heavy
                                         else light_tokens),
                          "stream": True},
                ) as resp:
                    ok = resp.status == 200
                    async for chunk, _ in resp.content.iter_chunks():
                        if chunk.strip():
                            if ttft is None:
                                ttft = time.monotonic() - t0
                            tokens += chunk.count(b'"text"')
                return {"ok": ok, "ttft": ttft,
                        "wall": time.monotonic() - t0, "tokens": tokens}

            async def drive() -> list:
                # Poisson arrivals with a FIXED seed: both modes see the
                # same arrival sequence (paired design).
                import random as _random

                rng = _random.Random(17)
                gaps = [rng.expovariate(offered_qps)
                        for _ in range(n_requests)]
                async with aiohttp.ClientSession() as session:
                    tasks = []
                    for i in range(n_requests):
                        if mid_load is not None and i == n_requests // 3:
                            mid_load()  # e.g. SIGKILL a kvserver shard
                        tasks.append(asyncio.create_task(one(session, i)))
                        await asyncio.sleep(gaps[i])
                    return await asyncio.gather(*tasks)

            t_start = time.monotonic()
            results = asyncio.run(drive())
            wall = time.monotonic() - t_start
            with urllib.request.urlopen(f"{base}/metrics", timeout=5) as r:
                metrics = r.read().decode()
            # Engine-side fused fallbacks (prefetch timed out → local
            # recompute) never reach the router's counter: a "healthy"
            # run gate blind to them would pass with zero KV actually
            # transferred.
            engine_fallbacks = 0
            published = prefetched = 0
            for p in ports[:-1]:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{p}/debug/state", timeout=5
                ) as r:
                    st = json.loads(r.read())
                engine_fallbacks += int(st.get("kv_transfer_fallbacks", 0))
                published += int(st.get("kv_published_blocks", 0))
                prefetched += int(st.get("kv_prefetched_blocks", 0))
            # Tail forensics while this leg's stack is still alive: an
            # unexplained e2e tail here harvests live evidence (the
            # engines are torn down in the finally below).
            ttfts = sorted(r["ttft"] for r in results
                           if r["ok"] and r["ttft"] is not None)
            if ttfts:
                q = lambda f: ttfts[min(int(len(ttfts) * f),  # noqa: E731
                                        len(ttfts) - 1)]
                bundle = forensics_collector().maybe_collect(
                    "disagg", tag, q(0.5) * 1e3, q(0.99) * 1e3,
                    engines=[f"http://127.0.0.1:{p}" for p in ports[:-1]],
                    router=base,
                    detail={"offered_qps": offered_qps,
                            "n_requests": n_requests},
                )
                if bundle:
                    log(f"forensics: disagg/{tag} tail bar crossed "
                        f"-> {bundle}")
            return {"results": results, "wall": wall, "metrics": metrics,
                    "engine_fallbacks": engine_fallbacks,
                    "published": published, "prefetched": prefetched}
        finally:
            for proc in procs:
                if proc.poll() is None:
                    proc.send_signal(signal.SIGTERM)
            for proc in procs:
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()

    def mval(text: str, name: str, label: str = "") -> float:
        for line in text.splitlines():
            if line.startswith(name) and (not label or label in line):
                return float(line.rsplit(" ", 1)[1])
        return 0.0

    def pct(samples, q):
        ordered = sorted(samples)
        return ordered[min(int(len(ordered) * q), len(ordered) - 1)]

    kv_port = free_port()
    kv_proc = subprocess.Popen(
        [sys.executable, "-m", "production_stack_tpu.kvserver.server",
         "--host", "127.0.0.1", "--port", str(kv_port)],
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
        cwd=REPO, env=env,
    )
    try:
        kv_url = f"http://127.0.0.1:{kv_port}"
        if not wait_http(f"{kv_url}/health", 30):
            raise RuntimeError("disagg kvserver not healthy")
        fused = measure("fused", None, kv_url)
        disagg = measure(
            "disagg", ["prefill", "prefill", "decode", "decode"], kv_url,
        )
    finally:
        if kv_proc.poll() is None:
            kv_proc.send_signal(signal.SIGTERM)
        try:
            kv_proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            kv_proc.kill()

    # kvserver_kill variant (docs/kvserver.md degradation matrix): the
    # same P/D pools over a 3-shard replicated ring (R=2); one shard is
    # SIGKILLed a third of the way through the offered load. The guarantee
    # under test: zero fused fallbacks and a prefetch hit rate within 5%
    # of the healthy-ring baseline.
    shard_ports = [free_port() for _ in range(3)]
    shard_urls = [f"http://127.0.0.1:{p}" for p in shard_ports]
    shard_procs = [
        subprocess.Popen(
            [sys.executable, "-m", "production_stack_tpu.kvserver.server",
             "--host", "127.0.0.1", "--port", str(p),
             "--peers", ",".join(shard_urls),
             "--self-url", shard_urls[i],
             "--replication", "2", "--sweep-interval-s", "1"],
            stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
            cwd=REPO, env=env,
        )
        for i, p in enumerate(shard_ports)
    ]
    try:
        for u in shard_urls:
            if not wait_http(f"{u}/health", 30):
                raise RuntimeError("disagg kvserver shard not healthy")
        chaos = measure(
            "shardkill", ["prefill", "prefill", "decode", "decode"],
            ",".join(shard_urls), mid_load=shard_procs[1].kill,
        )
    finally:
        for proc in shard_procs:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for proc in shard_procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()

    def summarize(run) -> dict:
        oks = [r for r in run["results"] if r["ok"] and r["ttft"] is not None]
        toks = sum(r["tokens"] for r in run["results"])
        return {
            "ok": len(oks),
            "p50": pct([r["ttft"] for r in oks], 0.5) if oks else None,
            "p99": pct([r["ttft"] for r in oks], 0.99) if oks else None,
            "tok_s_chip": toks / run["wall"] / 4.0,
        }

    f, d = summarize(fused), summarize(disagg)
    requests_ok = f["ok"] == n_requests and d["ok"] == n_requests
    overlap_sum = mval(disagg["metrics"], "pst_disagg_overlap_seconds_sum")
    transfer_sum = mval(disagg["metrics"], "pst_disagg_transfer_seconds_sum")
    fallbacks = sum(
        mval(disagg["metrics"], "pst_disagg_fallback_total",
             f'reason="{reason}"')
        for reason in ("prefill_error", "no_decode_backend", "deadline")
    ) + disagg.get("engine_fallbacks", 0)
    tok_delta = (
        (d["tok_s_chip"] - f["tok_s_chip"]) / f["tok_s_chip"]
        if f["tok_s_chip"] else None
    )

    def hit_rate(run) -> float:
        return run["prefetched"] / run["published"] if run["published"] else 0.0

    chaos_ok = sum(1 for r in chaos["results"] if r["ok"])
    chaos_fallbacks = int(
        sum(
            mval(chaos["metrics"], "pst_disagg_fallback_total",
                 f'reason="{reason}"')
            for reason in ("prefill_error", "no_decode_backend", "deadline")
        ) + chaos.get("engine_fallbacks", 0)
    )
    hit_rate_delta = round(hit_rate(chaos) - hit_rate(disagg), 4)
    kvserver_kill = {
        "requests_ok": chaos_ok == n_requests,
        "fallbacks": chaos_fallbacks,
        "hit_rate_healthy": round(hit_rate(disagg), 4),
        "hit_rate_shard_killed": round(hit_rate(chaos), 4),
        "hit_rate_delta": hit_rate_delta,
        # One dead shard of three at R=2: every request still serves,
        # nothing degrades to the fused path, and the transfer hit rate
        # holds within 5 points of the healthy ring.
        "meets_target": bool(
            chaos_ok == n_requests
            and chaos_fallbacks == 0
            and abs(hit_rate_delta) <= 0.05
        ),
    }
    return {
        "offered_qps": offered_qps,
        "requests": n_requests,
        "requests_ok": requests_ok,
        "p50_ttft_fused_ms": round(f["p50"] * 1e3, 1) if f["p50"] else None,
        "p99_ttft_fused_ms": round(f["p99"] * 1e3, 1) if f["p99"] else None,
        "p50_ttft_disagg_ms": round(d["p50"] * 1e3, 1) if d["p50"] else None,
        "p99_ttft_disagg_ms": round(d["p99"] * 1e3, 1) if d["p99"] else None,
        "tok_s_chip_fused": round(f["tok_s_chip"], 2),
        "tok_s_chip_disagg": round(d["tok_s_chip"], 2),
        "tok_s_chip_delta_frac": (
            round(tok_delta, 4) if tok_delta is not None else None
        ),
        "overlap_fraction": (
            round(overlap_sum / transfer_sum, 4) if transfer_sum else 0.0
        ),
        "fallbacks": int(fallbacks),
        "kvserver_kill": kvserver_kill,
        "target_tok_delta_frac": 0.05,
        # The guarantee: P/D pools beat the fused fleet on p99 TTFT at
        # this qps while holding tokens/s/chip within 5%, with every
        # request served and zero fused-path fallbacks.
        "meets_target": bool(
            requests_ok
            and f["p99"] is not None and d["p99"] is not None
            and d["p99"] < f["p99"]
            and tok_delta is not None and abs(tok_delta) <= 0.05
            and fallbacks == 0
        ),
    }


def probe_backend() -> str:
    """The backend a child process gets, from a child (this file never
    imports jax). A probe that fails or prints nothing is an error — it
    used to read as "cpu"."""
    proc = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.default_backend())"],
        stdout=subprocess.PIPE, text=True, env=child_env(), timeout=120,
    )
    out = proc.stdout.strip()
    if proc.returncode != 0 or not out:
        raise RuntimeError(
            f"backend probe failed (rc={proc.returncode}, "
            f"stdout={proc.stdout!r}): cannot tell which device the "
            "chip-owning phases would run on"
        )
    return out.splitlines()[-1]


# The watchdog thread and the main thread both emit; without the lock a
# T−30s force-emit could interleave with a phase emit and the "last
# stdout line is parseable JSON" contract would be the casualty.
_EMIT_LOCK = threading.Lock()


def emit(out: dict) -> None:
    """Emit the (cumulative) result: one JSON line on stdout per phase —
    the LAST stdout line is always a complete, parseable JSON object, so
    a harness that kills this process mid-run still parses every phase
    that finished — plus an atomic copy at $PST_BENCH_OUT when set."""
    with _EMIT_LOCK:
        print(json.dumps(out), flush=True)
        path = os.environ.get("PST_BENCH_OUT")
        if not path:
            return
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(out, f)
            os.replace(tmp, path)
        except OSError as e:
            log(f"could not write {path}: {e}")


_FORENSICS = None


def forensics_collector():
    """Lazy singleton: every phase shares one collector so its bundle
    list (and the evidence dir) is run-scoped, not phase-scoped."""
    global _FORENSICS
    if _FORENSICS is None:
        from production_stack_tpu.obs.forensics import (
            ForensicsCollector, evidence_dir_for,
        )

        _FORENSICS = ForensicsCollector(
            evidence_dir_for(os.environ.get("PST_BENCH_OUT"))
        )
    return _FORENSICS


def engine_snapshot_dir() -> str:
    """Where the engine child persists flight snapshots (the post-mortem
    forensics path): inside the run's evidence dir so bundles and their
    raw snapshots travel together."""
    return os.environ.get(
        "PST_BENCH_FLIGHT_SNAPSHOT_DIR",
        os.path.join(forensics_collector().evidence_dir, "engine_flight"),
    )


def collect_engine_tail_evidence(engine_res: dict) -> list:
    """Post-mortem forensics over the engine phase's sweep points: the
    child is gone by the time its JSON is parsed, so a tail-outlier point
    is matched against whatever snapshots
    the engine persisted to --flight-snapshot-dir before dying."""
    from production_stack_tpu.obs.forensics import crosses_tail_bar

    collector = forensics_collector()
    snap_dir = engine_snapshot_dir()
    bundles = []
    sweeps = [(engine_res.get("model") or "flagship",
               engine_res.get("sweep") or [])]
    for key in ("concurrency_8users", "llama_1b"):
        sub = engine_res.get(key)
        if isinstance(sub, dict):
            sweeps.append((key, sub.get("sweep") or []))
    for tag, sweep in sweeps:
        for p in sweep:
            if not isinstance(p, dict):
                continue
            trigger = crosses_tail_bar(
                p.get("p50_ttft_ms"), p.get("p99_ttft_ms")
            )
            if trigger is None:
                continue
            path = collector.collect_postmortem(
                f"engine_{tag}", f"qps{p.get('qps')}",
                snapshot_dirs=[snap_dir],
                detail={"trigger": trigger, **p},
            )
            if path:
                bundles.append(path)
                log(f"forensics: engine tail outlier ({tag} qps "
                    f"{p.get('qps')}) -> {path}")
    return bundles


def assemble(engine_res: dict, stack, fleet, tenants=None, cost=None,
             disagg=None, autoscale=None) -> dict:
    flag = engine_res.get("flagship", {})
    p50 = flag.get("p50_ttft_ms")
    return {
        "metric": "p50_ttft_warm",
        "value": p50,
        "unit": "ms",
        "vs_baseline": (
            round(TTFT_TARGET_S * 1e3 / p50, 3) if p50 else None
        ),
        "backend": engine_res.get("backend", "unknown"),
        "rpc_floor_ms": engine_res.get("rpc_floor_ms"),
        **{k: v for k, v in flag.items() if k != "p50_ttft_ms"},
        "concurrency_8users": engine_res.get("concurrency_8users"),
        "llama_1b": engine_res.get("llama_1b"),
        # Warmup story: restart_to_ready_seconds for a warm restart against
        # the persistent compile cache, and the run-level compile-pollution
        # verdict --require-warm enforces. Partial engine results may lack
        # the run-level verdict — fall back to the flagship phase's flag
        # so pollution is never hidden by a truncated run.
        "warm_restart": engine_res.get("warm_restart"),
        "compile_polluted": engine_res.get(
            "compile_polluted", flag.get("compile_polluted")
        ),
        "stack": stack,
        "fleet": fleet,
        "tenants": tenants,
        "cost": cost,
        "disagg": disagg,
        "autoscale": autoscale,
    }


def parse_time_budget(argv) -> float:
    """--time-budget SECONDS (or PST_BENCH_TIME_BUDGET): total wall this
    run may spend, carved into per-phase walls. 0 = unbudgeted."""
    for i, a in enumerate(argv):
        if a == "--time-budget" and i + 1 < len(argv):
            return float(argv[i + 1])
        if a.startswith("--time-budget="):
            return float(a.split("=", 1)[1])
    return float(os.environ.get("PST_BENCH_TIME_BUDGET", "0") or 0)


# Relative phase weights for budget carving (engine dominates: it pays
# the XLA warmup; the stack-side phases are fake-engine-cheap and the
# cost audit runs the tiny model).
_PHASE_WEIGHTS = {"engine": 6.0, "stack": 1.5, "fleet": 1.5, "tenants": 1.0,
                  "disagg": 1.0, "autoscale": 1.0, "cost": 0.5}
# Phases whose children own the chip (the rest time fake engines on the
# CPU): a failure in one of these fails the run.
_CHIP_PHASES = ("engine", "stack")


def finalize(state: dict, extra: dict = None) -> dict:
    """Assemble the cumulative result PLUS the verdicts block — the
    shape every terminal emit (normal, watchdog, interrupted) shares, so
    the driver's last-line parse always finds the same contract."""
    out = assemble(state["engine"], state["stack"], state["fleet"],
                   state["tenants"], state["cost"], state["disagg"],
                   state.get("autoscale"))
    if _FORENSICS is not None and _FORENSICS.bundles:
        out["evidence_bundles"] = list(_FORENSICS.bundles)
    if extra:
        out.update(extra)
    if state.get("watchdog_fired"):
        out["watchdog_fired"] = True
    try:
        from benchmarks.verdicts import evaluate_round

        out["verdicts"] = evaluate_round(out)
    except Exception as e:  # noqa: BLE001 — verdicts must not kill the emit
        out["verdicts"] = {"ok": False, "error": f"verdicts failed: {e}"}
    return out


def start_watchdog(budget: TimeBudget, state: dict,
                   lead: float = WATCHDOG_LEAD_S) -> threading.Event:
    """Arm the T−lead force-emit (the r05 hole: rc 124 with nothing on
    stdout). If the run is still going ``lead`` seconds before the
    budget's wall, the watchdog emits the partial result under the emit
    lock and SIGTERMs the main thread so it unwinds through the phase
    cleanups to the final emit. Returns the stop event the happy path
    sets before its own terminal emit."""
    stop = threading.Event()

    def _fire() -> None:
        delay = max(budget.remaining() - lead, 0.5)
        if stop.wait(delay):
            return
        state["watchdog_fired"] = True
        log(f"watchdog: T-{lead:.0f}s before the wall — force-emitting "
            "the partial result and interrupting the run")
        emit(finalize(state, {"partial": True}))
        os.kill(os.getpid(), signal.SIGTERM)

    threading.Thread(target=_fire, daemon=True,
                     name="bench-watchdog").start()
    return stop


def main() -> None:
    # --all is accepted for driver ergonomics and is the default anyway:
    # every phase (engine, stack, fleet, tenants, cost) runs unless its
    # PST_BENCH_SKIP_* env is set.
    # --require-warm (or PST_BENCH_REQUIRE_WARM=1): the engine phase exits
    # nonzero when any measured sweep point absorbs a cold XLA compile, and
    # this process mirrors the verdict after emitting the full result.
    require_warm = "--require-warm" in sys.argv[1:] or (
        os.environ.get("PST_BENCH_REQUIRE_WARM") == "1"
    )
    if require_warm:
        os.environ["PST_BENCH_REQUIRE_WARM"] = "1"
    # --tiny (or PST_BENCH_TINY=1): the CPU smoke profile CI's
    # bench-smoke job runs — small pair counts, light disagg load, a
    # 240 s budget. Only missing knobs are defaulted, so a caller can
    # still pin any of them.
    tiny = "--tiny" in sys.argv[1:] or os.environ.get("PST_BENCH_TINY") == "1"
    if tiny:
        os.environ["PST_BENCH_TINY"] = "1"
        os.environ.setdefault("PST_BENCH_CPU", "1")
        os.environ.setdefault("PST_BENCH_PAIRS", "40")
        os.environ.setdefault("PST_BENCH_PAIRS_R2", "24")
        os.environ.setdefault("PST_BENCH_DISAGG_REQUESTS", "40")
        os.environ.setdefault("PST_BENCH_DISAGG_QPS", "12.0")
    total = parse_time_budget(sys.argv[1:])
    if total <= 0:
        # Never run unbudgeted: r05's rc:124 was an unbudgeted run hitting
        # the driver's external wall mid-bring-up with nothing flushed.
        total = TINY_TIME_BUDGET_S if tiny else DEFAULT_TIME_BUDGET_S
        log(f"no --time-budget given; defaulting to {total:.0f}s "
            f"({'tiny' if tiny else 'full'} profile)")
    budget = TimeBudget(total)
    install_term_trap()
    interrupted = False
    weights_left = sum(_PHASE_WEIGHTS.values())
    state = {"engine": {"backend": "unknown"}, "stack": None, "fleet": None,
             "tenants": None, "cost": None, "disagg": None, "autoscale": None}
    watchdog_stop = start_watchdog(budget, state)

    engine_res = {"backend": "unknown"}
    try:
        if os.environ.get("PST_BENCH_SKIP_ENGINE") == "1":  # stack-only debug
            engine_res = {"backend": probe_backend()}
        else:
            if budget.enabled:
                # The engine child enforces its own wall (and flushes its
                # partial) via the existing timeout env + its budget env.
                wall = budget.phase_wall(
                    _PHASE_WEIGHTS["engine"], weights_left
                )
                os.environ["PST_BENCH_ENGINE_TIMEOUT"] = str(int(wall) + 60)
                os.environ["PST_BENCH_ENGINE_BUDGET"] = str(int(wall))
            engine_res = run_engine_phase()
    except BenchInterrupted as e:
        log(f"engine phase interrupted ({e}); flushing partial result")
        partial = read_partial(os.environ.get(
            "PST_BENCH_ENGINE_OUT", "/tmp/pst_bench_engine_partial.json"
        ))
        engine_res = partial or engine_res
        engine_res["partial"] = True
        engine_res["error"] = f"interrupted: {e}"
        interrupted = True
    weights_left -= _PHASE_WEIGHTS["engine"]
    # Chip-owning phases (engine, stack) that failed — as opposed to being
    # cut by the budget — fail the run after the JSON is out.
    failed_chip_phases = ["engine"] if engine_res.get("failed") else []
    backend = engine_res.get("backend", "unknown")
    on_tpu = backend == "tpu"
    state["engine"] = engine_res
    emit(assemble(engine_res, None, None))
    try:
        # Post-mortem forensics: tail-outlier sweep points matched to the
        # flight snapshots the (now dead) engine child persisted.
        collect_engine_tail_evidence(engine_res)
    except Exception as e:  # noqa: BLE001 — evidence is best-effort
        log(f"forensics: engine tail scan failed: {e}")

    def run_phase(key, fn):
        """One budget-walled stack-side phase: skipped outright when the
        budget is gone, marked partial when the wall (or a SIGTERM) cut
        it short — the final JSON always says what happened."""
        nonlocal interrupted, weights_left
        weight = _PHASE_WEIGHTS[key]
        try:
            if interrupted or budget.exhausted():
                # Say WHICH wall cut the run: an external SIGTERM is not
                # a misconfigured budget.
                return {"partial": True,
                        "skipped": ("interrupted" if interrupted
                                    else "time budget exhausted")}
            if budget.enabled:
                phase_alarm(budget.phase_wall(weight, weights_left))
            try:
                return fn()
            finally:
                phase_alarm(0.0)
        except BenchInterrupted as e:
            log(f"{key} phase interrupted ({e})")
            interrupted = str(e).startswith("signal 15")
            return {"partial": True, "error": f"interrupted: {e}"}
        except Exception as e:  # noqa: BLE001 — later phases still run
            log(f"{key} phase failed: {e}")
            if key in _CHIP_PHASES:
                failed_chip_phases.append(key)
            return {"error": str(e)}
        finally:
            weights_left -= weight

    stack = None
    if os.environ.get("PST_BENCH_SKIP_STACK") != "1":
        stack = run_phase("stack", lambda: run_stack_phase(on_tpu))
        state["stack"] = stack
        emit(assemble(engine_res, stack, None))

    fleet = None
    if os.environ.get("PST_BENCH_SKIP_FLEET") != "1":
        fleet = run_phase("fleet", run_fleet_phase)
        state["fleet"] = fleet
        emit(assemble(engine_res, stack, fleet))

    tenants = None
    if os.environ.get("PST_BENCH_SKIP_TENANTS") != "1":
        tenants = run_phase("tenants", run_tenant_phase)
        state["tenants"] = tenants
        emit(assemble(engine_res, stack, fleet, tenants))

    disagg = None
    if os.environ.get("PST_BENCH_SKIP_DISAGG") != "1":
        disagg = run_phase("disagg", run_disagg_phase)
        state["disagg"] = disagg
        emit(assemble(engine_res, stack, fleet, tenants, disagg=disagg))

    autoscale = None
    if os.environ.get("PST_BENCH_SKIP_AUTOSCALE") != "1":
        autoscale = run_phase("autoscale", run_autoscale_phase)
        state["autoscale"] = autoscale
        emit(assemble(engine_res, stack, fleet, tenants, disagg=disagg,
                      autoscale=autoscale))

    cost = None
    if os.environ.get("PST_BENCH_SKIP_COST") != "1":
        cost = run_phase("cost", run_cost_phase)
        state["cost"] = cost

    watchdog_stop.set()
    emit(finalize(state, {"interrupted": True} if interrupted else None))
    # Same fallback as assemble(): a truncated engine phase may carry only
    # per-phase pollution flags, never the run-level verdict — the exit
    # gate must not be laxer than the emitted JSON.
    polluted = engine_res.get("compile_polluted") or any(
        isinstance(v, dict) and v.get("compile_polluted")
        for v in engine_res.values()
    )
    if require_warm and polluted:
        log("--require-warm: measured sweep points were compile-polluted; "
            "exiting nonzero (full result emitted above)")
        sys.exit(3)
    if failed_chip_phases:
        log(f"chip-owning phase(s) failed: {failed_chip_phases}; exiting "
            "nonzero (full result emitted above)")
        sys.exit(4)


if __name__ == "__main__":
    main()
