"""Engine-phase benchmark: the reference multi-round-QA protocol in-process.

Run as a subprocess by the top-level ``bench.py`` (it owns the chip while it
runs; the stack phase needs the chip afterwards). Prints ONE JSON object.

Phases (BASELINE.md protocol; reference `run_single.sh:12-40`):
  0. env probe   — trivial dispatch→fetch round trips (`rpc_floor_ms`):
                   the least one host↔device synchronisation costs here.
                   Reported beside TTFT as its own number, never
                   subtracted from it.
  1a. 8B TTFT sweep — llama-3-8b (int4 group-wise weights via the Pallas
                   streaming matmul + fp8 KV on one 16 GiB chip), 4 users
                   (the workload must FIT so TTFT measures the engine, not
                   eviction thrash): cold prefill → prefill probe → warm
                   compile → QPS sweep (p50/p99 + the dispatch→fetch round
                   trip per point, ≥300 requests over 6 points spanning
                   0.1-1.1) → pipelined saturated decode probe.
  1b. 8B concurrency — EIGHT 20k-history users on the same chip (more
                   live KV than HBM holds; live-KV swap rotates the
                   overflow); headline: decode_tok_per_s_chip over
                   full-width pipelined 32-step bursts.
  2. 1B secondary — llama-1b at the r1-r3 workload (8 users, qps 1.0) for
                   round-over-round comparability + its decode probe.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from production_stack_tpu.device import (  # noqa: E402
    device_spec,
    require_device_spec,
)

def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class BenchInterrupted(BaseException):
    """Raised from the SIGTERM handler (the driver's `timeout` sends
    TERM before KILL): unwinds the running phase and reaches main()'s
    final flush — an rc:124 run still prints one parseable JSON object
    as its last stdout line. BaseException so per-phase ``except
    Exception`` guards cannot swallow it."""


def install_term_trap() -> None:
    def _raise(signum, frame):
        raise BenchInterrupted(f"signal {signum}")

    signal.signal(signal.SIGTERM, _raise)


_BUDGET_T0 = time.monotonic()


def time_budget() -> float:
    """--time-budget SECONDS / $PST_BENCH_ENGINE_BUDGET: total wall this
    phase process may spend; phases that would start past it are skipped
    and marked partial. 0 = unbudgeted."""
    argv = sys.argv[1:]
    for i, a in enumerate(argv):
        if a == "--time-budget" and i + 1 < len(argv):
            return float(argv[i + 1])
        if a.startswith("--time-budget="):
            return float(a.split("=", 1)[1])
    return float(os.environ.get("PST_BENCH_ENGINE_BUDGET", "0") or 0)


def budget_remaining() -> float:
    """Seconds left in the budget; +inf when unbudgeted."""
    total = time_budget()
    if total <= 0:
        return float("inf")
    return total - (time.monotonic() - _BUDGET_T0)


def budget_exhausted(floor: float = 30.0) -> bool:
    return budget_remaining() < floor


# Observed phase walls, so later phases are gated on what THIS run's
# hardware actually costs instead of a static floor. The r05 wreck was
# exactly this hole: the second engine bring-up started near the
# driver's wall because nothing asked whether it could still fit.
_PHASE_WALLS: dict = {}


def phase_estimate(key: str, default: float = 0.0) -> float:
    """Weighted estimate for a phase about to start: 0.6 x the heaviest
    observed model-phase wall (bring-up + warmup dominate and repeat;
    sweeps shrink), floored at ``default``. Before any phase has run
    there is nothing observed and the static floor is all we have."""
    observed = max(_PHASE_WALLS.values(), default=0.0)
    return max(0.6 * observed, default)


def roofline_table(
    engine, achieved_tok_s, batch: int, ctx_tokens: int
) -> dict | None:
    """Theoretical vs achieved HBM bandwidth and tok/s/chip for the
    saturated decode probe (VERDICT round 5's acceptance artifact).

    bytes/step = resident weight bytes (read once, amortized over the
    batch) + batch x ctx x per-token KV bytes; theoretical tok/s/chip =
    peak HBM BW / (bytes/step / batch). Printed in the driver capture and
    embedded in the phase JSON so the achieved fraction is a tracked
    number, not a postmortem estimate."""
    import jax

    cfg = engine.cfg
    mc = engine.model_cfg
    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        return None  # the CPU smoke profile has no device to put a roof on
    # A chip that is not in the table is an error, not another chip's
    # bandwidth (production_stack_tpu/device.py).
    dev_kind = dev.device_kind
    bw = require_device_spec(dev_kind).hbm_gbps
    kv_itemsize = np.dtype(cfg.kv_cache_dtype or mc.dtype).itemsize
    kv_bytes_per_tok_seq = (
        2 * mc.num_layers * mc.num_kv_heads * mc.head_dim * kv_itemsize
    )
    bytes_per_step = (
        engine.runner.param_bytes + batch * ctx_tokens * kv_bytes_per_tok_seq
    )
    bytes_per_token = bytes_per_step / max(batch, 1)
    theo_tok_s = bw * 1e9 / bytes_per_token
    ach = float(achieved_tok_s or 0.0)
    frac = ach / theo_tok_s if theo_tok_s else None
    ach_gbps = ach * bytes_per_token / 1e9
    out = {
        "device_kind": dev_kind,
        "hbm_gbps_peak": round(bw, 1),
        "batch": batch,
        "ctx_tokens": ctx_tokens,
        "bytes_per_token": int(bytes_per_token),
        "theoretical_tok_per_s_chip": round(theo_tok_s, 1),
        "achieved_tok_per_s_chip": round(ach, 1) if achieved_tok_s else None,
        "achieved_fraction": round(frac, 3) if achieved_tok_s else None,
        "achieved_hbm_gbps": round(ach_gbps, 1) if achieved_tok_s else None,
    }
    log(f"roofline ({mc.name}, batch {batch} x {ctx_tokens} ctx, "
        f"{dev_kind} {bw:.0f} GB/s):")
    log(f"  tok/s/chip: theoretical {theo_tok_s:8.1f}   achieved "
        f"{ach:8.1f}   fraction {frac if frac is None else round(frac, 3)}")
    log(f"  HBM GB/s:   theoretical {bw:8.1f}   achieved {ach_gbps:8.1f}")
    return out


def write_partial(obj: dict) -> None:
    """Atomically persist the partial result to $PST_BENCH_ENGINE_OUT.

    bench.py points this at a temp file and falls back to it when the
    harness times this phase out (BENCH_r05: rc=124, parsed null) — every
    completed qps point survives the kill."""
    path = os.environ.get("PST_BENCH_ENGINE_OUT")
    if not path:
        return
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def env_probe() -> float:
    """Median trivial dispatch→fetch round trip (ms)."""
    import jax
    import jax.numpy as jnp

    x = jnp.arange(32, dtype=jnp.int32)
    f = jax.jit(lambda x, i: x + i)
    jax.block_until_ready(f(x, 0))
    vals = []
    for i in range(7):
        t0 = time.perf_counter()
        jax.device_get(f(x, i))
        vals.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(vals))


def mfu(n_params: int, rate) -> float | None:
    """2·params·tok/s over the device's published bf16 peak; None on a
    device with no row in the table (the CPU smoke profile)."""
    import jax

    spec = device_spec(jax.local_devices()[0].device_kind)
    if not rate or spec is None:
        return None
    return round(2 * n_params * rate / spec.peak_bf16_flops, 4)


def require_warm_enabled(argv=None) -> bool:
    """--require-warm / $PST_BENCH_REQUIRE_WARM: a sweep point observing a
    cold XLA compile fails the whole run (nonzero exit) instead of merely
    flagging it — what CI wants once warmup makes zero compiles the norm."""
    args = argv if argv is not None else sys.argv[1:]
    return "--require-warm" in args or (
        os.environ.get("PST_BENCH_REQUIRE_WARM") == "1"
    )


def run_model_phase(
    model: str,
    *,
    quantization=None,
    n_users: int,
    sys_len: int,
    hist_len: int,
    question_len: int,
    answer_len: int,
    num_kv_blocks,
    sweep,  # [(qps, n_rounds), ...]
    stagger,
    decode_probe_tokens: int,
    num_decode_steps: int = 4,
    adaptive: int = 16,
    block_size: int = 128,
    max_model_len: int = 32768,
    attn_impl: str = "pallas",
    kv_cache_dtype="float8_e4m3fn",
    hbm_utilization: float = 0.88,
    pipelined_probe: bool = False,
    async_decode: bool = False,
    require_warm: bool = False,
    checkpoint=None,
) -> dict:
    from benchmarks.protocol import ProtocolRunner
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.obs import ENGINE_TELEMETRY

    cfg = EngineConfig(
        model=model,
        quantization=quantization,
        max_model_len=max_model_len,
        block_size=block_size,
        num_kv_blocks=num_kv_blocks,
        hbm_utilization=hbm_utilization,
        max_num_seqs=max(2 * n_users, 8),
        max_prefill_tokens=1024,
        attn_impl=attn_impl,
        kv_cache_dtype=kv_cache_dtype,
        num_decode_steps=num_decode_steps,
        async_decode=async_decode,
        adaptive_decode_steps=adaptive,
        # Deepen only when the arrival stream pauses AND every user's
        # request is already running (closed-loop traffic: nobody is left
        # to arrive, so a deep burst cannot delay a TTFT).
        adaptive_decode_quiet_s=1.0,
        adaptive_decode_min_running=n_users,
        min_decode_bucket=min(8, n_users),
        # Forensics: tail-outlier flight snapshots persist to disk so the
        # evidence survives this process (bench.py collects post-mortem).
        flight_snapshot_dir=(
            os.environ.get("PST_BENCH_FLIGHT_SNAPSHOT_DIR") or None
        ),
    )
    t0 = time.time()
    engine = LLMEngine(cfg)
    log(f"{model}: engine up in {time.time()-t0:.1f}s, "
        f"{engine.runner.param_count/1e9:.2f}B params, "
        f"{engine.runner.num_blocks} kv pages")
    pr = ProtocolRunner(
        engine, n_users, sys_len, hist_len, question_len, answer_len
    )
    t0 = time.time()
    pr.cold_prefill()
    log(f"{model}: cold prefill {time.time()-t0:.1f}s")
    prefill_rate = pr.prefill_probe()
    log(f"{model}: warm prefill {prefill_rate:.0f} tok/s")
    pr.warm_compile(stagger)
    log(f"{model}: warm compile done")
    # Compiles so far are the expected cold/warmup set; any compile during
    # a measured point is a recompile polluting that point's TTFTs and is
    # flagged in the output.
    warmup_compiles = ENGINE_TELEMETRY.compile_count()

    points = []
    all_ttfts: list = []
    sweep_truncated = False
    round_walls: list = []  # observed seconds per protocol round
    t_meas = time.time()
    for qps, n_rounds in sweep:
        # Point-level budget gate: estimate this point's wall from the
        # rounds already measured (first point: the static floor only)
        # and refuse to start a point that cannot finish — a truncated
        # sweep with N clean points beats a killed run with none.
        if round_walls:
            est = 1.2 * n_rounds * (sum(round_walls) / len(round_walls))
        else:
            est = 0.0
        if budget_remaining() < max(est, 30.0):
            log(f"{model}: stopping sweep before qps {qps}: "
                f"~{est:.0f}s point vs {budget_remaining():.0f}s left")
            sweep_truncated = True
            break
        t_point = time.time()
        # One dispatch→fetch round trip, sampled beside each point: every
        # first token pays at least one.
        floor = env_probe()
        compiles_before = ENGINE_TELEMETRY.compile_count()
        ttfts = pr.measured_rounds(qps, n_rounds, tag=f"q{qps}")
        point_compiles = ENGINE_TELEMETRY.compile_count() - compiles_before
        p50 = float(np.percentile(ttfts, 50)) * 1e3
        p99 = float(np.percentile(ttfts, 99)) * 1e3
        points.append({
            "qps": qps,
            "n_requests": len(ttfts),
            "p50_ttft_ms": round(p50, 1),
            "p99_ttft_ms": round(p99, 1),
            "rpc_floor_ms": round(floor, 1),
            # Warm-vs-cold compile accounting: >0 means this point's
            # percentiles include XLA compile time, not engine latency.
            "compiles": point_compiles,
            "compile_polluted": point_compiles > 0,
            # Tail-outlier flag (VERDICT item 2's standing ask): a p99
            # more than 3x the point's own p50 marks an unexplained tail —
            # read it with the compile flag and engine telemetry in hand.
            "tail_outlier": p99 > 3.0 * p50,
        })
        all_ttfts.extend(ttfts)
        round_walls.append((time.time() - t_point) / max(n_rounds, 1))
        log(f"{model}: qps {qps}: {points[-1]}")
        if checkpoint is not None:
            checkpoint({
                "model": model,
                "partial": True,
                "warmup_compiles": warmup_compiles,
                "sweep": list(points),
                "n_measured_requests": len(all_ttfts),
            })
    measure_wall = time.time() - t_meas

    if budget_exhausted():
        log(f"{model}: skipping decode probe "
            f"({budget_remaining():.0f}s budget left)")
        decode_rate = None
    else:
        decode_rate = pr.decode_probe(
            max_tokens=decode_probe_tokens, pipelined=pipelined_probe
        )
    # Roofline verdict for the saturated probe: theoretical vs achieved
    # HBM GB/s and tok/s/chip at the probe's batch/context shape.
    roofline = roofline_table(
        engine, decode_rate, batch=n_users, ctx_tokens=sys_len + hist_len
    )
    floor_end = env_probe()
    n_params = engine.runner.param_count
    # A fully budget-truncated sweep has no measured points; the phase
    # still returns (bring-up numbers + the truncation marker) instead
    # of crashing on empty percentiles.
    if all_ttfts:
        raw_p50 = float(np.percentile(all_ttfts, 50)) * 1e3
        raw_p99 = float(np.percentile(all_ttfts, 99)) * 1e3
        med_floor = float(np.median([p["rpc_floor_ms"] for p in points]))
    else:
        raw_p50 = raw_p99 = med_floor = 0.0
    out = {
        "model": engine.model_cfg.name,
        "quantization": quantization,
        "kv_cache_dtype": str(cfg.kv_cache_dtype or engine.model_cfg.dtype),
        "n_users": n_users,
        "system_prompt_tokens": sys_len,
        "history_tokens": hist_len,
        "max_model_len": max_model_len,
        "p50_ttft_ms": round(raw_p50, 2),
        "p99_ttft_ms": round(raw_p99, 2),
        "rpc_floor_ms_median": round(med_floor, 1),
        "rpc_floor_ms_end": round(floor_end, 1),
        "sweep": points,
        "sweep_truncated_for_budget": sweep_truncated,
        "warmup_compiles": warmup_compiles,
        "sweep_compiles": int(sum(p["compiles"] for p in points)),
        # True when ANY measured point absorbed a cold compile — the
        # condition --require-warm turns into a nonzero exit.
        "compile_polluted": any(p["compile_polluted"] for p in points),
        "n_measured_requests": len(all_ttfts),
        "measure_wall_s": round(measure_wall, 1),
        "prefill_tok_per_s": round(prefill_rate, 1) if prefill_rate else None,
        "prefill_mfu": mfu(n_params, prefill_rate),
        "decode_tok_per_s_chip": round(decode_rate, 1) if decode_rate else None,
        "decode_mfu": mfu(n_params, decode_rate),
        "roofline": roofline,
        "prefix_cache_hit_rate": round(engine.allocator.hit_rate, 3),
    }
    stats = engine.stats()
    for k in ("kv_swap_out_total", "kv_swap_in_total",
              "kv_swap_tail_pages_total", "kv_swap_fallback_recompute_total",
              "num_preemptions_total"):
        if k in stats:
            out[k] = stats[k]
    if require_warm and out["compile_polluted"]:
        log(f"{model}: REQUIRE-WARM VIOLATION — "
            f"{out['sweep_compiles']} compile(s) inside measured points")
    del pr
    del engine
    import gc

    gc.collect()  # release HBM before the next phase's engine builds
    return out


def warm_restart_phase(
    model: str, bucket_budget: int = 0, **cfg_over
) -> dict:
    """The warm-restart story end to end: build the same engine twice
    against one persistent compile cache (wherever the engine's own rule
    places it). Against an empty cache the first build pays XLA for the
    full lattice (all misses, entries written); the second deserializes
    (zero fresh misses) — its construct→ready wall time is
    ``restart_to_ready_seconds``, the number a rolling deploy budgets."""
    import gc

    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.obs import ENGINE_TELEMETRY

    def once(tag: str) -> dict:
        h0, m0 = ENGINE_TELEMETRY.cache_stats()
        t0 = time.time()
        cfg = EngineConfig(
            model=model,
            warmup="full",
            warmup_bucket_budget=bucket_budget,
            **cfg_over,
        )
        engine = LLMEngine(cfg)
        summary = engine.precompile()
        ready_s = time.time() - t0
        h1, m1 = ENGINE_TELEMETRY.cache_stats()
        del engine
        gc.collect()
        res = {
            "ready_s": round(ready_s, 2),
            "precompile_s": summary["seconds"],
            "buckets_compiled": summary["buckets_compiled"],
            "cache_hits": h1 - h0,
            "cache_misses": m1 - m0,
        }
        log(f"warm-restart[{tag}]: {res}")
        return res

    cold = once("cold")
    warm = once("warm")
    return {
        "model": model,
        "cold": cold,
        "warm": warm,
        "restart_to_ready_seconds": warm["ready_s"],
        "fresh_compiles_on_restart": warm["cache_misses"],
        "speedup": (
            round(cold["ready_s"] / warm["ready_s"], 2)
            if warm["ready_s"] else None
        ),
    }


def main() -> None:
    import jax

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    require_warm = require_warm_enabled()
    result: dict = {"backend": backend, "require_warm": require_warm}
    if time_budget() > 0:
        result["time_budget_s"] = time_budget()
    write_partial(result)
    install_term_trap()
    # The phase currently running, so an interruption can mark exactly it
    # partial (its checkpoints already persisted every finished point).
    running_phase = [None]

    def skip_for_budget(key: str, est_floor: float = 30.0) -> bool:
        # Gate on the phase's WEIGHTED ESTIMATE, not just a static floor:
        # once one model phase has run, its observed wall prices the next
        # bring-up — a second bring-up with less wall left than the first
        # one took never begins under this gate.
        est = phase_estimate(key, est_floor)
        if budget_remaining() < est:
            log(f"{key} phase skipped: ~{est:.0f}s estimate vs "
                f"{max(budget_remaining(), 0):.0f}s budget left")
            result[key] = {"partial": True,
                           "skipped": "time budget exhausted",
                           "estimate_s": round(est, 1)}
            write_partial(result)
            return True
        running_phase[0] = key
        return False

    def record_wall(key: str, t0: float) -> None:
        _PHASE_WALLS[key] = time.monotonic() - t0

    def phase_checkpoint(key):
        # Per-qps-point checkpointing: the phase's partial dict replaces
        # the key in the cumulative result, which is atomically persisted
        # — a harness timeout mid-sweep still yields every finished point.
        def cb(partial):
            result[key] = partial
            write_partial(result)
        return cb

    try:
      if on_tpu:
        result["rpc_floor_ms"] = round(env_probe(), 1)
        log(f"rpc floor {result['rpc_floor_ms']} ms")
        if os.environ.get("PST_BENCH_SKIP_8B") != "1" and not skip_for_budget("flagship"):
            # TTFT sweep phase: 4 users (the workload must FIT with
            # headroom for ≥300 requests of history growth — at 8 users
            # the growth alone oversubscribes any 16 GiB pool and every
            # round re-prefills evicted history: measured 10 s TTFTs).
            # int4's bigger pool gives MORE eviction headroom than r4's
            # int8 run (1232 vs 844 pages for the same 4-user set).
            t_phase = time.monotonic()
            result["flagship"] = run_model_phase(
                "llama-3-8b",
                quantization="int4",
                n_users=4,
                sys_len=1000,
                hist_len=20000,
                question_len=28,
                answer_len=100,
                num_kv_blocks=None,  # auto from the 16 GiB budget
                hbm_utilization=0.88,
                # ≥300 measured requests over 6 points spanning 0.1-1.1
                # (76 rounds x 4 users = 304).
                sweep=[(0.1, 2), (0.3, 6), (0.5, 12), (0.7, 16),
                       (0.9, 18), (1.1, 22)],
                stagger=((0,), (1, 2), (3,)),
                decode_probe_tokens=192,
                # Pipelined shallow bursts (async n=2): one burst always
                # in flight, fetch overlapped, so the host↔device sync no
                # longer idles the chip between bursts. Chosen on an
                # attachment that no longer exists; not re-tuned for a
                # directly attached chip (ROADMAP D3).
                num_decode_steps=2,
                adaptive=32,
                async_decode=True,
                pipelined_probe=True,
                require_warm=require_warm,
                checkpoint=phase_checkpoint("flagship"),
            )
            record_wall("flagship", t_phase)
            write_partial(result)
        if os.environ.get("PST_BENCH_SKIP_8B_CONC") != "1" and not skip_for_budget("concurrency_8users"):
            # Concurrency phase: EIGHT 20k-history users on the same chip
            # (r4 topped out at 4 on int8) — int4 weights (~4.4 GiB) leave
            # a ~158k-token pool holding ~7.5 of the 8 users' KV; live-KV
            # swap (engine/swap.py) parks/rotates the remainder, so the
            # fleet serves MORE sessions than HBM holds, degrading
            # smoothly instead of thrashing. One warm round for liveness,
            # then the pipelined saturated decode probe.
            t_phase = time.monotonic()
            conc = run_model_phase(
                "llama-3-8b",
                quantization="int4",
                n_users=8,
                sys_len=500,
                hist_len=20000,
                question_len=28,
                answer_len=100,
                num_kv_blocks=None,
                hbm_utilization=0.88,
                sweep=[(0.7, 2)],  # liveness only; TTFT story is above
                stagger=((0,), (1, 2), (3, 4, 5, 6), (7,)),
                decode_probe_tokens=192,
                num_decode_steps=2,
                adaptive=32,
                async_decode=True,
                pipelined_probe=True,
                require_warm=require_warm,
                checkpoint=phase_checkpoint("concurrency_8users"),
            )
            conc["note"] = (
                "TTFT fields here are the oversubscribed liveness round "
                "(8x20k cold re-admission on a pool sized for ~7.5 users) "
                "- the TTFT story is the flagship sweep; this phase's "
                "headline is decode_tok_per_s_chip"
            )
            record_wall("concurrency_8users", t_phase)
            result["concurrency_8users"] = conc
            write_partial(result)
        if os.environ.get("PST_BENCH_SKIP_1B") != "1" and not skip_for_budget("llama_1b"):
            t_phase = time.monotonic()
            result["llama_1b"] = run_model_phase(
                "llama-1b",
                n_users=8,
                sys_len=1000,
                hist_len=20000,
                question_len=28,
                answer_len=100,
                num_kv_blocks=1408,
                sweep=[(1.0, 4)],
                stagger=((0,), (1, 2), (3, 4, 5, 6), (7,)),
                decode_probe_tokens=256,
                adaptive=32,
                require_warm=require_warm,
                checkpoint=phase_checkpoint("llama_1b"),
            )
            record_wall("llama_1b", t_phase)
            write_partial(result)
      else:
        # CPU smoke: tiny model, tiny protocol — keeps the bench runnable
        # (and CI-checkable) anywhere. Budget-gated like the TPU phases:
        # the r05 re-entry bug was a loop iteration starting unbudgeted.
        if not skip_for_budget("flagship"):
          t_phase = time.monotonic()
          result["flagship"] = run_model_phase(
            "tiny-llama-debug",
            n_users=4,
            sys_len=64,
            hist_len=96,
            question_len=12,
            answer_len=16,
            num_kv_blocks=512,
            sweep=[(8.0, 2)],
            stagger=((0,), (1, 2), (3,)),
            decode_probe_tokens=16,
            num_decode_steps=4,
            adaptive=0,  # CPU drains the probe before the quiet gate opens
            block_size=8,
            max_model_len=512,
            attn_impl="gather",
            kv_cache_dtype=None,
            require_warm=require_warm,
            checkpoint=phase_checkpoint("flagship"),
          )
          record_wall("flagship", t_phase)

      # Warm-restart phase (docs/engine.md "Warmup & precompilation"):
      # the same engine built twice against one persistent compile cache;
      # restart_to_ready_seconds is the warm construct→ready wall time.
      # tiny-llama-debug on both backends: the cache mechanics (and on
      # TPU, real XLA serialization) are what's measured, not model-load
      # time.
      if (os.environ.get("PST_BENCH_SKIP_RESTART") != "1"
              and not skip_for_budget("warm_restart")):
        # One fixed cache root in the checkout (through the deployment
        # flag, so it also holds on the CPU profile; JAX_COMPILATION_CACHE_DIR
        # still wins) — never a temporary directory: "cold" is only as cold
        # as that cache, and its miss count says how cold that was. A
        # failure here fails the run like any other phase.
        from production_stack_tpu.engine.precompile import (
            DEFAULT_COMPILE_CACHE_DIR,
        )

        result["warm_restart"] = warm_restart_phase(
            "tiny-llama-debug",
            compile_cache_dir=DEFAULT_COMPILE_CACHE_DIR,
            max_model_len=256,
            block_size=16,
            num_kv_blocks=64,
            max_num_seqs=4,
            max_prefill_tokens=32,
            num_decode_steps=2,
            attn_impl="gather",
        )
        write_partial(result)
    except BenchInterrupted as e:
        # SIGTERM (or the parent's wall) cut the run: mark the running
        # phase — and the run — partial; everything already measured
        # flows into the final flush below instead of dying with rc:124
        # and nothing parseable.
        log(f"interrupted ({e}); flushing final JSON with finished phases")
        phase = running_phase[0]
        if phase is not None:
            entry = result.get(phase)
            if not isinstance(entry, dict):
                entry = result[phase] = {}
            entry["partial"] = True
            entry.setdefault("error", f"interrupted: {e}")
        result["partial"] = True

    # Run-level pollution verdict: any measured sweep point in any phase
    # that absorbed a cold compile.
    result["compile_polluted"] = any(
        isinstance(v, dict) and v.get("compile_polluted")
        for v in result.values()
    )
    write_partial(result)
    print(json.dumps(result), flush=True)
    if require_warm and result["compile_polluted"]:
        log("--require-warm: cold compiles landed inside measured sweep "
            "points; failing the run")
        sys.exit(3)


if __name__ == "__main__":
    main()
