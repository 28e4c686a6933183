"""Ahead-of-time shape-bucket precompilation + persistent compile cache.

The runner pads every device step into a small set of power-of-two bucket
shapes (docs/engine.md "Static-shape discipline"), which makes the full
set of executables live traffic can ever demand *enumerable from config
alone*. This module enumerates that lattice — prefill (rows x chunk),
decode rows, decode bursts, spec-verify, encode — and drives every jitted
dispatch in :mod:`runner` through it with all-padding dummy batches at
warmup, before the server's ``/ready`` flips. The result is the
prevention half of PR 5's detection machinery: after a ``full`` warmup a
live-traffic XLA recompile is impossible for any shape the lattice
covers, and ``pst_engine_compile_total`` staying
flat under traffic proves it.

Underneath sits a **persistent JAX compilation cache**, always on and
placeable from outside (``JAX_COMPILATION_CACHE_DIR``, else
``--compile-cache-dir``, else a fixed path in the checkout — see
:func:`configure_compile_cache`), so a warm restart (or a rolling-deploy
replacement pod on the same PVC/hostPath mount) deserializes instead of
rebuilding — ``pst_engine_compile_cache_{hits,misses}_total`` count the
outcomes via jax's monitoring events, and
``pst_engine_startup_seconds{phase="precompile"}`` shrinks accordingly.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import time
from typing import List, Optional

from .. import __version__
from ..logging_utils import init_logger
from ..obs.engine_telemetry import ENGINE_TELEMETRY
from .config import EngineConfig

logger = init_logger(__name__)

# Kind walk order when a bucket budget truncates the lattice: decode
# shapes serve every live token, prefill shapes gate TTFT, bursts/spec are
# throughput paths, encode only serves /v1/embeddings.
_KIND_RANK = {
    "decode": 0,
    "decode_burst": 1,
    "prefill": 2,
    "spec_verify": 3,
    "mtp_verify": 0,  # the decode step of an engine that drafts for itself
    "encode": 4,
}


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One compiled-executable-worth of padded shape + static jit flags."""

    kind: str  # decode | decode_burst | prefill | spec_verify | encode
    rows: int = 0  # padded batch rows (decode/prefill/spec)
    tokens: int = 0  # prefill chunk bucket / encode length / spec K
    width: int = 0  # block-table width bucket
    n_steps: int = 0  # burst depth (decode_burst)
    want_lp: bool = False
    greedy: bool = True
    # Penalty-bearing multi-step variant (decode_burst only): the dense
    # [rows, V] penalty_seen/counts state keeps these shapes derivable
    # from config alone, so — unlike the pow2-length id arrays of the
    # single-step path — they ARE enumerable and warmed.
    penalized: bool = False

    @property
    def label(self) -> str:
        """The telemetry ``shape_bucket`` label this bucket compiles."""
        if self.kind == "decode":
            return f"b{self.rows}"
        if self.kind == "decode_burst":
            return f"b{self.rows}xn{self.n_steps}"
        if self.kind == "prefill":
            return f"b{self.rows}xt{self.tokens}"
        if self.kind in ("spec_verify", "mtp_verify"):
            return f"b{self.rows}xk{self.tokens}"
        return f"t{self.tokens}"

    def sort_key(self) -> tuple:
        # Greedy-no-logprobs-unpenalized first (the overwhelmingly common
        # flag set), then ascending size so coverage climbs fastest per
        # second.
        return (
            _KIND_RANK[self.kind],
            (self.want_lp, not self.greedy, self.penalized),
            self.rows,
            self.n_steps,
            self.tokens,
            self.width,
        )


def _pow2_buckets(n: int) -> List[int]:
    """Every power-of-two bucket a real count in 1..n can pad into."""
    out, b = [], 1
    while True:
        out.append(b)
        if b >= n:
            return out
        b <<= 1


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


def decode_row_buckets(cfg: EngineConfig) -> List[int]:
    """Mirror of ``ModelRunner._row_bucket`` over all batch sizes."""
    floor = max(cfg.data_parallel_size, cfg.min_decode_bucket, 1)
    return sorted({max(p, floor) for p in _pow2_buckets(cfg.max_num_seqs)})


def table_width_buckets(cfg: EngineConfig) -> List[int]:
    """Mirror of ``ModelRunner._table_bucket`` over all sequence lengths."""
    from .runner import _MIN_TABLE_BUCKET

    max_table_width = -(-cfg.max_model_len // cfg.block_size)
    cap = _pow2(max_table_width)
    floor = min(_MIN_TABLE_BUCKET, cap)
    return sorted({max(p, floor) for p in _pow2_buckets(max_table_width)})


def prefill_shape_buckets(cfg: EngineConfig) -> List[tuple]:
    """Feasible (row bucket, chunk bucket) pairs under the scheduler's
    per-step token budget: a batch of B chunks with the longest C has
    B-1 (one-token rows) + C real tokens at minimum, which must fit
    ``max_prefill_tokens`` — infeasible bucket pairs can never be emitted
    and are excluded so coverage means what it says."""
    budget = cfg.max_prefill_tokens
    pairs = []
    for rb in _pow2_buckets(min(cfg.max_num_seqs, budget)):
        min_rows = 1 if rb == 1 else rb // 2 + 1
        for cb in _pow2_buckets(budget):
            min_chunk = 1 if cb == 1 else cb // 2 + 1
            if min_rows - 1 + min_chunk <= budget:
                pairs.append((rb, cb))
    return pairs


def encode_buckets(cfg: EngineConfig) -> List[int]:
    """Mirror of ``ModelRunner.encode``: pow2 length, rounded up to a
    multiple of the ring-encode shard count."""
    sp = max(cfg.sequence_parallel_size, 1)
    return sorted({-(-p // sp) * sp for p in _pow2_buckets(cfg.max_model_len)})


def burst_depths(cfg: EngineConfig) -> List[int]:
    """Burst depths the engine dispatches at steady state: the configured
    depth above 1 — and, when the pipeline is on (``overlap_decode``, the
    default: every chainable decode batch), the configured depth even at 1:
    the pipeline runs the multi-step
    executable (``b{B}xn{n}``) at whatever depth the scheduler emits, so
    a depth-1 engine decodes through ``b{B}xn1`` shapes, whose program
    is ``jit_pst_decode_step_chained`` (`ModelRunner._burst_fn`). (The
    per-sequence clamp near max_model_len can shrink n through arbitrary
    values on the last few tokens of a context-limit sequence — that long
    tail is deliberately NOT enumerated; it is one compile per engine
    lifetime at worst.)"""
    n = max(cfg.num_decode_steps, 1)
    # Mirrors LLMEngine._pipeline_ok: overlap defers to configured n-gram
    # speculation, so spec engines never dispatch the depth-1 variant; nor
    # does an engine whose decode step is the verify-and-draft step.
    if cfg.speculative_mtp:
        return []
    if n > 1 or (cfg.overlap_decode and not cfg.speculative_ngram):
        return [n]
    return []


# The (want_lp, greedy) static-flag sets warmed by default. Logprob
# variants compile distinct executables too but are rare enough in live
# traffic that doubling warmup for them is the wrong default; a logprobs
# request pays one compile, attributed by the PR 5 trace events.
_FLAG_SETS = ((False, True), (False, False))


def enumerate_lattice(cfg: EngineConfig) -> List[Bucket]:
    """The full padded shape-bucket lattice for this engine config, in
    priority order (what a bucket budget truncates from the tail)."""
    rows = decode_row_buckets(cfg)
    widths = table_width_buckets(cfg)
    buckets: List[Bucket] = []
    for lp, greedy in _FLAG_SETS:
        for r in rows:
            for w in widths:
                # every decode step of an engine that serves its model's own
                # draft is a verify-and-draft step of one draft a row
                buckets.append(
                    Bucket("mtp_verify", rows=r, tokens=1, width=w,
                           want_lp=lp, greedy=greedy)
                    if cfg.speculative_mtp else
                    Bucket("decode", rows=r, width=w, want_lp=lp, greedy=greedy)
                )
        for n in burst_depths(cfg):
            for r in rows:
                for w in widths:
                    for pen in (False, True):
                        # Penalized variants are real burst executables
                        # now (scheduler no longer clamps penalty rows to
                        # n=1): their dense [rows, V] state is config-
                        # derivable, so the first penalized request after
                        # warmup must not be a live compile.
                        buckets.append(
                            Bucket(
                                "decode_burst", rows=r, width=w, n_steps=n,
                                want_lp=lp, greedy=greedy, penalized=pen,
                            )
                        )
        for rb, cb in prefill_shape_buckets(cfg):
            for w in widths:
                buckets.append(
                    Bucket(
                        "prefill", rows=rb, tokens=cb, width=w,
                        want_lp=lp, greedy=greedy,
                    )
                )
    if cfg.speculative_ngram:
        for r in rows:
            for w in widths:
                buckets.append(
                    Bucket(
                        "spec_verify", rows=r, tokens=cfg.speculative_ngram,
                        width=w,
                    )
                )
    for t in encode_buckets(cfg):
        buckets.append(Bucket("encode", tokens=t))
    buckets.sort(key=Bucket.sort_key)
    return buckets


_LAZY_CAP = 8


def lazy_core(lattice: List[Bucket], cfg: EngineConfig) -> List[Bucket]:
    """The minimal set the very first requests hit: smallest decode
    row/table buckets (single step + configured burst) and the single-row
    full-chunk prefill shapes — dev runs come up in seconds with the cold
    paths still covered."""
    decode_rows = [b.rows for b in lattice if b.kind == "decode"]
    if not decode_rows:
        return lattice[:_LAZY_CAP]
    min_r = min(decode_rows)
    min_w = min(b.width for b in lattice if b.kind == "decode")
    max_chunk = max(
        (b.tokens for b in lattice if b.kind == "prefill"), default=0
    )
    core = [
        b
        for b in lattice
        if b.greedy
        and not b.want_lp
        and not b.penalized
        and (
            (b.kind in ("decode", "decode_burst") and b.rows == min_r
             and b.width == min_w)
            or (b.kind == "prefill" and b.rows == 1 and b.width == min_w
                and b.tokens == max_chunk)
        )
    ]
    return core[:_LAZY_CAP]


# ----------------------------------------------------------------------
# Persistent compilation cache
# ----------------------------------------------------------------------


def compile_cache_key(cfg: EngineConfig, model_cfg) -> str:
    """Stable key for the executable cache directory. Everything that
    changes the compiled programs is in here — model architecture, mesh
    shape, dtypes, quantization, kernel selection, and code versions —
    so a mismatched restart gets a fresh (empty) subdirectory instead of
    deserializing stale executables."""
    import jax

    parts = (
        f"model={model_cfg.name}",
        f"layers={model_cfg.num_layers}",
        # a looped stack: the same layers, another program and cache
        *((f"kv_layers={model_cfg.num_kv_layers}",) if model_cfg.looped
          else ()),
        f"kv_heads={model_cfg.num_kv_heads}",
        f"head_dim={model_cfg.head_dim}",
        f"vocab={model_cfg.vocab_size}",
        f"dtype={model_cfg.dtype}",
        f"kv_dtype={cfg.kv_cache_dtype or model_cfg.dtype}",
        f"quant={cfg.quantization}",
        f"tp={cfg.tensor_parallel_size}",
        f"dp={cfg.data_parallel_size}",
        f"pp={cfg.pipeline_parallel_size}",
        f"sp={cfg.sequence_parallel_size}",
        f"ep={cfg.expert_parallel_size}",
        f"block={cfg.block_size}",
        f"attn={cfg.attn_impl}",
        f"moe={cfg.moe_impl}",
        f"code={__version__}",
        f"jax={jax.__version__}",
    )
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


_cache_listener_installed = False

# Where the cache lives when nothing outside places it: one fixed path in
# the checkout (listed in .gitignore). The path is part of what makes a
# later process find the entries again, so it is never built from a
# temporary name, a pid or the time.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def _install_cache_listener() -> None:
    """Feed jax's compilation-cache monitoring events into the telemetry
    hit/miss counters, and its duration events (tracing, lowering,
    compiling, the cache's read) into the split of a step shape's first
    use. Process-global and idempotent."""
    global _cache_listener_installed
    if _cache_listener_installed:
        return
    from jax._src import monitoring

    def _on_event(name: str, **kwargs) -> None:
        if name.endswith("/compilation_cache/cache_hits"):
            ENGINE_TELEMETRY.record_cache_event(True)
        elif name.endswith("/compilation_cache/cache_misses"):
            ENGINE_TELEMETRY.record_cache_event(False)

    def _on_duration(name: str, seconds: float, **kwargs) -> None:
        ENGINE_TELEMETRY.first_use_event(name, seconds)

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _cache_listener_installed = True


def configure_compile_cache(cfg: EngineConfig, model_cfg) -> Optional[str]:
    """Turn on jax's persistent compilation cache and return its directory.

    Placement, in order: ``JAX_COMPILATION_CACHE_DIR`` — jax reads it
    itself, and this code then sets no directory at all, so whoever runs
    the process (a bench driver, a node with a warm disk) decides where
    executables live; else ``cfg.compile_cache_dir/<key>`` — the
    deployment flag (helm mounts a PVC there), keyed so engines of
    different shape sharing one volume stay apart; else, on the chip, the
    fixed in-checkout default. On the CPU platform an unplaced cache stays
    off (returns None): XLA:CPU logs two multi-KB machine-feature errors
    per cache hit. (A test engine's compiles are not cheap, a tiny hybrid's
    step programs take seconds each and most of a model file's CPU: the
    tests place a cache themselves, through the variable, in
    ``tests/conftest.py``.)

    Must run before the runner wires its jits (compiles that happen
    earlier are never written back)."""
    import jax
    from jax._src import compilation_cache

    _install_cache_listener()
    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        if cfg.compile_cache_dir:
            path = os.path.join(
                cfg.compile_cache_dir, compile_cache_key(cfg, model_cfg)
            )
        elif jax.default_backend() == "tpu":
            path = DEFAULT_COMPILE_CACHE_DIR
        else:
            return None
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # Persist everything: the lattice is full of sub-second compiles that
    # the default 1 s / 4 KiB thresholds would silently skip — and a
    # skipped entry is a fresh compile on every restart.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax initializes its cache object AT MOST ONCE per process, latching
    # "disabled" if any compile ran before the dir was configured (e.g. a
    # previous engine in this process, or an import-time jit). Reset to
    # pristine so the next compile initializes against the directory.
    compilation_cache.reset_cache()
    logger.info("persistent compilation cache: %s", path)
    return path


# ----------------------------------------------------------------------
# The warmup driver
# ----------------------------------------------------------------------


class Precompiler:
    """Walks the lattice through the runner's warmup dispatches, keeping
    the coverage gauge current so a half-warm engine is visible."""

    def __init__(
        self,
        runner,
        cfg: EngineConfig,
        mode: Optional[str] = None,
        bucket_budget: Optional[int] = None,
    ):
        self.runner = runner
        self.cfg = cfg
        self.mode = mode if mode is not None else cfg.warmup
        if self.mode not in ("off", "lazy", "full"):
            raise ValueError(f"unknown warmup mode {self.mode!r}")
        self.bucket_budget = (
            cfg.warmup_bucket_budget if bucket_budget is None else bucket_budget
        )

    def select(self, lattice: List[Bucket]) -> List[Bucket]:
        if self.mode == "off":
            return []
        selected = (
            lazy_core(lattice, self.cfg) if self.mode == "lazy" else lattice
        )
        if self.bucket_budget and len(selected) > self.bucket_budget:
            selected = selected[: self.bucket_budget]
        return selected

    def run(self, progress=None) -> dict:
        lattice = enumerate_lattice(self.cfg)
        total = len(lattice)
        selected = self.select(lattice)
        ENGINE_TELEMETRY.set_warmup_coverage(0, total)
        t0 = time.perf_counter()
        compiled = 0
        for bucket in selected:
            self.runner.warmup_bucket(bucket)
            compiled += 1
            ENGINE_TELEMETRY.set_warmup_coverage(compiled, total)
            if progress is not None:
                progress(compiled, total, bucket)
        seconds = time.perf_counter() - t0
        skipped = total - compiled
        if skipped:
            # No silent caps: an uncompiled bucket is a future live-traffic
            # compile — say so at startup, not in a p99 postmortem. A
            # truncated FULL warmup warns (the operator asked for complete
            # coverage and is not getting it); lazy/off skip by design and
            # log at info.
            done = set(selected)
            log = (
                logger.warning
                if self.mode == "full" and self.bucket_budget
                else logger.info
            )
            log(
                "warmup left %d/%d lattice buckets uncompiled "
                "(mode=%s, budget=%d): first skipped %s",
                skipped, total, self.mode, self.bucket_budget,
                next((b.label for b in lattice if b not in done), "-"),
            )
        logger.info(
            "precompile: %d/%d buckets in %.1fs (mode=%s)",
            compiled, total, seconds, self.mode,
        )
        return {
            "mode": self.mode,
            "buckets_total": total,
            "buckets_compiled": compiled,
            "buckets_skipped": skipped,
            "coverage": round(compiled / total, 4) if total else 1.0,
            "seconds": round(seconds, 3),
        }
