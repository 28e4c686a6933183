"""Engine configuration — the TPU analogue of the reference's vLLM flag set.

Field ↔ reference mapping (`helm/values.yaml:71-81`, CRD
`operator/api/v1alpha1/vllmruntime_types.go:67-95`):
``tensor_parallel_size`` ↔ ``--tensor-parallel-size``; ``max_model_len`` ↔
``--max-model-len``; ``max_num_seqs`` ↔ ``--max-num-seqs``;
``enable_prefix_caching`` ↔ ``--enable-prefix-caching``;
``max_prefill_tokens`` ↔ chunked-prefill token budget;
``hbm_utilization`` ↔ ``--gpu-memory-utilization``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from ..device import require_device_spec
from ..logging_utils import init_logger
from ..models.base import ModelConfig

logger = init_logger(__name__)


@dataclasses.dataclass
class EngineConfig:
    model: str = "tiny-llama-debug"
    tokenizer: Optional[str] = None  # default: model dir, or byte tokenizer
    served_model_name: Optional[str] = None
    max_model_len: int = 4096
    block_size: int = 32
    num_kv_blocks: Optional[int] = None  # None: size from HBM budget
    hbm_utilization: float = 0.9
    max_num_seqs: int = 64
    max_prefill_tokens: int = 2048
    tensor_parallel_size: int = 1
    data_parallel_size: int = 1
    # Layer-stage parallelism over the pp mesh axis (the reference's
    # Ray-cluster `--pipeline-parallel-size`, `ray-cluster.yaml:560-566`).
    # Stages hold L/pp layers + their KV pages; activations hop via ppermute.
    pipeline_parallel_size: int = 1
    # Ring (context-parallel) attention over the sp mesh axis for the
    # full-attention encode path (/v1/embeddings at contexts beyond one
    # device group's attention memory). See ops/ring_attention.py.
    sequence_parallel_size: int = 1
    # Expert parallel (MoE models): the expert bank shards over the ep mesh
    # axis; the combine reduction is the one ep all-reduce XLA inserts.
    expert_parallel_size: int = 1
    kv_cache_dtype: Optional[str] = None  # default: model dtype
    # Weight-only quantization: "int8" stores matmul weights as int8 with
    # per-output-channel scales (models/llama.py quantize_leaf). Halves
    # weight HBM and decode's weight-read bandwidth — what fits Llama-3-8B
    # plus its KV on one 16 GiB v5e chip (the reference serves the same 8B
    # benchmark model on a 40 GiB A100). "int4" packs two group-wise-scaled
    # (g=128, AWQ/GPTQ-family) nibbles per byte for the per-layer matmuls
    # (embed/lm_head stay int8): quarters weight HBM, freeing room for
    # ~2x the resident KV — 8 concurrent 20k-context users on one chip.
    # None = native dtype.
    quantization: Optional[str] = None  # None | int8 | int4
    attn_impl: str = "auto"  # auto | gather | pallas
    # MoE execution strategy: ragged (dropless lax.ragged_dot grouped
    # matmul — FLOP-proportional, the single-shard default) | dense
    # (expert-batched einsums, GSPMD-shardable over ep/tp) | auto.
    moe_impl: str = "auto"
    enable_prefix_caching: bool = True
    # Decode tokens generated per device call (lax.scan over steps inside one
    # jit). Amortizes the per-call host⇄device dispatch, which weighs most
    # on small models. Stop conditions are applied host-side after the
    # burst; at most n-1 speculatively-decoded tokens are discarded per
    # finished request. 1 = classic per-token stepping.
    num_decode_steps: int = 1
    # Floor for the decode-batch row bucket. Serving workloads whose active
    # set fluctuates otherwise walk through every power-of-two width,
    # compiling each one the first time it appears (an XLA compile mid-burst
    # is a multi-second TTFT outlier). Padding rows carry kv_len=0 and cost
    # ~nothing — the pallas kernel streams zero pages for them.
    min_decode_bucket: int = 1
    # Speculative decoding via n-gram prompt lookup (engine/spec.py): draft
    # up to this many tokens per greedy sequence per step and verify them in
    # one forward pass. 0 = off. Output is exactly the non-speculative
    # greedy output; sampled (temperature>0) batches bypass speculation.
    speculative_ngram: int = 0
    ngram_min: int = 1  # shortest suffix n-gram to match
    ngram_max: int = 3  # longest suffix n-gram to match
    # Cap the prompt-lookup scan to the last N tokens (0 = whole history).
    # Bounds the per-step host-side draft cost at long context.
    ngram_lookback: int = 8192
    # The model's own draft (multi-token prediction; docs/engine.md "Verify
    # and draft"): 1 = every decode step of a model that has the module
    # verifies one draft a row and makes the next, in one program on the
    # device, and emits 1 or 2 tokens a row; 0 = off. The depth is the
    # published module's (1). Token for token the draft-off output.
    speculative_mtp: int = 0
    # Overlapped decode pipeline (docs/engine.md "Overlapped decode
    # pipeline"): keep one burst in flight. As soon as burst N's token ids
    # are fetched, burst N+1 is dispatched and burst N's host bookkeeping
    # (detokenization, stop scans, stream frames, stats, scheduler
    # accounting) runs WHILE N+1 executes, which hides the host<->device
    # round trip. A pipeline starts whenever the decode batch can be
    # chained (no guided-choice row, no n-gram speculation configured, no
    # queue left standing), arrivals or not: an arrival waits for the one
    # burst in flight, as it waits for the running step in the synchronous
    # loop, and its prefill is launched behind that burst. At
    # ``num_decode_steps`` = 1 that is at most one decode step, against
    # the host's share of every step (7 ms of 27 on a v5e: PERF.md §6,
    # PR 30). False = the synchronous loop.
    overlap_decode: bool = True
    seed: int = 0
    # KV tiering (LMCache-analogue knobs; SURVEY.md §2.4).
    cpu_offload_blocks: int = 0
    # One kvserver base URL, or a comma-separated shard list — the latter
    # builds the replicated ShardedKVClient over the consistent-hash ring
    # (docs/kvserver.md).
    remote_kv_url: Optional[str] = None
    # Replicas per block/manifest on the kvserver ring (clamped to the
    # shard count; meaningful only with a multi-URL remote_kv_url).
    kv_replication: int = 2
    # Cache-controller registration (KV-aware routing; LMCACHE_CONTROLLER_URL
    # analogue). engine_url is what this pod reports itself as.
    cache_controller_url: Optional[str] = None
    engine_url: Optional[str] = None
    # LoRA serving (reference: vLLM --enable-lora + the operator's
    # load/unload HTTP flow, `loraadapter_controller.go:582-611`). Adapters
    # live in a stacked device bank; any mix serves in one compiled step.
    enable_lora: bool = False
    max_loras: int = 8
    max_lora_rank: int = 16
    lora_dir: str = "/adapters"
    # Live-sequence KV swap (engine/swap.py; vLLM --swap-space analogue).
    # Preemption parks KV host-side instead of recomputing, and the
    # scheduler timeslices more concurrent 20k-context users than HBM
    # holds. Committed pages never move (content-addressed in place /
    # existing tier); only uncommitted tail pages are stashed.
    kv_swap: bool = True
    # Rotate a running sequence out after this many decoded tokens when
    # parked/queued work exists (0 = only swap under allocation pressure).
    swap_quantum_tokens: int = 256
    # Host-DRAM budget for stashed tail pages, in KV pages.
    swap_stash_blocks: int = 4096
    # Disaggregated prefill role (reference: --kv-transfer-config
    # kv_producer/kv_consumer, `deployment-vllm-multi.yaml:180-189`).
    # producer: push each completed prefill's KV pages to the remote store
    # (device→host DMA then DCN — the NIXL-sender analogue).
    # consumer: fault pages up from the remote store at admission
    # (TieredAllocator.match_prefix — the NIXL-receiver analogue).
    kv_role: str = "none"  # none | producer | consumer | both
    # Streamed disagg KV handoff (docs/disagg.md). Consumer-side prefetch:
    # max blocks per batched GET while following a prefill's manifest
    # (bounds one response's host memory), and the wall-clock window the
    # decode engine will wait for the manifest's completion marker before
    # degrading to the fused path (recompute the prefill locally).
    kv_prefetch_depth: int = 64
    kv_transfer_timeout_s: float = 10.0
    # Deadline shedding (docs/resilience.md "Deadlines & hedging"): honor
    # the router-propagated X-PST-Deadline-Ms budget — 504 expired work at
    # admission, drop expired queued sequences before they consume a
    # prefill step, and stop decoding expired running sequences.
    deadline_shedding: bool = True
    # Tenant-aware scheduling (docs/multi-tenancy.md): honor the
    # router-stamped X-PST-Tenant / X-PST-Tenant-Class headers — the
    # ready queue admits weighted-fair across tenants with strict tier
    # priority (interactive before batch), and batch-tier sequences are
    # preempted first (swap/shed) when an interactive tenant is waiting
    # for pages. With every request untagged (or this off) scheduling is
    # byte-for-byte the plain FIFO behavior.
    tenant_fairness: bool = True
    # Ahead-of-time shape-bucket precompilation (engine/precompile.py;
    # docs/engine.md "Warmup & precompilation"). "full" compiles the whole
    # padded shape-bucket lattice before /ready flips; "lazy" compiles only
    # the core set the first requests hit; "off" skips warmup (compile on
    # demand — the pre-PR-6 behavior, and the embedded/test default; the
    # helm chart deploys engines with "full").
    warmup: str = "off"  # off | lazy | full
    # Cap on buckets compiled at warmup (0 = the entire lattice). Buckets
    # are walked most-likely-first, so a small budget still covers the
    # common traffic shapes; the coverage gauge reports what was skipped.
    warmup_bucket_budget: int = 0
    # Persistent JAX compilation cache root (vLLM VLLM_CACHE_ROOT
    # analogue). Executables land in a subdirectory keyed on model + mesh
    # + dtype + code version, so a warm restart (or a rolling-deploy
    # replacement pod on a PVC/hostPath mount) deserializes them instead
    # of compiling again. Used only when JAX_COMPILATION_CACHE_DIR is
    # unset (the variable wins, as is); None = the fixed in-checkout
    # default (engine/precompile.py).
    compile_cache_dir: Optional[str] = None
    # Flight recorder (docs/observability.md "Flight recorder"): always-on
    # bounded ring of per-device-step records (kind, bucket, step wall,
    # host gap, queue depths, KV occupancy, tier mix, compile events),
    # served at GET /debug/flight and auto-snapshotted on tail outliers
    # and SIGTERM/fatal. The value is the ring capacity in steps; 0
    # disables recording (the endpoint then serves an empty ring).
    flight_buffer: int = 512
    # Flight-snapshot persistence (docs/observability.md "Flight
    # recorder"): every retained snapshot (tail outlier, live compile,
    # SIGTERM/fatal) is also written as one JSON file under this
    # directory, bounded with oldest-first eviction, and loaded back into
    # GET /debug/flight?snapshots=1 after a restart — so the post-mortem
    # can be read even when the engine died before anyone scraped it.
    # None = in-memory retention only.
    flight_snapshot_dir: Optional[str] = None
    # Per-request cost attribution (docs/observability.md "Cost
    # attribution"): accumulate each request's prefill device-seconds,
    # active-row share of decode-burst device-seconds, KV page-seconds
    # and queue wait; surfaced as the X-PST-Cost response header + usage
    # extension and the pst_request_device_seconds /
    # pst_tenant_device_seconds metrics (chip-time billing).
    cost_attribution: bool = True


def state_slot_count(cfg: EngineConfig) -> int:
    """Recurrent-state slots a model with state-space layers is given: one a
    running sequence and a few spare, since a burst's finished members hold
    theirs until the drain. (The pool has one more, the scratch slot.)"""
    return cfg.max_num_seqs + max(2, cfg.max_num_seqs // 8)


def window_block_count(cfg: EngineConfig, model_cfg) -> int:
    """Pages of the window group a model with sliding-window layers is
    given (0 for any other): every sequence's steady residency (the
    window's pages, one where it straddles, one for the token being
    written) and twice a step's prefill budget for the chunks in flight.
    Where the group serves the prefix cache (a class that is not also
    recurrent), the window's pages once more a row: the last window of one
    conversation that waits for its next turn, which is all of it a later
    match needs, while another holds the row. From the model's window and
    the engine's own limits: no flag."""
    if not model_cfg.window_pages:
        return 0
    pages = lambda tokens: -(-tokens // cfg.block_size)  # noqa: E731
    window = pages(model_cfg.sliding_window)
    waiting = (window if cfg.enable_prefix_caching and not model_cfg.recurrent
               else 0)
    return (cfg.max_num_seqs * (window + 2 + waiting)
            + 2 * pages(cfg.max_prefill_tokens))


def _both(why: str) -> dict:
    return {"recurrent": why, "latent_pages": why, "window_pages": why}


# A looped stack's step has been held to the reference with these on
# (``tests/test_ouro.py``): the prefix cache, swap, n-gram drafts,
# quantised leaves, tensor parallelism. Everything else of the table it is
# refused with.
_UNPROVEN_LOOPED = ("it has not been held to the reference through "
                    "passes x layers cache slots")


# What a model class cannot be served with, refused at start-up by the
# flag's name: one table for every class that is not a plain K+V page list.
# A row: is the flag on, its name, and for each property of the model's
# config (``recurrent``: per-sequence state beside the pages;
# ``latent_pages``: a page is one latent row a token, not a K and a V half;
# ``window_pages``: a second page group, released below the window;
# ``wide_head_pages``: heads wider than the paged kernels have been proven at;
# ``looped``: more layers of pages than of weights, the stack run several
# times a step) why the flag cannot be served, or no entry where it can.
def _refusals(cfg: EngineConfig):
    return [
        (cfg.enable_prefix_caching, "--enable-prefix-caching", {
            "recurrent": "a cached page list carries no state snapshot; "
                         "pass --no-enable-prefix-caching"}),
        (cfg.kv_swap, "--kv-swap", {
            "recurrent": "a parked sequence's state is not swapped; pass "
                         "--no-kv-swap (preemption is by recompute)",
            "latent_pages": "the swap stash frames a page as a K and a V "
                            "half (engine/swap.py); pass --no-kv-swap "
                            "(preemption is by recompute)",
            "window_pages": "the swap stash knows one page group; pass "
                            "--no-kv-swap (preemption is by recompute)"}),
        (cfg.cpu_offload_blocks > 0, "--cpu-offload-blocks", {
            "recurrent": "host-tier pages carry no state",
            "latent_pages": "the host tier frames a page as a K and a V "
                            "half (engine/cache_tiering.py)",
            "window_pages": "the host tier knows one page group",
            "looped": _UNPROVEN_LOOPED}),
        (bool(cfg.remote_kv_url), "--remote-kv-url", {
            "recurrent": "remote-tier pages carry no state",
            "latent_pages": "the kvserver's framing is a K and a V half a "
                            "page",
            "window_pages": "the remote tier knows one page group",
            "looped": _UNPROVEN_LOOPED}),
        (cfg.kv_role != "none", "--kv-role", {
            "recurrent": "a KV hand-off ships pages, not the state",
            "latent_pages": "the hand-off ships K and V halves "
                            "(engine/kv_handoff.py)",
            "window_pages": "the hand-off ships one page group",
            "looped": _UNPROVEN_LOOPED}),
        (cfg.speculative_ngram > 0, "--speculative-ngram", {
            "recurrent": "a rejected draft would need the state rolled back",
            "window_pages": "an n-gram draft's rows have not been verified "
                            "through the window group (the model's own "
                            "draft, --speculative-mtp, has)"}),
        (cfg.speculative_mtp > 0, "--speculative-mtp", {
            "recurrent": "a rejected draft would need the state rolled back",
            "latent_pages": "no draft module is built over latent pages",
            "looped": _UNPROVEN_LOOPED}),
        (cfg.enable_lora, "--enable-lora", {
            **_both("no adapter bank exists for these layers"),
            "looped": _UNPROVEN_LOOPED}),
        (cfg.tensor_parallel_size > 1, "--tensor-parallel-size",
         _both("its pools and kernels run on one device")),
        (cfg.pipeline_parallel_size > 1, "--pipeline-parallel-size", {
            "recurrent": "the layer pattern is not staged",
            "latent_pages": "the dense and expert layers are not staged",
            "window_pages": "the two page groups are not staged",
            "looped": "a stage would be visited once a pass, several times "
                      "a step"}),
        (cfg.expert_parallel_size > 1, "--expert-parallel-size",
         _both("an expert-parallel share is told by the model config's "
               "ep_share, not by a mesh")),
        (cfg.data_parallel_size > 1, "--data-parallel-size", {
            **_both("its pools and kernels run on one device"),
            "looped": _UNPROVEN_LOOPED}),
        (cfg.sequence_parallel_size > 1, "--sequence-parallel-size", {
            "looped": "the embeddings path is not built for a looped stack"}),
        (bool(cfg.quantization), "--quantization",
         _both("no quantised leaves exist for these layers")),
        (bool(cfg.kv_cache_dtype)
         and jax.numpy.dtype(cfg.kv_cache_dtype).itemsize < 2,
         "--kv-cache-dtype", {
            "latent_pages": "one-byte latents are not built (the decode "
                            "kernel folds keys and values out of one "
                            "two-byte buffer)",
            "window_pages": "one-byte pages of a window group are not "
                            "calibrated",
            "wide_head_pages": "one-byte pages of 256-wide heads are not "
                               "proven (the paged kernels' one-byte path "
                               "has run at 128 lanes a head only)",
            "looped": "one-byte pages under a looped stack are not "
                      "calibrated (a pass reads what the pass before "
                      "rounded)"}),
    ]


_HAS = {"recurrent": "has recurrent (state-space) layers",
        "latent_pages": "keeps pages of latents (MLA)",
        "window_pages": "releases its window layers' pages below the window",
        "wide_head_pages": "keeps pages of 256-wide heads",
        "looped": "runs its layer stack several times a step, each pass on "
                  "cache slots of its own"}


def refuse_unserved(cfg: EngineConfig, model_cfg: ModelConfig) -> None:
    """Raise, naming the flag, for the first flag that is on and that one of
    the model config's properties rules out."""
    on_flags = [(flag, why) for on, flag, why in _refusals(cfg) if on]
    kinds = {"recurrent": model_cfg.recurrent,
             "latent_pages": model_cfg.latent_pages,
             "window_pages": model_cfg.window_pages,
             "wide_head_pages": model_cfg.wide_head_pages,
             "looped": model_cfg.looped}
    for prop, has in _HAS.items():
        if not kinds[prop]:
            continue
        for flag, why in on_flags:
            if prop in why:
                raise ValueError(
                    f"{flag} is not served for model {cfg.model!r}, which "
                    f"{has}: {why[prop]}")
    refuse_mtp(cfg, model_cfg)


def refuse_mtp(cfg: EngineConfig, model_cfg: ModelConfig) -> None:
    """``--speculative-mtp`` needs a class with the module, at its published
    depth, and is the step's only draft source; the chained pipeline is off
    with it (its step is synchronous: one launch and one fetch)."""
    n = cfg.speculative_mtp
    if not n:
        return
    if not model_cfg.mtp_layers:
        raise ValueError(
            f"--speculative-mtp is not served for model {cfg.model!r}, which "
            "has no multi-token-prediction module (num_nextn_predict_layers)")
    if n != model_cfg.mtp_layers:
        raise ValueError(
            f"--speculative-mtp {n}: the module's depth is "
            f"{model_cfg.mtp_layers}, and a deeper draft is not built")
    for on, flag, why in (
            (cfg.speculative_ngram > 0, "--speculative-ngram",
             "a step verifies one draft source"),
            (cfg.num_decode_steps > 1, "--num-decode-steps",
             "a verify-and-draft step is one step a launch")):
        if on:
            raise ValueError(f"{flag} with --speculative-mtp: {why}")


def resolve_num_kv_blocks(
    cfg: EngineConfig, model_cfg: ModelConfig, param_bytes_per_device: int
) -> int:
    """Page count from the HBM budget (``--gpu-memory-utilization`` analogue).

    bytes/page is the model config's to say (``ModelConfig.page_bytes``:
    2 (K+V) * L * bs * KH * hd * itemsize, divided by tp (kv heads sharded
    over the tensor axis) and pp (layers sharded over stages), unless its
    pages have another shape: latent rows). ``L`` counts the layers that
    hold pages (a hybrid model's attention layers alone: ``num_kv_layers``);
    a model with recurrent layers has its state pools taken off the budget
    first.
    """
    dtype_size = jax.numpy.dtype(cfg.kv_cache_dtype or model_cfg.dtype).itemsize
    tp = max(cfg.tensor_parallel_size, 1)
    pp = max(cfg.pipeline_parallel_size, 1)
    page_bytes = model_cfg.page_bytes(cfg.block_size, dtype_size, tp, pp)
    # local_devices, not devices: on a multi-host mesh devices()[0] may be
    # non-addressable here, and hosts that sized differently would diverge
    # in shape.
    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        # Virtual CPU devices: keep the cache modest (tests override anyway).
        budget = 512 * 1024 * 1024
    else:
        # A chip: what the backend says this process may allocate, else the
        # published HBM of the device kind — never a guess (a chip that
        # reports neither would get a silently tiny pool).
        hbm = (dev.memory_stats() or {}).get("bytes_limit")
        if not hbm:
            hbm = require_device_spec(dev.device_kind).hbm_bytes
        budget = int(hbm * cfg.hbm_utilization) - param_bytes_per_device
        # The state pool and the window group (nothing, for a model that
        # has neither) are sized by what the sequences can hold there; the
        # global group takes the rest.
        budget -= model_cfg.state_bytes_per_slot() * (state_slot_count(cfg) + 1)
        budget -= (model_cfg.window_page_bytes(cfg.block_size, dtype_size)
                   * window_block_count(cfg, model_cfg))
        if cfg.num_kv_blocks is not None:
            # Explicit pages are taken as they are, if the device can hold
            # them at all beside the weights and the pools above.
            over = cfg.num_kv_blocks * page_bytes - (
                budget + hbm - int(hbm * cfg.hbm_utilization))
            if over > 0:
                raise ValueError(
                    f"--num-kv-blocks {cfg.num_kv_blocks} x {page_bytes} B "
                    f"is {over / 2**20:.0f} MiB more than the device's "
                    f"{hbm} B hold beside {param_bytes_per_device} B of "
                    "weights and the model's state and window pools")
    if cfg.num_kv_blocks is not None:
        n = cfg.num_kv_blocks
    else:
        n = max(budget // page_bytes, cfg.max_num_seqs * 2)
        # Never fewer pages than one full-length sequence needs.
        n = max(n, -(-cfg.max_model_len // cfg.block_size) + 1)
    logger.info(
        "KV cache: %d pages x %d tokens over %d layers of pages, %d B a "
        "token (%.1f MiB/device)",
        n, cfg.block_size, model_cfg.num_kv_layers,
        page_bytes // cfg.block_size, n * page_bytes / 2**20,
    )
    return int(n)
