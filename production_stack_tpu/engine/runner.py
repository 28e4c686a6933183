"""Model runner: marshals scheduler output into jitted device steps.

Owns the device state (sharded params + KV page arrays) and the compiled
step functions. XLA's static-shape world meets continuous batching here:
every step is padded into power-of-two buckets — decode batch width, prefill
chunk length, block-table width — so the number of distinct compilations is
O(log² shapes), all cached by ``jax.jit``. Padding rows write to a
guaranteed-dropped slot (flat index ``nb*bs``) and are masked in attention by
``kv_len = 0``.

Sampling runs inside the same jit (logits never leave the device); only the
``[B]`` sampled token ids are transferred back.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence as Seq

import jax
import jax.numpy as jnp
import numpy as np
import xxhash
from jax.numpy import asarray as jnp_asarray
from jax.sharding import NamedSharding, PartitionSpec as P

from ..device import describe_devices, pallas_interpret, resolve_platform
from ..logging_utils import init_logger
from ..obs.engine_telemetry import ENGINE_TELEMETRY, next_runner_scope
from ..models.base import ModelConfig
from ..models.llama import (
    QUANT4_SUFFIX,
    QUANT_LAYER_KEYS,
    QUANT_SUFFIX,
    QUANT_TOP_KEYS,
    init_leaf,
    load_hf_params,
    quantize_leaf,
    quantize_leaf_int4,
)
from ..models.registry import get_model_config, model_for
from ..ops.attention import decode_sharing_calls, resolve_attn_impl
from ..ops.sampling import (
    apply_allowed_mask,
    apply_logit_bias,
    apply_penalties,
    apply_penalties_counts,
    sample_tokens_packed,
)
from ..parallel.mesh import MeshConfig, build_mesh
from .program_store import StepPrograms, open_store
from .config import (
    EngineConfig,
    refuse_unserved,
    resolve_num_kv_blocks,
    state_slot_count,
    window_block_count,
)
from .scheduler import PrefillItem
from .sequence import Sequence

logger = init_logger(__name__)


def _pow2(n: int, cap: Optional[int] = None) -> int:
    b = 1
    while b < n:
        b <<= 1
    return min(b, cap) if cap else b


# Block tables below this width share one bucket: sequences crossing small
# power-of-two boundaries would otherwise retrace mid-serving, and the pallas
# kernel skips out-of-range pages anyway (only the gather fallback pays for
# the extra width).
_MIN_TABLE_BUCKET = 64


class _ReadyClock:
    """The engine's own clock for the device: when each program the runner
    launched was seen ready, and from that its service time.

    The device runs a process's programs in launch order, so a program's
    time there is ``ready - max(ready of the one before, launched_at)``, the
    completion of a job in a one-server queue; what lies between an earlier
    ready and a later launch is device idle. Both stamps are readings the
    step thread makes anyway: ``launched_at`` is the close of the
    dispatch's ``pst.launch`` phase (where nothing was queued the device
    may have begun up to that phase's length earlier: idle so filed errs
    high by at most that), and ``ready`` is the reading of the poll of
    `_fetch` that first found the program's array ready. Errors are one
    poll (about 1 ms on the benchmark's host) a stamp and telescope: over a
    busy stretch the service times sum to last ready less first start.

    A short FIFO of programs launched and not yet seen ready. `_fetch`
    hands every poll here with its own array's answer; while that array is
    not ready the head of the FIFO, where it is another program (a prefill
    launched ahead of the chained step, an inner chunk nobody fetches), is
    asked ``is_ready()`` too: one extra call a poll at most. A program
    found ready with no ask in the `_FRESH_S` before that found it still
    running (the first poll that asked, or the first of a fetch after the
    host's own phases) was *seen late*: it ended at some moment since, the
    host and not the device set the pace, and its interval, which holds
    whatever the device then idled, is counted and marked so. Each opening
    ``pst.launch`` asks the head once as well, so that what ended while the
    host was busy is stamped before the next launch and the time between
    is idle, not service. What the clock cannot see is an idle stretch
    shorter than a stamp's lag (the runtime reports an array ready 0.4-1.4
    ms after its program's end on the benchmark's host, and a poll comes
    every millisecond): it lies in the service time of the program after
    it, which then reads the period between two programs' ends.

    ``sink(entry, start, ready, seen, idle_s, idle_state)`` is called for
    each program seen ready, under the clock's lock (an embedding's encode
    polls from an executor thread). ``no_work_count()``: how many waits for
    work the loop has made; idle across one is ``no_work``, else ``host``."""

    __slots__ = ("_sink", "_count", "_fifo", "_lock", "_ready_at",
                 "_ready_count")

    def __init__(self, sink, no_work_count=lambda: 0):
        self._sink = sink
        self._count = no_work_count
        self._fifo: deque = deque()
        self._lock = threading.Lock()
        self._ready_at: Optional[float] = None  # the last ready seen
        self._ready_count = 0  # no_work_count() then

    def launched(self, kind: str, handle, at: float, who=None) -> None:
        """``handle`` (an array the program writes) was launched at ``at``;
        ``who``: whose it is (`_Dispatch`), None for a program of no live
        traffic (a warm-up's, a follower's)."""
        with self._lock:
            # [kind, handle, launched_at, who, no_work_count, when last
            #  asked and found running]
            self._fifo.append([kind, handle, at, who, self._count(), None])

    def poll(self, now: float, own=None, own_ready: bool = False) -> None:
        """A poll at ``now``: ``own`` is the array the caller waits for and
        ``own_ready`` what it just answered (everything launched before a
        ready array is done too, and is not asked)."""
        fifo = self._fifo
        try:
            head = fifo[0]
        except IndexError:
            return
        if head[1] is own and not own_ready:
            head[5] = now  # most polls: the fetched program, still running
            return
        with self._lock:
            mine = None if own is None else next(
                (e for e in fifo if e[1] is own), None)
            through = own_ready and mine is not None
            if mine is not None and not own_ready:
                mine[5] = now
            while fifo:
                head = fifo[0]
                if head is mine:
                    if not own_ready:
                        return
                elif not through and not _is_ready(head[1]):
                    head[5] = now
                    return
                fifo.popleft()
                self._seen(head, now)
                if head is mine:
                    return

    def _seen(self, entry: list, now: float) -> None:
        launched_at = entry[2]
        before = self._ready_at
        if before is None or before < launched_at:
            start = launched_at
            idle_s = 0.0 if before is None else launched_at - before
        else:
            start, idle_s = before, 0.0
        state = "no_work" if entry[4] != self._ready_count else "host"
        # (a stamp from another thread may lie before this launch's close)
        ready = max(now, start)
        self._ready_at, self._ready_count = ready, self._count()
        asked = entry[5]
        self._sink(entry, start, ready,
                   "poll" if asked is not None and now - asked <= _FRESH_S
                   else "late", idle_s, state)


# An ask this long before the one that found a program ready still vouches
# for the stamp: two polls of `_fetch` at the benchmark's host's pace.
_FRESH_S = 0.0025


def _is_ready(handle) -> bool:
    try:
        return handle.is_ready()
    except RuntimeError:  # a deleted array: whatever wrote it is done
        return True


def _fetch(arr, kind: str = "", clock: Optional[_ReadyClock] = None) -> np.ndarray:
    """Device→host fetch: start the async copy, poll readiness, then read
    through ``jax.device_get`` (which returns the landed copy). The poll
    keeps the engine's step thread off a blocking transfer call so other
    Python threads run while the device finishes; the 0.3 ms interval was
    chosen on an attachment that no longer exists (ROADMAP D3). On the
    benchmark's host, with the chip directly attached, a poll comes every
    0.97 ms in the mean (``time.sleep(0.0003)`` alone takes 1.1 ms there:
    a system call costs 6 us and the timer is coarse), 8 polls a decode
    step of 19 ms, the longest gap of a step 1.2 ms in the median (one
    run, PR 36). The whole of it is the ``wait`` phase of the step of
    ``kind`` that asked. The polls are counted and the longest time between
    two kept (``ENGINE_TELEMETRY.polled``): a long wait of many polls at
    their pace is a device that stood still, one of a single long gap a
    thread that was not let run. ``clock``: the runner's `_ReadyClock`,
    where ``arr`` was registered with it at its launch; every poll's
    reading is handed to it."""
    with ENGINE_TELEMETRY.phase("wait", kind):
        arr.copy_to_host_async()
        polls, gap_max = 0, 0.0
        last = time.perf_counter()
        while True:
            ready = arr.is_ready()
            if clock is not None:
                clock.poll(last, arr, ready)
            if ready:
                break
            # pstlint: disable=async-blocking(0.3 ms device-readiness poll on the engine's dedicated step thread, never on an event loop)
            time.sleep(0.0003)
            now = time.perf_counter()
            polls += 1
            if now - last > gap_max:
                gap_max = now - last
            last = now
        ENGINE_TELEMETRY.polled(polls, gap_max)
        return np.asarray(jax.device_get(arr))


class _Dispatch:
    """The host's side of one dispatch, around the call that makes it:
    the wall around the call goes to ``record_dispatch`` when the block
    closes (the step histogram's number, a compile's cost, the tokens), or
    later where ``deferred`` (`ModelRunner.prefill_dispatch`). The device's
    side comes with the clock: the program launched inside the block is
    registered as this dispatch's (``who``), and when it is seen ready its
    service time is charged to ``charge`` (``(function, rows)``:
    `_charge_decode` and the sequences, `_charge_prefill` and the items;
    None for an embedding) and kept in ``service_s``."""

    __slots__ = ("kind", "key", "charge", "bucket", "tokens", "fill",
                 "deferred", "in_step", "service_s", "dt", "t0")

    def __init__(self, kind: str, key: tuple, charge, bucket: str, *,
                 tokens: int, fill: float, deferred: bool = False,
                 in_step: bool = True):
        self.kind, self.key, self.charge, self.bucket = kind, key, charge, bucket
        self.tokens, self.fill = tokens, fill
        self.deferred, self.in_step = deferred, in_step
        self.service_s = 0.0

    def __enter__(self) -> "_Dispatch":
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None:
            self.dt = time.perf_counter() - self.t0
            if not self.deferred:
                self.record()

    def record(self) -> None:
        if self.in_step:
            with ENGINE_TELEMETRY.phase("postprocess", self.kind):
                self._record()
        else:
            self._record()

    def _record(self) -> None:
        # pstlint: disable=recompile-risk(key and bucket are this dispatch's own, checked where `_dispatching` was called with them: the record is only made here, when the block closes or the rows are fetched)
        ENGINE_TELEMETRY.record_dispatch(
            self.kind, self.key, self.dt, batch_bucket=self.bucket,
            tokens=self.tokens, fill_ratio=self.fill,
            device_s=self.service_s,
        )


def shared_prefix_run(
    tables: np.ndarray, kv_lens: np.ndarray, block_size: int,
    positions: int = 1,
) -> "tuple[int, int]":
    """(pages, rows): the leading pages every live row of a decode batch
    holds in common, and the rows that share them (0 and 0 where nothing
    is shared). A prefix-cache hit gives rows the same page ids, so this is
    a comparison of the tables; rows with ``kv_len`` 0 (padding, a
    finished member of a chain) are left out, and the pages from a row's
    first query position on (its last ``positions`` tokens: a verify step's
    two), those it writes, are never counted. The decode kernel finds the same run
    itself, once a call (``ops/paged_attention_pallas.py::_find_shared_run``),
    and reads those pages once; here it is only counted."""
    # Plain lists, no array operation: on the step thread, beside 16-64
    # streaming responses, each array operation is a chance to lose the
    # interpreter lock (0.2-0.4 ms a step measured so: PERF.md §7).
    lens = kv_lens.tolist()
    live = [i for i, n in enumerate(lens) if n > 0]
    if len(live) < 2:
        return 0, 0
    cap = max(min(lens[i] for i in live) - positions, 0) // block_size
    first = tables[:, 0].tolist()
    if cap <= 0 or any(first[i] != first[live[0]] for i in live):
        return 0, 0  # most batches: nothing shared
    held = tables[:, :cap].tolist()
    named = held[live[0]]
    for at in range(1, cap):
        if any(held[i][at] != named[at] for i in live):
            return at, len(live)
    return cap, len(live)


def _seed_for(seq: Sequence, ahead: int = 0) -> int:
    """The seed of ``seq``'s next sample; ``ahead``: of the one that many
    tokens later."""
    base = (
        seq.sampling.seed
        if seq.sampling.seed is not None
        else xxhash.xxh32(seq.request_id.encode()).intdigest()
    )
    return (base + len(seq.output_token_ids) + ahead) & 0x7FFF_FFFF


class _Launch:
    """A ``pst.launch`` phase whose program goes to the clock: the head of
    the clock's FIFO is asked once as the phase opens, and the array the
    block leaves in ``handle`` is registered at the phase's close."""

    __slots__ = ("_clock", "_phase", "_kind", "_who", "handle")

    def __init__(self, clock: _ReadyClock, kind: str, who, meta: dict):
        self._clock, self._kind, self._who = clock, kind, who
        self._phase = ENGINE_TELEMETRY.phase("launch", kind, **meta)
        self.handle = None

    def __enter__(self) -> "_Launch":
        self._phase.__enter__()
        self._clock.poll(self._phase.t0)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self._phase.__exit__(exc_type, exc, tb)
        if exc_type is None and self.handle is not None:
            self._clock.launched(
                self._kind, self.handle, self._phase.t1, self._who)


class ModelRunner:
    def __init__(
        self,
        cfg: EngineConfig,
        model_cfg: Optional[ModelConfig] = None,
        mesh=None,
    ):
        t_init = time.perf_counter()
        # Distinct per-runner telemetry scope: jit caches are per-runner, so
        # a fresh runner's first dispatches are real compiles even when an
        # earlier runner in this process saw identical bucket shapes.
        self._tel_scope = next_runner_scope()
        self.cfg = cfg
        # First touch of the backend: tpu, or cpu by explicit request —
        # anything else stops here (device.py).
        self.platform = resolve_platform()
        self.model_cfg = model_cfg or get_model_config(cfg.model)
        self.model = model_for(self.model_cfg)
        # A model with recurrent layers keeps, beside its pages, one state
        # slot a sequence and a scratch slot for padding rows.
        self._recurrent = bool(self.model_cfg.recurrent)
        self.state_slots = 0
        refuse_unserved(cfg, self.model_cfg)
        if self._recurrent:
            self.state_slots = state_slot_count(cfg)
        # A model whose window layers keep a page group of their own
        # (released below the window) is handed that group's tables too.
        self.window_blocks = window_block_count(cfg, self.model_cfg)
        # A model whose prefill step runs its cross-decoder on sampled
        # positions alone is told which rows those are (``sample_rows``).
        self._skips_cross = bool(self.model.SKIPS_CROSS_DECODER)
        # The model's own draft is served (``--speculative-mtp``): every
        # prefill step runs the draft module a token ahead, every decode
        # step is a verify-and-draft step of two positions a row.
        self._mtp = bool(cfg.speculative_mtp)
        self._mtp_accepted_last = 0  # drafts the last verify step accepted
        # Tokens prefill steps computed, beside the positions their buckets
        # hold (rows x chunk, padding included).
        self.prefill_tokens_total = 0
        self.prefill_bucket_positions_total = 0
        # A looped stack runs its layers ``passes`` times a step over one
        # set of weights (1 for every other model): what a step's trace
        # record says beside its rows, and the layers a token costs.
        self.passes = (self.model_cfg.num_kv_layers // self.model_cfg.num_layers
                       if self.model_cfg.looped else 1)
        self.layers_a_token = self.passes * self.model_cfg.num_layers
        self.prefill_layer_passes_total = 0
        # Window-group pages the steps' rows held, summed over steps, beside
        # what the same rows would hold were every page kept.
        self.window_page_steps_total = 0
        self.window_whole_context_page_steps_total = 0
        # Context tokens the rows of decode dispatches held, a step of a
        # burst each, beside those a dispatch's rows held in common pages
        # and the kernel read once for all of them (`_step_info`).
        self.decode_context_tokens_total = 0
        self.decode_shared_tokens_spared_total = 0
        # by the bucket's rows and a row's query positions
        self._sharing_calls: Dict[tuple, int] = {}
        # Rows a step appends to its packed tokens (the model's step_aux,
        # one for each name in its AUX_NAMES), summed here as they are
        # fetched.
        self.aux_names = tuple(self.model.AUX_NAMES)
        self._aux_rows = len(self.aux_names)
        self.step_aux_totals = np.zeros(self._aux_rows, np.float64)
        tp = cfg.tensor_parallel_size
        pp = max(cfg.pipeline_parallel_size, 1)
        self._pp = pp
        if self.model_cfg.num_kv_heads % max(tp, 1):
            raise ValueError(
                f"num_kv_heads={self.model_cfg.num_kv_heads} not divisible by "
                f"tensor_parallel_size={tp}"
            )
        if self.model_cfg.num_layers % pp:
            raise ValueError(
                f"num_layers={self.model_cfg.num_layers} not divisible by "
                f"pipeline_parallel_size={pp}"
            )
        if cfg.sequence_parallel_size > 1 and pp > 1:
            # Fail at startup, not on the first /v1/embeddings request.
            raise ValueError(
                "sequence_parallel_size > 1 (ring encode) does not compose "
                "with pipeline_parallel_size > 1 yet"
            )
        ep = max(cfg.expert_parallel_size, 1)
        if ep > 1 and (
            not self.model_cfg.num_experts
            or self.model_cfg.num_experts % ep
        ):
            raise ValueError(
                f"expert_parallel_size={ep} needs a MoE model with "
                f"num_experts divisible by it "
                f"(model has {self.model_cfg.num_experts})"
            )
        self.mesh = mesh or build_mesh(
            MeshConfig(
                tensor_parallel_size=tp,
                data_parallel_size=cfg.data_parallel_size,
                pipeline_parallel_size=pp,
                sequence_parallel_size=max(cfg.sequence_parallel_size, 1),
                expert_parallel_size=ep,
            )
        )

        t0 = time.time()
        quant = cfg.quantization or None
        if quant not in (None, "int8", "int4"):
            raise ValueError(
                f"unsupported quantization {quant!r} (int8 or int4)"
            )
        self._quant = quant
        if quant == "int4" and self.mesh.size > 1 and self.platform == "tpu":
            # A Mosaic kernel cannot be partitioned by GSPMD: lowering the
            # int4 matmul under jit over several devices is an error on the
            # chip, it has no per-shard wrapper, and the row-parallel
            # shards (wo, w_down) would contract over F/tp rows, which its
            # 1024-row tile need not divide. Refused here, by name, rather
            # than at the first request.
            raise ValueError(
                "quantization='int4' on a mesh of more than one device is "
                "not served on tpu: the int4 Pallas kernel runs unsharded "
                "only (use int8, or a one-device mesh)"
            )
        pspecs = self.model.param_pspecs(pipeline=pp > 1, quantize=quant or False)
        if cfg.enable_lora:
            pspecs["layers"].update(self.model.lora_pspecs(pipeline=pp > 1))
        if os.path.isdir(cfg.model):
            # quantize=True stages + quantizes in numpy on the host: the
            # bf16 tree of an 8B model never exists in HBM next to the int8
            # one (and no CPU JAX backend is needed under a pinned
            # JAX_PLATFORMS).
            params = load_hf_params(
                self.model_cfg, cfg.model, quantize=quant or False
            )
            if cfg.enable_lora:
                params["layers"].update(
                    self.model.init_lora_bank(cfg.max_loras, cfg.max_lora_rank)
                )
            self.params = jax.tree.map(
                lambda x, s: jax.device_put(
                    x, NamedSharding(self.mesh, self._fit_spec(s, x.shape, x.dtype))
                ),
                params,
                pspecs,
            )
        elif quant:
            # Preset (random-init) + quantized: materialize leaf-by-leaf
            # straight into device shardings — peak HBM is the int8 tree
            # plus one transient bf16 leaf.
            self.params = self._init_params_streamed(pspecs)
        else:
            self.params = self._init_params_sharded(pspecs)
        # Weight bytes as resident (post-quantization).
        param_bytes = sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(self.params)
        )
        logger.info(
            "params ready: %.2f GiB total, %.1fs", param_bytes / 2**30, time.time() - t0
        )
        # Startup decomposition, phase 1: parameter materialization
        # (pst_engine_startup_seconds{phase="load"}).
        t_load_end = time.perf_counter()
        ENGINE_TELEMETRY.record_startup_phase("load", t_load_end - t_init)
        ENGINE_TELEMETRY.record_start_time()

        self.num_blocks = resolve_num_kv_blocks(
            cfg, self.model_cfg, param_bytes // (max(tp, 1) * pp)
        )
        self.max_table_width = -(-cfg.max_model_len // cfg.block_size)
        cache_sh = self._cache_sharding()
        self._dispatch_restore_kv()  # single source of truth for allocation
        self._repl = NamedSharding(self.mesh, P())
        # Decode batches shard rows over dp (independent sequences — the
        # in-engine data-parallel axis); prefill chunks stay replicated.
        self._dp = cfg.data_parallel_size
        self._row = NamedSharding(self.mesh, P("dp"))
        self._drop_slot = self.num_blocks * cfg.block_size

        model = self.model
        # Resolved here, once: the jitted steps never see "auto".
        attn_impl = self._attn_impl = resolve_attn_impl(cfg.attn_impl)
        # The model needs the mesh wherever it goes manual over its axes:
        # pp stage rotation, and the Pallas attention kernels, which run
        # per shard on any mesh of more than one device.
        model_mesh = self._model_mesh = (
            self.mesh if self.mesh.size > 1 else None
        )
        # MoE strategy: ragged_dot is the FLOP-proportional single-shard
        # path; whenever the expert bank is mesh-sharded (ep/tp/pp) use the
        # dense einsum formulation, whose contractions GSPMD partitions
        # cleanly (ragged_dot has no partitioning rule — XLA would gather
        # the full bank to every device).
        moe_impl = cfg.moe_impl
        if moe_impl == "auto":
            mesh_shape = dict(self.mesh.shape)
            sharded = (
                mesh_shape.get("ep", 1) > 1 or tp > 1 or pp > 1
            )
            moe_impl = "dense" if sharded else "ragged"
        self._moe_impl = moe_impl

        recurrent, scratch_slot = self._recurrent, self.state_slots
        aux_rows = self._aux_rows
        # token_budget, for a model whose expert dispatch takes it: no step
        # holds more real tokens than a prefill step's budget, however far
        # its rows are padded.
        mtp = self._mtp
        budget = (
            {"token_budget": max(cfg.max_prefill_tokens,
                                 cfg.max_num_seqs * (2 if mtp else 1))}
            if recurrent or model.TOKEN_BUDGET else {})

        def slots_of(batch, active=None):
            """What a model class is told beside the batch: the state slot
            of each row, for a model that keeps any (a row the caller knows
            to be padding goes to the scratch slot), and the token budget."""
            more = {k: batch[k] for k in ("window_tables", "sample_rows")
                    if k in batch}
            if not recurrent:
                return {**more, **budget}
            slots = batch["state_slots"]
            if active is not None:
                slots = jnp.where(active, slots, scratch_slot)
            return {"state_slots": slots, **more, **budget}

        def with_aux(packed, kv_cache):
            """What the model's step reports beside its tokens rides the
            same fetch: one row a number under the packed rows."""
            if not aux_rows:
                return packed
            aux = model.step_aux(kv_cache)
            return jnp.concatenate([
                packed, jnp.broadcast_to(aux[:, None], (aux.shape[0], packed.shape[1])),
            ])

        def step(
            params, kv_cache, batch: Dict[str, Any], want_lp: bool,
            greedy: bool,
        ):
            logits, *hidden, kv_cache = model.forward(
                params,
                batch["tokens"],
                batch["positions"],
                batch["write_idx"],
                batch["block_tables"],
                batch["kv_lens"],
                batch["last_idx"],
                kv_cache,
                lora_idx=batch.get("lora_idx"),
                lora_scale=batch.get("lora_scale"),
                attn_impl=attn_impl,
                moe_impl=moe_impl,
                pp_size=pp,
                mesh=model_mesh,
                **({"return_hidden": True} if "mtp_next" in batch else {}),
                **slots_of(batch),
            )
            if "penalty_prompt" in batch:
                logits = apply_penalties(
                    logits,
                    batch["penalty_prompt"],
                    batch["penalty_output"],
                    batch["presence"],
                    batch["frequency"],
                    batch["repetition"],
                )
            if "bias_ids" in batch:
                logits = apply_logit_bias(
                    logits, batch["bias_ids"], batch["bias_vals"]
                )
            if "allowed_ids" in batch:
                logits = apply_allowed_mask(
                    logits, batch["allowed_ids"], batch["allow_free"]
                )
            # Packed rows: [token] or [token, chosen_lp, top_lps,
            # top_ids] — one fetch serves both sampling and logprobs, and
            # the logprobs math compiles in only when requested.
            packed = sample_tokens_packed(
                logits,
                batch["temps"],
                batch["top_ps"],
                batch["top_ks"],
                batch["min_ps"],
                batch["seeds"],
                with_logprobs=want_lp,
                greedy_only=greedy,
            )
            if hidden:
                # The draft module over the same positions, a token ahead:
                # the token after each is the batch's, or, after a row's
                # last (-1), the one just sampled. The row's first draft
                # rides the packed row as one more column.
                nxt = jnp.where(batch["mtp_next"] >= 0, batch["mtp_next"],
                                packed[:, :1].astype(jnp.int32))
                draft_logits, kv_cache = model.mtp_forward(
                    params, hidden[0], nxt, batch["positions"],
                    batch["mtp_write_idx"], batch["block_tables"],
                    batch["mtp_kv_lens"], batch["last_idx"], kv_cache,
                    attn_impl=attn_impl, **budget)
                draft = jnp.argmax(draft_logits, axis=-1)
                packed = jnp.concatenate(
                    [packed, draft[:, None].astype(packed.dtype)], axis=1)
            return with_aux(packed, kv_cache), kv_cache

        def pst_decode_step_mtp(params, kv_cache, batch, want_lp, greedy):
            """Verify then draft, one program (docs/engine.md "Verify and
            draft"): the main stack on ``[t_n, d]`` at positions ``n, n +
            1``; ``t_{n+1}`` is position 0's sample, the draft ``d`` is
            accepted where it equals it (greedy rows the host marked
            ``draft_ok``) and position 1's sample is then ``t_{n+2}``; the
            draft module on the same two positions with those tokens after
            them; the next draft is its argmax at the last committed
            position. A row of the result: position 0's packed sample,
            position 1's, the accept count, the next draft."""
            tokens = batch["tokens"]
            logits, hidden, kv_cache = model.forward(
                params, tokens, batch["positions"], batch["write_idx"],
                batch["block_tables"], batch["kv_lens"], batch["last_idx"],
                kv_cache, attn_impl=attn_impl, moe_impl=moe_impl,
                pp_size=pp, mesh=model_mesh, all_logits=True,
                return_hidden=True, **slots_of(batch))
            l0, l1 = logits[:, 0], logits[:, 1]
            if "penalty_prompt" in batch:  # such a row is not drafted for
                l0 = apply_penalties(
                    l0, batch["penalty_prompt"], batch["penalty_output"],
                    batch["presence"], batch["frequency"],
                    batch["repetition"])
            if "bias_ids" in batch:
                l0, l1 = (apply_logit_bias(
                    x, batch["bias_ids"], batch["bias_vals"])
                    for x in (l0, l1))
            if "allowed_ids" in batch:  # nor is a guided row
                l0 = apply_allowed_mask(
                    l0, batch["allowed_ids"], batch["allow_free"])
            sample = lambda x, ahead: sample_tokens_packed(  # noqa: E731
                x, batch["temps"], batch["top_ps"], batch["top_ks"],
                batch["min_ps"], batch["seeds"] + jnp.uint32(ahead),
                with_logprobs=want_lp, greedy_only=greedy)
            p0, p1 = sample(l0, 0), sample(l1, 1)
            t1, t2 = (p[:, 0].astype(jnp.int32) for p in (p0, p1))
            live = batch["kv_lens"] > 0
            drafted = batch["draft_ok"] & live
            accept = drafted & (tokens[:, 1] == t1)
            draft_logits, kv_cache = model.mtp_forward(
                params, hidden, jnp.stack([t1, t2], axis=1),
                batch["positions"], batch["mtp_write_idx"],
                batch["block_tables"], batch["mtp_kv_lens"],
                batch["last_idx"], kv_cache, attn_impl=attn_impl,
                all_logits=True, **budget)
            drafts = jnp.argmax(draft_logits, axis=-1)  # [B, 2]
            nxt = jnp.where(accept, drafts[:, 1], drafts[:, 0])
            f32 = jnp.float32
            packed = jnp.concatenate(
                [p0, p1, accept[:, None].astype(f32),
                 nxt[:, None].astype(f32)], axis=1)
            rows = jnp.sum(live).astype(f32)
            took = jnp.sum(accept).astype(f32)
            counts = jnp.stack([  # in the order of the model's MTP_AUX_NAMES
                jnp.sum(drafted).astype(f32), took, jnp.ones((), f32), rows,
                rows + took])
            aux = kv_cache["aux"]
            kv_cache = dict(kv_cache, aux=jnp.concatenate(
                [aux[:aux.shape[0] - counts.shape[0]], counts]))
            return with_aux(packed, kv_cache), kv_cache

        def pst_decode_step_mtp_chained(params, kv_cache, batch, tokens,
                                        drafts, positions, seed_off,
                                        want_lp, greedy):
            """The verify-and-draft step as a chained step: the row's last
            committed token, its draft (-1: none) and its position are the
            device's own from the step before, so the next step's positions,
            write slots and lengths follow the device's accept counts and
            nothing is fetched in between. ``batch``: what the host renews
            (both groups' tables, which rows live, the sampling arrays,
            ``draft_ok``)."""
            tables = batch["block_tables"]
            width = tables.shape[1]
            active = batch["kv_lens"] > 0  # padding and finished rows

            def slots(p):  # [B, 2] positions -> flat slots of the global group
                blk = jnp.take_along_axis(
                    tables, jnp.minimum(p // bs, width - 1), axis=1)
                return jnp.where(
                    active[:, None] & (p // bs < width), blk * bs + p % bs,
                    drop_slot).astype(jnp.int32)

            pos2 = jnp.stack([positions, positions + 1], axis=1)
            packed, kv_cache = pst_decode_step_mtp(params, kv_cache, dict(
                batch,
                tokens=jnp.stack([tokens, jnp.maximum(drafts, 0)], axis=1),
                positions=pos2, write_idx=slots(pos2),
                mtp_write_idx=slots(pos2 + 1),
                kv_lens=jnp.where(active, positions + 2, 0),
                mtp_kv_lens=jnp.where(active, positions + 3, 0),
                last_idx=jnp.ones_like(positions),
                draft_ok=batch["draft_ok"] & (drafts >= 0),
                seeds=batch["seeds"] + seed_off), want_lp, greedy)
            rows = packed[: positions.shape[0]]  # (the aux rows lie below)
            took = rows[:, -2].astype(jnp.int32)
            second = (rows.shape[1] - 2) // 2  # position 1's packed sample
            nxt = jnp.where(took > 0, rows[:, second], rows[:, 0])
            return (packed, nxt.astype(jnp.int32),
                    rows[:, -1].astype(jnp.int32), positions + 1 + took,
                    seed_off + 1, kv_cache)

        def pst_chain_splice_mtp(tokens, drafts, positions, toks, src, pos):
            """`pst_chain_splice` for a chain of verify-and-draft steps: a
            joining row takes its token and its first draft (the last column
            of its prefill row); a member that finished takes no draft."""
            take = src >= 0
            row = toks[jnp.maximum(src, 0)]
            gone = src == -2
            return (
                jnp.where(take, row[:, 0].astype(jnp.int32),
                          jnp.where(gone, 0, tokens)),
                jnp.where(take, row[:, -1].astype(jnp.int32),
                          jnp.where(gone, -1, drafts)),
                jnp.where(take | gone, pos, positions))

        # Sampled tokens come back replicated: on a multi-host mesh the
        # primary must be able to device_get them (only addressable shards
        # are fetchable), and an all-gather of [B] int32 is free.
        # One body, jitted under two names: the device trace tells a decode
        # program (``jit_pst_decode_step``) from a prefill program by its
        # module name alone. Warm-up and live traffic share these objects.
        def pst_decode_step(params, kv_cache, batch, want_lp, greedy):
            return step(params, kv_cache, batch, want_lp, greedy)

        def pst_prefill_step(params, kv_cache, batch, want_lp, greedy):
            return step(params, kv_cache, batch, want_lp, greedy)

        step_jit = dict(
            static_argnums=(3, 4),
            donate_argnums=(1,),
            out_shardings=(self._repl, cache_sh),
        )
        # pstlint: jit-family=decode
        decode_step = jax.jit(pst_decode_step, **step_jit)
        # pstlint: jit-family=prefill
        prefill_step = jax.jit(pst_prefill_step, **step_jit)
        self._step = {"decode": decode_step, "prefill": prefill_step}
        if mtp:
            # found with the decode programs under ``jit_pst_decode_step*``
            # pstlint: jit-family=decode
            self._step["mtp_verify"] = jax.jit(pst_decode_step_mtp, **step_jit)
            # pstlint: jit-family=decode_burst
            self._mtp_chained = jax.jit(
                pst_decode_step_mtp_chained, static_argnums=(7, 8),
                donate_argnums=(1,),
                out_shardings=(self._repl,) * 5 + (cache_sh,))
            # pstlint: jit-family=decode_burst
            self._mtp_splice = jax.jit(
                pst_chain_splice_mtp, out_shardings=(self._repl,) * 3)
        # Every jitted step dispatch below is called through this holder,
        # by its shape key; `place_program_store` gives it the store.
        self.programs = StepPrograms()

        bs = cfg.block_size
        drop_slot = self.num_blocks * bs

        def pst_decode_burst(params, kv_cache, batch, tokens, positions,
                             seed_off, pen_counts, n_steps: int,
                             want_lp: bool, greedy: bool, with_pen: bool):
            """Decode ``n_steps`` tokens per sequence in one compiled call.

            The inter-token dependency (sampled token feeds the next forward)
            lives inside a ``lax.scan``: positions, page write slots, and
            per-step PRNG seeds are all derived on-device. ``tokens`` /
            ``positions`` / ``seed_off`` are explicit [B]/[B]/scalar inputs
            and are returned advanced, so a FOLLOW-UP burst can chain from
            the previous burst's device outputs with zero host round trips —
            the basis of pipelined decode (one burst always in flight, its
            fetch overlapped with the next burst's execution).

            ``pen_counts`` ([B, V] output-token occurrence counts, or a
            [1, 1] placeholder when ``with_pen`` is False) rides the scan
            carry and is returned advanced: each sampled token increments
            its own count ON DEVICE, so penalty/repetition rows decode at
            full burst depth — and a pipelined continuation chains the
            counts without ever rebuilding them host-side."""
            tables = batch["block_tables"]
            active = batch["kv_lens"] > 0  # padding rows never write

            def body(carry, i):
                kv_cache, tokens, positions, so, counts = carry
                blk = jnp.take_along_axis(
                    tables, (positions // bs)[:, None], axis=1
                )[:, 0]
                flat = jnp.where(
                    active, blk * bs + positions % bs, drop_slot
                ).astype(jnp.int32)
                logits, kv_cache = model.forward(
                    params,
                    tokens[:, None],
                    positions[:, None],
                    flat[:, None],
                    tables,
                    positions + 1,  # kv valid through the just-written slot
                    jnp.zeros_like(positions),
                    kv_cache,
                    lora_idx=batch.get("lora_idx"),
                    lora_scale=batch.get("lora_scale"),
                    attn_impl=attn_impl,
                    moe_impl=moe_impl,
                    pp_size=pp,
                    mesh=model_mesh,
                    **slots_of(batch, active),
                )
                if with_pen:
                    logits = apply_penalties_counts(
                        logits,
                        batch["penalty_seen"],
                        counts,
                        batch["presence"],
                        batch["frequency"],
                        batch["repetition"],
                    )
                if "bias_ids" in batch:
                    logits = apply_logit_bias(
                        logits, batch["bias_ids"], batch["bias_vals"]
                    )
                packed = sample_tokens_packed(
                    logits,
                    batch["temps"],
                    batch["top_ps"],
                    batch["top_ks"],
                    batch["min_ps"],
                    batch["seeds"] + so,
                    with_logprobs=want_lp,
                    greedy_only=greedy,
                )
                nxt = packed[:, 0].astype(jnp.int32)
                if with_pen:
                    counts = counts.at[
                        jnp.arange(counts.shape[0], dtype=jnp.int32), nxt
                    ].add(active.astype(jnp.float32))
                return (
                    (kv_cache, nxt, positions + 1, so + 1, counts),
                    with_aux(packed, kv_cache),
                )

            carry = (kv_cache, tokens, positions, seed_off, pen_counts)
            (kv_cache, tokens, positions, seed_off, pen_counts), packed = (
                jax.lax.scan(body, carry, jnp.arange(n_steps), length=n_steps)
            )
            # [n, B, W] -> [B, n, W]
            return (
                packed.transpose(1, 0, 2), tokens, positions, seed_off,
                pen_counts, kv_cache,
            )

        # One body, jitted under two names, as the step is above: a chained
        # step of depth 1 is a decode step, and the device trace finds it
        # with the synchronous one under ``jit_pst_decode_step``; deeper
        # bursts are ``jit_pst_decode_burst``. ``_burst_fn`` picks by depth
        # for live traffic, the warm-up and the multi-host follower alike.
        def pst_decode_step_chained(params, kv_cache, batch, tokens,
                                    positions, seed_off, pen_counts,
                                    n_steps: int, want_lp: bool,
                                    greedy: bool, with_pen: bool):
            return pst_decode_burst(
                params, kv_cache, batch, tokens, positions, seed_off,
                pen_counts, n_steps, want_lp, greedy, with_pen,
            )

        burst_jit = dict(
            static_argnums=(7, 8, 9, 10),
            donate_argnums=(1,),
            out_shardings=(
                self._repl, self._repl, self._repl, self._repl, self._repl,
                cache_sh,
            ),
        )
        # pstlint: jit-family=decode_burst
        self._multi_step = jax.jit(pst_decode_burst, **burst_jit)
        # pstlint: jit-family=decode_burst
        self._chained_step = jax.jit(pst_decode_step_chained, **burst_jit)

        def pst_chain_splice(tokens, positions, toks, src, pos):
            """A chain's carry between a prefill and the chained step behind
            it: row ``i`` takes the token prefill row ``src[i]`` sampled
            (column 0 of its packed row) and the position ``pos[i]`` where
            ``src[i] >= 0``, token 0 at ``pos[i]`` where ``src[i] == -2`` (a
            member that finished: its row reads no context from here on),
            and keeps the burst in flight's own otherwise."""
            take = src >= 0
            new = toks[jnp.maximum(src, 0), 0].astype(jnp.int32)
            tokens = jnp.where(take, new, jnp.where(src == -2, 0, tokens))
            return tokens, jnp.where(take | (src == -2), pos, positions)

        # Keyed by the chain's row bucket and the prefill's packed rows
        # alone: the decode and prefill programs stay what they were.
        # pstlint: jit-family=decode_burst
        self._splice = jax.jit(
            pst_chain_splice, out_shardings=(self._repl, self._repl))
        self._splice_warm: set = set()
        # The last prefill program's packed rows, still on the device: what
        # a chain kept across that prefill takes its new rows' tokens from.
        self._prefill_toks = None
        # A dispatch `prefill_dispatch` left for `prefill_fetch` to record,
        # and its items (whose first drafts the rows carry, `_take_drafts`).
        self._prefill_record = None
        self._prefill_items: List[PrefillItem] = []
        # When each launched program was seen ready (`_ReadyClock`).
        self._clock = _ReadyClock(
            self._program_ready, lambda: ENGINE_TELEMETRY.no_work_phases)
        # Service time no live row was left to pay (`_program_ready`).
        self._owed_s = 0.0
        # Pipelined-burst state: device handles of the burst in flight.
        self._burst = None
        # Per-request cost attribution (docs/observability.md "Cost
        # attribution"): when on, every dispatch's measured wall is split
        # across the sequences it served (token-weighted for prefill,
        # active-row share for decode/verify) so request costs sum to the
        # device-busy wall.
        self._cost_enabled = bool(cfg.cost_attribution)
        # Host-gap accounting: perf_counter stamp of the moment the last
        # decode step's tokens became host-visible with the device idle
        # (pst_engine_host_gap_seconds measures from here to the next
        # decode dispatch — the serial host bookkeeping on the critical
        # path that the overlapped pipeline exists to hide).
        self._host_gap_t0: Optional[float] = None
        # Multi-host control plane (None on single-host): installed by the
        # server when jax.process_count() > 1; every device dispatch below
        # announces first so followers issue the identical XLA call.
        self.publisher = None
        # Serializes announce+dispatch pairs: the engine step thread and the
        # executor threads serving /v1/embeddings//rerank//score would
        # otherwise interleave broadcasts, diverging the followers' XLA
        # program order from the primary's (collective deadlock).
        self._device_lock = threading.RLock()
        # Startup decomposition, phase 2: device placement + KV-cache
        # allocation + jit wiring (pst_engine_startup_seconds{phase="shard"}).
        ENGINE_TELEMETRY.record_startup_phase(
            "shard", time.perf_counter() - t_load_end
        )
        # What this engine resolved, stated once (log + GET /version) so a
        # smoke or an operator asserts it instead of guessing.
        self.device_info = {
            **describe_devices(),
            "mesh_shape": dict(self.mesh.shape),
            "mesh_device_ids": [int(d.id) for d in self.mesh.devices.flat],
            "attention_impl": self._attn_impl,
            "int4_impl": self._int4_impl(),
            "pallas_interpret": pallas_interpret(),
            "kv_pages": self.num_blocks,
            # Per mesh device, after load and KV allocation: a tree built
            # on device 0 and spread later shows up here as a pile.
            # (None where the backend reports none, or the device belongs
            # to another host.)
            "hbm_bytes_in_use": [
                (d.memory_stats() or {}).get("bytes_in_use")
                if d.process_index == jax.process_index() else None
                for d in self.mesh.devices.flat
            ],
        }
        logger.info("engine device path: %s", self.device_info)

    def place_program_store(self, cache_path: Optional[str]) -> None:
        """Keep step programs beside the compile cache at ``cache_path``
        (what ``configure_compile_cache`` returned), where one is kept at
        all (`program_store.open_store`). What the runner resolved itself
        names the entries with the configuration it resolved it from."""
        self.programs.store = open_store(
            cache_path, self.cfg, self.model_cfg, self.mesh,
            resolved={
                "attention_impl": self._attn_impl,
                "moe_impl": self._moe_impl,
                "int4_impl": self._int4_impl(),
                "pallas_interpret": pallas_interpret(),
                "kv_pages": self.num_blocks,
                "state_slots": self.state_slots,
                "window_blocks": self.window_blocks,
            },
        )
        self.device_info["program_store"] = (
            self.programs.store.path if self.programs.store else None)

    def _int4_impl(self) -> Optional[str]:
        """Which implementation the int4 layer matmuls trace to: ``pallas``
        (every leaf through the kernel), ``xla`` (dequant + dot), ``mixed``,
        or None without int4 weights."""
        from ..ops.int4_matmul import use_int4_kernel

        layers = self.params["layers"]
        picks = set()
        for name, scales in layers.items():
            if name.endswith(QUANT4_SUFFIX):
                # Per-layer slices of the stacked leaves, as the scan sees them.
                w = layers[name[: -len(QUANT4_SUFFIX)]]
                picks.add(use_int4_kernel(
                    jax.ShapeDtypeStruct(w.shape[1:], w.dtype),
                    jax.ShapeDtypeStruct(scales.shape[1:], scales.dtype),
                ))
        if not picks:
            return None
        if len(picks) == 2:
            return "mixed"
        return "pallas" if picks.pop() else "xla"

    # ------------------------------------------------------------------
    # Streamed param materialization (quantized presets)
    # ------------------------------------------------------------------

    # Leaves above this replicate-instead-of-shard threshold still raise on
    # non-divisible dims: silently replicating a multi-GB weight across tp
    # would turn a clear startup misconfiguration into a distant OOM.
    _FIT_SPEC_MAX_BYTES = 4 << 20

    def _fit_spec(self, spec: P, shape, dtype=None) -> P:
        """Drop sharding on SMALL axes the array's dims don't divide
        (replicate instead). Real serving shapes always divide; tiny debug
        models can end up with e.g. 2 int4 scale groups under tp=4 —
        replicating a few-KB scale there beats failing the mesh placement.
        Big leaves keep the loud divisibility error."""
        ent = list(spec) + [None] * (len(shape) - len(spec))
        for i, ax in enumerate(ent):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else tuple(ax)
            size = 1
            for a in axes:
                size *= self.mesh.shape[a]
            if shape[i] % size:
                nbytes = int(np.prod(shape)) * (
                    np.dtype(dtype).itemsize if dtype is not None else 4
                )
                if nbytes > self._FIT_SPEC_MAX_BYTES:
                    raise ValueError(
                        f"param leaf of shape {tuple(shape)} ({nbytes>>20} MiB)"
                        f" is not divisible by mesh axis {ax!r}"
                        f" (size {size}) on dim {i}; refusing to replicate a"
                        " large leaf — fix the parallelism config"
                    )
                logger.debug(
                    "replicating small leaf %s on mesh axis %r "
                    "(dim %d=%d not divisible by %d)",
                    tuple(shape), ax, i, shape[i], size,
                )
                ent[i] = None
        return P(*ent)

    def _init_params_sharded(self, pspecs: Dict[str, Any]) -> Dict[str, Any]:
        """Random-init an unquantized preset: the values of
        ``model.init_params(PRNGKey(seed))`` (to an fp32 ulp — XLA fuses
        the scaling), materialised under ``jit`` straight into the mesh
        shardings. Built eagerly the whole tree lands on device 0 first —
        an 8B bf16 tree is the size of one chip's HBM — before
        ``device_put`` spreads it."""
        cfg = self.cfg
        rng = jax.random.PRNGKey(cfg.seed)

        def full_tree(key):
            tree = self.model.init_params(key)
            if cfg.enable_lora:
                tree["layers"].update(
                    self.model.init_lora_bank(cfg.max_loras, cfg.max_lora_rank)
                )
            return tree

        shardings = jax.tree.map(
            lambda sds, spec: NamedSharding(
                self.mesh, self._fit_spec(spec, sds.shape, sds.dtype)
            ),
            jax.eval_shape(full_tree, rng),
            pspecs,
        )
        # pstlint: disable=recompile-risk(parameter materialization runs once at startup inside the load phase, before /ready — it can never be a live-traffic compile)
        return jax.jit(full_tree, out_shardings=shardings)(rng)

    def _init_params_streamed(self, pspecs: Dict[str, Any]) -> Dict[str, Any]:
        """Random-init params leaf-by-leaf, each jitted directly into its
        device sharding and (for matmul weights) quantized to int8 on
        device before the next leaf materializes. Peak HBM = final int8
        tree + ONE transient bf16 leaf — how an 8B preset initializes on a
        16 GiB chip where the bf16 tree alone would OOM."""
        cfg = self.cfg
        rng = jax.random.PRNGKey(cfg.seed)
        shapes = jax.eval_shape(self.model.init_params, rng)
        if cfg.enable_lora:
            shapes["layers"].update(
                jax.eval_shape(
                    functools.partial(
                        self.model.init_lora_bank,
                        cfg.max_loras,
                        cfg.max_lora_rank,
                    )
                )
            )

        def build(name, sds, specs_at, into):
            key = jax.random.fold_in(
                rng, xxhash.xxh32(name.encode()).intdigest() & 0x7FFF_FFFF
            )
            # Per-layer matmuls follow the configured mode (int8 or group-
            # wise int4); embed/lm_head stay per-channel int8 in both modes.
            int4 = self._quant == "int4" and name in QUANT_LAYER_KEYS
            qaxis = (
                -2 if name in QUANT_LAYER_KEYS
                else -1 if name in QUANT_TOP_KEYS
                else None
            )
            if qaxis is None:
                # pstlint: disable=recompile-risk(parameter materialization runs once at startup inside the load phase, before /ready — it can never be a live-traffic compile)
                into[name] = jax.jit(
                    functools.partial(init_leaf, name, sds.shape, sds.dtype),
                    out_shardings=NamedSharding(
                        self.mesh, self._fit_spec(specs_at[name], sds.shape, sds.dtype)
                    ),
                )(key)
                return

            def init_q(k):  # one jit per leaf: init + quantize fused
                w = init_leaf(name, sds.shape, sds.dtype, k)
                return (
                    quantize_leaf_int4(w) if int4
                    else quantize_leaf(w, axis=qaxis)
                )

            qname = name + (QUANT4_SUFFIX if int4 else QUANT_SUFFIX)
            q_sds, s_sds = jax.eval_shape(init_q, key)
            # pstlint: disable=recompile-risk(weight quantization runs once at startup inside the load phase, before /ready — it can never be a live-traffic compile)
            q, s = jax.jit(
                init_q,
                out_shardings=(
                    NamedSharding(
                        self.mesh, self._fit_spec(specs_at[name], q_sds.shape, q_sds.dtype)
                    ),
                    NamedSharding(
                        self.mesh, self._fit_spec(specs_at[qname], s_sds.shape, s_sds.dtype)
                    ),
                ),
            )(key)
            into[name], into[qname] = q, s

        out: Dict[str, Any] = {"layers": {}}
        for name, sds in shapes.items():
            if name == "layers":
                continue
            build(name, sds, pspecs, out)
        for name, sds in shapes["layers"].items():
            build(name, sds, pspecs["layers"], out["layers"])
        return out

    # ------------------------------------------------------------------
    # Page I/O for KV tiering (HBM ↔ host DRAM, the LMCache-offload hook).
    # blk is a traced scalar so each direction compiles exactly once.
    # ------------------------------------------------------------------

    def download_page(self, blk: int):
        """Fetch one page's K/V across all layers → host numpy [L, bs, KH, hd]."""
        with self._device_lock:
            if self.publisher is not None:
                self.publisher.announce("download_page", int(blk))
            return self._dispatch_download_page(blk)

    def _dispatch_download_page(self, blk: int):
        if not hasattr(self, "_page_get"):
            # pstlint: disable=recompile-risk(KV page download is a fixed-shape maintenance op — one compile per engine lifetime at first swap-out, off the TTFT path)
            self._page_get = jax.jit(
                lambda c, i: c[:, i], out_shardings=self._repl
            )
        page = _fetch(self._page_get(self.kv_cache, blk))
        L, _, bs, _ = page.shape
        KH, hd = self.model_cfg.num_kv_heads, self.model_cfg.head_dim
        k = page[:, 0].reshape(L, bs, KH, hd)
        v = page[:, 1].reshape(L, bs, KH, hd)
        return k, v

    def upload_page(self, blk: int, k_np, v_np) -> None:
        """Install host page data into HBM page ``blk`` (donated, in-place)."""
        with self._device_lock:
            if self.publisher is not None:
                self.publisher.announce("upload_page", (int(blk), k_np, v_np))
            self._dispatch_upload_page(blk, k_np, v_np)

    def _dispatch_upload_page(self, blk: int, k_np, v_np) -> None:
        if not hasattr(self, "_page_set"):
            # pstlint: disable=recompile-risk(KV page upload is a fixed-shape maintenance op — one compile per engine lifetime at first swap-in, off the TTFT path)
            self._page_set = jax.jit(
                lambda c, i, x: c.at[:, i].set(x), donate_argnums=(0,)
            )
        k_np, v_np = np.asarray(k_np), np.asarray(v_np)
        L, bs = k_np.shape[0], k_np.shape[1]
        page = np.stack(
            [k_np.reshape(L, bs, -1), v_np.reshape(L, bs, -1)], axis=1
        )  # [L, 2, bs, KH*hd]
        self.kv_cache = self._page_set(
            self.kv_cache, blk, jnp_asarray(page, self.kv_cache.dtype)
        )

    # ------------------------------------------------------------------
    # LoRA bank slots (engine/lora.py owns name->slot; device arrays here)
    # ------------------------------------------------------------------

    def install_adapter(self, slot: int, arrays: Dict[str, Any]) -> None:
        """Write one adapter's A/B matrices into bank slot ``slot``.

        arrays: {target: (A [L, in, r_max], B [L, r_max, out])} host numpy.
        """
        with self._device_lock:
            if self.publisher is not None:
                self.publisher.announce("install_adapter", (int(slot), arrays))
            self._dispatch_install_adapter(slot, arrays)

    def _dispatch_install_adapter(self, slot: int, arrays: Dict[str, Any]) -> None:
        if not hasattr(self, "_slot_set"):
            # pstlint: disable=recompile-risk(LoRA bank install is a fixed-shape admin op paid on adapter load, not on live decode)
            self._slot_set = jax.jit(
                lambda bank, s, x: bank.at[:, s].set(x), donate_argnums=(0,)
            )
        layers = self.params["layers"]
        for t, (a_np, b_np) in arrays.items():
            for key, host in ((f"lora_a_{t}", a_np), (f"lora_b_{t}", b_np)):
                bank = layers[key]
                layers[key] = self._slot_set(
                    bank, slot, jnp_asarray(host, bank.dtype)
                )

    def uninstall_adapter(self, slot: int) -> None:
        """Zero bank slot ``slot`` (unload: the slot id may be reused)."""
        with self._device_lock:
            if self.publisher is not None:
                self.publisher.announce("uninstall_adapter", int(slot))
            self._dispatch_uninstall_adapter(slot)

    def _dispatch_uninstall_adapter(self, slot: int) -> None:
        if not hasattr(self, "_slot_zero"):
            # pstlint: disable=recompile-risk(LoRA bank zeroing is a fixed-shape admin op paid on adapter unload, not on live decode)
            self._slot_zero = jax.jit(
                lambda bank, s: bank.at[:, s].set(0.0), donate_argnums=(0,)
            )
        layers = self.params["layers"]
        for key in list(layers):
            if key.startswith("lora_"):
                layers[key] = self._slot_zero(layers[key], slot)

    # ------------------------------------------------------------------
    # Sleep / wake (reference tutorial 19: free accelerator memory without
    # restarting the pod; KV contents are discarded, shapes restored on wake)
    # ------------------------------------------------------------------

    def drop_kv_cache(self) -> None:
        with self._device_lock:
            if self.publisher is not None:
                self.publisher.announce("drop_kv", None)
            self._dispatch_drop_kv()

    def _dispatch_drop_kv(self) -> None:
        jax.tree.map(lambda a: a.delete(), self.kv_cache)
        self.kv_cache = None

    def restore_kv_cache(self) -> None:
        with self._device_lock:
            if self.publisher is not None:
                self.publisher.announce("restore_kv", None)
            self._dispatch_restore_kv()

    def _cache_sharding(self):
        """The cache's shardings, in the cache's own tree (one array of
        pages, or a model's pages and state pools)."""
        return jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec),
            self.model.cache_pspec(pipeline=self._pp > 1),
            is_leaf=lambda x: isinstance(x, P),
        )

    def _dispatch_restore_kv(self) -> None:
        cache_sh = self._cache_sharding()
        pools = {"state_slots": self.state_slots} if self._recurrent else {}
        if self.window_blocks:
            pools["window_blocks"] = self.window_blocks
        # Allocated under jit so each device zero-fills only its own shard:
        # built eagerly the whole cache would land on device 0 first, and a
        # tp-sharded cache is sized to fill every device.
        # pstlint: disable=recompile-risk(KV cache allocation is a fixed-shape startup/wake op, never on a live decode step)
        self.kv_cache = jax.jit(
            functools.partial(
                self.model.make_kv_cache,
                self.num_blocks, self.cfg.block_size, self.cfg.kv_cache_dtype,
                **pools,
            ),
            out_shardings=cache_sh,
        )()

    # ------------------------------------------------------------------
    # Embeddings (/v1/embeddings): full-attention encode, mean-pooled
    # ------------------------------------------------------------------

    def encode(self, token_ids: Seq[int]) -> np.ndarray:
        T = _pow2(max(len(token_ids), 1), cap=_pow2(self.cfg.max_model_len))
        # Ring encode shards T over sp: round the bucket UP to a multiple
        # (a power of two is never divisible by e.g. sp=3).
        sp = max(self.cfg.sequence_parallel_size, 1)
        T = -(-T // sp) * sp
        toks = np.zeros((1, T), np.int32)
        toks[0, : len(token_ids)] = token_ids
        length = np.array([len(token_ids)], np.int32)
        key = (self._tel_scope, "encode", T)
        self._host_gap_cancel()
        with self._dispatching(
            "encode", key, None, f"t{T}", tokens=len(token_ids),
            fill=len(token_ids) / max(T, 1), in_step=False,
        ) as who, self._device_lock:
            if self.publisher is not None:
                self.publisher.announce("encode", (toks, length))
            return self._dispatch_encode(toks, length, key, who)

    def _dispatch_encode(
        self, toks: np.ndarray, length: np.ndarray,
        key: Optional[tuple] = None, who: Optional[_Dispatch] = None,
    ) -> np.ndarray:
        if not hasattr(self, "_encode_fn"):
            model = self.model
            pp = self._pp
            sp = max(self.cfg.sequence_parallel_size, 1)
            mesh = self.mesh if (pp > 1 or sp > 1) else None

            moe_impl = self._moe_impl

            def enc(params, toks, length):
                return model.encode(
                    params, toks, length, pp_size=pp, sp_size=sp,
                    moe_impl=moe_impl, mesh=mesh,
                )

            # pstlint: jit-family=encode
            self._encode_fn = jax.jit(enc, out_shardings=self._repl)
        with self._launch("encode", who) as launch:
            launch.handle = out = self.programs.call(
                key, self._encode_fn,
                (self.params, jax.device_put(toks, self._repl),
                 jax.device_put(length, self._repl)),
            )
        return _fetch(out, "encode", self._clock)[0]

    # ------------------------------------------------------------------
    # Public entry points
    # ------------------------------------------------------------------

    @staticmethod
    def _want_lp(seqs: List[Sequence]) -> bool:
        return any(s.sampling.logprobs is not None for s in seqs)

    @staticmethod
    def _all_greedy(seqs: List[Sequence]) -> bool:
        """True when every row is greedy: the compiled step then skips the
        full sampling machinery (static fast path in ops/sampling.py)."""
        return all(s.sampling.greedy for s in seqs)

    def _tel_key(
        self, kind: str, batch: Dict[str, np.ndarray], extras: tuple = ()
    ) -> tuple:
        """Shape-bucket signature for compile detection: the padded array
        shapes plus the static jit flags are exactly what keys the XLA
        executable cache, so a fresh signature means a fresh compile."""
        shapes = tuple(sorted((k, np.shape(v)) for k, v in batch.items()))
        return (self._tel_scope, kind, shapes, extras)

    def _step_info(
        self, kind: str, bucket: str, seqs: List[Sequence],
        batch: Dict[str, np.ndarray], new_tokens: int, kv_ahead: int = 0,
        verify: Optional[dict] = None,
    ) -> None:
        """Tell the trace and the open step phase what this step is:
        ``kv_tokens`` sums ``kv_lens`` over the real rows after this step's
        tokens (``batch`` has a burst's lengths at its first step; a live
        row holds ``kv_ahead`` more after its last), ``kv_pages`` the pages
        those rows hold; of a decode dispatch, ``shared_kv_tokens`` the
        tokens in the leading pages its ``shared_rows`` live rows hold in
        common and the decode kernel reads once a call, which are in
        ``kv_tokens`` once a row all the same."""
        n = len(seqs)
        # (plain lists: see `shared_prefix_run`)
        first = [x for x in batch["kv_lens"][:n].tolist() if x > 0]
        kv_tokens = sum(first) + len(first) * kv_ahead
        # state_slots: rows whose recurrent state the step reads and writes
        slots = {"state_slots": n} if self._recurrent else {}
        if self.model_cfg.num_state_layers:
            # a matrix-valued state: the rows and the layers whose slots the
            # step reads and writes (what its decode kernel's bytes follow)
            slots.update(state_rows=n,
                         state_layers=self.model_cfg.num_state_layers)
        if self.window_blocks:
            # window_tokens: what the window layers read, a row at most its
            # window; window_pages: what the rows hold in that group
            win = self.model_cfg.sliding_window
            slots["window_tokens"] = sum(min(x + kv_ahead, win) for x in first)
            whole = sum(len(s.window_block_ids) for s in seqs)
            slots["window_pages"] = whole - sum(s.window_released for s in seqs)
            self.window_page_steps_total += slots["window_pages"]
            self.window_whole_context_page_steps_total += whole
        if self.passes > 1:
            # a looped stack: the step reads its weights and writes a layer
            # of pages once a pass
            slots["passes"] = self.passes
        if kind == "decode":
            # What a layer that reads a row's whole context would read a
            # walk a row, and what the kernel's shared phase spares of it:
            # the run by the lengths of the dispatch's first step (a later
            # step's may be a page longer), and only where the calls take
            # it, by the rule they trace by (``decode_sharing_calls``).
            depth, Bb = kv_ahead + 1, len(batch["kv_lens"])
            # (a verify-and-draft step's two positions a row ride the same
            # stream: a short run, its shared pages below the first of them)
            positions = 2 if verify else 1
            calls = self._sharing_calls.get((Bb, positions))
            if calls is None:
                cfg = self.model_cfg
                calls = self._sharing_calls[Bb, positions] = (
                    0 if cfg.latent_pages else decode_sharing_calls(
                        self._attn_impl, self.mesh, Bb,
                        *cfg.paged_query_shape, cfg.global_window, positions))
            pages, rows = shared_prefix_run(
                batch["block_tables"][:n], batch["kv_lens"][:n],
                self.cfg.block_size, positions,
            ) if calls else (0, 0)
            slots.update(shared_kv_tokens=pages * self.cfg.block_size,
                         shared_rows=rows)
            self.decode_context_tokens_total += (
                sum(first) * depth + len(first) * depth * (depth - 1) // 2)
            self.decode_shared_tokens_spared_total += (
                max(rows - calls, 0) * slots["shared_kv_tokens"] * depth)
        ENGINE_TELEMETRY.step_info(
            kind, bucket=bucket, rows=n, new_tokens=new_tokens,
            kv_tokens=kv_tokens,
            kv_pages=sum(len(s.block_ids) for s in seqs),
            **slots, **(verify or {}),
        )

    # -- the device's side of a dispatch (`_ReadyClock`) ------------------

    def _dispatching(self, kind: str, key: tuple, charge, bucket: str,
                     **how) -> _Dispatch:
        """Around one dispatch call: see `_Dispatch`. ``charge``: the
        sequences of a decode or verify step, the items of a prefill step,
        None where no request pays (an embedding)."""
        if charge is not None:
            charge = (self._charge_prefill if kind == "prefill"
                      else self._charge_decode, charge)
        return _Dispatch(kind, key, charge, bucket, **how)

    def _launch(self, kind: str, who: Optional[_Dispatch] = None,
                **meta) -> _Launch:
        """The ``pst.launch`` phase of a program of ``who``'s (None: a
        warm-up's or a follower's, which moves the clock and no counter)."""
        return _Launch(self._clock, kind, who, meta)

    def _program_ready(self, entry: list, start: float, ready: float,
                       seen: str, idle_s: float, idle_state: str) -> None:
        """The clock's sink: a program was seen ready. Its service time
        goes to the telemetry and to whoever its dispatch charges, here,
        when it is known, which for a chained step or a prefill launched
        ahead of one is a cycle after the dispatch."""
        kind, _, launched_at, who = entry[:4]
        ENGINE_TELEMETRY.record_ready(
            kind, who.bucket if who is not None else "", launched_at, start,
            ready, seen, idle_s, idle_state, live=who is not None)
        if who is not None:
            service = ready - start
            who.service_s += service
            if who.charge is not None:
                # A program none of whose rows is alive any more (the step
                # a chain had launched before its last member's end was
                # read) is paid by the rows of the next program charged:
                # the shares sum to the busy counter.
                owed = service + self._owed_s
                charged = who.charge[0](who.charge[1], owed)
                self._owed_s = 0.0 if charged else owed

    # -- per-request cost attribution ------------------------------------

    def _charge_decode(self, seqs: List[Sequence], seconds: float) -> bool:
        """Split one decode/verify program's service time equally across
        its ACTIVE rows when it is seen ready (padding rows and members
        that finished meanwhile cost nothing; each program's time is
        charged exactly once, so shares sum to the busy counter). False
        where no row was left to pay."""
        if not self._cost_enabled or seconds <= 0:
            return True
        alive = [s for s in seqs if not s.is_finished]
        if not alive:
            return False
        share = seconds / len(alive)
        now = time.monotonic()
        for s in alive:
            s.cost_decode_s += share
            s.charge_kv_pages(now)
        return True

    def _charge_prefill(self, items: List[PrefillItem], seconds: float) -> bool:
        """Split one prefill program's service time across its chunks by
        real-token weight (a 2k-token chunk sharing a step with a 64-token
        one pays accordingly)."""
        if not self._cost_enabled or seconds <= 0:
            return True
        total = sum(it.end - it.start for it in items)
        if total <= 0:
            return False
        now = time.monotonic()
        for it in items:
            it.seq.cost_prefill_s += seconds * (it.end - it.start) / total
            it.seq.charge_kv_pages(now)
        return True

    # -- host-gap accounting (pst_engine_host_gap_seconds) ---------------

    def _host_gap_mark(
        self, bucket: str, t_dispatch: float, seqs=None
    ) -> None:
        """Close the open host gap at a decode dispatch: the wall between
        the previous decode step's completion and this dispatch is pure
        serial host bookkeeping (batch build, detok, stop scans, scheduler
        accounting) that idled the device. One sequence of the dispatching
        burst rides along as the histogram exemplar (a slow gap bucket
        links to the request timeline that absorbed it)."""
        t0, self._host_gap_t0 = self._host_gap_t0, None
        if t0 is not None:
            ENGINE_TELEMETRY.record_host_gap(
                bucket, t_dispatch - t0,
                request_id=seqs[0].request_id if seqs else None,
            )

    def _host_gap_arm(self) -> None:
        """A decode step's tokens just became host-visible with no further
        device work queued: the host gap starts now."""
        self._host_gap_t0 = time.perf_counter()

    def _host_gap_cancel(self) -> None:
        """A non-decode dispatch (prefill/spec/encode) intervened: the
        decode→decode gap is no longer host bookkeeping — drop it."""
        self._host_gap_t0 = None

    def execute_decode(self, seqs: List[Sequence]) -> np.ndarray:
        """One decode step per sequence. Returns packed sample rows
        [len(seqs), 1 or PACKED_WIDTH] (token [+ logprobs]; ops/sampling.py)."""
        with ENGINE_TELEMETRY.phase("batch_build", "decode"):
            batch = self._decode_batch(seqs)
            want_lp, greedy = self._want_lp(seqs), self._all_greedy(seqs)
            key = self._tel_key("decode", batch, (want_lp, greedy))
            Bb = batch["kv_lens"].shape[0]
            self._step_info("decode", f"b{Bb}", seqs, batch, len(seqs))
        with self._dispatching(
            "decode", key, seqs, f"b{Bb}", tokens=len(seqs),
            fill=len(seqs) / Bb,
        ) as who:
            self._host_gap_mark(f"b{Bb}", who.t0, seqs)
            rows = self._run(batch, want_lp, greedy, "decode", key, who)
            self._host_gap_arm()
        return rows[: len(seqs)]

    def execute_decode_multi(self, seqs: List[Sequence], n_steps: int) -> np.ndarray:
        """Decode burst: ``n_steps`` tokens per sequence in one device call.
        Returns packed rows [len(seqs), n_steps, PACKED_WIDTH] (host trims
        at stops)."""
        if n_steps == 1:
            return self.execute_decode(seqs)[:, None]
        with ENGINE_TELEMETRY.phase("batch_build", "decode"):
            batch = self._decode_batch(seqs, multi=True)
            # Guided-choice masks are rebuilt per token host-side; the scan
            # body cannot apply them. The scheduler forces n=1 for guided
            # rows — fail loudly if that invariant ever breaks instead of
            # dropping the mask (RuntimeError, not assert: must survive
            # `python -O`).
            if "allowed_ids" in batch:
                raise RuntimeError(
                    "guided-choice rows reached a multi-step decode burst"
                )
            counts = self._penalty_counts_for(seqs, batch)
            want_lp = self._want_lp(seqs)
            greedy = self._all_greedy(seqs)
            key = self._tel_key("decode", batch, (n_steps, want_lp, greedy))
            Bb = batch["kv_lens"].shape[0]
            self._step_info(
                "decode", f"b{Bb}xn{n_steps}", seqs, batch,
                len(seqs) * n_steps, n_steps - 1,
            )
        with self._dispatching(
            "decode", key, seqs, f"b{Bb}xn{n_steps}",
            tokens=len(seqs) * n_steps, fill=len(seqs) / Bb,
        ) as who:
            self._host_gap_mark(f"b{Bb}xn{n_steps}", who.t0, seqs)
            with self._device_lock:
                if self.publisher is not None:
                    self.publisher.announce(
                        "multi_step", (batch, counts, n_steps, want_lp, greedy)
                    )
                rows = self._dispatch_multi_step(
                    batch, counts, n_steps, want_lp, greedy, key, who
                )
            self._host_gap_arm()
        return rows[: len(seqs)]

    def _penalty_counts_for(
        self, seqs: List[Sequence], batch: Dict[str, np.ndarray]
    ) -> np.ndarray:
        """Dense penalty state for a multi-step batch, replacing the
        token-id arrays ``_sampling_arrays`` builds for the single-step
        path: ``penalty_seen`` [Bb, V] bool (prompt occurrence — constant
        over the whole burst/pipeline) goes INTO the batch, and the
        returned [Bb, V] float32 output-token counts ride ``multi_step``'s
        scan carry. Dense state keeps the executable's trace signature
        independent of prompt/output lengths (one penalized variant per
        bucket, not one per pow2 length). Returns the [1, 1] placeholder
        when no row is penalized."""
        if not any(s.sampling.has_penalties for s in seqs):
            # The id-array penalty fields are only built when a row is
            # penalized; nothing to strip.
            return np.zeros((1, 1), np.float32)
        Bb = batch["kv_lens"].shape[0]
        V = self.model_cfg.vocab_size
        seen = np.zeros((Bb, V), bool)
        counts = np.zeros((Bb, V), np.float32)
        for i, s in enumerate(seqs):
            ids = np.asarray(s.prompt_token_ids, np.int64)
            seen[i, ids[(ids >= 0) & (ids < V)]] = True
            if s.output_token_ids:
                out = np.asarray(s.output_token_ids, np.int64)
                uniq, cnt = np.unique(
                    out[(out >= 0) & (out < V)], return_counts=True
                )
                counts[i, uniq] = cnt
        # Replace the pow2-length id arrays with the dense form.
        batch.pop("penalty_prompt", None)
        batch.pop("penalty_output", None)
        batch["penalty_seen"] = seen
        return counts

    def _put_batch(self, batch: Dict[str, np.ndarray]) -> Dict[str, Any]:
        """ONE device_put for the whole batch tree (a dozen small arrays
        per step): one transfer call instead of one per array."""
        B = batch["kv_lens"].shape[0]
        row_shard = self._dp > 1 and B % self._dp == 0
        return jax.device_put(batch, self._row if row_shard else self._repl)

    def _burst_fn(self, n_steps: int):
        """The jitted program of a ``b{B}xn{n_steps}`` dispatch."""
        return self._chained_step if n_steps == 1 else self._multi_step

    def _dispatch_multi_step(
        self,
        batch: Dict[str, np.ndarray],
        counts: np.ndarray,
        n_steps: int,
        want_lp: bool = False,
        greedy: bool = False,
        key: Optional[tuple] = None,
        who: Optional[_Dispatch] = None,
    ) -> np.ndarray:
        with self._launch("decode", who) as launch:
            dev = self._put_batch(batch)
            seed0 = jax.device_put(np.zeros((), np.uint32), self._repl)
            cdev = jax.device_put(counts, self._repl)
            tokens = dev.pop("tokens")
            positions = dev.pop("positions")
            with_pen = "penalty_seen" in batch
            toks, _, _, _, _, self.kv_cache = self.programs.call(
                key, self._burst_fn(n_steps),
                (self.params, self.kv_cache, dev, tokens, positions, seed0,
                 cdev),
                (n_steps, want_lp, greedy, with_pen),
            )
            launch.handle = toks
        return self._take_aux(_fetch(toks, "decode", self._clock))

    # ------------------------------------------------------------------
    # Pipelined decode bursts: one burst always in flight; its token fetch
    # overlaps the next burst's execution, hiding the dispatch→fetch round
    # trip a synchronous loop pays per burst.
    # ------------------------------------------------------------------

    @property
    def burst_in_flight(self) -> bool:
        return self._burst is not None

    def burst_start(self, seqs: List[Sequence], n_steps: int) -> None:
        """Dispatch the first burst of a pipeline (async; nothing fetched)."""
        if self._burst is not None:
            raise RuntimeError("burst already in flight (drain first)")
        if self._mtp:
            return self._mtp_burst_start(seqs)
        with ENGINE_TELEMETRY.phase("batch_build", "decode"):
            batch = self._decode_batch(seqs, multi=True)
            if "allowed_ids" in batch:
                raise RuntimeError(
                    "guided-choice rows reached a pipelined decode burst"
                )
            counts = self._penalty_counts_for(seqs, batch)
            want_lp = self._want_lp(seqs)
            greedy = self._all_greedy(seqs)
            key = self._tel_key("decode", batch, (n_steps, want_lp, greedy))
            Bb = batch["kv_lens"].shape[0]
            bucket = f"b{Bb}xn{n_steps}"
            self._step_info(
                "decode", bucket, seqs, batch, len(seqs) * n_steps, n_steps - 1
            )
        with self._dispatching(
            "decode", key, seqs, bucket, tokens=len(seqs) * n_steps,
            fill=len(seqs) / Bb,
        ) as who:
            self._host_gap_mark(bucket, who.t0, seqs)
            with self._device_lock:
                if self.publisher is not None:
                    self.publisher.announce(
                        "burst_start",
                        (batch, counts, n_steps, want_lp, greedy)
                    )
                self._dispatch_burst_start(
                    batch, counts, n_steps, want_lp, greedy, key, who)
        # Continuations re-dispatch the same executable: keep the signature
        # so their step timings land in the same bucket without re-counting
        # a compile.
        self._burst_tel = (key, bucket, Bb, n_steps)

    def _dispatch_burst_start(
        self,
        batch: Dict[str, np.ndarray],
        counts: np.ndarray,
        n_steps: int,
        want_lp: bool = False,
        greedy: bool = False,
        key: Optional[tuple] = None,
        who: Optional[_Dispatch] = None,
    ) -> None:
        # pipelined: a later step fetches what this launch computes
        with self._launch("decode", who, pipelined=1) as launch:
            dev = self._put_batch(batch)
            seed = jax.device_put(np.zeros((), np.uint32), self._repl)
            cdev = jax.device_put(counts, self._repl)
            tokens = dev.pop("tokens")
            positions = dev.pop("positions")
            with_pen = "penalty_seen" in batch
            toks, tokens, positions, seed, cdev, self.kv_cache = (
                self.programs.call(
                    key, self._burst_fn(n_steps),
                    (self.params, self.kv_cache, dev, tokens, positions,
                     seed, cdev),
                    (n_steps, want_lp, greedy, with_pen),
                )
            )
            # Start the host copy NOW; the eventual fetch finds it resident.
            toks.copy_to_host_async()
            launch.handle = toks
        # ``rows``: the host's copy of what it owns of the batch, a row a
        # member, renewed in place by every continuation; ``steps``: scan
        # steps launched so far, which is the seed offset the next one
        # starts from.
        self._burst = {
            "batch": dev, "tokens": tokens, "positions": positions,
            "seed": seed, "counts": cdev, "with_pen": with_pen,
            "toks": toks, "n": n_steps, "want_lp": want_lp,
            "greedy": greedy, "steps": n_steps,
            # A continuation takes `tokens` and `positions` as the step
            # before left them, replicated; with dp > 1 the start took them
            # by rows: another program, under a key of its own.
            "key": key + ("continued",) if key and self._dp > 1 else key,
            "rows": {k: v for k, v in batch.items()
                     if k not in ("tokens", "positions")},
        }
        self._warm_splice(batch["kv_lens"].shape[0])

    def _mtp_burst_start(self, seqs: List[Sequence]) -> None:
        """`burst_start` for a chain of verify-and-draft steps: the carry is
        each row's last committed token, its draft (-1: none yet) and its
        position."""
        with ENGINE_TELEMETRY.phase("batch_build", "decode"):
            batch = self._decode_batch(seqs, multi=True)
            Bb = batch["kv_lens"].shape[0]
            drafts = np.full(Bb, -1, np.int32)
            for i, s in enumerate(seqs):
                if s.mtp_draft is not None:
                    drafts[i] = s.mtp_draft
            want_lp, greedy = self._want_lp(seqs), self._all_greedy(seqs)
            key = self._tel_key("mtp_chain", batch, (want_lp, greedy))
            bucket = f"b{Bb}xk1"
            self._step_info(
                "decode", bucket, seqs,
                dict(batch, kv_lens=np.where(
                    batch["kv_lens"] > 0, batch["kv_lens"] + 1, 0)),
                2 * len(seqs), verify=self._verify_info(
                    int(sum(self._draft_ok(s) and s.mtp_draft is not None
                            for s in seqs))))
        with self._dispatching(
            "decode", key, seqs, bucket, tokens=2 * len(seqs),
            fill=len(seqs) / Bb,
        ) as who, self._device_lock:
            self._host_gap_mark(bucket, who.t0, seqs)
            with self._launch("decode", who, pipelined=1) as launch:
                dev = self._put_batch(batch)
                carry = jax.device_put(
                    (dev.pop("tokens"), drafts, dev.pop("positions"),
                     np.zeros((), np.uint32)), self._repl)
                toks, *carry, self.kv_cache = self.programs.call(
                    key, self._mtp_chained,
                    (self.params, self.kv_cache, dev, *carry),
                    (want_lp, greedy))
                toks.copy_to_host_async()
                launch.handle = toks
        self._burst = {
            "mtp": True, "batch": dev, "carry": carry, "toks": toks, "n": 1,
            "want_lp": want_lp, "greedy": greedy, "with_pen": False,
            "steps": 1, "key": key, "members": list(seqs),
            "rows": {k: v for k, v in batch.items()
                     if k not in ("tokens", "positions")},
        }
        self._burst_tel = (key, bucket, Bb, 1)
        self._warm_splice(Bb)

    def _verify_info(self, drafted: int) -> dict:
        """What a verify-and-draft step's ``pst.step_info`` carries beside a
        decode step's (``accepted``: the last fetched step's)."""
        return {"step": "mtp_verify", "draft_positions": drafted,
                "accepted": self._mtp_accepted_last}

    def _mtp_rows(self, rows: np.ndarray, members: List[Sequence]) -> list:
        """A fetched verify-and-draft step's packed rows as the engine reads
        a burst's: for each row its one or two packed samples. Each member's
        next draft is kept on it (what a synchronous step after a drain
        verifies)."""
        out, took_all = [], rows[:, -2].astype(np.int32)
        for i, took in enumerate(took_all.tolist()):
            out.append(rows[i, :-2].reshape(2, -1)[: 1 + took])
        for s, draft in zip(members, rows[:, -1].tolist()):
            s.mtp_draft = int(draft)
        self._mtp_accepted_last = int(took_all[: len(members)].sum())
        return out

    def _warm_splice(self, Bb: int) -> None:
        """Compile `_splice` for a chain of ``Bb`` rows behind every row
        bucket a prefill step can have, the first time a chain of that size
        starts: a chain's first arrival then loads nothing. (Packed rows
        with log-probabilities compile when first met, as their step
        programs do.)"""
        if Bb in self._splice_warm:
            return
        self._splice_warm.add(Bb)
        put = lambda x: jax.device_put(x, self._repl)  # noqa: E731
        carry = put(np.zeros(Bb, np.int32))
        src = put(np.full(Bb, -1, np.int32))
        rows = 1
        while rows <= _pow2(
                min(self.cfg.max_num_seqs, self.cfg.max_prefill_tokens)):
            # (a prefill row of an engine that drafts carries its first draft)
            toks = put(np.zeros(
                (rows + self._aux_rows, 2 if self._mtp else 1), np.float32))
            if self._mtp:
                self._call_splice_mtp(carry, carry, carry, toks, src, carry)
            else:
                self._call_splice(carry, carry, toks, src, carry)
            rows <<= 1

    def _call_splice(self, tokens, positions, toks, src, pos):
        """`_splice` through its program: keyed by the chain's rows and
        the prefill's packed rows."""
        return self.programs.call(
            (self._tel_scope, "splice", tokens.shape, toks.shape),
            self._splice, (tokens, positions, toks, src, pos))

    def _call_splice_mtp(self, tokens, drafts, positions, toks, src, pos):
        return self.programs.call(
            (self._tel_scope, "splice_mtp", tokens.shape, toks.shape),
            self._mtp_splice, (tokens, drafts, positions, toks, src, pos))

    def burst_width_stable(self, members: List[Sequence]) -> bool:
        """True while the members' block tables still fit the width bucket
        the in-flight burst compiled with (growth past it needs a drain)."""
        if self._burst is None:
            return False
        Wb = self._burst["batch"]["block_tables"].shape[1]
        return max((len(s.block_ids) for s in members), default=0) <= Wb

    def burst_rows(self) -> int:
        """Rows of the burst in flight's batch: members and padding."""
        return self._burst["batch"]["kv_lens"].shape[0]

    def burst_variant_fits(self, seqs: List[Sequence]) -> bool:
        """Can ``seqs`` take rows of the chain as it was compiled? Not a
        row that wants log-probabilities of a chain without them, a sampled
        row of a greedy chain, or a row whose arrays are not the shape of
        the chain's (a logit bias); and no row joins a chain that carries
        penalty counts, nor one with penalties of its own: the counts need
        the first token, which only the device has."""
        st = self._burst
        if st["with_pen"] or any(s.sampling.has_penalties for s in seqs):
            return False
        if self._want_lp(seqs) and not st["want_lp"]:
            return False
        if st["greedy"] and not self._all_greedy(seqs):
            return False
        own = {k: v.shape[1:] for k, v in st["rows"].items()
               if k not in ("block_tables", "kv_lens")}
        Wb = st["rows"]["block_tables"].shape[1]
        return all(
            {k: v.shape[1:] for k, v in self._member_rows([s], 1, Wb).items()}
            == own for s in seqs)

    def _member_rows(
        self, seqs: List[Sequence], B: int, W: int
    ) -> Dict[str, np.ndarray]:
        """What `_decode_batch` builds a row beside its table and length."""
        rows = self._slot_rows(seqs, B, W)
        rows.update(self._sampling_arrays(seqs, B))
        if self._mtp:
            rows["draft_ok"] = np.zeros(B, bool)
            rows["draft_ok"][: len(seqs)] = [self._draft_ok(s) for s in seqs]
        return rows

    def burst_continue(
        self, members: List[Sequence], joins: Seq[tuple] = ()
    ) -> np.ndarray:
        """Dispatch the NEXT burst, then fetch and return the PREVIOUS
        burst's tokens [Bb, n] (the fetch overlaps the new burst's
        execution). ``members`` is the membership of the burst being
        dispatched, a row each: their block tables are refreshed (the
        scheduler reserves lookahead pages host-side; the device table must
        see them) and members that finished host-side get kv_len 0 so their
        speculative rows stop writing KV. The rows returned are those of
        the membership the previous dispatch was given.

        ``joins``: ``(row, sequence, prefill row)`` for every member that
        was not in the previous burst: a sequence whose prompt the prefill
        launched just before (`prefill_dispatch`) completes. Its row of the
        batch is written whole, and its token and position reach the carry
        on the device (`_splice`), so the chain goes on behind the prefill
        with nothing fetched in between."""
        assert self._burst is not None
        tel = self._burst_tel  # `burst_start` left it
        with ENGINE_TELEMETRY.phase("batch_build", "decode"):
            st = self._burst
            own = st["rows"]
            Bb, Wb = own["block_tables"].shape
            tables = np.zeros((Bb, Wb), np.int32)
            kv_lens = np.zeros(Bb, np.int32)
            for i, s in enumerate(members):
                tables[i] = self._table_row(s, Wb)
                kv_lens[i] = 0 if s.is_finished else max(s.num_tokens, 1)
            refresh = {"block_tables": tables, "kv_lens": kv_lens}
            if self.window_blocks:
                refresh["window_tables"] = self._window_tables(members, Bb, Wb)
            own.update(refresh)
            splice = None
            if joins:
                # copies: the burst in flight may still read the old arrays
                own = st["rows"] = {k: v.copy() for k, v in own.items()}
                src = np.where(kv_lens > 0, -1, -2).astype(np.int32)
                pos = np.zeros(Bb, np.int32)
                for row, s, prefill_row in joins:
                    for k, v in self._member_rows([s], 1, Wb).items():
                        if k != "window_tables":
                            own[k][row] = v[0]
                    # the chain adds the steps it has run to every row's
                    # seed: the row's first sample here is its second
                    own["seeds"][row] = np.uint32(
                        (_seed_for(s, 1) - st["steps"]) & 0xFFFF_FFFF)
                    src[row], pos[row] = prefill_row, s.num_tokens
                refresh, splice = own, (src, pos)
            st["fetch_members"], st["members"] = (
                st.get("members", ()), list(members))
            alive = sum(1 for s in members if not s.is_finished)
            # The host's view lags the device by the burst in flight: a
            # live row holds n more tokens after this burst's first step
            # than its kv_len here says, and 2n - 1 more after its last (a
            # joining row's prefill is the program its view lags by).
            n = tel[3]
            if st.get("mtp"):
                # (two positions a row; the step in flight may have
                # committed two tokens more than the host has seen)
                self._step_info(
                    "decode", tel[1], members,
                    {"kv_lens": np.where(kv_lens > 0, kv_lens + 2, 0),
                     "block_tables": tables}, 2 * alive,
                    verify=self._verify_info(int(
                        own["draft_ok"][: len(members)][
                            kv_lens[: len(members)] > 0].sum())))
            else:
                self._step_info(
                    "decode", tel[1], members,
                    {"kv_lens": np.where(kv_lens > 0, kv_lens + n, 0),
                     "block_tables": tables},
                    alive * n, n - 1,
                )
        key, bucket, rows_b, n = tel
        # The program launched here is charged, when it is seen ready a
        # cycle on, to the members still alive then; the host's wall around
        # this call (launch the next, fetch the one before) is the step
        # histogram's.
        # pstlint: disable=recompile-risk(key and bucket are carried verbatim from burst_start's registered _tel_key via _burst_tel — a continuation re-dispatches the same executable, so the shape identity cannot drift)
        with self._dispatching(
            "decode", key, members, bucket, tokens=alive * n,
            fill=alive / max(rows_b, 1),
        ) as who:
            with self._device_lock:
                if self.publisher is not None:
                    self.publisher.announce("burst_cont", (refresh, splice))
                rows = self._dispatch_burst_continue(refresh, splice, who)
            # The continuation was dispatched BEFORE the previous burst's
            # tokens were even read: the device runs the two back-to-back,
            # so the host gap on this step is — by construction — zero.
            # Recording it keeps the histogram's percentiles honest about
            # what the pipeline removed (not silently absent at steady
            # state).
            ENGINE_TELEMETRY.record_host_gap(bucket, 0.0)
        return rows

    def _dispatch_burst_continue(
        self, refresh: Dict[str, np.ndarray], splice: Optional[tuple] = None,
        who: Optional[_Dispatch] = None,
    ) -> np.ndarray:
        """``refresh``: what the host renews of the burst's batch: the block
        tables (both groups') and ``kv_lens``, or every array it owns when
        rows join. ``splice``: ``(src, pos)`` of `_splice` then, applied to
        the carry with the last prefill's rows."""
        st = self._burst
        prev = st["toks"]
        if st.get("mtp"):
            with self._launch("decode", who, pipelined=1) as launch:
                st["batch"].update(self._put_batch(refresh))
                tokens, drafts, positions, seed = st["carry"]
                if splice is not None:
                    src, pos = jax.device_put(splice, self._repl)
                    tokens, drafts, positions = self._call_splice_mtp(
                        tokens, drafts, positions, self._prefill_toks,
                        src, pos)
                toks, *st["carry"], self.kv_cache = self.programs.call(
                    st["key"], self._mtp_chained,
                    (self.params, self.kv_cache, st["batch"], tokens, drafts,
                     positions, seed), (st["want_lp"], st["greedy"]))
                toks.copy_to_host_async()
                launch.handle = toks
                st.update(toks=toks, steps=st["steps"] + 1)
            return self._mtp_rows(
                self._take_aux(_fetch(prev, "decode", self._clock)),
                st["fetch_members"])
        with self._launch("decode", who, pipelined=1) as launch:
            st["batch"].update(self._put_batch(refresh))
            if splice is not None:
                src, pos = jax.device_put(splice, self._repl)
                st["tokens"], st["positions"] = self._call_splice(
                    st["tokens"], st["positions"], self._prefill_toks,
                    src, pos)
            toks, tokens, positions, seed, counts, self.kv_cache = (
                self.programs.call(
                    st["key"], self._burst_fn(st["n"]),
                    (self.params, self.kv_cache, st["batch"], st["tokens"],
                     st["positions"], st["seed"], st["counts"]),
                    (st["n"], st["want_lp"], st["greedy"], st["with_pen"]),
                )
            )
            # Start the host copy NOW; the eventual fetch finds it resident.
            toks.copy_to_host_async()
            launch.handle = toks
            st.update(
                tokens=tokens, positions=positions, seed=seed, counts=counts,
                toks=toks, steps=st["steps"] + st["n"],
            )
        return self._take_aux(_fetch(prev, "decode", self._clock))

    def burst_drain(self) -> np.ndarray:
        """Fetch the in-flight burst's tokens and end the pipeline."""
        assert self._burst is not None
        st, self._burst = self._burst, None
        # No device op, so no multihost announce: followers hold no pending
        # fetch (they never read tokens) and their next announced dispatch
        # keeps program order identical.
        rows = self._take_aux(_fetch(st["toks"], "decode", self._clock))
        if st.get("mtp"):
            rows = self._mtp_rows(rows, st["members"])
        # Drains are transitions (an arrival or shape change broke the
        # pipeline) and a prefill may already be queued behind this fetch —
        # the wall from here to the next decode dispatch is not steady-state
        # host bookkeeping, so the gap clock does not run across it.
        self._host_gap_cancel()
        return rows

    def execute_spec_verify(
        self, seqs: List[Sequence], drafts: np.ndarray
    ) -> "tuple[np.ndarray, np.ndarray]":
        """Speculative-decoding verify step: score each sequence's last
        committed token plus its K draft tokens in ONE forward pass.

        ``drafts`` is [B, K] int32. Returns ``(argmax_ids [B, K+1],
        packed0 [B, 1 or PACKED_WIDTH])`` — row j's argmax is the token the
        model itself would emit after consuming positions ≤ p0+j (the engine
        compares it against the drafts to count acceptances), and
        ``packed0`` is position 0 put through the full sampling pipeline
        (temperature / top-p / seeds / logit_bias) and packed as a decode
        step packs it, with log-probabilities where some row asks for them,
        so draftless rows in a mixed batch get exactly what a plain decode
        step would have produced. KV for
        all K+1 positions is written during the pass; rejected positions
        sit past the committed kv_len and are overwritten on real decode.
        """
        B, K = drafts.shape
        with ENGINE_TELEMETRY.phase("batch_build", "spec_verify"):
            batch = self._spec_batch(seqs, drafts)
            key = self._tel_key("spec_verify", batch, (K,))
            Bb = batch["kv_lens"].shape[0]
            self._step_info(
                "spec_verify", f"b{Bb}xk{K}", seqs, batch, len(seqs) * (K + 1)
            )
        self._host_gap_cancel()
        with self._dispatching(
            "spec_verify", key, seqs, f"b{Bb}xk{K}",
            tokens=len(seqs) * (K + 1), fill=len(seqs) / Bb,
        ) as who, self._device_lock:
            if self.publisher is not None:
                self.publisher.announce("spec_verify", batch)
            ids, packed0 = self._dispatch_spec_verify(batch, key, who)
        return ids[: len(seqs)], packed0[: len(seqs)]

    def _spec_batch(
        self, seqs: List[Sequence], drafts: np.ndarray
    ) -> Dict[str, np.ndarray]:
        B, K = drafts.shape
        T = K + 1
        Bb = self._row_bucket(B)
        Wb = self._table_bucket(seqs)
        bs = self.cfg.block_size
        tokens = np.zeros((Bb, T), np.int32)
        positions = np.zeros((Bb, T), np.int32)
        write_idx = np.full((Bb, T), self._drop_slot, np.int32)
        tables = np.zeros((Bb, Wb), np.int32)
        kv_lens = np.zeros(Bb, np.int32)
        last_idx = np.zeros(Bb, np.int32)
        for i, s in enumerate(seqs):
            p0 = s.num_tokens - 1  # the not-yet-computed last token
            # Direct last-token read: all_token_ids would rebuild the full
            # prompt+output list per row per step (O(context) host work).
            tokens[i, 0] = (
                s.output_token_ids[-1]
                if s.output_token_ids
                else s.prompt_token_ids[-1]
            )
            tokens[i, 1:] = drafts[i]
            positions[i] = p0 + np.arange(T, dtype=np.int32)
            covered = len(s.block_ids) * bs  # draftless near-limit rows may
            for j in range(T):  # not have pages for all K+1 positions
                pos = p0 + j
                if pos < covered:
                    write_idx[i, j] = s.block_ids[pos // bs] * bs + pos % bs
            tables[i] = self._table_row(s, Wb)
            kv_lens[i] = min(s.num_tokens + K, covered)
        batch = {
            "tokens": tokens,
            "positions": positions,
            "write_idx": write_idx,
            "block_tables": tables,
            "kv_lens": kv_lens,
            "last_idx": last_idx,
        }
        # Full sampling arrays: position 0 is sampled exactly like a plain
        # decode step (draftless rows in a mixed batch rely on this), and
        # LoRA rows verify WITH their adapter.
        batch.update(self._sampling_arrays(seqs, Bb))
        if self._want_lp(seqs):
            # its presence compiles position 0's log-probabilities in (a
            # follower replays the batch: the same program there)
            batch["lp_rows"] = np.zeros(Bb, bool)
        batch.pop("penalty_prompt", None)  # penalized rows never reach spec
        batch.pop("penalty_output", None)
        batch.pop("presence", None)
        batch.pop("frequency", None)
        batch.pop("repetition", None)
        return batch

    def _dispatch_spec_verify(
        self, batch: Dict[str, np.ndarray], key: Optional[tuple] = None,
        who: Optional[_Dispatch] = None,
    ) -> np.ndarray:
        if not hasattr(self, "_spec_step"):
            model = self.model
            attn_impl = self._attn_impl
            pp = self._pp
            model_mesh = self._model_mesh
            moe_impl = self._moe_impl

            def pst_spec_verify(params, kv_cache, batch):
                logits, kv_cache = model.forward(
                    params,
                    batch["tokens"],
                    batch["positions"],
                    batch["write_idx"],
                    batch["block_tables"],
                    batch["kv_lens"],
                    batch["last_idx"],
                    kv_cache,
                    lora_idx=batch.get("lora_idx"),
                    lora_scale=batch.get("lora_scale"),
                    attn_impl=attn_impl,
                    moe_impl=moe_impl,
                    pp_size=pp,
                    mesh=model_mesh,
                    all_logits=True,
                )  # [B, T, V] fp32
                if "bias_ids" in batch:
                    # logit_bias applies at EVERY verified position (a
                    # biased greedy row's accept chain must follow the
                    # biased argmax).
                    logits = jax.vmap(
                        apply_logit_bias, in_axes=(1, None, None), out_axes=1
                    )(logits, batch["bias_ids"], batch["bias_vals"])
                ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, T]
                logits0 = logits[:, 0]
                if "allowed_ids" in batch:  # guided rows ride draftless
                    logits0 = apply_allowed_mask(
                        logits0, batch["allowed_ids"], batch["allow_free"]
                    )
                packed0 = sample_tokens_packed(
                    logits0,
                    batch["temps"],
                    batch["top_ps"],
                    batch["top_ks"],
                    batch["min_ps"],
                    batch["seeds"],
                    with_logprobs="lp_rows" in batch,
                )
                # ONE output array = ONE host fetch: the columns past K+1
                # carry position 0's packed sample.
                return jnp.concatenate(
                    [ids.astype(packed0.dtype), packed0], axis=1), kv_cache

            cache_sh = self._cache_sharding()
            # pstlint: jit-family=spec_verify
            self._spec_step = jax.jit(
                pst_spec_verify,
                donate_argnums=(1,),
                out_shardings=(self._repl, cache_sh),
            )
        with self._launch("spec_verify", who) as launch:
            packed, self.kv_cache = self.programs.call(
                key, self._spec_step,
                (self.params, self.kv_cache, self._put_batch(batch)),
            )
            launch.handle = packed
        packed = _fetch(packed, "spec_verify", self._clock)
        T = batch["tokens"].shape[1]
        return packed[:, :T].astype(np.int32), packed[:, T:]

    def _prefill_tel(
        self, items: List[PrefillItem], batch: Dict[str, np.ndarray],
        extras: tuple,
    ) -> tuple:
        """(shape key, bucket label, real tokens, fill ratio) for one
        prefill step's telemetry."""
        Bb, Tb = batch["tokens"].shape
        real = sum(it.end - it.start for it in items)
        bucket = f"b{Bb}xt{Tb}"
        self._step_info("prefill", bucket, [it.seq for it in items], batch, real)
        self.prefill_tokens_total += real
        self.prefill_bucket_positions_total += Bb * Tb
        self.prefill_layer_passes_total += self.layers_a_token
        return (
            self._tel_key("prefill", batch, extras),
            bucket,
            real,
            real / max(Bb * Tb, 1),
        )

    def execute_prefill(self, item: PrefillItem) -> int:
        """Process one prefill chunk; returns the sampled token id (only
        meaningful when the chunk completes the prompt)."""
        return int(self.execute_prefill_batch([item])[0, 0])

    def execute_prefill_batch(self, items: List[PrefillItem]) -> np.ndarray:
        """Prefill several chunks in one device call (rows padded to a
        common chunk bucket). Returns packed sample rows
        [len(items), 1 or PACKED_WIDTH] (token [+ logprobs])."""
        seqs = [i.seq for i in items]
        with ENGINE_TELEMETRY.phase("batch_build", "prefill"):
            batch = self._prefill_batch(items)
            want_lp, greedy = self._want_lp(seqs), self._all_greedy(seqs)
            key, bucket, real, fill = self._prefill_tel(
                items, batch, (want_lp, greedy)
            )
        self._host_gap_cancel()
        with self._dispatching(
            "prefill", key, items, bucket, tokens=real, fill=fill,
        ) as who:
            rows = self._run(batch, want_lp, greedy, "prefill", key, who)
        return self._take_drafts(items, rows[: len(items)])

    def execute_prefill_batch_nofetch(self, items: List[PrefillItem]) -> None:
        """Dispatch a prefill step WITHOUT fetching its sampled tokens.

        Intermediate chunks of a long prompt sample nothing anyone reads
        (only the prompt-completing chunk's token matters), yet a fetch
        synchronizes host and device once per chunk — ~20 times per
        20k-token prompt. The KV writes chain on-device through the
        donated cache, so correctness is unaffected; the next fetching step
        transitively waits for all queued work."""
        with ENGINE_TELEMETRY.phase("batch_build", "prefill"):
            batch = self._prefill_batch(items)
            # nofetch steps compile as (want_lp=False, greedy=True) — the
            # same executable a fetching greedy step uses.
            key, bucket, real, fill = self._prefill_tel(
                items, batch, (False, True)
            )
        self._host_gap_cancel()
        with self._dispatching(
            "prefill", key, items, bucket, tokens=real, fill=fill,
        ) as who, self._device_lock:
            if self.publisher is not None:
                self.publisher.announce("step_nofetch", batch)
            self._dispatch_step_nofetch(batch, key, who)

    def _dispatch_step_nofetch(
        self, batch: Dict[str, np.ndarray], key: Optional[tuple] = None,
        who: Optional[_Dispatch] = None,
    ) -> None:
        # greedy=True: nobody reads an intermediate chunk's sample, so the
        # cheapest sampling variant (plain argmax) is always correct here.
        # The sample is kept all the same, as the clock's handle on the
        # program: a later fetch's poll sees it ready.
        with self._launch("prefill", who) as launch:
            launch.handle, self.kv_cache = self.programs.call(
                key, self._step["prefill"],
                (self.params, self.kv_cache, self._put_batch(batch)),
                (False, True),
            )

    def prefill_dispatch(  # noqa: D401
        self, items: List[PrefillItem], record_at_fetch: bool = False
    ):
        """Async half of a prefill step: dispatch and return the device
        handle without fetching. Used to slip a new arrival's prefill in
        BEHIND an in-flight decode burst (the device serializes them),
        cutting one full host<->device round trip out of TTFT. The chain's
        next step is launched behind it in turn (`burst_continue` with
        ``joins``, which reads the rows kept here on the device), or, where
        the chain cannot go on, the burst's drain overlaps the prefill's
        execution. `prefill_fetch` reads the rows; an inner chunk's are
        never read. ``record_at_fetch``: the dispatch is recorded when its
        rows are fetched, after the chained step launched behind it, so
        that the cycle is the prefill's in the flight recorder (it waits
        for the prefill program, as the cycle of a prefill behind a
        draining burst does) and is held against cycles of its own kind."""
        with ENGINE_TELEMETRY.phase("batch_build", "prefill"):
            batch = self._prefill_batch(items)
            want_lp = self._want_lp([i.seq for i in items])
            greedy = self._all_greedy([i.seq for i in items])
            key, bucket, real, fill = self._prefill_tel(
                items, batch, (want_lp, greedy)
            )
        self._host_gap_cancel()
        with self._dispatching(
            "prefill", key, items, bucket, tokens=real, fill=fill,
            deferred=record_at_fetch,
        ) as who, self._device_lock:
            if self.publisher is not None:
                self.publisher.announce(
                    "step", (batch, want_lp, greedy, "prefill")
                )
            with self._launch("prefill", who) as launch:
                toks, self.kv_cache = self.programs.call(
                    key, self._step["prefill"],
                    (self.params, self.kv_cache, self._put_batch(batch)),
                    (want_lp, greedy),
                )
                toks.copy_to_host_async()
                launch.handle = self._prefill_toks = toks
        self._prefill_items = items
        if record_at_fetch:
            self._prefill_record = who.record
        return toks

    def prefill_fetch(self, handle, n_items: int) -> np.ndarray:
        rows = self._take_drafts(self._prefill_items, self._take_aux(
            _fetch(handle, "prefill", self._clock))[:n_items])
        record, self._prefill_record = self._prefill_record, None
        if record is not None:
            record()
        return rows

    def _run(
        self,
        batch: Dict[str, np.ndarray],
        want_lp: bool,
        greedy: bool,
        kind: str,
        key: Optional[tuple] = None,
        who: Optional[_Dispatch] = None,
    ) -> np.ndarray:
        with self._device_lock:
            if self.publisher is not None:
                self.publisher.announce("step", (batch, want_lp, greedy, kind))
            return self._dispatch_step(batch, want_lp, greedy, kind, key, who)

    def _dispatch_step(
        self,
        batch: Dict[str, np.ndarray],
        want_lp: bool,
        greedy: bool,
        kind: str,
        key: Optional[tuple] = None,
        who: Optional[_Dispatch] = None,
    ) -> np.ndarray:
        """Launch the ``kind`` ("decode" | "prefill") program on ``batch``
        and fetch its packed rows. ``key``: the step's shape key, which
        finds its program (a follower's replay has none: the jit);
        ``who``: the dispatch whose program this is (a warm-up's and a
        follower's have none)."""
        with self._launch(kind, who) as launch:
            toks, self.kv_cache = self.programs.call(
                key, self._step[kind],
                (self.params, self.kv_cache, self._put_batch(batch)),
                (want_lp, greedy),
            )
            launch.handle = toks
            if kind == "prefill":
                self._prefill_toks = toks  # a follower's, for `_splice`
        return self._take_aux(_fetch(toks, kind, self._clock))

    def _take_aux(self, rows: np.ndarray) -> np.ndarray:
        """Split a fetched step's packed rows from what the model reported
        under them (``with_aux``), and add that to the running totals."""
        n = self._aux_rows
        if not n:
            return rows
        self.step_aux_totals += rows[-n:].reshape(n, -1, rows.shape[-1])[
            :, :, 0].sum(axis=1)
        return rows[:-n]

    # ------------------------------------------------------------------
    # Warmup precompilation (engine/precompile.py drives this)
    # ------------------------------------------------------------------

    def _warmup_sampling_arrays(self, B: int, W: int) -> Dict[str, np.ndarray]:
        """The sampling-array tree every live batch carries, all-neutral.
        Shapes and dtypes must match ``_sampling_arrays`` exactly — they
        are part of both the jit trace and the telemetry shape key."""
        out: Dict[str, np.ndarray] = {
            "temps": np.zeros(B, np.float32),
            "top_ps": np.ones(B, np.float32),
            "top_ks": np.zeros(B, np.int32),
            "min_ps": np.zeros(B, np.float32),
            "seeds": np.zeros(B, np.uint32),
        }
        if self.cfg.enable_lora:
            out["lora_idx"] = np.zeros(B, np.int32)
            out["lora_scale"] = np.zeros(B, np.float32)
        out.update(self._slot_rows([], B, W))
        return out

    def warmup_bucket(self, bucket) -> None:
        """Compile one lattice bucket with an all-padding dummy batch.

        Every row carries ``kv_len = 0`` and writes to the drop slot, so
        the dispatch touches no real KV state; the shapes and static jit
        flags are exactly what live traffic produces, so both jax.jit's
        executable cache AND the telemetry shape registry treat the
        bucket as already-seen when a real batch arrives — a warmed shape
        can never count as a live-traffic compile again."""
        kind = bucket.kind
        if kind == "decode":
            self._warmup_decode(bucket)
        elif kind == "decode_burst":
            self._warmup_decode_burst(bucket)
        elif kind == "prefill":
            self._warmup_prefill(bucket)
        elif kind == "spec_verify":
            self._warmup_spec_verify(bucket)
        elif kind == "mtp_verify":
            self._warmup_mtp_verify(bucket)
        elif kind == "encode":
            self._warmup_encode(bucket)
        else:
            raise ValueError(f"unknown warmup bucket kind {kind!r}")

    def _record_warmup(self, kind: str, key: tuple, seconds: float,
                       label: str) -> None:
        # tokens=0: warmup moves no real tokens, so the throughput window
        # stays honest; the compile itself is counted (it is one).
        # count_busy=False: warmup serves no request, so it stays out of
        # the device-busy denominator and the flight ring (a warmup pass
        # would otherwise flood the ring with compile snapshots).
        ENGINE_TELEMETRY.record_dispatch(
            kind, key, seconds, batch_bucket=label, tokens=0,
            count_busy=False,
        )

    def _warmup_decode(self, bucket) -> None:
        Bb, Wb = bucket.rows, bucket.width
        batch = {
            "tokens": np.zeros((Bb, 1), np.int32),
            "positions": np.zeros((Bb, 1), np.int32),
            "block_tables": np.zeros((Bb, Wb), np.int32),
            "kv_lens": np.zeros(Bb, np.int32),
            "write_idx": np.full((Bb, 1), self._drop_slot, np.int32),
            "last_idx": np.zeros(Bb, np.int32),
        }
        batch.update(self._warmup_sampling_arrays(Bb, Wb))
        key = self._tel_key("decode", batch, (bucket.want_lp, bucket.greedy))
        t0 = time.perf_counter()
        self._run(batch, bucket.want_lp, bucket.greedy, "decode", key)
        self._record_warmup(
            "decode", key, time.perf_counter() - t0, bucket.label
        )

    def _warmup_decode_burst(self, bucket) -> None:
        Bb, Wb, n = bucket.rows, bucket.width, bucket.n_steps
        batch = {
            "tokens": np.zeros(Bb, np.int32),
            "positions": np.zeros(Bb, np.int32),
            "block_tables": np.zeros((Bb, Wb), np.int32),
            "kv_lens": np.zeros(Bb, np.int32),
        }
        batch.update(self._warmup_sampling_arrays(Bb, Wb))
        if getattr(bucket, "penalized", False):
            # The dense penalty form _penalty_counts_for builds for live
            # penalized bursts: all-neutral state, exact same shapes.
            V = self.model_cfg.vocab_size
            batch["penalty_seen"] = np.zeros((Bb, V), bool)
            batch["presence"] = np.zeros(Bb, np.float32)
            batch["frequency"] = np.zeros(Bb, np.float32)
            batch["repetition"] = np.ones(Bb, np.float32)
            counts = np.zeros((Bb, V), np.float32)
        else:
            counts = np.zeros((1, 1), np.float32)
        key = self._tel_key(
            "decode", batch, (n, bucket.want_lp, bucket.greedy)
        )
        t0 = time.perf_counter()
        with self._device_lock:
            if self.publisher is not None:
                self.publisher.announce(
                    "multi_step",
                    (batch, counts, n, bucket.want_lp, bucket.greedy),
                )
            self._dispatch_multi_step(
                batch, counts, n, bucket.want_lp, bucket.greedy, key
            )
            # what a chain of this many rows runs between a prefill and
            # the step behind it
            if self.publisher is not None:
                self.publisher.announce("warm_splice", Bb)
            self._warm_splice(Bb)
        self._record_warmup(
            "decode", key, time.perf_counter() - t0, bucket.label
        )

    def _warmup_prefill(self, bucket) -> None:
        Bb, Tb, Wb = bucket.rows, bucket.tokens, bucket.width
        batch = {
            "tokens": np.zeros((Bb, Tb), np.int32),
            "positions": np.zeros((Bb, Tb), np.int32),
            "write_idx": np.full((Bb, Tb), self._drop_slot, np.int32),
            "block_tables": np.zeros((Bb, Wb), np.int32),
            "kv_lens": np.zeros(Bb, np.int32),
            "last_idx": np.zeros(Bb, np.int32),
        }
        batch.update(self._warmup_sampling_arrays(Bb, Wb))
        if self._skips_cross:
            batch["sample_rows"] = np.zeros(Bb, bool)
        if self._mtp:
            batch.update(
                mtp_next=np.zeros((Bb, Tb), np.int32),
                mtp_write_idx=np.full((Bb, Tb), self._drop_slot, np.int32),
                mtp_kv_lens=np.zeros(Bb, np.int32))
        key = self._tel_key("prefill", batch, (bucket.want_lp, bucket.greedy))
        t0 = time.perf_counter()
        self._run(batch, bucket.want_lp, bucket.greedy, "prefill", key)
        self._record_warmup(
            "prefill", key, time.perf_counter() - t0, bucket.label
        )

    def _warmup_spec_verify(self, bucket) -> None:
        Bb, K, Wb = bucket.rows, bucket.tokens, bucket.width
        T = K + 1
        batch = {
            "tokens": np.zeros((Bb, T), np.int32),
            "positions": np.zeros((Bb, T), np.int32),
            "write_idx": np.full((Bb, T), self._drop_slot, np.int32),
            "block_tables": np.zeros((Bb, Wb), np.int32),
            "kv_lens": np.zeros(Bb, np.int32),
            "last_idx": np.zeros(Bb, np.int32),
        }
        batch.update(self._warmup_sampling_arrays(Bb, Wb))
        key = self._tel_key("spec_verify", batch, (K,))
        t0 = time.perf_counter()
        with self._device_lock:
            if self.publisher is not None:
                self.publisher.announce("spec_verify", batch)
            self._dispatch_spec_verify(batch, key)
        self._record_warmup(
            "spec_verify", key, time.perf_counter() - t0, bucket.label
        )

    def _warmup_mtp_verify(self, bucket) -> None:
        Bb, Wb = bucket.rows, bucket.width
        drop = np.full((Bb, 2), self._drop_slot, np.int32)
        batch = {
            "tokens": np.zeros((Bb, 2), np.int32),
            "positions": np.zeros((Bb, 2), np.int32),
            "write_idx": drop, "mtp_write_idx": drop.copy(),
            "block_tables": np.zeros((Bb, Wb), np.int32),
            "kv_lens": np.zeros(Bb, np.int32),
            "mtp_kv_lens": np.zeros(Bb, np.int32),
            "last_idx": np.ones(Bb, np.int32),
            "draft_ok": np.zeros(Bb, bool),
        }
        batch.update(self._warmup_sampling_arrays(Bb, Wb))
        flags = (bucket.want_lp, bucket.greedy)
        key = self._tel_key("mtp_verify", batch, flags)
        t0 = time.perf_counter()
        with self._device_lock:
            self._dispatch_mtp_verify(batch, *flags, key)
        self._record_warmup(
            "decode", key, time.perf_counter() - t0, bucket.label)
        if not self.cfg.overlap_decode:
            return
        # the chained form: what every step of such an engine is but for a
        # row the chain cannot take
        chain = {k: v for k, v in batch.items() if k not in (
            "tokens", "positions", "write_idx", "mtp_write_idx",
            "mtp_kv_lens", "last_idx")}
        key = self._tel_key("mtp_chain", dict(
            chain, tokens=np.zeros(Bb, np.int32),
            positions=np.zeros(Bb, np.int32)), flags)
        t0 = time.perf_counter()
        with self._device_lock:
            dev = self._put_batch(chain)
            carry = jax.device_put(
                (np.zeros(Bb, np.int32), np.full(Bb, -1, np.int32),
                 np.zeros(Bb, np.int32), np.zeros((), np.uint32)), self._repl)
            *_, self.kv_cache = self.programs.call(
                key, self._mtp_chained,
                (self.params, self.kv_cache, dev, *carry), flags)
        self._record_warmup(
            "decode", key, time.perf_counter() - t0, bucket.label)

    def _warmup_encode(self, bucket) -> None:
        T = bucket.tokens
        toks = np.zeros((1, T), np.int32)
        length = np.array([1], np.int32)  # 1, not 0: mean-pool divides by it
        key = (self._tel_scope, "encode", T)
        t0 = time.perf_counter()
        with self._device_lock:
            if self.publisher is not None:
                self.publisher.announce("encode", (toks, length))
            self._dispatch_encode(toks, length, key)
        self._record_warmup(
            "encode", key, time.perf_counter() - t0, bucket.label
        )

    # ------------------------------------------------------------------
    # Batch construction (host side, numpy)
    # ------------------------------------------------------------------

    def _slot_rows(
        self, seqs: List[Sequence], B: int, W: int
    ) -> Dict[str, np.ndarray]:
        """``state_slots`` [B] for a model with recurrent layers: each
        row's slot, padding rows the scratch slot. ``window_tables`` [B, W]
        for a model with a window page group: each row's pages there by the
        block table's logical index (a released entry reads 0)."""
        out = {}
        if self._recurrent:
            slots = np.full(B, self.state_slots, np.int32)
            for i, s in enumerate(seqs):
                slots[i] = s.state_slot
            out["state_slots"] = slots
        if self.window_blocks:
            out["window_tables"] = self._window_tables(seqs, B, W)
        return out

    def _window_tables(self, seqs: List[Sequence], B: int, W: int) -> np.ndarray:
        tables = np.zeros((B, W), np.int32)
        for i, s in enumerate(seqs):
            n = min(len(s.window_block_ids), W)
            tables[i, :n] = s.window_block_ids[:n]
        return tables

    def _table_row(self, seq: Sequence, width: int) -> np.ndarray:
        row = np.zeros(width, np.int32)
        n = min(len(seq.block_ids), width)
        row[:n] = seq.block_ids[:n]
        return row

    def _row_bucket(self, B: int) -> int:
        """Decode/verify batch-row bucket: pow2, floored by dp divisibility
        and the compile-stability floor."""
        Bb = _pow2(B, cap=_pow2(self.cfg.max_num_seqs))
        return max(Bb, B, self._dp, self.cfg.min_decode_bucket)

    def _table_bucket(self, seqs: List[Sequence]) -> int:
        W = max(max(len(s.block_ids) for s in seqs), 1)
        return max(
            _pow2(W, cap=_pow2(self.max_table_width)),
            min(_MIN_TABLE_BUCKET, _pow2(self.max_table_width)),
        )

    def _lora_arrays(self, seqs: List[Sequence], B: int) -> Dict[str, np.ndarray]:
        lora_idx = np.zeros(B, np.int32)
        lora_scale = np.zeros(B, np.float32)
        for i, s in enumerate(seqs):
            lora_idx[i] = getattr(s, "lora_idx", 0)
            lora_scale[i] = getattr(s, "lora_scale", 0.0)
        return {"lora_idx": lora_idx, "lora_scale": lora_scale}

    def _decode_batch(
        self, seqs: List[Sequence], multi: bool = False
    ) -> Dict[str, np.ndarray]:
        B = len(seqs)
        Bb = self._row_bucket(B)
        Wb = self._table_bucket(seqs)
        bs = self.cfg.block_size

        shape = (Bb,) if multi else (Bb, 1)
        tokens = np.zeros(shape, np.int32)
        positions = np.zeros(shape, np.int32)
        tables = np.zeros((Bb, Wb), np.int32)
        kv_lens = np.zeros(Bb, np.int32)
        if not multi:
            write_idx = np.full((Bb, 1), self._drop_slot, np.int32)
            last_idx = np.zeros(Bb, np.int32)
        for i, s in enumerate(seqs):
            pos = s.num_tokens - 1
            tokens[i, ...] = s.all_token_ids[-1]
            positions[i, ...] = pos
            tables[i] = self._table_row(s, Wb)
            kv_lens[i] = s.num_tokens
            if not multi:
                write_idx[i, 0] = s.block_ids[pos // bs] * bs + pos % bs
        batch = {
            "tokens": tokens,
            "positions": positions,
            "block_tables": tables,
            "kv_lens": kv_lens,
        }
        if not multi:
            batch["write_idx"] = write_idx
            batch["last_idx"] = last_idx
        batch.update(self._member_rows(seqs, Bb, Wb) if multi else {
            **self._slot_rows(seqs, Bb, Wb),
            **self._sampling_arrays(seqs, Bb)})
        return batch

    def _prefill_batch(self, items: List[PrefillItem]) -> Dict[str, np.ndarray]:
        B = len(items)
        Bb = _pow2(B)
        chunk_max = max(it.end - it.start for it in items)
        Tb = _pow2(chunk_max, cap=_pow2(self.cfg.max_prefill_tokens))
        Tb = max(Tb, chunk_max)
        Wb = self._table_bucket([it.seq for it in items])
        bs = self.cfg.block_size

        tokens = np.zeros((Bb, Tb), np.int32)
        positions = np.zeros((Bb, Tb), np.int32)
        write_idx = np.full((Bb, Tb), self._drop_slot, np.int32)
        tables = np.zeros((Bb, Wb), np.int32)
        kv_lens = np.zeros(Bb, np.int32)
        last_idx = np.zeros(Bb, np.int32)
        for i, it in enumerate(items):
            s, start, end = it.seq, it.start, it.end
            chunk = end - start
            ids = s.all_token_ids
            for j in range(chunk):
                pos = start + j
                tokens[i, j] = ids[pos]
                positions[i, j] = pos
                write_idx[i, j] = s.block_ids[pos // bs] * bs + pos % bs
            positions[i, chunk:] = max(end - 1, 0)
            tables[i] = self._table_row(s, Wb)
            kv_lens[i] = end
            last_idx[i] = chunk - 1
        batch = {
            "tokens": tokens,
            "positions": positions,
            "write_idx": write_idx,
            "block_tables": tables,
            "kv_lens": kv_lens,
            "last_idx": last_idx,
        }
        if self._mtp:
            batch.update(self._mtp_prefill_arrays(items, batch))
        batch.update(self._slot_rows([it.seq for it in items], Bb, Wb))
        if self._skips_cross:
            # rows whose chunk ends what they have to prefill: the only
            # ones a token is sampled from
            batch["sample_rows"] = np.zeros(Bb, bool)
            for i, it in enumerate(items):
                s = it.seq
                batch["sample_rows"][i] = it.end >= (
                    s.num_prompt_tokens if not s.output_token_ids
                    else s.num_tokens - 1)
        batch.update(self._sampling_arrays([it.seq for it in items], Bb))
        return batch

    def _row_slots(self, seq: Sequence, first: int, n: int) -> np.ndarray:
        """Flat slots of the global group for ``seq``'s positions ``first ..
        first + n - 1``: the drop slot where it holds no page for one."""
        bs = self.cfg.block_size
        pos = first + np.arange(n)
        held = pos < len(seq.block_ids) * bs
        pages = np.asarray(seq.block_ids, np.int64)[pos[held] // bs]
        idx = np.full(n, self._drop_slot, np.int32)
        idx[held] = pages * bs + pos[held] % bs
        return idx

    def _mtp_slots(self, seq: Sequence, first: int, n: int) -> tuple:
        """Where the draft layer's entries for positions ``first .. first +
        n - 1`` go (the slot rule: position ``i``'s at slot ``i + 1``) and
        the length its attention reads up to: the last of those slots that
        has a page."""
        covered = len(seq.block_ids) * self.cfg.block_size
        return (self._row_slots(seq, first + 1, n),
                min(first + n + 1, covered))

    def _mtp_prefill_arrays(
        self, items: List[PrefillItem], batch: Dict[str, np.ndarray]
    ) -> Dict[str, np.ndarray]:
        """What a prefill step hands the draft module beside the batch:
        ``mtp_next`` the token after each position (-1 after a sequence's
        last: the step's own sample), ``mtp_write_idx`` / ``mtp_kv_lens``
        the slots one ahead. And the main stack's writes into pages this
        sequence took from the prefix cache are dropped (``batch`` in
        place): the one position a hit computes again, for the state that
        makes the first slot past the hit, is in a page others read."""
        bs = self.cfg.block_size
        Bb, Tb = batch["tokens"].shape
        nxt = np.zeros((Bb, Tb), np.int32)
        idx = np.full((Bb, Tb), self._drop_slot, np.int32)
        lens = np.zeros(Bb, np.int32)
        for i, it in enumerate(items):
            s, start, chunk = it.seq, it.start, it.end - it.start
            ids = s.all_token_ids
            for j in range(chunk):
                nxt[i, j] = ids[start + j + 1] if start + j + 1 < len(ids) else -1
            idx[i, :chunk], lens[i] = self._mtp_slots(s, start, chunk)
            shared = min(s._committed_blocks * bs - start, chunk)
            if shared > 0:
                batch["write_idx"][i, :shared] = self._drop_slot
        return {"mtp_next": nxt, "mtp_write_idx": idx, "mtp_kv_lens": lens}

    def _take_drafts(self, items: List[PrefillItem], rows: np.ndarray) -> np.ndarray:
        """Split a fetched prefill step's rows from the first drafts under
        their last column, and keep each for the sequence whose prompt the
        step completed (its first decode step verifies it)."""
        if not self._mtp:
            return rows
        for it, draft in zip(items, rows[:, -1]):
            it.seq.mtp_draft = (
                int(draft) if it.end == it.seq.num_prompt_tokens
                and not it.seq.output_token_ids else None)
        return rows[:, :-1]

    # -- the verify-and-draft step (``--speculative-mtp``) -----------------

    def _draftable(self, s: Sequence) -> bool:
        """Whether ``s``'s draft may be verified this step: greedy rows
        only (a sampled row's second token would need the first's seed
        history), no penalties (the first token would change the counts),
        no guided choice (the mask is rebuilt a token on the host), and
        room for two tokens under ``max_model_len``."""
        return (s.mtp_draft is not None and self._draft_ok(s)
                and s.num_tokens + 2 <= self.cfg.max_model_len)

    @staticmethod
    def _draft_ok(s: Sequence) -> bool:
        """What of `_draftable` a row keeps for its whole life (a chain's
        batch carries it a row; the draft and the room are the device's
        carry's and `LLMEngine._chainable`'s to say there)."""
        sp = s.sampling
        return sp.greedy and not sp.has_penalties and not sp.guided_choice

    def _mtp_batch(self, seqs: List[Sequence]) -> Dict[str, np.ndarray]:
        B = len(seqs)
        Bb = self._row_bucket(B)
        Wb = self._table_bucket(seqs)
        bs = self.cfg.block_size
        tokens = np.zeros((Bb, 2), np.int32)
        positions = np.zeros((Bb, 2), np.int32)
        write_idx = np.full((Bb, 2), self._drop_slot, np.int32)
        mtp_idx = np.full((Bb, 2), self._drop_slot, np.int32)
        tables = np.zeros((Bb, Wb), np.int32)
        kv_lens = np.zeros(Bb, np.int32)
        mtp_lens = np.zeros(Bb, np.int32)
        draft_ok = np.zeros(Bb, bool)
        for i, s in enumerate(seqs):
            p0 = s.num_tokens - 1  # the not-yet-computed last token
            tokens[i, 0] = (s.output_token_ids[-1] if s.output_token_ids
                            else s.prompt_token_ids[-1])
            draft_ok[i] = self._draftable(s)
            if draft_ok[i]:
                tokens[i, 1] = s.mtp_draft
            positions[i] = (p0, p0 + 1)
            write_idx[i] = self._row_slots(s, p0, 2)
            mtp_idx[i], mtp_lens[i] = self._mtp_slots(s, p0, 2)
            tables[i] = self._table_row(s, Wb)
            kv_lens[i] = min(p0 + 2, len(s.block_ids) * bs)
        batch = {
            "tokens": tokens, "positions": positions, "write_idx": write_idx,
            "block_tables": tables, "kv_lens": kv_lens,
            # every live row holds two positions: what the model's ``valid``
            # mask and the expert dispatch count as real
            "last_idx": np.ones(Bb, np.int32),
            "mtp_write_idx": mtp_idx, "mtp_kv_lens": mtp_lens,
            "draft_ok": draft_ok,
        }
        batch.update(self._slot_rows(seqs, Bb, Wb))
        batch.update(self._sampling_arrays(seqs, Bb))
        return batch

    def execute_mtp_verify(self, seqs: List[Sequence]) -> list:
        """One verify-and-draft step, synchronous: for each sequence its
        one or two packed samples (two where the device accepted its draft)
        as a decode step packs them, log-probabilities included where a row
        asks; what a chained step's fetch gives (`_mtp_rows`). Each
        sequence's next draft is kept on it. One launch, one fetch."""
        with ENGINE_TELEMETRY.phase("batch_build", "decode"):
            batch = self._mtp_batch(seqs)
            want_lp, greedy = self._want_lp(seqs), self._all_greedy(seqs)
            key = self._tel_key("mtp_verify", batch, (want_lp, greedy))
            Bb = batch["kv_lens"].shape[0]
            bucket = f"b{Bb}xk1"
            # The step is a decode step to the telemetry and the trace (its
            # program is found under ``jit_pst_decode_step*``), told apart
            # by its bucket and these fields; ``accepted`` is the last
            # step's, known only after its fetch.
            self._step_info(
                "decode", bucket, seqs, batch, 2 * len(seqs),
                verify={"step": "mtp_verify",
                        "draft_positions": int(batch["draft_ok"].sum()),
                        "accepted": self._mtp_accepted_last})
        with self._dispatching(
            "decode", key, seqs, bucket, tokens=2 * len(seqs),
            fill=len(seqs) / Bb,
        ) as who, self._device_lock:
            self._host_gap_mark(bucket, who.t0, seqs)
            rows = self._dispatch_mtp_verify(batch, want_lp, greedy, key, who)
            self._host_gap_arm()
        return self._mtp_rows(rows[: len(seqs)], seqs)

    def _dispatch_mtp_verify(
        self, batch: Dict[str, np.ndarray], want_lp: bool, greedy: bool,
        key: Optional[tuple] = None, who: Optional[_Dispatch] = None,
    ) -> np.ndarray:
        with self._launch("decode", who) as launch:
            packed, self.kv_cache = self.programs.call(
                key, self._step["mtp_verify"],
                (self.params, self.kv_cache, self._put_batch(batch)),
                (want_lp, greedy),
            )
            launch.handle = packed
        return self._take_aux(_fetch(packed, "decode", self._clock))

    def _sampling_arrays(
        self, seqs: List[Sequence], B: int
    ) -> Dict[str, np.ndarray]:
        temps = np.zeros(B, np.float32)
        top_ps = np.ones(B, np.float32)
        top_ks = np.zeros(B, np.int32)
        min_ps = np.zeros(B, np.float32)
        seeds = np.zeros(B, np.uint32)
        for i, s in enumerate(seqs):
            sp = s.sampling
            temps[i] = sp.temperature
            top_ps[i] = sp.top_p
            top_ks[i] = sp.top_k
            min_ps[i] = sp.min_p
            seeds[i] = _seed_for(s)
        out = {
            "temps": temps,
            "top_ps": top_ps,
            "top_ks": top_ks,
            "min_ps": min_ps,
            "seeds": seeds,
        }
        if self.cfg.enable_lora:
            out.update(self._lora_arrays(seqs, B))
        if any(s.sampling.has_penalties for s in seqs):
            out.update(self._penalty_arrays(seqs, B))
        if any(s.sampling.guided_choice for s in seqs):
            V = self.model_cfg.vocab_size  # pad id: dropped by the scatter
            per_row = [
                s.sampling.guided_allowed(
                    s.output_token_ids, self.model_cfg.eos_token_ids
                )
                for s in seqs
            ]
            Na = _pow2(max(max((len(a) for a in per_row if a), default=1), 1))
            allowed_ids = np.full((B, Na), V, np.int32)
            allow_free = np.ones(B, bool)
            for i, allowed in enumerate(per_row):
                if allowed is None:
                    continue
                allow_free[i] = False
                for j, tid in enumerate(allowed[:Na]):
                    allowed_ids[i, j] = tid
            out["allowed_ids"] = allowed_ids
            out["allow_free"] = allow_free
        if any(s.sampling.logit_bias for s in seqs):
            V = self.model_cfg.vocab_size  # pad id: dropped by the scatter
            Nb = _pow2(max(max(len(s.sampling.logit_bias) for s in seqs), 1))
            bias_ids = np.full((B, Nb), V, np.int32)
            bias_vals = np.zeros((B, Nb), np.float32)
            for i, s in enumerate(seqs):
                for j, (tid, bv) in enumerate(s.sampling.logit_bias[:Nb]):
                    if 0 <= tid < V:
                        bias_ids[i, j] = tid
                        bias_vals[i, j] = bv
            out["bias_ids"] = bias_ids
            out["bias_vals"] = bias_vals
        return out

    def _penalty_arrays(
        self, seqs: List[Sequence], B: int
    ) -> Dict[str, np.ndarray]:
        V = self.model_cfg.vocab_size  # pad value: dropped by scatter
        Pp = _pow2(max(max(s.num_prompt_tokens for s in seqs), 1))
        Po = _pow2(max(max(len(s.output_token_ids) for s in seqs), 1))
        prompt = np.full((B, Pp), V, np.int32)
        output = np.full((B, Po), V, np.int32)
        presence = np.zeros(B, np.float32)
        frequency = np.zeros(B, np.float32)
        repetition = np.ones(B, np.float32)
        for i, s in enumerate(seqs):
            sp = s.sampling
            prompt[i, : s.num_prompt_tokens] = s.prompt_token_ids
            output[i, : len(s.output_token_ids)] = s.output_token_ids
            presence[i] = sp.presence_penalty
            frequency[i] = sp.frequency_penalty
            repetition[i] = sp.repetition_penalty
        return {
            "penalty_prompt": prompt,
            "penalty_output": output,
            "presence": presence,
            "frequency": frequency,
            "repetition": repetition,
        }
