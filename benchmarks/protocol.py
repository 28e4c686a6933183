"""In-process multi-round-QA workload driver (the reference protocol).

Reusable core for ``bench.py`` and the tuning scripts: N concurrent users
share a system prompt, each keeps a growing ~20k-token chat history, sends
one question per round, Poisson-paced at a target QPS; 100-token answers.
Mirrors the reference harness semantics
(`benchmarks/multi-round-qa/multi-round-qa.py:17-43` WorkloadConfig,
`run_single.sh:12-40` single-accelerator sweep) but steps the engine
directly — no HTTP — so its numbers are the engine's own.

Open-loop measurement: a request's TTFT is charged from its *scheduled*
Poisson arrival, not the submit time, so queueing delay behind a busy
device counts (same as the reference harness).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np


class ProtocolRunner:
    def __init__(
        self,
        engine,
        n_users: int,
        sys_len: int = 1000,
        hist_len: int = 20000,
        question_len: int = 28,
        answer_len: int = 100,
        seed: int = 0,
    ):
        from production_stack_tpu.engine.sequence import SamplingParams

        self._SP = SamplingParams
        self.engine = engine
        self.n_users = n_users
        self.question_len = question_len
        self.answer_len = answer_len
        self.rng = np.random.default_rng(seed)
        self.V = engine.model_cfg.vocab_size
        self.system_prompt = self._toks(sys_len)
        self.histories: List[List[int]] = [
            self.system_prompt + self._toks(hist_len) for _ in range(n_users)
        ]

    def _toks(self, n: int) -> List[int]:
        return self.rng.integers(1, self.V - 1, size=n).tolist()

    def _params(self, max_tokens: int):
        return self._SP(max_tokens=max_tokens, temperature=0.0, ignore_eos=True)

    # ------------------------------------------------------------------

    def drive(
        self,
        requests: List[Tuple[str, int, List[int], int]],
        paced_qps: Optional[float] = None,
        measure_decode: bool = False,
        decode_burst: Optional[int] = None,
    ) -> Tuple[Dict[str, float], Dict[int, List[int]], Optional[float]]:
        """Submit (tag, user, prompt, max_tokens) all at once or at Poisson
        arrival times; step the engine until drained. Returns
        (ttfts by request id, answer tokens by user, decode tok/s or None).

        ``measure_decode`` accumulates time only over steps that produced a
        full decode burst (``decode_burst`` tokens, default
        n_users*num_decode_steps) — the saturated-decode throughput."""
        engine = self.engine
        if decode_burst is None:
            # Saturated-decode qualification: count only full-width,
            # full-depth bursts. With adaptive depth enabled that means
            # DEEP bursts — the shallow ramp before the gate opens spends
            # a whole dispatch→fetch round trip on
            # n_users*num_decode_steps tokens and would drag the "saturated" average far below the
            # steady-state rate.
            steps = max(
                engine.cfg.num_decode_steps,
                engine.cfg.adaptive_decode_steps,
                1,
            )
            decode_burst = self.n_users * steps
        # Monotonic: arrival_time feeds Sequence queue/TTFT bookkeeping,
        # which rides time.monotonic() (engine/sequence.py).
        t_base = time.monotonic()
        offset = 0.0
        pending = []
        for req in requests:
            if paced_qps:
                offset += float(self.rng.exponential(1.0 / paced_qps))
            pending.append((t_base + offset, req))
        ttfts: Dict[str, float] = {}
        answers: Dict[int, List[int]] = {}
        dec_toks, dec_time = 0, 0.0
        while pending or engine.has_work():
            now = time.monotonic()
            while pending and pending[0][0] <= now:
                sched, (tag, u, prompt, max_tokens) = pending.pop(0)
                engine.add_request(
                    tag,
                    prompt_token_ids=prompt,
                    sampling=self._params(max_tokens),
                    arrival_time=sched,
                )
            if not engine.has_work():
                time.sleep(max(min(pending[0][0] - time.monotonic(), 0.01), 0.0))
                continue
            ts = time.time()
            outs = engine.step()
            dt = time.time() - ts
            step_toks = 0
            for out in outs:
                step_toks += len(out.new_token_ids)
                u = int(out.request_id.rsplit("-", 1)[1])
                answers.setdefault(u, []).extend(out.new_token_ids)
                if out.ttft is not None and out.request_id not in ttfts:
                    ttfts[out.request_id] = out.ttft
            if measure_decode and step_toks >= decode_burst:
                dec_toks += step_toks
                dec_time += dt
        rate = dec_toks / dec_time if dec_time > 0 else None
        return ttfts, answers, rate

    def qa_round(
        self,
        tag: str,
        users: Optional[List[int]] = None,
        paced_qps: Optional[float] = None,
        measure_decode: bool = False,
        ask: bool = True,
        max_tokens: Optional[int] = None,
        decode_burst: Optional[int] = None,
    ) -> Tuple[List[float], Optional[float]]:
        """One QA round: each user appends a fresh question and requests an
        answer; answers extend the history (multi-round-QA structure)."""
        users = list(range(self.n_users)) if users is None else users
        reqs = []
        for u in users:
            if ask:
                self.histories[u] = self.histories[u] + self._toks(
                    self.question_len
                )
            reqs.append((
                f"{tag}-{u}",
                u,
                self.histories[u],
                self.answer_len if max_tokens is None else max_tokens,
            ))
        ttfts, answers, rate = self.drive(
            reqs, paced_qps=paced_qps, measure_decode=measure_decode,
            decode_burst=decode_burst,
        )
        for u in users:
            self.histories[u] = self.histories[u] + answers.get(u, [])
        return list(ttfts.values()), rate

    # -- canonical phases ----------------------------------------------

    def cold_prefill(self) -> float:
        """Phase 1: every user's full history prefilled (fills the prefix
        cache, compiles the cold buckets). Returns wall seconds."""
        t0 = time.time()
        self.qa_round("cold", ask=False, max_tokens=1)
        return time.time() - t0

    def prefill_probe(self) -> float:
        """Phase 2: one fresh user-sized prompt, warm compiles — prefill
        tok/s over the non-cached suffix. The probe's pages are never
        re-touched afterwards, so later allocation pressure evicts exactly
        them (LRU) rather than any live user history."""
        fresh = self.system_prompt + self._toks(
            len(self.histories[0]) - len(self.system_prompt)
        )
        t0 = time.time()
        self.drive([("fresh-0", 0, fresh, 1)])
        wall = time.time() - t0
        return (len(fresh) - len(self.system_prompt)) / wall

    def warm_compile(self, stagger_groups=((0,), (1, 2), (3, 4, 5, 6), (7,))):
        """Phase 3: all-at-once rounds + a staggered round so every batch
        bucket the Poisson phase can hit is compiled — including the
        adaptive deep-burst shape (its first use must not land inside a
        measured phase: an XLA compile there reads as seconds of fake
        latency)."""
        for r in range(2):
            self.qa_round(f"warmup{r}")
        for group in stagger_groups:
            group = [u for u in group if u < self.n_users]
            if group:
                self.qa_round(f"stagger{group[0]}", users=list(group))
        cfg = self.engine.cfg
        if cfg.adaptive_decode_steps > cfg.num_decode_steps:
            # Force the adaptive gate open so the deep-burst shape
            # DETERMINISTICALLY compiles here (relying on the quiet timer
            # is racy: a fast model can drain the round before it opens).
            # drive() directly — not qa_round — so user histories are NOT
            # extended: measured rounds must start from identical context
            # whether or not the adaptive warm-up ran.
            old = (cfg.adaptive_decode_quiet_s, cfg.adaptive_decode_min_running)
            cfg.adaptive_decode_quiet_s = 0.0
            cfg.adaptive_decode_min_running = 0
            try:
                self.drive([
                    (f"warmdeep-{u}", u, self.histories[u],
                     2 * cfg.adaptive_decode_steps)
                    for u in range(self.n_users)
                ])
            finally:
                cfg.adaptive_decode_quiet_s = old[0]
                cfg.adaptive_decode_min_running = old[1]
        self.engine.allocator.reset_metrics()

    def measured_rounds(
        self, qps: float, n_rounds: int, tag: str = "round"
    ) -> List[float]:
        """Phase 4: Poisson-paced QA rounds; returns all TTFTs."""
        out: List[float] = []
        for r in range(n_rounds):
            ttfts, _ = self.qa_round(f"{tag}{r}", paced_qps=qps)
            out.extend(ttfts)
        return out

    def decode_probe(
        self, max_tokens: int = 96, pipelined: bool = False, burst: int = 32
    ) -> Optional[float]:
        """Phase 5: all users decode concurrently at full context; tok/s
        over full-burst steps.

        ``pipelined`` runs the probe under async decode (one burst always
        in flight, its token fetch overlapped with the next burst's
        execution) — the throughput-serving configuration: the
        dispatch→fetch round trip a synchronous loop pays per burst leaves
        the steady state instead of being amortized."""
        import dataclasses as _dc

        if not pipelined:
            _, rate = self.qa_round("probe", measure_decode=True,
                                    max_tokens=max_tokens)
            return rate
        cfg = self.engine.cfg
        sched = self.engine.scheduler
        old = (cfg.async_decode, cfg.num_decode_steps,
               cfg.adaptive_decode_steps, sched.config)
        cfg.async_decode = True
        cfg.num_decode_steps = burst
        cfg.adaptive_decode_steps = 0
        # The in-flight continuation writes one burst past the host view:
        # its pages must be reserved at dispatch time.
        sched.config = _dc.replace(sched.config, decode_lookahead=2,
                                   num_decode_steps=burst)
        try:
            # Warm the burst-start/continue/drain shapes outside the
            # measured window (their first compile would land inside the
            # first qualified burst's dt otherwise).
            self.drive([
                (f"warmpipe-{u}", u, self.histories[u], 2 * burst)
                for u in range(self.n_users)
            ])
            # Qualify at one user short of full width: with the pool sized
            # to ~7.5 of 8 users, one sequence may be parked (KV swap) at
            # any instant — the chip is still saturated.
            _, rate = self.qa_round(
                "probe", measure_decode=True, max_tokens=max_tokens,
                decode_burst=max(self.n_users - 1, 1) * burst,
            )
            return rate
        finally:
            cfg.async_decode, cfg.num_decode_steps = old[0], old[1]
            cfg.adaptive_decode_steps = old[2]
            sched.config = old[3]
