"""The warm-up of one run: a fixed amount of work, drawn from the mix file
and never from ``--seed``, so that set-up costs the same in every run and
the window starts from the contexts the plan gave it.

Two parts, both under the mix's ``warmup`` key:

``probes`` touch the step programs the traffic can reach, one by one. The
program pads a prefill step to (rows, longest uncached chunk, pages of the
longest context) and loads or compiles a program the first time it meets a
padded shape, stalling every request while it does. A probe group
(``groups``: one list of sizes per group) is that many prompts sent at the
same instant, each a cached context cut at a page boundary (``page_tokens``)
plus that many fresh tokens, one token out. The cached contexts are the
sessions at the listed quantiles of context length (``contexts``; without
sessions, the shared prefix). Ahead of every group, by ``lead_s``, goes a
*blocker*: a fresh prompt of ``blocker_tokens`` (one whole prefill step).
The engine is inside that step while the group arrives and schedules the
group as one step afterwards, not as one-then-the-rest.

``passes`` x ``seconds`` of the cell's own traffic with fixed seeds, on
copies of the sessions, for what only real traffic reaches (the decode
programs, the first turns of every user at once).
"""

from __future__ import annotations

import time

from . import client, harness
from .generators import common
from .harness import BenchError, log

PROBE_SEED = 0  # the probes are the same in every run


def probe_groups(spec: dict, plan: dict, vocab: int) -> list:
    """-> [(blocker prompt, [prompts to send together]), ...]."""
    page = int(spec["page_tokens"])
    if plan["sessions"]:
        by_len = sorted(plan["sessions"], key=len)
        bases = [by_len[int(round(float(q) * (len(by_len) - 1)))]
                 for q in spec["contexts"]]
    else:
        bases = [plan["shared_prefix"]]
    rng = common.rng_for(PROBE_SEED, "probe")
    return [(common.tokens(int(spec["blocker_tokens"]), vocab, rng),
             [base[:len(base) // page * page] + common.tokens(int(n), vocab, rng)
              for n in sizes])
            for base in bases for sizes in spec["groups"]]


def _report(what: str, before: dict, after: dict, t0: float) -> None:
    """How many step shapes this part met first, by the program's own label
    (a label stands for several shapes: context widths, sampling variants)."""
    name, prev = "pst_engine_compile_total", {}
    for labels, v in before.get(name, []):
        prev[labels.get("shape_bucket")] = prev.get(labels.get("shape_bucket"), 0) + v
    new = {}
    for labels, v in after.get(name, []):
        new[labels.get("shape_bucket")] = new.get(labels.get("shape_bucket"), 0) + v
    new = {k: int(v - prev.get(k, 0)) for k, v in new.items() if v > prev.get(k, 0)}
    misses = harness.counter_delta(before, after,
                                   "pst_engine_compile_cache_misses_total")
    log(f"{what} in {time.monotonic() - t0:.1f}s: {sum(new.values())} new step "
        f"shapes {new}, {misses:.0f} programs compiled")


def run(engine, gen, mix: dict, plan: dict, vocab: int) -> dict:
    w = mix["warmup"]
    t0 = time.monotonic()
    spec = w.get("probes")
    if spec:
        groups = probe_groups(spec, plan, vocab)
        before = harness.scrape(engine.base)
        for blocker, prompts in groups:
            failed = [r.error for r in client.send_together(
                engine.base, engine.cfg.name, prompts, blocker=blocker,
                lead_s=float(spec["lead_s"])) if r.error]
            if failed:
                raise BenchError(f"warm-up probe failed: {failed[:3]}")
        _report(f"warm-up probes: {len(groups)} groups", before,
                harness.scrape(engine.base), t0)
    for i in range(int(w["passes"])):
        t1, before = time.monotonic(), harness.scrape(engine.base)
        warm_plan = gen.plan(mix, 1000 + i, float(w["seconds"]), vocab)
        sessions = [client.Session(t) for t in plan["sessions"]]  # copies
        records, _ = client.run_plan(engine.base, engine.cfg.name, warm_plan,
                                     sessions, float(w["seconds"]), drain=True)
        failed = [r.error for r in records if r.error]
        if failed:
            raise BenchError(f"warm-up traffic failed: {failed[:3]}")
        _report(f"warm-up pass {i + 1}", before, harness.scrape(engine.base), t1)
    return {"seconds": time.monotonic() - t0}
