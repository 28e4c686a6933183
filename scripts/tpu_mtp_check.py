#!/usr/bin/env python3
"""The draft module's logits from the served path against the plain
reference's ``mtp_logits``, at the configuration's own widths: what
``correct`` cannot see (a wrong draft costs speed, never a token).

    chiprun --timeout 1800 -- python3 scripts/tpu_mtp_check.py \
        [--config perf/configs/k-exaone-ep8-cut.json] [--seeds 3] [--impl pallas]

For each seed a set of sequences after the cell's check set (behind the
shared prefix 1,200 x 3; fresh 3,000 / 1,536 / 200 / 64; 16 generated
positions each, teacher-forced on random tokens; 16 rows are too few to hold
a share against: one expert flip in a sequence is 2-3 of them) goes through the model's own paged
path as the runner drives it: prefill in chunks of 1,024 through both page
groups and the draft layer's pages one slot ahead, then eight steps of two
positions a row (a verify-and-draft step's shape). The sequence behind the
shared prefix is served twice: cold, and **after a prefix hit**, on the pages
another sequence with the same prefix and another next token left, one
position back as the scheduler restarts it. Every row's log-probabilities
are compared as ``perf/check.py`` compares (the largest difference over the
served top 5), against the configuration's ``tau`` and ``tau_median``.

Controls, each expected **not** to pass: ``mtp_hidden_unnormed`` (the
reference takes the hidden state before the final norm), under the same
tolerances; and ``slot_unshifted`` (the served path with the draft layer's
entries at their own slot and the hit taken as it is: the last slot of the
hit's last page was made from the other sequence's token). One wrong key
among a thousand moves a logit by less than bfloat16's rounding, so that
control is held to what the slot rule promises instead: **the run after the
hit equals the cold run** (``hit_vs_cold``: both cut into the same chunks,
so the same programs on the same numbers; exactly 0 under the rule, above 0
without it). One JSON line on standard output; the readings in
``chiprun_out/mtp_check/report.json``."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

GEN = 16  # generated positions a sequence, as the check set's
TOP = 5


def _bucket(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


class Served:
    """The model's paged path driven by hand, one row at a time."""

    def __init__(self, model, params, impl: str, block: int, pages: int,
                 chunk: int):
        import jax

        self.model, self.params, self.impl = model, params, impl
        self.block, self.pages, self.chunk = block, pages, chunk
        self.width = _bucket(pages)
        self.cache = model.make_kv_cache(pages + 1, block, None, pages + 1)
        self._step = jax.jit(self._forward, static_argnames=("shifted",),
                             donate_argnums=(1,))

    def _forward(self, params, cache, tok, nxt, pos, widx, midx, table, lens,
                 mlens, last, *, shifted):
        import jax

        _, hidden, cache = self.model.forward(
            params, tok, pos, widx, table, lens, last, cache,
            window_tables=table, attn_impl=self.impl, return_hidden=True,
            token_budget=tok.shape[1])
        logits, cache = self.model.mtp_forward(
            params, hidden, nxt, pos, midx, table, mlens, last, cache,
            attn_impl=self.impl, all_logits=True, shifted=shifted,
            token_budget=tok.shape[1])
        return jax.nn.log_softmax(logits[0], axis=-1), cache

    def run(self, tokens, table, *, start=0, shifted=True, steps=GEN // 2,
            keep_from=0, cut=0):
        """``tokens`` through pages ``table`` (page of block i) from position
        ``start`` (below it the pages hold what an earlier run left): chunks
        (one ends at ``cut``), then ``steps`` two-position steps. ->
        log-probabilities of rows ``keep_from ..`` (row i: position i paired
        with token i + 1)."""
        import jax.numpy as jnp
        import numpy as np

        bs, drop = self.block, (self.pages + 1) * self.block
        n = len(tokens) - 1  # the last token has none after it
        head = n - 2 * steps  # positions the chunks cover
        tab = np.zeros((1, self.width), np.int32)
        tab[0, :len(table)] = table
        slot = lambda p: int(tab[0, p // bs]) * bs + p % bs  # noqa: E731
        ahead = 1 if shifted else 0
        spans, at = [], start
        while at < head:
            hi = min(at + self.chunk, head)
            spans.append((at, cut if at < cut < hi else hi))
            at = spans[-1][1]
        spans += [(p, p + 2) for p in range(max(head, start), n, 2)]
        out = {}
        for lo, hi in spans:
            real, T = hi - lo, _bucket(hi - lo)
            pos = np.full((1, T), hi - 1, np.int32)
            pos[0, :real] = np.arange(lo, hi)
            tok = np.zeros((1, T), np.int32)
            nxt = np.zeros((1, T), np.int32)
            tok[0, :real], nxt[0, :real] = tokens[lo:hi], tokens[lo + 1:hi + 1]
            widx = np.full((1, T), drop, np.int32)
            midx = np.full((1, T), drop, np.int32)
            for j in range(real):
                # the position a hit computes again lies in a page others read
                if not (shifted and start and lo + j == start):
                    widx[0, j] = slot(lo + j)
                midx[0, j] = slot(lo + j + ahead)
            lps, self.cache = self._step(
                self.params, self.cache, jnp.asarray(tok), jnp.asarray(nxt),
                jnp.asarray(pos), jnp.asarray(widx), jnp.asarray(midx),
                jnp.asarray(tab), jnp.asarray([hi], jnp.int32),
                jnp.asarray([hi + ahead], jnp.int32),
                jnp.asarray([real - 1], jnp.int32), shifted=shifted)
            lps = np.asarray(lps[:real], np.float32)
            for j in range(real):
                if lo + j >= keep_from:
                    out[lo + j] = lps[j]
        return np.stack([out[i] for i in sorted(out)])


def compare(served, ref):
    """The largest |difference| a row over the served top ``TOP`` ids."""
    import numpy as np

    top = np.argsort(served, axis=-1)[:, -TOP:]
    return np.abs(np.take_along_axis(served - ref, top, axis=-1)).max(axis=-1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default="perf/configs/k-exaone-ep8-cut.json")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2300000301)
    ap.add_argument("--impl", default="auto")
    ap.add_argument("--lengths", default="1200p,1200p,1200p,3000,1536,200,64",
                    help="body tokens; a trailing p: behind the shared prefix")
    ap.add_argument("--prefix", type=int, default=1024)
    ap.add_argument("--out", default="chiprun_out/mtp_check")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from perf import config as configs
    from perf import reference
    from production_stack_tpu.models import registry
    from production_stack_tpu.ops.attention import resolve_attn_impl

    t0 = time.monotonic()
    cfg = configs.load(args.config)
    ref = reference.load(cfg.reference)
    model = registry.model_for(configs.program_model_config(cfg))
    params = ref.weights(cfg)
    jax.block_until_ready(params)
    block = int(cfg.flag("--block-size"))
    chunk = int(cfg.flag("--max-num-batched-tokens"))
    vocab = model.cfg.vocab_size
    specs = [(int(s.rstrip("p")), s.endswith("p")) for s in args.lengths.split(",")]
    longest = max(n + (args.prefix if p else 0) for n, p in specs) + GEN + 2
    pages = -(-longest // block) + 1
    impl = resolve_attn_impl(args.impl)
    served = Served(model, params, impl, block, 2 * pages, chunk)
    own = list(range(1, pages + 1))  # a sequence's pages
    other = list(range(pages + 1, 2 * pages + 1))  # the hit's own pages
    tau, tau_median = cfg.check["tau"], cfg.check.get("tau_median")
    print(f"[mtp_check] {cfg.name}: weights ready +{time.monotonic() - t0:.0f}s "
          f"on {jax.devices()[0].device_kind}, attention {impl}", file=sys.stderr)

    def verdict(errs):
        errs = np.concatenate(errs)
        ok = bool(np.isfinite(errs).all() and (errs <= tau).mean() >= cfg.check.get(
            "clear_within_min", 1.0) and (
            tau_median is None or np.median(errs) <= tau_median))
        return {"median": float(np.median(errs)), "max": float(errs.max()),
                "within_tau": float((errs <= tau).mean()), "rows": int(errs.size),
                "passes": ok}

    report = {"config": cfg.name, "device": jax.devices()[0].device_kind,
              "attention": impl, "tau": tau, "tau_median": tau_median,
              "seeds": []}
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        rng = np.random.default_rng(seed)
        prefix = [int(t) for t in rng.integers(0, vocab, args.prefix)]
        errs = {k: [] for k in ("served", "served_after_hit", "slot_unshifted",
                                "mtp_hidden_unnormed")}
        gaps = {}  # the run after a hit against the cold run, largest row
        for n, behind in specs:
            body = [int(t) for t in rng.integers(0, vocab, n + GEN + 1)]
            tokens = (prefix if behind else []) + body
            first = len(tokens) - GEN - 1  # rows of the generated positions
            rows = list(range(first, len(tokens) - 1))
            with jax.default_matmul_precision("highest"):
                want = ref.mtp_logits(cfg, params, tokens, rows)
                wrong = ref.mtp_logits(cfg, params, tokens, rows,
                                       "mtp_hidden_unnormed")
            got = served.run(tokens, own, keep_from=first)
            errs["served"].append(compare(got, want))
            errs["mtp_hidden_unnormed"].append(compare(got, wrong))
            if behind:
                # another sequence behind the same prefix (and as long) leaves
                # its pages; this one takes the prefix's and goes on in pages
                # of its own. Cold, elder and hit are cut at the same point.
                hit = args.prefix // block
                table = own[:hit] + other[hit:]
                elder = prefix + [int(t) for t in rng.integers(0, vocab, len(body))]
                for shifted, key in ((True, "served_after_hit"),
                                     (False, "slot_unshifted")):
                    at = hit * block - (1 if shifted else 0)
                    cold = served.run(tokens, own, shifted=shifted,
                                      keep_from=first, cut=at)
                    served.run(elder, own, shifted=shifted, cut=at)
                    got = served.run(tokens, table, start=at, shifted=shifted,
                                     keep_from=first)
                    errs[key].append(compare(got, want))
                    gaps[key] = max(gaps.get(key, 0.0),
                                    float(np.abs(got - cold).max()))
        line = {"seed": seed, **{k: verdict(v) for k, v in errs.items() if v}}
        for key, gap in gaps.items():
            line[key]["hit_vs_cold"] = gap
        if "slot_unshifted" in line:  # held to the rule's own promise
            line["slot_unshifted"]["passes"] = gaps["slot_unshifted"] <= max(
                10 * gaps["served_after_hit"], 1e-6)
        report["seeds"].append(line)
        print(f"[mtp_check] seed {seed}: " + ", ".join(
            f"{k} median {v['median']:.4f} max {v['max']:.4f}"
            + (f" hit-cold {v['hit_vs_cold']:.2e}" if "hit_vs_cold" in v else "")
            + f" {'passes' if v['passes'] else 'NOT'}"
            for k, v in line.items() if k != "seed")
            + f" +{time.monotonic() - t0:.0f}s", file=sys.stderr)
    report["ok"] = all(
        s["served"]["passes"] and s.get("served_after_hit", {"passes": True})["passes"]
        and not s["mtp_hidden_unnormed"]["passes"]
        and not s.get("slot_unshifted", {"passes": False})["passes"]
        for s in report["seeds"])
    report["seconds"] = time.monotonic() - t0
    os.makedirs(os.path.join(ROOT, args.out), exist_ok=True)
    with open(os.path.join(ROOT, args.out, "report.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({"ok": report["ok"], "device": report["device"],
                      "seeds": report["seeds"]}))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
