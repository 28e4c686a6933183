"""``correct``: the served path's numbers against the plain reference.

After the window the harness sends the cell's check set (drawn from
``--seed``): each sequence generates ``CHECK_TOKENS`` tokens, greedy, with
``logprobs=5``. The reference is teacher-forced on the system's own tokens
(tokens are never compared: with random weights the arg-max changes on
rounding), and the error at a position is the largest
``|logprob_sys - logprob_ref|`` over the ids the system reported there.

Flip-aware for MoE: a position is *clear* when the reference's gap between
the top-k-th and the next router logit exceeds ``delta`` at every layer
(dense: always). ``correct`` requires every response complete and finite;
at least ``clear_within_min`` of the clear positions within ``tau`` (1.0,
every one, unless the configuration says otherwise: the calibration on the
chip showed that a served bf16 MoE flips experts at a few positions whose
reference gap is far from a tie, and that a flip reaches the positions
after it); the median error over clear positions within ``tau_median``
where the configuration gives one (the bulk must sit at the noise floor,
which a lower precision or a wrong equation moves); every position within
``tau_loose`` (which only catches garbage); and clear positions to be at
least half of those checked. Timing, failures and compiles never enter.
"""

from __future__ import annotations

import math

from . import tokenizer
from .generators import common

CHECK_TOKENS = 16
TOP_N = 5


def check_set(mix: dict, plan: dict, sessions: list, seed: int, vocab: int) -> list:
    """The sequences to check, from the mix's ``check`` list. Each entry:
    ``{"kind": "fresh", "shared_prefix": bool, "tokens": n}`` or
    ``{"kind": "session_turn", "tokens": n}`` (a further turn of the
    session with the shortest context, so the reference stays affordable).
    """
    rng = common.rng_for(seed, "check")
    out = []
    for i, item in enumerate(mix["check"]):
        body = common.tokens(int(item["tokens"]), vocab, rng)
        if item["kind"] == "fresh":
            prefix = plan["shared_prefix"] if item.get("shared_prefix") else []
            prompt = list(prefix) + body
        elif item["kind"] == "session_turn":
            idle = [s for s in sessions if not s.busy]
            if not idle:
                raise ValueError("session_turn check needs an idle session")
            prompt = min(idle, key=lambda s: len(s.tokens)).tokens + body
        else:
            raise ValueError(f"unknown check kind {item['kind']!r}")
        out.append({"id": f"check{i}.{item['kind']}", "prompt": prompt})
    return out


def request_body(model: str, prompt: list) -> dict:
    return {"model": model, "prompt": prompt, "max_tokens": CHECK_TOKENS,
            "temperature": 0.0, "ignore_eos": True, "logprobs": TOP_N}


def parse_response(seq: dict, completion: dict) -> dict:
    """-> {"id", "tokens", "n_prompt", "want", "sys"}; ``sys[p]`` maps the
    ids the system reported at generated position p to their logprobs.
    ``complete`` is False when the response is short or malformed."""
    out = {"id": seq["id"], "n_prompt": len(seq["prompt"]), "complete": False,
           "tokens": list(seq["prompt"]), "want": [], "sys": []}
    try:
        lp = completion["choices"][0]["logprobs"]
        chosen = [tokenizer.ids_of(t) for t in lp["tokens"]]
        if len(chosen) != CHECK_TOKENS or any(len(c) != 1 for c in chosen):
            return out
        for p in range(CHECK_TOKENS):
            at = {}
            for key, val in lp["top_logprobs"][p].items():
                ids = tokenizer.ids_of(key)
                if len(ids) != 1:
                    return out
                at[ids[0]] = float(val)
            at[chosen[p][0]] = float(lp["token_logprobs"][p])
            if len(at) < TOP_N or not all(math.isfinite(v) for v in at.values()):
                return out
            out["sys"].append(at)
            out["want"].append(sorted(at))
        out["tokens"] = list(seq["prompt"]) + [c[0] for c in chosen]
        out["complete"] = True
    except (KeyError, IndexError, TypeError, ValueError):
        out["sys"], out["want"] = [], []
    return out


def compare(parsed: list, reference: list, thresholds: dict) -> dict:
    """``reference`` is one variant's list from ``perf/reference/run.py``.
    Returns the verdict with the shares and largest errors."""
    delta = float(thresholds["delta"])
    tau, tau_loose = float(thresholds["tau"]), float(thresholds["tau_loose"])
    by_id = {r["id"]: r for r in reference}
    positions = []
    incomplete = [p["id"] for p in parsed if not p["complete"]]
    for p in parsed:
        if not p["complete"] or p["id"] not in by_id:
            continue
        ref = by_id[p["id"]]
        for pos in range(len(p["sys"])):
            errs = [abs(v - ref["logprobs"][pos][str(t)])
                    for t, v in p["sys"][pos].items()]
            err = max(errs)
            if not math.isfinite(err):
                err = float("inf")
            clear = ref["gap"][pos] > delta
            positions.append({"id": p["id"], "pos": pos, "err": err,
                              "clear": clear, "gap": ref["gap"][pos]})
    clear = sorted(x["err"] for x in positions if x["clear"])
    unclear = [x["err"] for x in positions if not x["clear"]]
    n = len(positions)
    within_min = float(thresholds.get("clear_within_min", 1.0))
    tau_median = thresholds.get("tau_median")
    within = sum(1 for e in clear if e <= tau) / len(clear) if clear else 0.0
    median = clear[len(clear) // 2] if clear else float("inf")
    ok = (
        not incomplete and n > 0
        and within >= within_min
        and (tau_median is None or median <= float(tau_median))
        and all(e <= tau_loose for e in clear + unclear)
        and len(clear) * 2 >= n
    )
    return {
        "correct": bool(ok),
        "positions": n,
        "clear_share": len(clear) / n if n else 0.0,
        "clear_within_tau": within,
        "median_clear_err": median if clear else 0.0,
        "max_clear_err": max(clear, default=0.0),
        "max_unclear_err": max(unclear, default=0.0),
        "incomplete": incomplete,
        "thresholds": {"delta": delta, "tau": tau, "tau_loose": tau_loose,
                       "clear_within_min": within_min, "tau_median": tau_median},
        "detail": positions,
    }
