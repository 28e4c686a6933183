"""Automated regression verdicts over a bench round's JSON.

Every bench round so far was judged by a human reading the JSON against
ROADMAP claims. This module encodes those claims as machine-checkable
predicates and evaluates a round in one call — ``bench.py`` attaches the
resulting ``verdicts`` block to its final emit, and the driver (or CI)
gets a pass/fail/unevaluable triage instead of a wall of numbers.

Three inputs are accepted by :func:`load_round`:

- a bare bench result (the JSON ``bench.py`` prints as its last line);
- a driver capture ``{"n", "cmd", "rc", "tail", "parsed"}`` (the
  ``BENCH_rNN.json`` files) — when ``parsed`` is present it is used;
- a driver capture with ``parsed: null`` (r04: truncated emit; r05:
  rc 124 with nothing flushed) — the loader *recovers* what it can from
  the stderr tail: the per-qps sweep lines bench_engine logs are Python
  dict literals (``qps 0.5: {...}``), so even the r05 wreck yields a
  sweep whose 120 s p99 the tail-shape claim flags.

Claims that cannot be evaluated (phase skipped, field missing) report
``unevaluable`` with the reason — a truncated round must say *which*
claims it silently dropped, not just pass the ones it kept.

Stdlib-only on purpose: the driver may run this with no repo deps.
"""

from __future__ import annotations

import ast
import json
import os
import re
import sys
from typing import Callable, List, Optional, Tuple

# Claim targets (ROADMAP / docs/benchmarking.md acceptance bars).
RESTART_READY_BAR_S = 30.0
ROOFLINE_FRACTION_BAR = 0.9
FLEET_HIT_RATE_BAR = 0.9
REPLICAS2_DELTA_BAR_MS = 5.0
TENANT_P99_DELTA_BAR = 0.10
COST_FRACTION_BAND = (0.9, 1.1)
KV_KILL_HIT_RATE_BAND = 0.05
TAIL_FACTOR = 3.0

_QPS_LINE = re.compile(r"qps\s+([0-9.]+):\s+(\{.*\})\s*$")


# --------------------------------------------------------------------------
# Round loading / tail recovery
# --------------------------------------------------------------------------

def recover_from_tail(tail: str) -> Optional[dict]:
    """Salvage a partial result from a driver capture's stderr tail.

    Preference order: a complete JSON result line (the emit contract —
    any line parsing to a dict with ``"backend"``), else the per-qps
    sweep lines (Python dict literals logged per measured point)."""
    best_json = None
    sweep: List[dict] = []
    for line in tail.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                obj = json.loads(line)
                if isinstance(obj, dict) and "backend" in obj:
                    best_json = obj
            except ValueError:
                pass
        m = _QPS_LINE.search(line)
        if m:
            try:
                point = ast.literal_eval(m.group(2))
                if isinstance(point, dict):
                    sweep.append(point)
            except (ValueError, SyntaxError):
                pass
    if best_json is not None:
        best_json.setdefault("recovered_from", "tail_json")
        return best_json
    if sweep:
        return {"sweep": sweep, "recovered_from": "tail_sweep_lines"}
    return None


def load_round(obj) -> Tuple[Optional[dict], dict]:
    """(parsed_result_or_None, meta) from a path / dict / JSON string.

    ``meta`` carries provenance: driver rc, whether the result was
    recovered from the tail, the round index when present."""
    if isinstance(obj, str):
        if os.path.exists(obj):
            with open(obj) as f:
                obj = json.load(f)
        else:
            obj = json.loads(obj)
    if not isinstance(obj, dict):
        return None, {"error": "not a JSON object"}
    meta: dict = {}
    if "tail" in obj or "rc" in obj or "parsed" in obj:
        # Driver capture wrapper.
        meta["rc"] = obj.get("rc")
        if obj.get("n") is not None:
            meta["round"] = obj.get("n")
        parsed = obj.get("parsed")
        if isinstance(parsed, dict):
            return parsed, meta
        recovered = recover_from_tail(obj.get("tail") or "")
        if recovered is not None:
            meta["recovered_from"] = recovered.get("recovered_from")
            return recovered, meta
        meta["error"] = "no parseable result (parsed null, tail barren)"
        return None, meta
    return obj, meta


# --------------------------------------------------------------------------
# Claim predicates
# --------------------------------------------------------------------------

def _claim(name, target, status, observed=None, note=None) -> dict:
    out = {"claim": name, "target": target, "status": status}
    if observed is not None:
        out["observed"] = observed
    if note:
        out["note"] = note
    return out


def _unevaluable(name, target, why) -> dict:
    return _claim(name, target, "unevaluable", note=why)


def _get(parsed: dict, *path):
    cur = parsed
    for key in path:
        if not isinstance(cur, dict) or cur.get(key) is None:
            return None
        cur = cur[key]
    return cur


def claim_compile_polluted(parsed: dict) -> dict:
    name, target = "compile_polluted", "compile_polluted == false"
    val = parsed.get("compile_polluted")
    if val is None:
        return _unevaluable(name, target, "engine phase absent/truncated")
    return _claim(name, target, "fail" if val else "pass", observed=val)


def claim_warm_restart(parsed: dict) -> dict:
    name = "restart_to_ready"
    target = f"restart_to_ready_seconds < {RESTART_READY_BAR_S:g}"
    val = _get(parsed, "warm_restart", "restart_to_ready_seconds")
    if val is None:
        return _unevaluable(name, target, "warm_restart phase absent")
    return _claim(name, target,
                  "pass" if val < RESTART_READY_BAR_S else "fail",
                  observed=val)


def claim_roofline(parsed: dict) -> dict:
    name = "roofline_fraction"
    target = f"decode achieved_fraction >= {ROOFLINE_FRACTION_BAR:g}"
    frac = _get(parsed, "roofline", "achieved_fraction")
    if frac is None:
        return _unevaluable(name, target, "roofline absent (no real chip "
                                          "or engine phase truncated)")
    status = "pass" if frac >= ROOFLINE_FRACTION_BAR else "fail"
    return _claim(name, target, status, observed={"achieved_fraction": frac})


def claim_fleet(parsed: dict) -> dict:
    name = "fleet_hit_rates"
    target = (f"fleet & churn hit rates >= {FLEET_HIT_RATE_BAR:g}, "
              "both beat roundrobin")
    fleet = parsed.get("fleet")
    if not isinstance(fleet, dict) or fleet.get("fleet_hit_rate") is None:
        return _unevaluable(name, target, "fleet phase absent/failed")
    f, c, rr = (fleet.get("fleet_hit_rate"), fleet.get("churn_hit_rate"),
                fleet.get("rr_hit_rate"))
    ok = (f is not None and c is not None and rr is not None
          and f >= FLEET_HIT_RATE_BAR and c >= FLEET_HIT_RATE_BAR
          and f > rr and c > rr)
    return _claim(name, target, "pass" if ok else "fail",
                  observed={"fleet": f, "churn": c, "roundrobin": rr})


def claim_replicas2(parsed: dict) -> dict:
    name = "replicas2_overhead"
    target = f"replicas:2 p50 delta <= +{REPLICAS2_DELTA_BAR_MS:g} ms"
    delta = _get(parsed, "stack", "replicas2", "p50_delta_vs_single_ms")
    if delta is None:
        return _unevaluable(name, target, "stack replicas2 leg absent")
    return _claim(name, target,
                  "pass" if delta <= REPLICAS2_DELTA_BAR_MS else "fail",
                  observed=delta)


def claim_tenants(parsed: dict) -> dict:
    name = "tenant_isolation"
    target = (f"victim p99_delta_frac <= {TENANT_P99_DELTA_BAR:g} "
              "with zero victim sheds")
    tenants = parsed.get("tenants")
    if not isinstance(tenants, dict) or tenants.get("p99_delta_frac") is None:
        return _unevaluable(name, target, "tenants phase absent/failed")
    delta = tenants["p99_delta_frac"]
    sheds = tenants.get("victim_sheds")
    ok = delta <= TENANT_P99_DELTA_BAR and (sheds or 0) == 0
    return _claim(name, target, "pass" if ok else "fail",
                  observed={"p99_delta_frac": delta, "victim_sheds": sheds})


def claim_disagg(parsed: dict) -> dict:
    name = "disagg_ttft"
    target = ("disagg p99 TTFT < fused p99 TTFT, overlap_fraction > 0, "
              "zero fallbacks")
    disagg = parsed.get("disagg")
    if not isinstance(disagg, dict) or disagg.get("p99_ttft_disagg_ms") is None:
        return _unevaluable(name, target, "disagg phase absent/failed")
    dp99 = disagg["p99_ttft_disagg_ms"]
    fp99 = disagg.get("p99_ttft_fused_ms")
    ovl = disagg.get("overlap_fraction")
    ok = (fp99 is not None and dp99 < fp99
          and (ovl or 0) > 0 and (disagg.get("fallbacks") or 0) == 0)
    return _claim(name, target, "pass" if ok else "fail",
                  observed={"p99_disagg_ms": dp99, "p99_fused_ms": fp99,
                            "overlap_fraction": ovl,
                            "fallbacks": disagg.get("fallbacks")})


def claim_cost(parsed: dict) -> dict:
    name = "cost_attribution"
    lo, hi = COST_FRACTION_BAND
    target = f"attributed_fraction in [{lo:g}, {hi:g}] in both modes"
    cost = parsed.get("cost")
    if not isinstance(cost, dict):
        return _unevaluable(name, target, "cost phase absent/failed")
    fracs = {mode: _get(cost, mode, "attributed_fraction")
             for mode in ("unpipelined", "overlap")}
    if all(v is None for v in fracs.values()):
        return _unevaluable(name, target, "cost phase carried no fractions")
    ok = all(v is not None and lo <= v <= hi for v in fracs.values())
    return _claim(name, target, "pass" if ok else "fail", observed=fracs)


def claim_kvserver_kill(parsed: dict) -> dict:
    name = "kvserver_kill_hold"
    target = (f"one dead shard: all requests serve, hit rate holds "
              f"within {KV_KILL_HIT_RATE_BAND:g}")
    kill = _get(parsed, "disagg", "kvserver_kill")
    if not isinstance(kill, dict) or kill.get("hit_rate_delta") is None:
        return _unevaluable(name, target, "kvserver-kill leg absent")
    ok = bool(kill.get("meets_target"))
    return _claim(name, target, "pass" if ok else "fail",
                  observed={"hit_rate_delta": kill.get("hit_rate_delta"),
                            "requests_ok": kill.get("requests_ok"),
                            "fallbacks": kill.get("fallbacks")})


def claim_autoscale(parsed: dict) -> dict:
    """The closed-loop surge claim (docs/autoscaling.md): doubled offered
    load is absorbed — p99 inside the phase's SLO, the scaled-up replicas
    come up with ZERO fresh compiles (warm-start path), nothing was shed,
    and a scaled-to-zero pool's wake→first-token bound was measured."""
    name = "autoscale_surge_absorb"
    target = ("surge absorbed: p99 <= slo_ms, 0 cold compiles on new "
              "replicas, 0 sheds, wake_to_first_token_s measured")
    a = parsed.get("autoscale")
    if not isinstance(a, dict) or a.get("absorb_seconds") is None:
        return _unevaluable(name, target, "autoscale phase absent/failed")
    ok = bool(a.get("meets_target"))
    return _claim(
        name, target, "pass" if ok else "fail",
        observed={
            "absorb_seconds": a.get("absorb_seconds"),
            "p99_during_absorb_ms": a.get("p99_during_absorb_ms"),
            "cold_compiles_on_new_replicas":
                a.get("cold_compiles_on_new_replicas"),
            "failed_during_absorb": a.get("failed_during_absorb"),
            "wake_to_first_token_s": a.get("wake_to_first_token_s"),
        })


def _iter_sweeps(parsed: dict):
    """Every (model_tag, sweep point) in the round — flagship fields are
    inlined at top level, the other models nest under their keys, and a
    tail-recovered round carries a bare top-level ``sweep``."""
    if isinstance(parsed.get("sweep"), list):
        tag = parsed.get("model") or "flagship"
        for p in parsed["sweep"]:
            yield tag, p
    for key in ("concurrency_8users", "llama_1b"):
        sub = parsed.get(key)
        if isinstance(sub, dict) and isinstance(sub.get("sweep"), list):
            for p in sub["sweep"]:
                yield key, p


def claim_tail_shape(parsed: dict) -> dict:
    """The r05 lesson: a sweep whose p99 is >3x its p50 is an unexplained
    tail — the claim that turns a 120 s outlier into a named failure
    (and, live, into a forensics bundle)."""
    name = "tail_shape"
    target = f"every sweep point: p99_ttft <= {TAIL_FACTOR:g} x p50_ttft"
    outliers = []
    n_points = 0
    for tag, p in _iter_sweeps(parsed):
        if not isinstance(p, dict):
            continue
        p50, p99 = p.get("p50_ttft_ms"), p.get("p99_ttft_ms")
        if p50 is None or p99 is None:
            continue
        n_points += 1
        if p50 > 0 and p99 > TAIL_FACTOR * p50:
            outliers.append({"model": tag, "qps": p.get("qps"),
                             "p50_ttft_ms": p50, "p99_ttft_ms": p99,
                             "ratio": round(p99 / p50, 1)})
    if n_points == 0:
        return _unevaluable(name, target, "no sweep points in round")
    if outliers:
        return _claim(name, target, "fail", observed=outliers,
                      note=f"{len(outliers)}/{n_points} points over the bar")
    return _claim(name, target, "pass",
                  observed={"points": n_points, "outliers": 0})


CLAIMS: List[Callable[[dict], dict]] = [
    claim_compile_polluted,
    claim_warm_restart,
    claim_roofline,
    claim_fleet,
    claim_replicas2,
    claim_tenants,
    claim_disagg,
    claim_cost,
    claim_kvserver_kill,
    claim_autoscale,
    claim_tail_shape,
]


def evaluate_round(parsed: Optional[dict], meta: Optional[dict] = None) -> dict:
    """The ``verdicts`` block: every claim evaluated, plus counts.

    ``ok`` means *no claim failed* — unevaluable claims don't pass, they
    are surfaced (``n_unevaluable``) so a truncated round can't look
    healthier than a complete one."""
    meta = dict(meta or {})
    if not isinstance(parsed, dict):
        return {"ok": False, "claims": [], "n_pass": 0, "n_fail": 0,
                "n_unevaluable": len(CLAIMS),
                "error": meta.get("error", "no parseable result"), **meta}
    claims = [fn(parsed) for fn in CLAIMS]
    n_pass = sum(1 for c in claims if c["status"] == "pass")
    n_fail = sum(1 for c in claims if c["status"] == "fail")
    n_un = sum(1 for c in claims if c["status"] == "unevaluable")
    return {"ok": n_fail == 0, "n_pass": n_pass, "n_fail": n_fail,
            "n_unevaluable": n_un, "claims": claims, **meta}


# --------------------------------------------------------------------------
# Trajectory across rounds
# --------------------------------------------------------------------------

def round_files(root: str) -> List[str]:
    """The BENCH_rNN.json captures in ``root``, in round order."""
    out = []
    for name in sorted(os.listdir(root)):
        if re.fullmatch(r"BENCH_r\d+\.json", name):
            out.append(os.path.join(root, name))
    return out


def trajectory(paths: List[str], current: Optional[dict] = None) -> List[dict]:
    """Headline numbers per round (p50 TTFT + p99 + parse health), so a
    verdicts report shows the trend the round sits in, not a lone value."""
    rows = []
    for path in paths:
        parsed, meta = load_round(path)
        rows.append(_traj_row(os.path.basename(path), parsed, meta))
    if current is not None:
        rows.append(_traj_row("current", current, {}))
    return rows


def _traj_row(label: str, parsed: Optional[dict], meta: dict) -> dict:
    row = {"round": label,
           "parsed": isinstance(parsed, dict),
           "recovered_from": meta.get("recovered_from"),
           "rc": meta.get("rc")}
    if isinstance(parsed, dict):
        p50 = parsed.get("value") or parsed.get("p50_ttft_ms")
        if p50 is not None:
            row["p50_ttft_ms"] = p50
        if parsed.get("p99_ttft_ms") is not None:
            row["p99_ttft_ms"] = parsed["p99_ttft_ms"]
        restart = _get(parsed, "warm_restart", "restart_to_ready_seconds")
        if restart is not None:
            row["restart_to_ready_s"] = restart
    return row


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Evaluate a bench round JSON against the ROADMAP "
                    "claims; exit 1 when any claim fails.")
    ap.add_argument("round", help="bench result JSON or BENCH_rNN capture")
    ap.add_argument("--rounds-dir", default=None,
                    help="directory holding BENCH_rNN.json captures for "
                         "the trajectory section (default: the round "
                         "file's own directory)")
    ap.add_argument("--no-trajectory", action="store_true")
    args = ap.parse_args(argv)

    parsed, meta = load_round(args.round)
    verdicts = evaluate_round(parsed, meta)
    if not args.no_trajectory:
        root = args.rounds_dir or os.path.dirname(
            os.path.abspath(args.round)) or "."
        try:
            verdicts["trajectory"] = trajectory(round_files(root))
        except OSError:
            pass
    json.dump(verdicts, sys.stdout, indent=2)
    print()
    return 0 if verdicts["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
