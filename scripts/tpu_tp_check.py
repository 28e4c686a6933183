#!/usr/bin/env python3
"""The smoke's server under tensor parallelism, on a host with several chips.

Run from the checkout root on the chip (never imports jax; every engine is
a child, one at a time, as in ``chip_smoke.py``):

    python scripts/tpu_tp_check.py smoke --tp 4
        llama-3-8b, bf16 weights, Pallas attention, --tensor-parallel-size 4:
        the smoke's requests; the mesh spans ``tp`` distinct devices; bytes
        in use after load are of the same order on each; no compiled step
        all-gathers anything the size of a weight or a cache shard (read
        from XLA's dump of the optimized step modules); int4 x tp>1 is
        refused at start-up.
    python scripts/tpu_tp_check.py logprobs --tp N --out FILE
        int8 weights (a bf16 8B does not fit one chip), same seed: the
        chosen-token logprob of the first generated position for a fixed
        set of prompts, written to FILE.
    python scripts/tpu_tp_check.py compare A B
        max abs difference between two such files, against the tolerance
        of tests/test_numerics_oracle.py (atol 2e-3 x max|ref|, rtol 2e-3).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke as cs  # noqa: E402

# Several engine bring-ups in a row: this is not held to the smoke's wall.
cs.DEADLINE_S = 3300.0

BASE_FLAGS = [
    "--model", cs.MODEL,
    "--attn-impl", "pallas",
    "--max-model-len", "32768",
    "--block-size", "128",
    "--max-num-batched-tokens", "1024",
    "--max-num-seqs", "16",
    "--min-decode-bucket", "4",
]
LOGPROB_PROMPT_TOKENS = (16, 33, 100, 128, 256, 400, 777, 1024, 1500, 3000)
# Result buffers at or above this size, produced by an all-gather inside a
# compiled step, are weights or cache — activations at these shapes are a
# few MiB ([1024, 4096] bf16 = 8 MiB).
ALL_GATHER_LIMIT_BYTES = 32 << 20
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "bf16": 2,
                "f16": 2, "s16": 2, "u16": 2, "f32": 4, "s32": 4, "u32": 4,
                "f64": 8, "s64": 8, "u64": 8}
_SHAPE = re.compile(r"\b(\w+)\[([\d,]*)\]")


def _optimized_modules(dump_dir: str) -> list:
    # jax dumps each module into its own subdirectory.
    return glob.glob(
        os.path.join(dump_dir, "**", "*after_optimizations.txt"),
        recursive=True,
    )


def all_gathers(dump_dir: str) -> list:
    """(bytes, dtype, dims, module file) of every all-gather result in the
    optimized HLO of the dumped step modules, largest first. An async
    all-gather-start yields (operand, result): the larger one counts."""
    found = []
    for path in _optimized_modules(dump_dir):
        with open(path, errors="replace") as f:
            for line in f:
                head, sep, _ = line.partition(" all-gather")
                if not sep or "=" not in head:
                    continue
                best = None
                for dtype, dims in _SHAPE.findall(head.split("=", 1)[1]):
                    if dtype not in _DTYPE_BYTES:
                        continue
                    dims = [int(d) for d in dims.split(",") if d]
                    n = _DTYPE_BYTES[dtype]
                    for d in dims:
                        n *= d
                    if best is None or n > best[0]:
                        best = (n, dtype, dims, os.path.basename(path))
                if best:
                    found.append(best)
    return sorted(found, reverse=True)


def serve(flags: list, children: list, name: str, env: dict = None):
    engine, base = cs.start_engine(flags, children, env=env, name=name)
    cs.wait_http_ok(f"{base}/ready", engine, f"{name} /ready", 900.0)
    dev = cs.get_json(f"{base}/version")["device"]
    cs.say(f"{name} device path: {json.dumps(dev)}")
    return engine, base, dev


def check_mesh(dev: dict, tp: int) -> None:
    ids = dev.get("mesh_device_ids", [])
    if dev.get("platform") != "tpu" or len(set(ids)) != tp:
        raise cs.SmokeFailure(f"mesh does not span {tp} distinct chips: {dev}")
    if dev.get("attention_impl") != "pallas" or dev.get("pallas_interpret"):
        raise cs.SmokeFailure(f"attention is not the compiled kernel: {dev}")


def cmd_smoke(tp: int) -> dict:
    report: dict = {"tp": tp}
    try:
        return _smoke(tp, report)
    finally:
        # Whatever was established before a failure is still a finding.
        os.makedirs("chiprun_out/tp", exist_ok=True)
        with open("chiprun_out/tp/smoke_report.json", "w") as f:
            json.dump(report, f, indent=1)
        cs.say("tp smoke report so far: " + json.dumps(report))


def _smoke(tp: int, report: dict) -> dict:
    children: list = []
    dump = tempfile.mkdtemp(prefix="pst_hlo_", dir="/tmp")
    try:
        env = cs.child_env()
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "") + f" --xla_dump_to={dump}"
            " --xla_dump_hlo_as_text --xla_dump_hlo_module_re=.*step.*"
        ).strip()
        flags = BASE_FLAGS + ["--tensor-parallel-size", str(tp)]
        engine, base, dev = serve(flags, children, "engine_tp_bf16", env)
        check_mesh(dev, tp)
        in_use = dev["hbm_bytes_in_use"]
        if None in in_use or max(in_use) > 1.5 * min(in_use):
            raise cs.SmokeFailure(
                f"bytes in use after load are not balanced: {in_use}"
            )
        router_base = cs.start_router(base, children)
        cs.traffic(base, router_base, "tp-cold")
        m1 = cs.scrape(base)
        cs.traffic(base, router_base, "tp-warm")
        m2 = cs.scrape(base)
        report.update(
            device_path=dev,
            compiles_cold=cs.total(m1, "pst_engine_compile_total"),
            compiles_warm_pass=cs.total(m2, "pst_engine_compile_total")
            - cs.total(m1, "pst_engine_compile_total"),
            startup_seconds=dict(m1.get("pst_engine_startup_seconds", [])),
        )
        if not engine.alive():
            raise cs.SmokeFailure(f"engine died:\n{engine.log_tail()}")
        gathers = all_gathers(dump)
        n_modules = len(_optimized_modules(dump))
        report["all_gather_check"] = {
            # Without a dumped module the check says nothing either way.
            "established": n_modules > 0,
            "step_modules_dumped": n_modules,
            "all_gather_count": len(gathers),
            "largest": gathers[:8],
            "limit_bytes": ALL_GATHER_LIMIT_BYTES,
        }
        big = [g for g in gathers if g[0] >= ALL_GATHER_LIMIT_BYTES]
        if big:
            raise cs.SmokeFailure(
                f"compiled step all-gathers weight/cache-sized buffers: {big[:4]}"
            )
    finally:
        for child in reversed(children):
            child.stop()

    # int4 under tp>1: refused at start-up, never served through an
    # all-gather of packed weights.
    children = []
    try:
        engine, _ = cs.start_engine(
            BASE_FLAGS + ["--tensor-parallel-size", str(tp),
                          "--quantization", "int4"],
            children, name="engine_tp_int4",
        )
        try:
            rc = engine.proc.wait(timeout=300)
        except Exception as e:  # subprocess.TimeoutExpired
            raise cs.SmokeFailure(
                "int4 x tp>1 engine did not refuse to start within 300 s"
            ) from e
        tail = engine.log_tail(8)
        if rc == 0 or "int4" not in tail:
            raise cs.SmokeFailure(
                f"int4 x tp>1: expected a start-up refusal, got rc={rc}:\n{tail}"
            )
        report["int4_tp_refusal"] = tail.splitlines()[-1][:300]
    finally:
        for child in reversed(children):
            child.stop()
    return report


def cmd_logprobs(tp: int, out_path: str) -> dict:
    children: list = []
    try:
        flags = BASE_FLAGS + ["--quantization", "int8",
                              "--tensor-parallel-size", str(tp)]
        _, base, dev = serve(flags, children, f"engine_tp{tp}_int8")
        check_mesh(dev, tp)
        rows = []
        for i, n in enumerate(LOGPROB_PROMPT_TOKENS):
            out = cs.post_completion(
                base,
                {"model": cs.MODEL, "prompt": cs.prompt(100 + i, n),
                 "max_tokens": 1, "temperature": 0.0, "ignore_eos": True,
                 "logprobs": 1},
                timeout=600,
            )
            lp = out["choices"][0]["logprobs"]["token_logprobs"]
            if len(lp) != 1 or lp[0] is None:
                raise cs.SmokeFailure(f"no logprob came back: {out}")
            rows.append({"prompt_tokens": n, "logprob": lp[0]})
            cs.say(f"tp={tp} prompt {n} tokens: first-token logprob {lp[0]:.6f}")
        report = {"tp": tp, "device_path": dev, "rows": rows}
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
        return report
    finally:
        for child in reversed(children):
            child.stop()


def cmd_compare(path_a: str, path_b: str) -> dict:
    with open(path_a) as f:
        a = json.load(f)
    with open(path_b) as f:
        b = json.load(f)
    ref = [r["logprob"] for r in a["rows"]]
    got = [r["logprob"] for r in b["rows"]]
    if [r["prompt_tokens"] for r in a["rows"]] != [
        r["prompt_tokens"] for r in b["rows"]
    ]:
        raise cs.SmokeFailure("the two files hold different prompt sets")
    scale = max(abs(x) for x in ref)
    diffs = [abs(x - y) for x, y in zip(ref, got)]
    within = all(
        d <= 2e-3 * scale + 2e-3 * abs(x) for d, x in zip(diffs, ref)
    )
    return {
        "tp_a": a["tp"], "tp_b": b["tp"], "n": len(ref),
        "max_abs_diff": max(diffs), "ref_abs_max": scale,
        "oracle_atol": 2e-3 * scale, "within_oracle_tolerance": within,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("smoke")
    p.add_argument("--tp", type=int, default=4)
    p = sub.add_parser("logprobs")
    p.add_argument("--tp", type=int, required=True)
    p.add_argument("--out", required=True)
    p = sub.add_parser("compare")
    p.add_argument("a")
    p.add_argument("b")
    args = ap.parse_args()
    try:
        if args.cmd == "smoke":
            report = cmd_smoke(args.tp)
        elif args.cmd == "logprobs":
            report = cmd_logprobs(args.tp, args.out)
        else:
            report = cmd_compare(args.a, args.b)
    except cs.SmokeFailure as e:
        print(f"tpu_tp_check: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, args.cmd: report}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
