#!/usr/bin/env python3
"""What outlives a benchmark run that is cut from outside (PR 46): start
`perf/run.py` of one cell, send it SIGTERM at a named point, and list the
processes of the run that are still there afterwards.

    chiprun --timeout 1800 -- python3 scripts/tpu_cut_probe.py <tag> <cell> \\
        [--cwd <checkout>] <point>:<seed> [<point>:<seed> ...]

`<point>` is `setup` (the engine child is loading), `window` (15 s after
the warm-up's last pass was reported), `reference` (the reference child has
appeared) or `whole` (no cut: the run's own end). After the cut, or the end,
the processes whose command line names `launch_engine`, `engine.server` or
`perf/reference/run.py` are listed at once and again every 5 s for
`--linger` seconds (default 60), then whatever is left is killed so that the
next run starts clean. One JSON row a run on standard output, and the table
in `chiprun_out/<tag>/cuts.json`. Never imports jax.

`perf/run.py` installs no handler for SIGTERM and `perf/harness.py::Child`
starts each child in a session of its own, so a cut leaves the children to
themselves: an engine whose configuration passes `--exit-with-parent`
(`engine/server.py`) ends itself within a poll of its parent's death; the
reference child computes to its end and exits (it serves nothing)."""
import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = ("launch_engine", "engine.server", "perf/reference/run.py")


def left() -> list:
    """[(pid, command line)] of the run's kinds of process that are alive."""
    out = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
            with open(f"/proc/{pid}/stat") as f:
                state = f.read().rsplit(")", 1)[1].split()[0]
        except OSError:
            continue
        if state != "Z" and any(n in cmd for n in NAMES) \
                and "tpu_cut_probe" not in cmd:
            out.append((int(pid), cmd[:160]))
    return out


def wait_for(proc, predicate, timeout: float) -> bool:
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end and proc.poll() is None:
        if predicate():
            return True
        time.sleep(0.5)
    return False


def main(argv: list) -> int:
    cwd, linger = ROOT, 60.0
    while argv and argv[0] in ("--cwd", "--linger"):
        if argv[0] == "--cwd":
            cwd = os.path.abspath(argv[1])
        else:
            linger = float(argv[1])
        argv = argv[2:]
    tag, cell, runs = argv[0], argv[1], argv[2:]
    out_root = os.path.join(ROOT, "chiprun_out", tag)
    os.makedirs(out_root, exist_ok=True)
    table = []
    for n, spec in enumerate(runs):
        point, seed = spec.split(":")
        run_out = os.path.join(cwd, "perf_out", f"cut.{n}.{point}")
        log_path = os.path.join(out_root, f"run{n}.{point}.log.txt")
        t0 = time.monotonic()
        with open(log_path, "w") as log:
            proc = subprocess.Popen(
                [sys.executable, "perf/run.py", "--workload", cell, "--seed",
                 seed, "--seconds", "50", "--trace", "0", "--out", run_out],
                cwd=cwd, stdout=log, stderr=subprocess.STDOUT)

            def logged(text: str) -> bool:
                with open(log_path, errors="replace") as f:
                    return text in f.read()

            reached = True
            if point == "setup":
                reached = wait_for(proc, lambda: any(
                    "launch_engine" in c for _, c in left()), 120)
                time.sleep(25)
            elif point == "window":
                reached = wait_for(proc, lambda: logged("warm-up pass"), 1500)
                time.sleep(15)
            elif point == "reference":
                reached = wait_for(proc, lambda: any(
                    "perf/reference/run.py" in c for _, c in left()), 2400)
                time.sleep(5)
            if point != "whole" and proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
            try:
                rc = proc.wait(timeout=3000 if point == "whole" else 60)
            except subprocess.TimeoutExpired:
                proc.kill()
                rc = proc.wait()
        cut_s = time.monotonic() - t0
        seen, t_end = [], time.monotonic() + linger
        while True:
            now = left()
            seen.append([round(time.monotonic() - t0 - cut_s, 1),
                         [c for _, c in now]])
            if not now or time.monotonic() >= t_end:
                break
            time.sleep(5)
        for pid, _ in left():  # the next run starts clean
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        row = {"run": n, "point": point, "seed": seed, "reached": reached,
               "rc": rc, "ended_s": round(cut_s, 1),
               "left_at_once": seen[0][1], "left_last": seen[-1],
               "gone_after_s": next((t for t, c in seen if not c), None)}
        table.append(row)
        print(json.dumps(row), flush=True)
        with open(os.path.join(out_root, "cuts.json"), "w") as f:
            json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
