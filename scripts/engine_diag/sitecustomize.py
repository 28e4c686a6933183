"""Diagnosis hook for stalls inside a benchmark window (PERF.md §7 (h)).

    PYTHONPATH=$PWD/scripts/engine_diag PST_DIAG_FILE=chiprun_out/diag/run1 \\
        python3 perf/run.py --workload <cell> ...

``perf/harness.py::child_env`` keeps ``PYTHONPATH``, so this module is
imported at the start of every child; it installs itself in the engine child
alone (``launch_engine.py`` on its command line) and writes to
``$PST_DIAG_FILE.<pid>``: every cyclic collection over 50 ms (each stops all
threads of the process), and every time a thread that sleeps 50 ms woke more
than 0.2 s late (the interpreter lock was held, or the whole process or
machine stood still). Nothing in the program imports it.
"""

import gc
import os
import sys
import threading
import time


def _install() -> None:
    try:
        with open("/proc/self/cmdline") as f:
            cmd = f.read().replace("\0", " ")
    except OSError:
        return
    if "launch_engine.py" not in cmd:
        return
    path = os.environ.get("PST_DIAG_FILE", "/tmp/pst_diag") + f".{os.getpid()}"
    out = open(path, "a", buffering=1)

    def log(msg: str) -> None:
        out.write(f"{time.time():.3f} {msg}\n")

    log("installed in: " + cmd[:200])
    started = [0.0]

    def on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            started[0] = time.monotonic()
        elif (dt := time.monotonic() - started[0]) > 0.05:
            log(f"gc generation {info['generation']} took {dt:.3f}s, "
                f"collected {info['collected']}")

    gc.callbacks.append(on_gc)

    def watchdog() -> None:
        while True:
            t = time.monotonic()
            time.sleep(0.05)
            if (lag := time.monotonic() - t - 0.05) > 0.2:
                log(f"watchdog woke {lag:.3f}s late")

    threading.Thread(target=watchdog, name="pst-diag", daemon=True).start()


try:
    _install()
except Exception as e:  # noqa: BLE001: a diagnosis must never stop the engine
    sys.stderr.write(f"engine_diag: {e!r}\n")
