#!/usr/bin/env python3
"""Child 1 of a benchmark run: the program's normal engine server, serving
one configuration file of the benchmark.

    python perf/launch_engine.py <config-file> <memory-file> <server flags…>

Builds the program's model config from the file's published keys with the
program's own ``config_from_hf_json``, registers it as a preset under the
configuration's name, and calls ``engine.server.main``: the normal server,
scheduler, cache and kernels. The model's weights come from the file's
``weights_seed`` (passed as the server's ``--seed``), never from the run's
seed, so every run of every check serves the same model.

On SIGUSR1 the process writes what JAX reports of its devices' memory
(``peak_bytes_in_use`` and friends) to ``<memory-file>``: only the process
that holds the chip can read it, and the program has no endpoint for the
peak.
"""

from __future__ import annotations

import json
import os
import signal
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _write_memory(path: str) -> None:
    import jax

    stats = []
    for d in jax.local_devices():
        s = d.memory_stats() or {}
        stats.append({
            "id": d.id, "platform": d.platform, "kind": d.device_kind,
            "peak_bytes_in_use": s.get("peak_bytes_in_use"),
            "bytes_in_use": s.get("bytes_in_use"),
            "bytes_limit": s.get("bytes_limit"),
        })
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(stats, f)
    os.replace(tmp, path)


def main(argv) -> int:
    if len(argv) < 3:
        print(__doc__, file=sys.stderr)
        return 2
    from perf import config as configs

    cfg = configs.load(argv[1])
    memory_file = argv[2]
    from production_stack_tpu.models import registry

    registry.PRESETS[cfg.name] = configs.program_model_config(cfg)
    signal.signal(signal.SIGUSR1, lambda *_: _write_memory(memory_file))
    from production_stack_tpu.engine import server

    server.main([
        "--model", cfg.name, "--seed", str(cfg.weights_seed),
        *cfg.engine_flags, *argv[3:],
    ])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
