"""Cost functions, one module for each kernel: ``cost(call, hf, cfg)`` ->
``{"flops", "bytes", "peak"}`` or None (``perf/README.md``, "A cost
function"). A per-layer metric names its module under ``params.cost``; the
readers find it here, or in a directory the caller adds (the tests keep
theirs beside their data)."""

import importlib
import importlib.util
import os


def load(name: str, extra_dirs=None):
    for d in extra_dirs or []:
        path = os.path.join(d, f"{name}.py")
        if os.path.isfile(path):
            spec = importlib.util.spec_from_file_location(f"perf_cost_{name}", path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module
    return importlib.import_module(f"perf.cost.{name}")
