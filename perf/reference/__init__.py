"""The plain references, one module for each architecture's equations.

A configuration file names its own under the key ``reference``
(``perf/config.py``; absent, it is ``mistral``): the module
``perf/reference/<name>.py``, or ``<name>.py`` in a directory the caller
adds (the tests keep theirs beside their data). ``run.py``, child 2 of a
run, loads it and asks it for three things:

``VARIANTS``
    Names of the mathematics it can compute. The first is ``"none"``, the
    reference itself; the others each break one piece on purpose, for the
    negative controls of ``calibrate.py --negative``.
``weights(cfg)``
    The served model's parameters for ``cfg.weights_seed``, made once for
    all variants: ``weights.engine_params`` with the program's model
    object (its initialisers, nothing of its forward pass).
``teacher_force(cfg, params, sequences, variant)``
    For each of ``sequences`` (``{"tokens", "n_prompt", "want"}``; the
    generated positions are ``len(want)`` rows from ``n_prompt - 1``), the
    pair ``(logprobs [generated, vocab], gap [generated] or None)``:
    float32 log-probabilities over the vocabulary at the generated
    positions, and the router's smallest top-k / next gap over the layers
    where the model has a router. The module owns its padding sizes and its
    blocking.

A module imports nothing of the program's forward pass, and takes nothing
that the program has computed.
"""

from __future__ import annotations

import importlib
import importlib.util
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
INTERFACE = ("VARIANTS", "weights", "teacher_force")
_NAME = re.compile(r"^[A-Za-z0-9_]+$")


def find(name: str, extra_dirs=None) -> str:
    """The module's file: no fallback, the error names every file looked for."""
    if not _NAME.match(name):
        raise ValueError(f"reference {name!r}: a module's name, not a path")
    looked = [os.path.join(d, f"{name}.py")
              for d in list(extra_dirs or []) + [HERE]]
    for path in looked:
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(
        f"no reference module {name!r}: looked for {', '.join(looked)}")


def load(name: str, extra_dirs=None):
    path = find(name, extra_dirs)
    if os.path.dirname(path) == HERE:
        module = importlib.import_module(f"perf.reference.{name}")
    else:
        spec = importlib.util.spec_from_file_location(f"perf_reference_{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    missing = [a for a in INTERFACE if not hasattr(module, a)]
    if missing:
        raise TypeError(f"{path} is no reference module: it lacks {missing}")
    return module
