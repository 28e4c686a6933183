"""A configuration file: the published ``config.json`` keys at the top
level, beside the benchmark's own keys (``OWN_KEYS``, which every file has,
and ``OPTIONAL_KEYS``)."""

from __future__ import annotations

import dataclasses
import json
import os
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OWN_KEYS = (
    "source", "reduced", "published", "assumed", "deployment", "weights_seed",
    "engine_flags", "check",
)
# ``reference``: the module under ``perf/reference/`` that computes this
# architecture's plain reference (``perf/reference/__init__.py``).
OPTIONAL_KEYS = ("reference",)
DEFAULT_REFERENCE = "mistral"


@dataclasses.dataclass(frozen=True)
class Config:
    name: str
    path: str
    hf: dict  # the model's config.json as it is run
    engine_flags: tuple  # what defines the deployment, as server flags
    weights_seed: int
    check: dict  # delta / tau / tau_loose, with their reason
    reference: str  # the plain reference's module, by name
    raw: dict

    def flag(self, name: str):
        """Value of an engine flag, or None."""
        flags = list(self.engine_flags)
        return flags[flags.index(name) + 1] if name in flags else None


def load(path: str) -> Config:
    if not os.path.isabs(path):
        path = os.path.join(ROOT, path)
    with open(path) as f:
        raw = json.load(f)
    missing = [k for k in OWN_KEYS if k not in raw]
    if missing:
        raise ValueError(f"{path}: missing keys {missing}")
    return Config(
        name=os.path.splitext(os.path.basename(path))[0],
        path=path,
        hf={k: v for k, v in raw.items() if k not in OWN_KEYS + OPTIONAL_KEYS},
        engine_flags=tuple(str(x) for x in raw["engine_flags"]),
        weights_seed=int(raw["weights_seed"]),
        check=dict(raw["check"]),
        reference=str(raw.get("reference", DEFAULT_REFERENCE)),
        raw=raw,
    )


def program_model_config(cfg: Config):
    """The program's model config, built by its own reader of published
    key names (so a refactor of its dataclass breaks no configuration)."""
    from production_stack_tpu.models.llama import config_from_hf_json

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "config.json")
        with open(p, "w") as f:
            json.dump(cfg.hf, f)
        return config_from_hf_json(p, name=cfg.name)
