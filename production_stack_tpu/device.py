"""What the process runs on, resolved once and never guessed.

Two ways this program runs, and nothing in between:

- on the chip: JAX's default backend is ``tpu``; Pallas kernels compile
  through Mosaic; attention and int4 matmuls resolve to the kernels.
- on the CPU, for tests: only when ``JAX_PLATFORMS=cpu`` is set
  explicitly; Pallas kernels run interpreted, ``auto`` attention is the
  gather reference, int4 uses the XLA dequant.

JAX itself drops to the CPU with a warning when TPU initialisation fails;
a serving engine that carried on there would report healthy and serve at
interpreter speed. :func:`resolve_platform` turns that into a start-up
error, and everything that used to assume a device when it could not
identify one (the HBM size that KV sizing starts from) reads
:data:`DEVICE_TABLE` or fails.
"""

from __future__ import annotations

import dataclasses
import importlib.metadata
import os
from typing import Dict, Optional

INTERPRET_ENV = "PST_FORCE_PALLAS_INTERPRET"


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    hbm_bytes: int


# What the engine needs to know of a chip, keyed by the exact
# ``device_kind`` string the backend reports. Source: Google Cloud
# documentation, "TPU v5e" system architecture — 16 GB HBM2e. A kind that
# is not here has no size: KV sizing then needs the backend's own
# ``bytes_limit``. The peaks a roofline divides by belong to the benchmark
# (``perf/peaks.json``). Add a row (with its source) when the program meets
# another chip; do not add a default.
DEVICE_TABLE: Dict[str, DeviceSpec] = {
    "TPU v5 lite": DeviceSpec(hbm_bytes=16 * 1024**3),
}


def require_device_spec(device_kind: Optional[str]) -> DeviceSpec:
    spec = DEVICE_TABLE.get(device_kind or "")
    if spec is None:
        raise RuntimeError(
            f"device_kind {device_kind!r} is not in DEVICE_TABLE "
            f"(production_stack_tpu/device.py; known: "
            f"{sorted(DEVICE_TABLE)}): refusing to assume another chip's "
            "memory — add a row with its source"
        )
    return spec


def resolve_platform() -> str:
    """The platform this process serves on: ``"tpu"`` or ``"cpu"``.

    ``cpu`` only when ``JAX_PLATFORMS=cpu`` asked for it; any other
    default backend that is not ``tpu`` (JAX's silent CPU fallback after a
    failed TPU init, a GPU) is an error. On ``tpu`` the interpret variable
    is refused: the kernels there always compile, and a process that
    inherited the variable would otherwise believe it was debugging."""
    import jax

    backend = jax.default_backend()
    asked = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if backend == "tpu":
        if os.environ.get(INTERPRET_ENV):
            raise RuntimeError(
                f"{INTERPRET_ENV} is set but the backend is tpu: interpret "
                "mode belongs to the CPU platform (JAX_PLATFORMS=cpu); "
                "unset it to run the compiled kernels"
            )
        return "tpu"
    if backend == "cpu" and asked == "cpu":
        return "cpu"
    raise RuntimeError(
        f"JAX default backend is {backend!r}, not 'tpu' "
        f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). The engine "
        "runs on the CPU only when JAX_PLATFORMS=cpu is set explicitly; "
        "otherwise no accelerator is a start-up error, not a fallback"
    )


def pallas_interpret() -> bool:
    """Pallas kernels compile on ``tpu`` and run interpreted everywhere
    else — decided by the backend, never by the environment."""
    import jax

    return jax.default_backend() != "tpu"


def _dist_version(name: str) -> Optional[str]:
    try:
        return importlib.metadata.version(name)
    except importlib.metadata.PackageNotFoundError:
        return None


def describe_devices() -> dict:
    """Platform, device and library versions as JAX reports them."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "jax": jax.__version__,
        "jaxlib": _dist_version("jaxlib"),
        "libtpu": _dist_version("libtpu"),
    }
