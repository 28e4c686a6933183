"""Test harness config: force an 8-device virtual CPU mesh before JAX loads.

Mirrors the reference's "multi-node without a real cluster" testing strategy
(SURVEY.md §4): all sharding/multi-chip tests run on virtual CPU devices.
"""

import os
import sys

# Tests are hermetic on the virtual 8-device CPU mesh: the engine runs on
# the CPU only when JAX_PLATFORMS=cpu says so (production_stack_tpu/device.py),
# Pallas kernels run interpreted there, and PST_FORCE_PALLAS_INTERPRET opts
# the int4 matmul into its (interpreted) kernel. Both variables and XLA_FLAGS
# must be set before jax initializes its backend.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("PST_FORCE_PALLAS_INTERPRET", "1")

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

# A program is compiled once: jax's persistent compilation cache, at one
# fixed path in the checkout (git-ignored) that the xdist workers and the
# engine processes the tests start share through the environment, which
# ``engine/precompile.py::configure_compile_cache`` honours. An entry is
# keyed by the program's text, jax's version and the compile options, so an
# old entry is never a wrong one and a second run on the same copy starts
# warm. Most of what the model files' engines compile, another engine of
# the same settings compiled before. The thresholds keep every program,
# however quickly it compiled. Tests that place a cache of their own take
# the variable out first (``tests/test_precompile.py``).
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.join(_ROOT, ".jax_cache", "tests"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def event_loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


# Fast/slow rings. Modules that start several jax processes, build C++ or
# were never timed under the tier-1 command are `slow`; everything else,
# the engine's own tests included, is `fast` and runs in tier 1
# (`-m 'not slow'`). Per-test markers override the file default.
_SLOW_FILES = {
    "test_cross_encoder.py",
    "test_disagg_prefill.py",
    "test_multihost.py",
    "test_operator.py",  # C++ build (plain + TSAN) on first run
    "test_ring_attention.py",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if any(m.name in ("fast", "slow") for m in item.iter_markers()):
            continue
        fname = os.path.basename(str(item.fspath))
        item.add_marker(
            pytest.mark.slow if fname in _SLOW_FILES else pytest.mark.fast
        )


def pytest_pyfunc_call(pyfuncitem):
    """Minimal asyncio test support (pytest-asyncio may be absent).

    If the test requested the ``event_loop`` fixture, the coroutine runs on
    that same loop so callbacks scheduled through the fixture fire correctly.
    """
    func = pyfuncitem.function
    if inspect.iscoroutinefunction(func):
        sig = inspect.signature(func)
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in sig.parameters
            if name in pyfuncitem.funcargs
        }
        loop = pyfuncitem.funcargs.get("event_loop")
        own_loop = loop is None
        if own_loop:
            loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(func(**kwargs))
        finally:
            if own_loop:
                loop.close()
        return True
    return None
