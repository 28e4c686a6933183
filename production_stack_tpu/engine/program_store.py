"""Step programs kept in their compiled form, in the process and on disk.

A step shape's first use in a process costs its program's tracing, its
lowering (kernels' bodies included) and a cache key before XLA's persistent
cache can answer: one to four seconds of the step thread's Python for work
whose result the last process already had. The runner calls every jitted
step dispatch through one :class:`StepPrograms`, which keeps the compiled
program of each shape key; where a :class:`ProgramStore` is placed (a
``programs/`` directory inside the compile cache, see :func:`open_store`) a
shape the process meets first is loaded from it, and one the store lacks is
traced, lowered and compiled once, then written there. The store is then
the one place a step program is kept: it is built past XLA's persistent
cache (:func:`_xla_cache_bypassed`), which would hold a second copy that
nothing reads again.

What names an entry is the whole of what decides the program
(:func:`wiring_digest` and :func:`program_name`): a stale program is a
silently wrong server. An entry is never trusted: one that does not read,
does not load or refuses its arguments is dropped, counted and rebuilt. The
directory can be deleted at any time, and bounds itself: a file carries its
wiring's prefix, and a store that opens removes the entries of other wirings
(another image, configuration or device) that nothing loaded for
``STALE_AFTER_S``.

The serialised form is ``jax.experimental.serialize_executable``'s: a pickle
that carries the compiled executable. Whoever can write the cache volume
could already replace XLA's own entries; it deserves the same care.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import importlib.metadata
import os
import pickle
import tempfile
import threading
import time
import zlib
from typing import Any, Callable, Dict, Optional

import jax
from jax._src import compilation_cache
from jax.experimental import serialize_executable

from ..logging_utils import init_logger
from ..obs.engine_telemetry import ENGINE_TELEMETRY

logger = init_logger(__name__)

PROGRAMS_DIR = "programs"
_SUFFIX = ".z"
_FORMAT = 1
# An entry of another wiring that was last loaded (or written) this long ago
# is removed when a store opens: two weeks keeps a rollback's programs.
STALE_AFTER_S = 14 * 86400.0
_PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Fields of EngineConfig that say where a deployment's things are, not what
# any program is: a replaced replica has another address and the same
# programs. Every other field names an entry, a new one included.
PLACEMENT_FIELDS = frozenset({
    "tokenizer", "served_model_name", "compile_cache_dir", "remote_kv_url",
    "cache_controller_url", "engine_url", "lora_dir", "flight_snapshot_dir",
})


def source_digest() -> str:
    """A digest of the contents of every ``.py`` file of the package (10 ms
    for its 1.8 MB): the version string does not change with the code."""
    root = _PACKAGE_ROOT
    h = hashlib.sha256()
    for folder, dirs, files in os.walk(root):
        dirs.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                h.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as f:
                    h.update(hashlib.sha256(f.read()).digest())
    return h.hexdigest()


def _versions() -> Dict[str, str]:
    out = {}
    for package in ("jax", "jaxlib", "libtpu"):
        try:
            out[package] = importlib.metadata.version(package)
        except importlib.metadata.PackageNotFoundError:
            out[package] = ""
    return out


def wiring_digest(cfg, model_cfg, mesh, resolved: Dict[str, Any]) -> str:
    """What every program of one runner shares, hashed once when it is
    wired: the engine's and the model's configuration, the code, the
    versions underneath, the devices, and the environment and ``jax.config``
    values that tracing, lowering and compiling read. ``resolved``: what the
    runner made of ``auto`` settings and sizes it computed itself."""
    devices = [(d.id, d.device_kind, d.process_index)
               for d in mesh.devices.flat]
    parts = {
        "format": _FORMAT,
        "engine": sorted(
            (k, repr(v)) for k, v in dataclasses.asdict(cfg).items()
            if k not in PLACEMENT_FIELDS),
        "model": (type(model_cfg).__name__, repr(model_cfg)),
        "resolved": sorted((k, repr(v)) for k, v in resolved.items()),
        "source": source_digest(),
        "versions": _versions(),
        "platform": (devices[0][1], len(jax.devices()),
                     mesh.devices.flat[0].client.platform_version),
        "mesh": (tuple(mesh.shape.items()), devices),
        "env": sorted(
            (k, v) for k, v in os.environ.items()
            if k in ("XLA_FLAGS", "LIBTPU_INIT_ARGS") or k.startswith("PST_")),
        # every value, the cache's own place apart: jax's cache key reads a
        # handful and tracing reads others; a value that changed names
        # another program
        "jax_config": sorted(
            (k, repr(v)) for k, v in jax.config.values.items()
            if k != "jax_compilation_cache_dir"),
    }
    return hashlib.sha256(repr(sorted(parts.items())).encode()).hexdigest()


def program_name(wiring: str, jitted, args: tuple, static: tuple) -> str:
    """The entry of ``jitted`` on ``args`` (arrays as it will be called)
    and ``static``: the wiring, the function, the static flags, and the
    tree, shape, dtype and sharding of every argument: ``params`` and the
    cache with their quantised leaves and ``num_kv_blocks``, the batch with
    its padded shapes. Made at a shape's first use only (under a
    millisecond); no step hashes anything."""
    leaves, tree = jax.tree_util.tree_flatten(args)
    shardings: Dict[Any, str] = {}  # a tree's leaves share a few

    def signature(x) -> str:
        sharding = getattr(x, "sharding", None)
        if sharding not in shardings:
            shardings[sharding] = repr(sharding)
        return (f"{tuple(x.shape)}|{x.dtype}|{getattr(x, 'weak_type', False)}"
                f"|{getattr(x, '_committed', None)}|{shardings[sharding]}")

    h = hashlib.sha256()
    for part in (wiring, getattr(jitted, "__name__", "?"), repr(static),
                 str(tree), *map(signature, leaves)):
        h.update(part.encode())
        h.update(b"\0")
    return h.hexdigest()[:40]


_bypass_lock = threading.Lock()


@contextlib.contextmanager
def _xla_cache_bypassed():
    """Compile without XLA's persistent cache: what is built here goes into
    the store, and the cache would keep a second copy of it (2-2.5 MB a
    program on the chip) that no process reads again. It also keeps what is
    serialised an executable the compiler made: XLA:CPU serialises one its
    own cache gave into a program that loads and then fails its first call
    (jax 0.9.0). jax decides once a process whether its cache is used and
    has no switch for one compile, so its decision is held at "no"
    meanwhile; one build at a time, whoever builds."""
    cc = compilation_cache
    with _bypass_lock:
        was = cc._cache_checked, cc._cache_used
        cc._cache_checked, cc._cache_used = True, False
        try:
            yield
        finally:
            cc._cache_checked, cc._cache_used = was


class ProgramStore:
    """The directory of stored programs: one file an entry, named by its
    wiring's prefix and the entry's name, written under a temporary name
    and renamed, compressed."""

    def __init__(self, path: str, wiring: str, devices):
        self.path = path
        self.wiring = wiring
        self._prefix = wiring[:16] + "-"
        self._devices = list(devices)
        os.makedirs(path, exist_ok=True)
        self._sweep()

    def _file(self, name: str) -> str:
        return os.path.join(self.path, self._prefix + name + _SUFFIX)

    def _sweep(self) -> None:
        """Remove what no wiring that is still started has use for: files
        of other wirings (and writers' temporary files) untouched for
        ``STALE_AFTER_S``. A load touches its entry."""
        horizon = time.time() - STALE_AFTER_S
        removed = 0
        try:
            with os.scandir(self.path) as found:
                for entry in found:
                    if entry.name.startswith(self._prefix):
                        continue
                    try:
                        if entry.stat().st_mtime < horizon:
                            os.unlink(entry.path)
                            removed += 1
                    except OSError:
                        pass  # another process swept it, or a volume to read
        except OSError:
            return
        if removed:
            logger.info("program store: removed %d entries of other wirings, "
                        "unused for %d days", removed, STALE_AFTER_S // 86400)

    def load(self, name: str) -> Optional[Callable]:
        """The stored program, loaded onto the devices; None where the
        store has none, or had one that did not read or load (rejected)."""
        try:
            with open(self._file(name), "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            return None
        except OSError as e:
            self.reject(name, f"did not read: {e}")
            return None
        try:
            entry = pickle.loads(zlib.decompress(blob))
            if entry["format"] != _FORMAT or entry["name"] != name:
                raise ValueError("another entry's contents")
            program = serialize_executable.deserialize_and_load(
                entry["executable"], entry["in_tree"], entry["out_tree"],
                backend=self._devices[0].client,
                execution_devices=self._devices)
        except Exception as e:  # noqa: BLE001 — whatever a bad entry raises
            self.reject(name, f"did not load: {type(e).__name__}: {e}")
            return None
        try:
            os.utime(self._file(name))  # used now: `_sweep` goes by this
        except OSError:
            pass  # a volume to read only: the entry is as good
        return program

    def write(self, name: str, compiled) -> None:
        """Keep ``compiled`` under ``name``. A store that cannot be written
        costs the next process its tracing, never this one a request."""
        try:
            executable, in_tree, out_tree = serialize_executable.serialize(
                compiled)
            blob = zlib.compress(pickle.dumps({
                "format": _FORMAT, "name": name, "executable": executable,
                "in_tree": in_tree, "out_tree": out_tree}), 1)
            fd, tmp = tempfile.mkstemp(dir=self.path, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, self._file(name))
            except BaseException:
                os.unlink(tmp)
                raise
        except Exception as e:  # noqa: BLE001
            logger.warning("program store: %s was not written: %s: %s",
                           name, type(e).__name__, e)

    def reject(self, name: str, why: str, drop: bool = True) -> None:
        """Count and log an entry that was no use; ``drop``: and remove it,
        so that what is built next takes its place."""
        ENGINE_TELEMETRY.program_rejected()
        logger.warning("program store: entry %s rejected, %s", name, why)
        if drop:
            try:
                os.unlink(self._file(name))
            except OSError:
                pass


def open_store(cache_path: Optional[str], cfg, model_cfg, mesh,
               resolved: Dict[str, Any]) -> Optional[ProgramStore]:
    """The store beside the compile cache at ``cache_path`` (what
    ``configure_compile_cache`` returned), or None: where no cache is
    placed; in a process of several (the followers replay the primary's
    dispatches through the jit objects); and on the CPU unless the engine's
    own configuration placed the cache (its loader logs two multi-KB lines
    a load, and the tests' shared cache is placed through the variable)."""
    if not cache_path or jax.process_count() > 1:
        return None
    if jax.default_backend() == "cpu" and not cfg.compile_cache_dir:
        return None
    path = os.path.join(cache_path, PROGRAMS_DIR)
    try:
        store = ProgramStore(
            path, wiring_digest(cfg, model_cfg, mesh, resolved),
            mesh.devices.flat)
    except OSError as e:
        logger.warning("program store: none at %s: %s", path, e)
        return None
    logger.info("program store: %s (wiring %s)", path, store.wiring[:12])
    return store


class _Program:
    """A shape key's program in the process: the loaded or compiled
    executable under its entry's name, or (``name`` None) the jit itself."""

    __slots__ = ("call", "name")

    def __init__(self, call: Callable, name: Optional[str]):
        self.call = call
        self.name = name


class StepPrograms:
    """The holder every jitted step dispatch is called through: per shape
    key (the runner's ``_tel_key``: kind, padded shapes, static flags) the
    compiled program. One dictionary look-up a step. Live traffic,
    ``precompile()`` and the warm-up share it, as they share the jits.

    Without a store a key's program is the jit, traced at its first call as
    ever (XLA's persistent cache underneath); with one it is the stored
    executable, or the one built here and stored. ``key`` None (a multi-host follower's replay) calls the jit."""

    def __init__(self, store: Optional[ProgramStore] = None):
        self.store = store
        self._programs: Dict[tuple, _Program] = {}

    def call(self, key: Optional[tuple], jitted, args: tuple,
             static: tuple = ()):
        """``jitted(*args, *static)`` through ``key``'s program."""
        if key is None:
            return jitted(*args, *static)
        program = self._programs.get(key)
        if program is None:
            return self._first_use(key, jitted, args, static)
        try:
            return program.call(*args)
        except (TypeError, ValueError) as e:
            # A compiled program checks its arguments before it runs (and
            # before anything is donated) and takes them more strictly than
            # the jit: the key's later calls go through the jit.
            if program.name is None:
                raise
            # The entry served this key's first call: what differs now is
            # what this process hands it, so the entry stays where it is.
            self.store.reject(
                program.name, f"refused a later call's arguments: {e}",
                drop=False)
            program = self._programs[key] = _traced(jitted, static)
            return program.call(*args)

    def _first_use(self, key, jitted, args, static):
        with ENGINE_TELEMETRY.program_first_use() as use:
            if self.store is None:
                program = _traced(jitted, static)
                out = program.call(*args)
            else:
                program, out = self._from_store(jitted, args, static, use)
            self._programs[key] = program
            return out

    def _from_store(self, jitted, args, static, use):
        store = self.store
        name = program_name(store.wiring, jitted, args, static)
        t0 = time.perf_counter()
        loaded = store.load(name)
        use.seconds["load"] = time.perf_counter() - t0
        if loaded is not None:
            try:
                out = loaded(*args)
            except (TypeError, ValueError) as e:
                store.reject(name, f"refused its arguments: {e}")
            else:
                use.outcome = "loaded"
                return _Program(loaded, name), out
        with _xla_cache_bypassed():
            built = jitted.lower(*args, *static).compile()
        use.outcome = "built"
        t0 = time.perf_counter()
        store.write(name, built)
        use.seconds["write"] = time.perf_counter() - t0
        return _Program(built, name), built(*args)


def _traced(jitted, static: tuple) -> _Program:
    if not static:
        return _Program(jitted, None)
    return _Program(lambda *args: jitted(*args, *static), None)
