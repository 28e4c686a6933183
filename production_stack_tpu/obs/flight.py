"""Engine flight recorder: an always-on ring of step-loop records.

The *write side* of deep performance introspection (docs/observability.md
"Flight recorder"). PR 3's ``/debug/requests`` answers "what happened to
THIS request"; the flight recorder answers the question a lone tail
outlier leaves open — "what exactly was the engine doing when that p99
outlier happened, and what held it?" One fixed-size record a cycle of the
step loop (one ``pst.intake`` and the ``pst.step`` after it), carried by
the cycle's last dispatch: step kind, padded batch bucket, the service
time of the programs the cycle saw ready (``device_s``), the host gap that
preceded it, queue depths, KV occupancy, preemption count, tenant tier mix,
whether a dispatch absorbed an XLA compile — and the cycle's own account:
its wall, the six phase sums, the step thread's off-CPU and CPU time, the
process's CPU time, collection pauses, how the fetch polled, and of the
program it fetched the service time and how long it stood behind others.

Design constraints, in order:

- **Always on.** The ring is a preallocated list of ``capacity`` slots
  written round-robin under a tiny lock — no allocation grows with
  uptime, and the per-cycle cost is one tuple build + one list store.
- **Post-mortem by construction.** A cycle whose wall, less what the
  program it fetched stood behind others inside it (``queued_s``: a decode
  step launched behind a long prefill program waits that program out, and
  nothing stalled), is past the bar (``outlier_factor`` × the rolling
  median of the same of its ``(kind, bucket)`` and of whether it fetched,
  floored and armed as below) is a *stall*: the
  recorder names its cause (:func:`stall_cause`, a pure function of the
  record) and snapshots the ring — so any such event leaves a trace
  naming the stalled step's bucket, queue state and cause even if nobody
  was scraping. SIGTERM/fatal paths snapshot too (``engine/server.py``
  and ``engine/async_engine.py``).
- **Feed-forward, not call-site churn.** :class:`EngineTelemetry`
  already sees every dispatch and every phase; it hands the recorder one
  cycle when ``pst.step`` closes, and the engine supplies a state probe
  closure (scheduler depths + KV occupancy).

Served by ``GET /debug/flight`` (last-N or time-window) on the engine.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

# Record tuple layout (kept positional — a dict per step would allocate
# a hash table on the hot path; rows render to dicts only at read time).
_F_WALL = 0  # time.time() stamp (for ?window_s= and human output)

# The phases whose sums a cycle's record carries, in record order.
CYCLE_PHASES = (
    "intake", "schedule", "batch_build", "launch", "wait", "postprocess",
)
_DISPATCH_FIELDS = (
    "ts", "kind", "bucket", "device_s", "host_gap_s", "compiled",
    "waiting", "running", "swapped", "kv_occupancy", "preemptions",
    "batch_tier_rows", "tokens",
)
# What a record says of the cycle it closes; None on a dispatch that closed
# none (an embedding's encode, or a dispatch that was not its cycle's last).
CYCLE_FIELDS = (
    "cycle_s", *(p + "_s" for p in CYCLE_PHASES), "offcpu_s",
    "thread_cpu_s", "process_cpu_s", "gc_s", "polls", "poll_gap_max_s",
    "service_s", "queued_s",
)
FIELDS = _DISPATCH_FIELDS + CYCLE_FIELDS
_NO_CYCLE = (None,) * len(CYCLE_FIELDS)
# Where each phase's sum lies in a cycle's account (after cycle_s).
PHASE_AT = {name: i for i, name in enumerate(CYCLE_PHASES)}
_QUEUED_AT = CYCLE_FIELDS.index("queued_s") - 1

STALL_CAUSES = (
    "compile", "gc", "device", "machine", "interpreter", "host_work",
    "unknown",
)
# Polls of a fetch further apart than this were not the step thread's doing:
# it sleeps 0.3 ms between two.
_POLL_GAP_HELD_S = 0.02
# A reading of the process's CPU clock is good to one scheduler tick: the
# benchmark's host accounts CPU time in steps of 10 ms.
_CPU_TICK_S = 0.01


def stall_cause(rec: dict, excess_s: float) -> str:
    """What held a stalled cycle, from its record alone: the first that
    holds of

    - ``compile``: a dispatch of the cycle was its shape's first;
    - ``gc``: collections paused the process for half the excess or more;
    - ``device``: half the excess or more lies in ``wait``, the fetch
      polled at its pace throughout, and the service time of the program
      it fetched carries half the excess itself — the device, the runtime
      or the transfer stood still, not this thread, and not behind another
      program (a record from before ``service_s`` was kept is read as it
      was: by its wait);
    - ``machine``: the step thread was kept off the CPU (two polls far
      apart, or off-CPU time outside its waits, where it never sleeps) and
      the process's CPU time advanced by less than half the excess —
      nothing of this process ran: run queue, steal, a stopped process;
    - ``interpreter``: the same, and the process's CPU time did advance —
      another thread of this process ran while the step thread could not;
    - ``host_work``: the step thread's own CPU time outside its waits
      covers half the excess — its own Python ran long;
    - ``unknown``.

    The process's clock says ``machine`` or ``interpreter`` only where it
    lies a tick or more from the line between them (``_CPU_TICK_S``): a
    short stall whose reading a single tick could move to the other side
    is ``unknown``, not a coin's toss.
    """
    half = excess_s / 2.0
    if rec["compiled"]:
        return "compile"
    if rec["gc_s"] >= half:
        return "gc"
    if (min(rec["wait_s"], rec.get("service_s", rec["wait_s"])) >= half
            and rec["poll_gap_max_s"] < _POLL_GAP_HELD_S):
        return "device"
    if rec["poll_gap_max_s"] >= half or rec["offcpu_s"] >= half:
        if rec["process_cpu_s"] <= half - _CPU_TICK_S:
            return "machine"
        if rec["process_cpu_s"] >= half + _CPU_TICK_S:
            return "interpreter"
    if rec["thread_cpu_s"] >= half:
        return "host_work"
    return "unknown"


def _row_dict(row: tuple) -> dict:
    """A record as ``GET /debug/flight`` shows it; the cycle's account is
    rounded here, not on the step thread."""
    return dict(zip(FIELDS, (
        round(v, 6) if isinstance(v, float) else v for v in row)))


def load_snapshot_dir(path: str, limit: Optional[int] = None) -> List[dict]:
    """Read persisted snapshots back from a ``--flight-snapshot-dir``,
    oldest first. Filenames encode a monotone (time_ns, seq) pair so a
    lexical sort is chronological. Unparseable files are skipped — a
    snapshot half-written at SIGKILL must not poison the post-mortem.

    The recorder's restart load-back reads through this, and so can
    whoever collects a dead engine's snapshot dir."""
    snaps: List[dict] = []
    try:
        names = sorted(
            f for f in os.listdir(path)
            if f.startswith("flight_") and f.endswith(".json")
        )
    except OSError:
        return snaps
    if limit is not None and limit > 0:
        names = names[-limit:]
    for name in names:
        try:
            with open(os.path.join(path, name)) as f:
                snap = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(snap, dict):
            snap.setdefault("persisted_as", name)
            snaps.append(snap)
    return snaps


class FlightRecorder:
    """Bounded, thread-safe ring of step-loop records + stall snapshots.

    Written from the engine step thread (and executor threads for
    encode); read from the asyncio loop by ``GET /debug/flight``. The
    lock guards only the slot store / ring copy — never a device wait.
    """

    # Rolling per-bucket median window for the stall bar. Small on
    # purpose: the bar should track the CURRENT steady state (post-warmup
    # cycle times), not the whole process history.
    _MEDIAN_WINDOW = 64
    # Cycles below this never stall regardless of the median —
    # 3x a 2 ms CPU decode cycle is noise.
    _MIN_OUTLIER_S = 0.05
    # Buckets need this many samples before the bar arms (a fresh bucket's
    # first few cycles straddle cache effects).
    _MIN_SAMPLES = 8

    def __init__(
        self,
        capacity: int = 512,
        outlier_factor: float = 3.0,
        snapshot_keep: int = 8,
        snapshot_tail: int = 64,
        snapshot_dir: Optional[str] = None,
        snapshot_disk_keep: int = 32,
    ):
        self.capacity = max(int(capacity), 0)
        self.outlier_factor = float(outlier_factor)
        self._ring: List[Optional[tuple]] = [None] * self.capacity
        self._idx = 0
        self._total = 0
        self._lock = threading.Lock()
        self._snapshots: "deque[dict]" = deque(maxlen=max(snapshot_keep, 1))
        self._snapshot_tail = max(int(snapshot_tail), 1)
        # Snapshot persistence (--flight-snapshot-dir): every retained
        # snapshot is also written as one JSON file, bounded to
        # ``snapshot_disk_keep`` with oldest-first eviction, and loaded
        # back after a restart — the post-mortem survives the process.
        self.snapshot_dir = snapshot_dir or None
        self._snapshot_disk_keep = max(int(snapshot_disk_keep), 1)
        self._persist_seq = 0
        self._restored: List[dict] = []
        if self.snapshot_dir:
            try:
                os.makedirs(self.snapshot_dir, exist_ok=True)
            except OSError:
                self.snapshot_dir = None
            else:
                self._restored = load_snapshot_dir(
                    self.snapshot_dir, limit=self._snapshot_disk_keep
                )
        # ((kind, bucket, fetched) -> recent samples of cycle_s less
        # queued_s) for the rolling median.
        self._samples: Dict[Tuple[str, str, bool], "deque[float]"] = {}
        # Engine-supplied closure: () -> dict(waiting, running, swapped,
        # batch_tier_rows, kv_occupancy, preemptions). Must be cheap and
        # safe on the step thread.
        self._probe: Optional[Callable[[], dict]] = None
        # Host gap noted between steps: consumed by the next record.
        self._pending_gap = 0.0

    @property
    def enabled(self) -> bool:
        return self.capacity > 0

    def set_probe(self, probe: Optional[Callable[[], dict]]) -> None:
        self._probe = probe

    # -- write side (engine step thread) --------------------------------

    def note_host_gap(self, seconds: float) -> None:
        """The host gap closing at the NEXT decode dispatch; attached to
        that dispatch's record (EngineTelemetry.record_host_gap feeds
        this)."""
        self._pending_gap = max(float(seconds), 0.0)

    def _row(self, dispatch: tuple, cycle: tuple) -> tuple:
        kind, bucket, device_s, compiled, tokens = dispatch
        state: dict = {}
        if self._probe is not None:
            try:
                state = self._probe() or {}
            except Exception:  # noqa: BLE001 — telemetry must not kill steps
                state = {}
        gap, self._pending_gap = self._pending_gap, 0.0
        return (
            time.time(),
            kind,
            bucket,
            round(max(device_s, 0.0), 6),
            round(gap, 6),
            bool(compiled),
            int(state.get("waiting", 0)),
            int(state.get("running", 0)),
            int(state.get("swapped", 0)),
            round(float(state.get("kv_occupancy", 0.0)), 4),
            int(state.get("preemptions", 0)),
            int(state.get("batch_tier_rows", 0)),
            int(tokens),
        ) + cycle

    def _store_locked(self, row: tuple) -> None:
        self._ring[self._idx] = row
        self._idx = (self._idx + 1) % self.capacity
        self._total += 1

    def record_step(
        self,
        kind: str,
        bucket: str,
        device_s: float,
        *,
        compiled: bool = False,
        tokens: int = 0,
    ) -> None:
        """One dispatch that closes no cycle of the step loop (an
        embedding's encode on an executor thread): a record without the
        cycle's fields, held to no bar."""
        if not self.enabled:
            return
        row = self._row((kind, bucket, device_s, compiled, tokens), _NO_CYCLE)
        with self._lock:
            self._store_locked(row)

    def record_cycle(
        self, dispatches: list, cycle_s: float, account: tuple,
        kind: str = "", held_to_bar: bool = True,
    ) -> Optional[dict]:
        """One cycle of the step loop, when its ``pst.step`` closes.
        ``dispatches``: (kind, bucket, device_s, compiled, tokens) of each
        live dispatch of the cycle, in order; the last carries the cycle
        (a cycle that dispatched nothing is a record of its own, of the
        step's ``kind``). ``account``: the values of ``CYCLE_FIELDS`` after
        ``cycle_s``. Held to the bar, and kept for the median, is the
        cycle's wall less its ``queued_s``. Returns the stall (its
        snapshot's ``detail``) when the cycle passed the bar, else None. A
        cycle not ``held_to_bar`` (a profiler started under it) is
        recorded, sets no baseline and is no stall."""
        if not self.enabled:
            return None
        compiled = any(d[3] for d in dispatches)
        if not dispatches:
            dispatches = [(kind or "none", "", 0.0, False, 0)]
        *earlier, last = dispatches
        rows = [self._row(d, _NO_CYCLE) for d in earlier]
        last = (*last[:3], compiled, last[4])
        rows.append(self._row(last, (cycle_s, *account)))
        # Nothing at or under the floor is a stall: the median is looked up
        # only for the few cycles above it.
        held = cycle_s - account[_QUEUED_AT]
        slow = compiled or held > self._MIN_OUTLIER_S
        median = None
        with self._lock:
            for row in rows:
                self._store_locked(row)
            if not held_to_bar:
                return None
            # A cycle that fetched is held against cycles that fetched: a
            # prompt's inner chunks are launched and left (a few ms of
            # host), its last waits for the device.
            key = (last[0], last[1], account[PHASE_AT["wait"]] > 0)
            dq = self._samples.get(key)
            if dq is None:
                dq = self._samples[key] = deque(maxlen=self._MEDIAN_WINDOW)
            # Compile-bearing cycles are architecture, not steady state:
            # they set no baseline (and ARE flagged via `compiled`).
            if not compiled:
                if slow and len(dq) >= self._MIN_SAMPLES:
                    median = sorted(dq)[len(dq) // 2]
                dq.append(held)
        if not slow:
            return None
        if compiled:
            bar = self._MIN_OUTLIER_S
        elif median is not None:
            bar = max(median * self.outlier_factor, self._MIN_OUTLIER_S)
        else:
            return None
        if held <= bar:
            return None
        rec = _row_dict(rows[-1])
        excess = held - (median or 0.0)
        detail = dict(
            rec,
            cause=stall_cause(rec, excess),
            # the phase that holds most of the cycle
            phase=max(CYCLE_PHASES, key=lambda p: rec[p + "_s"]),
            excess_s=round(excess, 6),
            median_s=round(median, 6) if median is not None else None,
            bar_s=round(bar, 6),
        )
        self.snapshot("compile" if compiled else "tail_outlier", detail)
        return detail

    # -- read side -------------------------------------------------------

    def _rows_locked(self) -> List[tuple]:
        """Chronological copy of the live ring (oldest first)."""
        if self._total < self.capacity:
            rows = self._ring[: self._idx]
        else:
            rows = self._ring[self._idx:] + self._ring[: self._idx]
        return [r for r in rows if r is not None]

    def records(
        self, n: Optional[int] = None, window_s: Optional[float] = None
    ) -> List[dict]:
        with self._lock:
            rows = self._rows_locked()
        if window_s is not None and window_s > 0:
            cutoff = time.time() - window_s
            rows = [r for r in rows if r[_F_WALL] >= cutoff]
        if n is not None and n > 0:
            rows = rows[-n:]
        return [_row_dict(r) for r in rows]

    def snapshot(self, reason: str, detail: Optional[dict] = None) -> dict:
        """Freeze the ring tail as a post-mortem and retain it (bounded).

        Returns the snapshot so shutdown paths can also log it. The tail
        (not the whole ring) keeps SIGTERM dumps one log line, not a MB.
        """
        with self._lock:
            rows = self._rows_locked()[-self._snapshot_tail:]
            snap = {
                "reason": reason,
                "ts": time.time(),
                "detail": detail or {},
                "total_steps": self._total,
                "records": [_row_dict(r) for r in rows],
            }
            self._snapshots.append(snap)
        self._persist(snap)
        return snap

    def _persist(self, snap: dict) -> None:
        """Write one snapshot file (atomic rename) and evict beyond the
        disk bound, oldest first. Disk I/O stays off the ring lock; any
        failure downgrades to in-memory-only retention."""
        d = self.snapshot_dir
        if not d:
            return
        with self._lock:
            self._persist_seq += 1
            seq = self._persist_seq
        name = f"flight_{time.time_ns():020d}_{seq:06d}_{snap['reason']}.json"
        try:
            tmp = os.path.join(d, name + ".tmp")
            with open(tmp, "w") as f:
                json.dump(snap, f)
            os.replace(tmp, os.path.join(d, name))
            stale = sorted(
                f for f in os.listdir(d)
                if f.startswith("flight_") and f.endswith(".json")
            )[: -self._snapshot_disk_keep]
            for old in stale:
                try:
                    os.remove(os.path.join(d, old))
                except OSError:
                    pass
        except OSError:
            return
        try:
            from .metrics import note_flight_snapshot_persisted

            note_flight_snapshot_persisted()
        except Exception:  # noqa: BLE001 — metrics must not kill snapshots
            pass

    def restored_snapshots(self) -> List[dict]:
        """Snapshots a previous process persisted to the snapshot dir,
        loaded at construction (``GET /debug/flight?snapshots=1``)."""
        return list(self._restored)

    def snapshots(self) -> List[dict]:
        with self._lock:
            return list(self._snapshots)

    def stats(self) -> dict:
        with self._lock:
            return {
                "capacity": self.capacity,
                "total_steps": self._total,
                "resident": min(self._total, self.capacity),
                "snapshots": len(self._snapshots),
            }

    def to_payload(
        self,
        n: Optional[int] = None,
        window_s: Optional[float] = None,
        include_restored: bool = False,
    ) -> dict:
        """The ``GET /debug/flight`` response body. ``include_restored``
        (the ``?snapshots=1`` query) adds snapshots persisted by a
        previous process to this snapshot dir — the post-mortem read
        after a restart."""
        payload = {
            **self.stats(),
            "fields": list(FIELDS),
            "records": self.records(n=n, window_s=window_s),
            "snapshot_log": self.snapshots(),
        }
        if include_restored:
            payload["restored_snapshots"] = self.restored_snapshots()
            payload["snapshot_dir"] = self.snapshot_dir
        return payload

    def reset_for_tests(self) -> None:
        with self._lock:
            self._ring = [None] * self.capacity
            self._idx = 0
            self._total = 0
            self._snapshots.clear()
            self._samples.clear()
            self._pending_gap = 0.0


class _NullFlightRecorder(FlightRecorder):
    """``--flight-buffer 0``: every write is a no-op, reads are empty."""

    def __init__(self):
        super().__init__(capacity=0)


NULL_FLIGHT_RECORDER = _NullFlightRecorder()
