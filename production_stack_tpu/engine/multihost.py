"""Multi-host engine execution: primary broadcasts steps, followers mirror.

The reference runs multi-host engines as a Ray cluster — `vllm serve` on the
head, workers joined via Ray, NCCL moving tensors
(`helm/templates/ray-cluster.yaml:3-15,520,560-566`). TPU-native, a
multi-host engine is ONE jitted SPMD program over a mesh that spans hosts:
every process must enter the same XLA computation in the same order, and XLA
moves tensors over ICI/DCN. The only asymmetry is the control plane:

- **Host 0** (``is_primary()``): runs the scheduler, the HTTP server, and the
  KV bookkeeping. Before each device call, the logical batch (a dict of small
  numpy arrays) is published over the :class:`HostBridge`.
- **Other hosts**: run :func:`run_follower` — receive each step description
  and issue the identical device call on their mesh shard.

Everything device-side (params, KV pages, collectives) is already global via
the shared mesh; only step *descriptions* cross the control plane, and they
are tiny (the token ids and tables for one step).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..logging_utils import init_logger
from ..parallel.distributed import HostBridge, is_primary

logger = init_logger(__name__)


class StepPublisher:
    """Primary-side hook: mirrors every runner device call to the followers.

    Installed on the :class:`~production_stack_tpu.engine.runner.ModelRunner`
    as ``runner.publisher``; the runner calls :meth:`announce` immediately
    before each jitted dispatch, keeping all processes' XLA program order
    identical (a diverged order deadlocks the collectives — this ordering
    contract is the whole design).
    """

    def __init__(self, bridge: Optional[HostBridge] = None):
        self.bridge = bridge or HostBridge()

    def announce(self, kind: str, payload) -> None:
        self.bridge.publish((kind, payload))

    def shutdown(self) -> None:
        try:
            self.announce("shutdown", None)
        except Exception as e:  # noqa: BLE001 — best-effort at teardown
            logger.warning("follower shutdown broadcast failed: %s", e)


def run_follower(runner, bridge: Optional[HostBridge] = None) -> None:
    """Follower main loop: mirror the primary's device calls until shutdown.

    ``runner`` must be constructed identically to the primary's (same
    EngineConfig → same mesh, same seed/checkpoint → same params), which the
    deterministic construction guarantees.
    """
    import jax

    assert not is_primary(), "run_follower must not run on host 0"
    bridge = bridge or HostBridge()
    logger.info("follower loop up (process %d)", jax.process_index())
    while True:
        try:
            kind, payload = bridge.publish(None)  # blocks on host-0 broadcast
        except Exception:  # noqa: BLE001
            # Python-level broadcast failure (e.g. a payload that fails to
            # deserialize): exit so the pod restarts instead of wedging.
            # NOTE a DEAD PRIMARY does not reach this handler — the JAX
            # distributed runtime detects the lost coordinator and
            # hard-terminates the process at the C++ layer (fatal in
            # client.h), which equally gets the pod restarted; this except
            # covers the failures that stay inside Python. Traceback logged
            # so either class stays diagnosable.
            logger.error(
                "follower broadcast failed (primary lost?); exiting",
                exc_info=True,
            )
            return
        if kind == "shutdown":
            logger.info("follower shutting down")
            return
        if kind == "step":
            runner._dispatch_step(*payload)
        elif kind == "step_nofetch":
            runner._dispatch_step_nofetch(payload)
        elif kind == "multi_step":
            runner._dispatch_multi_step(*payload)
        elif kind == "encode":
            toks, length = payload
            runner._dispatch_encode(toks, length)
        elif kind == "download_page":
            runner._dispatch_download_page(int(payload))
        elif kind == "upload_page":
            blk, k_np, v_np = payload
            runner._dispatch_upload_page(int(blk), k_np, v_np)
        elif kind == "drop_kv":
            runner._dispatch_drop_kv()
        elif kind == "restore_kv":
            runner._dispatch_restore_kv()
        elif kind == "install_adapter":
            slot, arrays = payload
            runner._dispatch_install_adapter(int(slot), arrays)
        elif kind == "uninstall_adapter":
            runner._dispatch_uninstall_adapter(int(payload))
        elif kind == "burst_start":
            runner._dispatch_burst_start(*payload)
        elif kind == "burst_cont":
            runner._dispatch_burst_continue(*payload)
        elif kind == "warm_splice":
            runner._warm_splice(int(payload))
        elif kind == "spec_verify":
            runner._dispatch_spec_verify(payload)
        else:  # future-proof: unknown step kinds are fatal (order contract)
            raise RuntimeError(f"unknown multihost step kind: {kind!r}")


def make_follower_runner(cfg):
    """Build the runner exactly as the primary does (no scheduler/server)."""
    from .runner import ModelRunner

    return ModelRunner(cfg)
