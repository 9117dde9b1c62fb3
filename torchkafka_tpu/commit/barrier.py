"""Pod-wide commit barrier.

Replaces the reference's POSIX-signal control plane (SIGUSR1 "commit now"
from orchestrator to worker, /root/reference/src/kafka_dataset.py:47-55,235-239;
/root/reference/src/auto_commit.py:59-72) with a first-class barrier:

1. wait for the step's device work to retire locally (jax.block_until_ready),
2. synchronize every process in the pod over ICI/DCN
   (multihost_utils.sync_global_devices),
3. only then is the commit allowed to proceed.

Fail-closed: if any host dies, the barrier raises on the survivors instead of
timing out silently; no host commits, Kafka re-delivers the batch — the zero
uncommitted-batch-loss property (SURVEY.md §7 hard part (c)). The signal-race
class the reference handles with its deferred-flag dance (SURVEY.md §5 race
row) does not exist here: commits run synchronously on the host's own thread,
never from an interrupt context.
"""

from __future__ import annotations

import logging
from typing import Any

import jax

from torchkafka_tpu.errors import BarrierError
from torchkafka_tpu.utils import tracing as xprof

logger = logging.getLogger(__name__)


class CommitBarrier:
    """Callable barrier used by CommitToken before offsets are committed.

    Single-process (the degenerate case, SURVEY.md §7 minimum slice): only
    ``block_until_ready``. Multi-process: adds a pod-wide
    ``sync_global_devices`` with a per-call unique name so distinct batches
    can never alias each other's barrier.
    """

    def __init__(self, name: str = "tpukafka_commit", strict: bool = True) -> None:
        self._name = name
        self._calls = 0
        self._strict = strict

    def _retire(self, wait_for: Any) -> None:
        """Prove the step's device work is complete.

        ``block_until_ready`` plus — in strict mode — a one-scalar host
        fetch from the first array leaf. The fetch guarantees that a VALUE
        computed by the step has reached the host before its offsets
        become committable: a proof that does not rest on a backend's
        ``block_until_ready`` being exact. Committing offsets for a step
        that had not retired would break the at-least-once contract.
        Cost: one scalar D2H per batch.
        """
        with xprof.span(xprof.SPAN_COMMIT_WAIT):
            jax.block_until_ready(wait_for)
        if not self._strict:
            return
        with xprof.span(xprof.SPAN_COMMIT_FETCH):
            leaves = [
                leaf for leaf in jax.tree_util.tree_leaves(wait_for)
                if isinstance(leaf, jax.Array) and leaf.size > 0
            ]
            if leaves:
                jax.device_get(leaves[0].ravel()[0])

    def __call__(self, wait_for: Any = None) -> None:
        try:
            if wait_for is not None:
                # Retire the step that consumed the batch: host-side proof the
                # batch's results exist before its offsets become committable
                # (the reference's yield-then-commit ordering,
                # /root/reference/src/auto_commit.py:55-58, made device-aware).
                self._retire(wait_for)
            self._calls += 1
            # Executed for real in tests/test_pod.py (spawned jax.distributed
            # processes) — the cross-process commit coordination path.
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils

                with xprof.span(xprof.SPAN_COMMIT_SYNC):
                    multihost_utils.sync_global_devices(
                        f"{self._name}:{self._calls}"
                    )
        except BarrierError:
            raise
        except Exception as e:
            # Fail closed: a barrier failure means we cannot prove every host
            # finished the step -> nobody commits -> Kafka re-delivers.
            raise BarrierError(f"commit barrier failed (no offsets committed): {e}") from e


#: Barrier that only waits for local device work — explicit single-host mode.
class LocalBarrier(CommitBarrier):
    def __call__(self, wait_for: Any = None) -> None:
        if wait_for is not None:
            self._retire(wait_for)
