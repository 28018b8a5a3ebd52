"""Per-worker training session (reference: ``train/_internal/session.py``
— ``_TrainSession.report`` :612; user API ``ray.train.report`` /
``get_context()``).

Workers call ``report(metrics, checkpoint=...)`` each epoch/interval;
results stream back to the trainer through a driver-owned results queue.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional

from .._private import telemetry
from ..util import tracing
from .checkpoint import Checkpoint

M_REPORT_SECONDS = telemetry.define(
    "histogram", "rtpu_train_report_seconds",
    "Seconds a train worker spent inside train.report (checkpoint "
    "metadata and the put into the results queue)")

_session_local = threading.local()


class TrainContext:
    def __init__(self, world_rank: int, world_size: int,
                 results_queue, latest_checkpoint: Optional[Checkpoint],
                 config: Optional[Dict[str, Any]] = None,
                 storage_path: Optional[str] = None,
                 experiment_name: str = "train",
                 dataset_shards: Optional[Dict[str, Any]] = None):
        self.world_rank = world_rank
        self.world_size = world_size
        self.results_queue = results_queue
        self.latest_checkpoint = latest_checkpoint
        self.config = config or {}
        self.storage_path = storage_path
        self.experiment_name = experiment_name
        self.dataset_shards = dataset_shards or {}
        self.iteration = 0

    def get_dataset_shard(self, name: str = "train"):
        """This worker's streaming shard of the trainer's ``datasets``
        (reference: ``ray.train.get_dataset_shard``); a
        ``data.DataIterator`` — iterate ``iter_device_batches(...)`` to
        feed the step function."""
        try:
            return self.dataset_shards[name]
        except KeyError:
            raise KeyError(
                f"no dataset {name!r} was passed to JaxTrainer(datasets=...)"
                f"; have {sorted(self.dataset_shards)}") from None

    # reference: ray.train.get_context() surface
    def get_world_rank(self) -> int:
        return self.world_rank

    def get_world_size(self) -> int:
        return self.world_size

    def get_local_rank(self) -> int:
        return self.world_rank   # one worker per host

    def get_trial_name(self) -> str:
        return self.experiment_name


def _set_session(ctx: Optional[TrainContext]) -> None:
    _session_local.ctx = ctx


def get_context() -> TrainContext:
    ctx = getattr(_session_local, "ctx", None)
    if ctx is None:
        raise RuntimeError(
            "not inside a train session (call from train_loop_per_worker)")
    return ctx


def report(metrics: Dict[str, Any],
           checkpoint: Optional[Checkpoint] = None) -> None:
    """Report metrics (and optionally a checkpoint) for this iteration.

    Rank 0's checkpoint is persisted; other ranks' checkpoints are
    ignored (TPU SPMD state is replicated or resharded on restore, so
    one host's copy suffices — pass fully-addressable trees).
    """
    ctx = get_context()
    ctx.iteration += 1
    with tracing.timed_span("train::report", M_REPORT_SECONDS):
        payload = {
            "rank": ctx.world_rank,
            "iteration": ctx.iteration,
            "metrics": dict(metrics),
            "checkpoint_path": None,
            # the driver reads how long the report waited for it
            # (`trainer._drain`; one host, so one clock)
            "reported_wall": time.time(),
        }
        if checkpoint is not None and ctx.world_rank == 0:
            with tracing.start_span("train::report_checkpoint"):
                checkpoint.set_metrics(metrics)
            payload["checkpoint_path"] = checkpoint.path
            ctx.latest_checkpoint = checkpoint
        with tracing.start_span("train::report_put"):
            ctx.results_queue.put(payload)


def get_checkpoint() -> Optional[Checkpoint]:
    """Latest checkpoint to resume from (set on restart after failure)."""
    return get_context().latest_checkpoint


def get_dataset_shard(name: str = "train"):
    """Module-level convenience (reference: ``ray.train.get_dataset_shard``)."""
    return get_context().get_dataset_shard(name)
