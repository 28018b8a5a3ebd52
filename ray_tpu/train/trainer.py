"""JaxTrainer — gang-scheduled SPMD training driver.

Reference flow (SURVEY §3.4): ``BaseTrainer.fit``
(``train/base_trainer.py:608``) → ``BackendExecutor``
(``_internal/backend_executor.py:46``) → ``WorkerGroup``
(``_internal/worker_group.py:101``) spawns N worker actors in a
placement-group gang, sets up a torch process group, runs
``train_loop_per_worker``, streams ``session.report`` results back.

TPU-native differences:
  * one worker per *host*, not per chip: the worker's process is granted
    the host's chips (`ScalingConfig(use_tpu=True)`) and is the only one
    that may open the TPU backend; inside it the user loop builds a
    `jax.sharding.Mesh` over the host's devices (`build_mesh`). Hosts are
    not stitched into one global mesh yet; no NCCL/TCPStore rendezvous.
  * parallelism is a mesh (a MeshSpec) built in the loop, not DDP/FSDP
    wrapper classes.
  * failure handling is checkpoint-based elastic restart: on worker
    death the whole gang restarts from the last reported checkpoint
    (SPMD programs can't lose a single participant).
"""

from __future__ import annotations

import os
import shutil
import time
from typing import Any, Callable, Dict, List, Optional

from .. import get, kill, wait
from .._private import context, telemetry
from ..api import remote
from ..exceptions import TaskError, WorkerCrashedError
from ..util.placement_group import placement_group, remove_placement_group
from ..util import tracing
from ..util.scheduling_strategies import PlacementGroupSchedulingStrategy
from .checkpoint import Checkpoint
from .config import (CheckpointConfig, FailureConfig, RunConfig,
                     ScalingConfig)
from .result import Result
from .session import TrainContext, _set_session

M_GANG_START = telemetry.define(
    "histogram", "rtpu_train_gang_start_seconds",
    "Seconds of one phase of a train worker's start, observed in the "
    "worker, in order and disjoint (one host: one clock): phase=spawn "
    "(the driver's first _TrainWorker.remote() until the worker process "
    "enters its main(): scheduling, process start, the runtime's imports; "
    "0 in a process that was already up), load (until __init__ is "
    "entered: registration, fetching and unpickling the actor class, which "
    "imports ray_tpu.train and jax; in a fresh process it is made up of "
    "rtpu_worker_start_seconds{phase=runtime} and {phase=first_task}, "
    "rtpu_worker_load_code_seconds{name=_TrainWorker} and the loading of "
    "__init__'s arguments), run_wait (until run() is entered: the driver "
    "splitting its datasets, the loop shipped and unpickled)",
    buckets=telemetry.LONG_BUCKETS)
M_REPORT_LAG = telemetry.define(
    "histogram", "rtpu_train_report_lag_seconds",
    "Seconds from a worker's train.report to the driver taking the "
    "report into the run's history (one host: one clock)",
    buckets=telemetry.LONG_BUCKETS)
M_CHECKPOINT_PERSIST = telemetry.define(
    "histogram", "rtpu_train_checkpoint_persist_seconds",
    "Seconds the driver spent copying one reported checkpoint into the "
    "experiment directory",
    buckets=telemetry.LONG_BUCKETS)


@remote
class _TrainWorker:
    """One gang member; executes the user loop under a session."""

    def __init__(self, rank: int, world_size: int, storage_path: str,
                 experiment_name: str, created_wall: float):
        self._entered_wall = time.time()
        # jax is imported by now (this module's own imports): whatever the
        # loop traces, lowers and compiles is counted from its first line
        telemetry.install_jax_listeners()
        self.rank = rank
        self.world_size = world_size
        self.storage_path = storage_path
        self.experiment_name = experiment_name
        # `created_wall`: the driver's clock before its first `.remote()`
        # (one host: one clock); a process that was up before it spawned in 0
        up = max(created_wall, context.worker_started_wall or 0.0)
        _observe_phase("spawn", up - created_wall)
        _observe_phase("load", self._entered_wall - up)

    def run(self, loop_fn: Callable, config: Dict[str, Any],
            results_queue, resume_ckpt_path: Optional[str],
            dataset_shards: Optional[Dict[str, Any]] = None):
        _observe_phase("run_wait", time.time() - self._entered_wall)
        resume = (Checkpoint(resume_ckpt_path)
                  if resume_ckpt_path else None)
        ctx = TrainContext(self.rank, self.world_size, results_queue,
                           resume, config=config,
                           storage_path=self.storage_path,
                           experiment_name=self.experiment_name,
                           dataset_shards=dataset_shards)
        _set_session(ctx)
        try:
            if _loop_takes_config(loop_fn):
                loop_fn(config)
            else:
                loop_fn()
        finally:
            _set_session(None)
            # before the call returns: the flush that follows TASK_DONE
            # races the gang's kill
            tracing.flush()
            telemetry.flush()
        return self.rank


def _observe_phase(phase: str, seconds: float) -> None:
    telemetry.hist_observe(M_GANG_START, seconds, (("phase", phase),))


def _loop_takes_config(fn: Callable) -> bool:
    import inspect
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False
    return len([p for p in params.values()
                if p.default is inspect.Parameter.empty
                and p.kind in (p.POSITIONAL_ONLY,
                               p.POSITIONAL_OR_KEYWORD)]) >= 1


class JaxTrainer:
    """Run ``train_loop_per_worker`` on a gang of host workers.

    train_loop_per_worker: callable taking (config) or (); uses
    ``ray_tpu.train.report`` / ``get_checkpoint`` / ``get_context``.
    """

    def __init__(self,
                 train_loop_per_worker: Callable,
                 *,
                 train_loop_config: Optional[Dict[str, Any]] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 datasets: Optional[Dict[str, Any]] = None):
        self._loop = train_loop_per_worker
        self._loop_config = train_loop_config or {}
        self._scaling = scaling_config or ScalingConfig()
        self._run_config = run_config or RunConfig()
        self._failure = self._run_config.failure_config or FailureConfig()
        self._ckpt_config = (self._run_config.checkpoint_config
                             or CheckpointConfig())
        # {name: ray_tpu.data.Dataset} — each split into one streaming
        # shard per worker at fit() (and again per elastic restart);
        # workers consume via session.get_dataset_shard(name)
        # (reference: Train datasets= + data_config.py streaming split)
        self._datasets = datasets or {}

    # ------------------------------------------------------------------ fit
    def fit(self) -> Result:
        from ..util.queue import Queue

        name = self._run_config.name or "jax_train"
        storage = self._run_config.storage_path or os.path.join(
            os.path.expanduser("~"), "rtpu_results")
        exp_dir = os.path.join(storage, name)
        os.makedirs(exp_dir, exist_ok=True)

        attempts = 0
        latest_ckpt: Optional[Checkpoint] = None
        history: List[Dict[str, Any]] = []
        last_metrics: Dict[str, Any] = {}
        saved_ckpts: List[str] = []
        error: Optional[Exception] = None

        while True:
            queue = Queue()
            gang = self._spawn_gang(name, storage)
            # fresh streaming shards per attempt: the pipeline re-executes
            # from the start on an elastic restart
            shard_sets = {
                ds_name: ds.streaming_split(self._scaling.num_workers)
                for ds_name, ds in self._datasets.items()}
            try:
                refs = [w.run.remote(self._loop, self._loop_config, queue,
                                     latest_ckpt.path if latest_ckpt
                                     else None,
                                     {ds_name: shards[rank]
                                      for ds_name, shards
                                      in shard_sets.items()})
                        for rank, w in enumerate(gang["workers"])]
                pending = list(refs)
                while pending:
                    _drain(queue, exp_dir, saved_ckpts, self._ckpt_config,
                           history)
                    latest_ckpt, last_metrics = _latest(history, latest_ckpt,
                                                        last_metrics)
                    done, pending = wait(pending,
                                         num_returns=len(pending),
                                         timeout=0.05)
                    for ref in done:
                        get(ref)        # surface worker exceptions
                _drain(queue, exp_dir, saved_ckpts, self._ckpt_config,
                       history)
                latest_ckpt, last_metrics = _latest(history, latest_ckpt,
                                                    last_metrics)
                error = None
                break
            except (TaskError, WorkerCrashedError) as e:
                # capture reports that landed before the crash — the last
                # checkpoint is the restart point
                try:
                    _drain(queue, exp_dir, saved_ckpts, self._ckpt_config,
                           history)
                    latest_ckpt, last_metrics = _latest(
                        history, latest_ckpt, last_metrics)
                except Exception:
                    pass
                attempts += 1
                budget = self._failure.max_failures
                if budget >= 0 and attempts > budget:
                    error = e
                    break
                # elastic restart from last checkpoint
            finally:
                self._teardown_gang(gang)
                try:
                    queue.shutdown()
                except Exception:
                    pass
                # shard queues + their feeder threads must die with the
                # attempt, or elastic restarts leak a queue-actor set
                # (and the pinned block refs inside) per retry
                for shards in shard_sets.values():
                    for shard in shards:
                        shard.shutdown()

        # surface the persisted copy of the final checkpoint if any
        final_ckpt = Checkpoint(saved_ckpts[-1]) if saved_ckpts else \
            latest_ckpt
        return Result(metrics=last_metrics, checkpoint=final_ckpt,
                      path=exp_dir, error=error,
                      metrics_history=[h["metrics"] for h in history
                                       if h["rank"] == 0])

    # ------------------------------------------------------------- plumbing
    def _spawn_gang(self, name: str, storage: str) -> dict:
        sc = self._scaling
        bundle = sc.bundle()
        # the one wait of a gang's start on the driver's side: the queue,
        # the workers and the shards are submissions, which do not block
        with tracing.start_span("train::pg_ready"):
            pg = placement_group([bundle] * sc.num_workers,
                                 strategy=sc.placement_strategy)
            try:
                pg.ready(timeout=60.0)
            except TimeoutError:
                if sc.placement_strategy == "STRICT_SPREAD":
                    # dev fallback: fewer nodes than workers — pack instead
                    remove_placement_group(pg)
                    pg = placement_group([bundle] * sc.num_workers,
                                         strategy="PACK")
                    pg.ready(timeout=60.0)
                else:
                    raise
        workers = []
        try:
            # the phases of `rtpu_train_gang_start_seconds` start here,
            # at the first `.remote()`: the worker observes them
            created_wall = time.time()
            for rank in range(sc.num_workers):
                strat = PlacementGroupSchedulingStrategy(
                    placement_group=pg,
                    placement_group_bundle_index=rank)
                opts = {"scheduling_strategy": strat,
                        "num_cpus": bundle.get("CPU", 1.0)}
                extra = {k: v for k, v in bundle.items() if k != "CPU"}
                if extra:
                    opts["resources"] = extra
                workers.append(_TrainWorker.options(**opts).remote(
                    rank, sc.num_workers, storage, name, created_wall))
            return {"pg": pg, "workers": workers}
        except Exception:
            for w in workers:
                try:
                    kill(w)
                except Exception:
                    pass
            remove_placement_group(pg)
            raise

    def _teardown_gang(self, gang: dict) -> None:
        for w in gang.get("workers", ()):
            try:
                kill(w)
            except Exception:
                pass
        try:
            remove_placement_group(gang["pg"])
        except Exception:
            pass


def _latest(history, latest_ckpt, last_metrics):
    """Rank-0's most recent report drives Result metrics/checkpoint."""
    for payload in reversed(history):
        if payload["rank"] == 0:
            last_metrics = payload["metrics"]
            if payload.get("checkpoint_path"):
                latest_ckpt = Checkpoint(payload["checkpoint_path"])
            break
    return latest_ckpt, last_metrics


def _next_report(queue) -> Optional[Dict[str, Any]]:
    from ..util.queue import Empty
    try:
        return queue.get_nowait()
    except Empty:
        return None


def _drain(queue, exp_dir: str, saved: List[str],
           ckpt_config: CheckpointConfig,
           history: List[Dict[str, Any]]) -> None:
    """Pull all pending reports; persist rank-0 checkpoints into the
    experiment dir (checkpoint_000N) honoring num_to_keep."""
    payload = _next_report(queue)
    if payload is None:
        return      # the poll found nothing: no span for an empty turn
    with tracing.start_span("train::drain"):
        while payload is not None:
            _take_report(payload, exp_dir, saved, ckpt_config, history)
            payload = _next_report(queue)


def _take_report(payload: Dict[str, Any], exp_dir: str, saved: List[str],
                 ckpt_config: CheckpointConfig,
                 history: List[Dict[str, Any]]) -> None:
    history.append(payload)
    if "reported_wall" in payload:
        telemetry.hist_observe(M_REPORT_LAG,
                               time.time() - payload["reported_wall"])
    src = payload.get("checkpoint_path")
    if src and os.path.isdir(src):
        dst = os.path.join(exp_dir, f"checkpoint_{len(saved):06d}")
        with tracing.timed_span("train::persist_checkpoint",
                                M_CHECKPOINT_PERSIST):
            if os.path.exists(dst):
                shutil.rmtree(dst)
            shutil.copytree(src, dst)
        payload["checkpoint_path"] = dst
        saved.append(dst)
        keep = ckpt_config.num_to_keep
        if keep and len(saved) > keep:
            with tracing.start_span("train::prune_checkpoints"):
                for old in saved[:-keep]:
                    shutil.rmtree(old, ignore_errors=True)
            del saved[:-keep]
