"""Worker group: a gang of train-worker actors on a placement group.

Counterpart of the reference's WorkerGroup
(/root/reference/python/ray/train/v2/_internal/execution/worker_group/
worker_group.py:105 — PG at :242, per-rank bundles at :364) with the thread
runner (thread_runner.py) folded into the worker actor.  TPU-native twist:
each worker is one *host* of a slice; when ``use_jax_distributed`` is set the
group wires a JAX coordination service (rank0 hosts it) so all processes form
one global device mesh — the multi-controller SPMD model replacing
torch.distributed process groups.
"""

from __future__ import annotations

import socket
import threading
import traceback
from typing import Any, Optional

import cloudpickle

import ray_tpu
from ray_tpu.train.config import ScalingConfig
from ray_tpu.train import context as train_context


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class TrainWorker:
    """Actor hosting one rank: runs the user's train fn on a thread."""

    def __init__(self):
        self._ctx: Optional[train_context.TrainContext] = None
        self._thread: Optional[threading.Thread] = None
        self._done = False
        self._error: Optional[str] = None

    def setup(self, rank: int, local_rank: int, world_size: int,
              experiment_name: str, experiment_dir: str,
              restore_checkpoint_path: Optional[str],
              coordinator_address: Optional[str],
              dataset_shards_blob: Optional[bytes],
              trial_info: Optional[dict] = None,
              start_report_index: int = 0) -> bool:
        shards = (cloudpickle.loads(dataset_shards_blob)
                  if dataset_shards_blob else None)
        self._ctx = train_context.TrainContext(
            rank=rank, local_rank=local_rank, world_size=world_size,
            experiment_name=experiment_name, experiment_dir=experiment_dir,
            restore_checkpoint_path=restore_checkpoint_path,
            dataset_shards=shards, trial_info=trial_info,
            start_report_index=start_report_index)
        if coordinator_address is not None:
            import jax
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=world_size, process_id=rank)
        return True

    def run(self, fn_blob: bytes, config: Optional[dict]) -> bool:
        fn = cloudpickle.loads(fn_blob)
        ctx = self._ctx

        def target():
            train_context._set_context(ctx)
            try:
                if config is not None:
                    fn(config)
                else:
                    fn()
            except train_context._StopTraining:
                pass
            except BaseException:
                self._error = traceback.format_exc()
            finally:
                self._done = True
                train_context._set_context(None)

        self._done = False
        self._error = None
        self._thread = threading.Thread(target=target, daemon=True)
        self._thread.start()
        return True

    def poll(self) -> dict:
        # Snapshot done/error BEFORE draining: report() enqueues happen-before
        # _done=True, so done-then-drain can never lose the final report.
        done, error = self._done, self._error
        reports = []
        ctx = self._ctx
        if ctx is not None:
            while not ctx.outbox.empty():
                reports.append(ctx.outbox.get_nowait())
        return {"reports": reports, "done": done, "error": error}

    def stop(self) -> bool:
        if self._ctx is not None:
            self._ctx.stop_event.set()
        return True

    def health_check(self) -> bool:
        return True

    def shutdown(self) -> bool:
        try:
            import jax
            jax.distributed.shutdown()
        except Exception:
            pass
        return True


class WorkerGroup:
    """Creates/destroys the gang; fans calls out to all ranks."""

    def __init__(self, scaling_config: ScalingConfig,
                 num_workers: Optional[int] = None):
        """num_workers overrides the config's size — the controller's
        elastic policy passes the per-attempt world size here."""
        self._config = scaling_config
        self._num_workers = num_workers or scaling_config.num_workers
        self._pg = None
        self._workers: list[Any] = []

    @property
    def workers(self):
        return self._workers

    @property
    def num_workers(self) -> int:
        return self._num_workers

    def start(self, experiment_name: str, experiment_dir: str,
              restore_checkpoint_path: Optional[str] = None,
              dataset_shards_per_rank: Optional[list] = None,
              trial_info: Optional[dict] = None,
              start_report_index: int = 0):
        from ray_tpu.util.placement_group import placement_group
        from ray_tpu.util.scheduling_strategies import (
            PlacementGroupSchedulingStrategy,
        )

        cfg = self._config
        n = self._num_workers
        bundle = cfg.bundle()
        self._pg = placement_group(
            [dict(bundle) for _ in range(n)],
            strategy=cfg.placement_strategy)
        actor_cls = ray_tpu.remote(TrainWorker)
        self._workers = []
        for rank in range(n):
            strategy = PlacementGroupSchedulingStrategy(
                self._pg, placement_group_bundle_index=rank)
            opts = {"scheduling_strategy": strategy,
                    "num_cpus": bundle.get("CPU", 0)}
            if "TPU" in bundle:
                opts["resources"] = {"TPU": bundle["TPU"]}
            self._workers.append(actor_cls.options(**opts).remote())

        coordinator = (f"127.0.0.1:{_free_port()}"
                       if cfg.use_jax_distributed and n > 1
                       else None)
        setups = []
        for rank, w in enumerate(self._workers):
            shards = None
            if dataset_shards_per_rank is not None:
                shards = cloudpickle.dumps(dataset_shards_per_rank[rank])
            setups.append(w.setup.remote(
                rank, rank, n, experiment_name, experiment_dir,
                restore_checkpoint_path, coordinator, shards, trial_info,
                start_report_index))
        ray_tpu.get(setups)

    def run(self, train_fn, config: Optional[dict]):
        blob = cloudpickle.dumps(train_fn)
        ray_tpu.get([w.run.remote(blob, config) for w in self._workers])

    def poll(self) -> list[dict]:
        return ray_tpu.get([w.poll.remote() for w in self._workers])

    def stop(self):
        try:
            ray_tpu.get([w.stop.remote() for w in self._workers], timeout=5)
        except Exception:
            pass

    def shutdown(self, graceful: bool = True):
        if graceful and self._workers:
            try:
                ray_tpu.get(
                    [w.shutdown.remote() for w in self._workers], timeout=5)
            except Exception:
                pass
        for w in self._workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self._workers = []
        if self._pg is not None:
            from ray_tpu.util.placement_group import remove_placement_group
            try:
                remove_placement_group(self._pg)
            except Exception:
                pass
            self._pg = None
