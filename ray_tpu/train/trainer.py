"""JaxTrainer — the DataParallelTrainer-shaped entry point.

Reference call stack being mirrored (SURVEY.md §3.4): TorchTrainer.fit
→ DataParallelTrainer.training_loop → BackendExecutor.start (creates
the worker gang, sets ranks, runs backend hooks) → per-worker
train_loop_per_worker with a session for report()/checkpoints →
TrainingIterator gathers results; failures restart from the latest
checkpoint up to FailureConfig.max_failures (backend_executor.py:759).

TPU-native shape: `num_workers=1` is the single-controller JAX mode —
one process runs the loop and pjit spans every device it sees (a
whole slice on real pods). `num_workers>1` builds an actor gang via
WorkerGroup + JaxBackend rendezvous for multi-host DCN setups.

Who owns the chips: a chip belongs to one process at a time. When the
runtime advertises chips, the single-controller loop runs in a
one-member gang whose worker leases them and exits when `fit` returns,
so a driver that trains and then serves never holds the chip its
replica needs. With no chips advertised (or no runtime) the loop runs
in the calling process, as it does inside a worker that already holds
its chips.
"""

from __future__ import annotations

import os
import tempfile
import traceback
from typing import Any, Callable, Dict, Optional

from .backend import Backend, JaxBackend
from .checkpoint import wait_for_checkpoints
from .config import Result, RunConfig, ScalingConfig
from ..util.accelerators.tpu import cluster_tpu_chips
from .session import TrainContext, clear_session, init_session
from .worker_group import WorkerGroup


class JaxTrainer:
    def __init__(
        self,
        train_loop_per_worker: Callable[[Optional[dict]], Any],
        *,
        train_loop_config: Optional[dict] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        backend: Optional[Backend] = None,
        backend_config: Optional[dict] = None,
        datasets: Optional[Dict[str, Any]] = None,
    ):
        self._train_loop = train_loop_per_worker
        self._train_loop_config = train_loop_config
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.backend = backend or JaxBackend()
        self.backend_config = backend_config or {}
        self.datasets = datasets or {}

    # -- public API (reference: BaseTrainer.fit, base_trainer.py:567) --
    def fit(self) -> Result:
        max_failures = self.run_config.failure_config.max_failures
        name = self.run_config.name or "jax_trainer"
        # One storage dir for all attempts: retries find the previous
        # attempt's checkpoint marker there and resume from it.
        storage = self.run_config.storage_path or tempfile.mkdtemp(
            prefix=f"rt_train_{name}_"
        )
        os.makedirs(storage, exist_ok=True)
        attempt = 0
        while True:
            try:
                return self._fit_once(name, storage)
            except Exception as e:  # noqa: BLE001
                attempt += 1
                if attempt > max_failures:
                    return Result(
                        metrics={}, checkpoint_path=None, error=e
                    )
                traceback.print_exc()

    # ------------------------------------------------------------------
    def _fit_once(self, name: str, storage: str) -> Result:
        if self.scaling_config.num_workers <= 1 and (
            os.environ.get("RT_WORKER_CHIPS") or not cluster_tpu_chips()
        ):
            return self._fit_local(name, storage)
        return self._fit_gang(name, storage)

    def _loop_args(self):
        return (
            (self._train_loop_config,)
            if self._train_loop_config is not None
            or self._takes_config()
            else ()
        )

    def _takes_config(self) -> bool:
        import inspect

        try:
            sig = inspect.signature(self._train_loop)
            return len(sig.parameters) > 0
        except (TypeError, ValueError):
            return False

    def _make_shards(self, world_size: int, rank: int):
        """Streaming-split each dataset; rank's shard only (reference:
        DataConfig streaming-split, train/_internal/data_config.py:112)."""
        if not self.datasets:
            return {}
        if not hasattr(self, "_split_cache"):
            self._split_cache = {
                name: ds.streaming_split(world_size, equal=True)
                for name, ds in self.datasets.items()
            }
        return {
            name: splits[rank]
            for name, splits in self._split_cache.items()
        }

    def _make_gang_shards(self, world_size: int):
        if not self.datasets:
            return None
        return [
            self._make_shards(world_size, rank)
            for rank in range(world_size)
        ]

    def _fit_local(self, name: str, storage: str) -> Result:
        """Single-controller path: the loop runs here, pjit spans all
        visible devices."""
        history = []

        def on_result(metrics, checkpoint):
            history.append(dict(metrics))

        context = TrainContext(
            world_rank=0,
            world_size=1,
            experiment_name=name,
            trial_dir=storage,
        )
        session = init_session(
            context,
            result_callback=on_result,
            dataset_shards=self._make_shards(1, rank=0),
        )
        try:
            self._train_loop(*self._loop_args())
        finally:
            clear_session()
            # Fit-exit durability barrier: async saves issued by the
            # loop must be on disk before fit() returns (or before a
            # retry attempt restores from them).
            wait_for_checkpoints()
        metrics = history[-1] if history else {}
        return Result(
            metrics=metrics,
            checkpoint_path=session.latest_checkpoint,
            metrics_history=history,
        )

    def _fit_gang(self, name: str, storage: str) -> Result:
        """Multi-worker gang over the actor runtime (reference:
        BackendExecutor.start + start_training)."""
        group = WorkerGroup(
            max(1, self.scaling_config.num_workers),
            self.scaling_config.resources_per_worker,
        )
        try:
            self.backend.on_start(group, self.backend_config)
            outs = group.run_train_loop(
                self._train_loop,
                name,
                self._loop_args(),
                trial_dir=storage,
                dataset_shards_per_rank=self._make_gang_shards(
                    group.size
                ),
            )
        finally:
            self.backend.on_shutdown(group)
            group.shutdown()
        rank0 = outs[0]
        history = rank0["reported"]
        return Result(
            metrics=history[-1] if history else {},
            checkpoint_path=rank0["checkpoint"],
            metrics_history=history,
        )
