"""Checkpoint save/restore with top-k retention and async persistence.

Orbax-backed sharded checkpointing (the TPU ecosystem standard),
wrapped in the reference's Checkpoint-directory semantics (reference:
train/_checkpoint.py Checkpoint = a directory handle;
train/_internal/checkpoint_manager.py top-k retention by score).

Async persistence (reference: orbax AsyncCheckpointer split — a
blocking device->host snapshot, then commit off the critical path):
``save_checkpoint(..., async_save=True)`` snapshots the pytree to host
memory synchronously (safe against donated buffers: the NEXT train
step may reuse the device HBM the moment save_checkpoint returns) and
hands the disk write to a single background writer thread, so step
N+1 runs while save N persists. ``wait_for_checkpoints()`` is the
durability barrier: the trainer calls it at fit-exit, and
restore/retention paths call it before trusting directory contents.
The writer publishes ``metadata.json`` only AFTER the array data is
fully written, so its presence marks a complete checkpoint.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

from .._private import step_telemetry

# -- async writer machinery --------------------------------------------------

_PENDING_LOCK = threading.Lock()
#: path -> futures of the in-flight background writes for that path
#: (same-path re-saves append; the single writer runs them in order).
_PENDING: Dict[str, List[Future]] = {}
_EXECUTOR: Optional[ThreadPoolExecutor] = None


def _writer() -> ThreadPoolExecutor:
    """One writer thread: saves persist in submission order, and at
    most one disk commit competes with training for host resources."""
    global _EXECUTOR
    with _PENDING_LOCK:
        if _EXECUTOR is None:
            _EXECUTOR = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="rt-ckpt-writer"
            )
        return _EXECUTOR


def _host_snapshot(state: Any) -> Any:
    """Blocking device->host copy of a pytree. Must complete BEFORE the
    caller's next train step: with donate_argnums the step reuses the
    state's HBM in place, so a lazy read from the writer thread would
    see garbage. Non-jax pytrees (numpy/python) pass through."""
    import jax

    return jax.device_get(state)


def _fully_addressable(state: Any) -> bool:
    """True when every array in the pytree lives on devices this
    process can read. device_get raises on arrays spanning
    non-addressable devices (multi-host meshes), so async_save falls
    back to the sync orbax path — which gathers per-host — for such
    state."""
    import jax

    return all(
        getattr(leaf, "is_fully_addressable", True)
        for leaf in jax.tree.leaves(state)
    )


def _write_payload(path: str, state: Any, metadata: Optional[dict]) -> None:
    """Persist one checkpoint directory. metadata.json lands LAST so
    readers can treat its presence as the completeness marker."""
    import orbax.checkpoint as ocp

    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.join(path, "state"), state)
    ckptr.wait_until_finished()
    if metadata is not None:
        tmp = os.path.join(path, "metadata.json.tmp")
        with open(tmp, "w") as f:
            json.dump(metadata, f)
        os.replace(tmp, os.path.join(path, "metadata.json"))


def save_checkpoint(
    path: str,
    state: Any,
    metadata: Optional[dict] = None,
    *,
    async_save: bool = False,
) -> str:
    """Save a pytree (sharded arrays gathered per-host by orbax).

    async_save=True returns as soon as the state is snapshotted to
    host memory; the disk write runs on a background writer thread.
    Call :func:`wait_for_checkpoints` (the trainer does at fit-exit)
    before treating the directory as durable. State spanning
    non-addressable devices (multi-host meshes) cannot be host-
    snapshotted from one process, so it saves synchronously — orbax
    gathers per-host — rather than racing the next step's donation.
    """
    path = os.path.abspath(path)
    # The step-blocking portion of a save (full write when sync, the
    # device->host snapshot when async) is a train-loop phase the step
    # telemetry attributes per step.
    with step_telemetry.phase_timer("ckpt_block_ms"):
        if not async_save or not _fully_addressable(state):
            _write_payload(path, state, metadata)
            return path
        snapshot = _host_snapshot(state)
        executor = _writer()
        with _PENDING_LOCK:
            # Submit under the lock: registration is atomic with the
            # submit, so a concurrent barrier can never miss an
            # in-flight write (and the single writer thread already
            # serializes same-path saves in submission order).
            future = executor.submit(
                _write_payload, path, snapshot, metadata
            )
            _PENDING.setdefault(path, []).append(future)
    return path


def _wait_futures(path: str, futures: List[Future]) -> None:
    """Wait for the given writes; deregister them; re-raise the first
    error. Deregistration happens only AFTER the result — a concurrent
    barrier that snapshots _PENDING mid-wait still sees (and waits on)
    the in-flight write."""
    first_error: Optional[BaseException] = None
    for future in futures:
        try:
            future.result()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            if first_error is None:
                first_error = e
    with _PENDING_LOCK:
        remaining = _PENDING.get(path)
        if remaining is not None:
            remaining[:] = [f for f in remaining if f not in futures]
            if not remaining:
                del _PENDING[path]
    if first_error is not None:
        raise first_error


def wait_for_checkpoints(path: Optional[str] = None) -> None:
    """Durability barrier for async saves. With a path, waits only for
    that checkpoint; otherwise drains every pending save. Re-raises
    the first write error — a failed persist must surface at the
    barrier, not vanish into a daemon thread."""
    if path is not None:
        path = os.path.abspath(path)
        with _PENDING_LOCK:
            futures = list(_PENDING.get(path, ()))
        if futures:
            _wait_futures(path, futures)
        return
    first_error: Optional[BaseException] = None
    while True:
        with _PENDING_LOCK:
            items = [(p, list(fs)) for p, fs in _PENDING.items()]
        if not items:
            break
        for pending_path, futures in items:
            try:
                _wait_futures(pending_path, futures)
            except BaseException as e:  # noqa: BLE001 — re-raised below
                if first_error is None:
                    first_error = e
    if first_error is not None:
        raise first_error


def pending_checkpoints() -> List[str]:
    """Paths with an in-flight background write (newest last)."""
    with _PENDING_LOCK:
        return list(_PENDING)


def restore_checkpoint(path: str, target: Any) -> Any:
    """Restore into the sharding/structure of `target` (an abstract or
    concrete pytree). Waits for any in-flight save of `path` first so
    an async save followed by an immediate restore reads full data."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    wait_for_checkpoints(path)
    ckptr = ocp.StandardCheckpointer()
    return ckptr.restore(os.path.join(path, "state"), target)


def load_metadata(path: str) -> dict:
    wait_for_checkpoints(path)
    meta_path = os.path.join(path, "metadata.json")
    if not os.path.exists(meta_path):
        return {}
    with open(meta_path) as f:
        return json.load(f)


class CheckpointManager:
    """Keep-last-k checkpoint retention (reference:
    train/_internal/checkpoint_manager.py; score-based top-k TBD)."""

    def __init__(self, root: str, num_to_keep: Optional[int] = None):
        self.root = os.path.abspath(root)
        self.num_to_keep = num_to_keep
        os.makedirs(self.root, exist_ok=True)
        self._checkpoints: List[Tuple[int, str]] = []

    def save(
        self,
        step: int,
        state: Any,
        metrics: Optional[dict] = None,
        *,
        async_save: bool = False,
    ):
        path = os.path.join(self.root, f"checkpoint_{step:08d}")
        save_checkpoint(
            path,
            state,
            {"step": step, **(metrics or {})},
            async_save=async_save,
        )
        self._checkpoints.append((step, path))
        if self.num_to_keep is not None:
            while len(self._checkpoints) > self.num_to_keep:
                _, old = self._checkpoints.pop(0)
                # Never delete a directory whose write is still in
                # flight — the writer would resurrect a half-deleted
                # tree and "retained" checkpoints could be corrupt.
                wait_for_checkpoints(old)
                shutil.rmtree(old, ignore_errors=True)
        return path

    def wait(self) -> None:
        """Block until every save issued through this manager (and any
        other async save in the process) is durable."""
        wait_for_checkpoints()

    def latest(self) -> Optional[str]:
        wait_for_checkpoints()
        existing = sorted(
            d
            for d in os.listdir(self.root)
            if d.startswith("checkpoint_")
        )
        if not existing:
            return None
        return os.path.join(self.root, existing[-1])
