"""Worker process entrypoint (reference:
python/ray/_private/workers/default_worker.py — connects the embedded
core worker and runs the task execution loop)."""

from __future__ import annotations

import os
import sys


def main() -> None:
    socket_path = os.environ["RT_SOCKET"]
    if os.environ.get("RT_WORKER_CHIPS"):
        # This process owns chips: place XLA's persistent cache before
        # anything it runs can compile.
        from .accelerators.tpu import wait_for_chips_at_tpu_init
        from .compile_cache import ensure_compile_cache

        ensure_compile_cache()
        wait_for_chips_at_tpu_init(
            [int(c) for c in os.environ["RT_WORKER_CHIPS"].split(",")]
        )
    profile_dir = os.environ.get("RT_WORKER_PROFILE")
    prof = None
    if profile_dir:
        # Startup-cost diagnosis: profile interpreter + CoreWorker
        # init (imports, store attach, register) and dump BEFORE the
        # task loop. Same-thread enable/disable only — cProfile hooks
        # are per-thread, so a timer-thread disable would leave the
        # main thread profiled (and slowed ~2x) forever.
        import cProfile

        prof = cProfile.Profile()
        prof.enable()
    from .worker import CoreWorker, set_global_worker

    worker = CoreWorker(socket_path, role="worker")
    set_global_worker(worker)
    if prof is not None:
        prof.disable()
        prof.dump_stats(
            os.path.join(profile_dir, f"worker-{os.getpid()}.prof")
        )
    try:
        worker.run_task_loop()
    finally:
        worker.shutdown()


if __name__ == "__main__":
    sys.exit(main())
