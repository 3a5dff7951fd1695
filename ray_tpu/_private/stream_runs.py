"""The items of `num_returns="streaming"` tasks on their way from the
producing worker to the consumer of the task's ObjectRefGenerator.

One stream is an ordered run of serialized items keyed by its task,
held by the head daemon. The producer appends (a notify per yield);
the consumer keeps at most ONE request parked per stream, "everything
after index i", answered by whichever handler thread brings the next
item or the stream's end, with the items' bytes in the answer. Nothing
here takes the daemon's state lock, starts a thread or touches the
object directory: an item becomes an object only if its ref leaves
the consumer (worker.CoreWorker._publish_stream_item).

A run that is over (its end delivered, or its consumer gone) stays as
an empty, closed entry, so that a late message of the other side
cannot bring it back to life; `sweep` forgets closed entries."""

from __future__ import annotations

import time
from typing import Dict, List, Optional

from ray_tpu.devtools.lock_witness import make_lock

#: A closed run is forgotten this long after it closed.
CLOSED_KEPT_S = 600.0

#: What a producer may say of its stream's end
#: (worker.note_stream_end): the keys ride `stream_end` and then the
#: answer that brings the end, as `first_ts` rides with the first
#: item. A stream whose producer noted nothing carries none of them.
STREAM_END_NOTE = ("handler_ms", "exhausted_ts", "end_ts")


class _Run:
    __slots__ = (
        "items", "base", "end", "parked", "closed_at", "first_ts"
    )

    def __init__(self):
        #: items[k] is item base + k: serialized bytes, or None for an
        #: item too large for a message, which the producer sealed in
        #: the object store under the item's id.
        self.items: List[Optional[bytes]] = []
        self.base = 0
        #: (count or None, error payload or None, the producer's note
        #: of the end: a dict, mostly empty) once the producer is
        #: known to have stopped. With a count the end is delivered
        #: only behind that many items: an error that travelled by
        #: another connection can overtake the last appends.
        self.end: Optional[tuple] = None
        self.parked: Optional[tuple] = None  # (conn, mid, after)
        self.closed_at: Optional[float] = None
        #: The producer's epoch time on item 0, if it stamped one; it
        #: rides in the answer that brings item 0 and in no other.
        self.first_ts: Optional[float] = None


class StreamRuns:
    def __init__(self):
        self._lock = make_lock("daemon.streams")
        self._runs: Dict[bytes, _Run] = {}

    def __len__(self) -> int:
        return len(self._runs)

    def _run(self, task: bytes) -> _Run:
        run = self._runs.get(task)
        if run is None:
            run = self._runs[task] = _Run()
        return run

    @staticmethod
    def _close(run: _Run) -> None:
        run.items = []
        run.parked = None
        run.closed_at = time.monotonic()

    def _answer(self, run: _Run) -> Optional[tuple]:
        """(conn, mid, reply) for the parked request if there is
        something to tell it; the caller sends it after releasing the
        lock. Delivering the end closes the run."""
        if run.parked is None:
            return None
        conn, mid, after = run.parked
        have = run.base + len(run.items)
        items = run.items[max(after - run.base, 0):]
        end = None
        if run.end is not None:
            count, error, note = run.end
            if count is None or have >= count:
                end = {"count": have, "error": error, **note}
        if not items and end is None:
            return None
        run.parked = None
        if end is not None:
            self._close(run)
        reply = {"items": items, "end": end}
        if after == 0 and items and run.first_ts is not None:
            reply["first_ts"] = run.first_ts
        return conn, mid, reply

    @staticmethod
    def _send(answer: Optional[tuple]) -> None:
        if answer is not None:
            conn, mid, reply = answer
            conn.reply(mid, reply)

    def put(
        self,
        task: bytes,
        index: int,
        data: Optional[bytes],
        first_ts: Optional[float] = None,
    ) -> None:
        with self._lock:
            run = self._run(task)
            if run.closed_at is not None:
                return
            if index != run.base + len(run.items):
                # A retried task yields its first items again under
                # the same ids; the consumer may have them already.
                return
            run.items.append(data)
            if first_ts is not None:
                run.first_ts = first_ts
            answer = self._answer(run)
        self._send(answer)

    def end(
        self,
        task: bytes,
        count: Optional[int],
        error: Optional[bytes],
        create: bool = True,
        note: Optional[dict] = None,
    ) -> None:
        with self._lock:
            run = self._run(task) if create else self._runs.get(task)
            if run is None or run.closed_at is not None:
                return
            if run.end is None:
                run.end = (count, error, note or {})
            answer = self._answer(run)
        self._send(answer)

    def fetch(self, conn, mid, task: bytes, after: int) -> bool:
        """Park the consumer's request for what follows item
        `after - 1` (which also says that it holds everything before)
        and answer it at once if it can be. True if the run is new."""
        stale = None
        with self._lock:
            created = task not in self._runs
            run = self._run(task)
            if run.closed_at is not None:
                answer = conn, mid, {
                    "items": [], "end": {"count": after, "error": None}
                }
            else:
                drop = after - run.base
                if drop > 0:
                    del run.items[:drop]
                    run.base = after
                if run.parked is not None:
                    stale = run.parked[0], run.parked[1], {
                        "items": [], "end": None
                    }
                run.parked = (conn, mid, after)
                answer = self._answer(run)
        self._send(stale)
        self._send(answer)
        return created

    def close(self, task: bytes) -> None:
        """The consumer will ask no more: drop what it did not take."""
        with self._lock:
            run = self._run(task)
            if run.closed_at is not None:
                return
            answer = None
            if run.parked is not None:
                answer = run.parked[0], run.parked[1], {
                    "items": [], "end": None
                }
            self._close(run)
        self._send(answer)

    def drop_consumer(self, conn_id: int) -> None:
        """A client's connection is gone: close the runs it was parked
        on."""
        with self._lock:
            for run in self._runs.values():
                if (
                    run.parked is not None
                    and run.parked[0].conn_id == conn_id
                ):
                    self._close(run)

    def sweep(self) -> None:
        horizon = time.monotonic() - CLOSED_KEPT_S
        with self._lock:
            for task in [
                t for t, run in self._runs.items()
                if run.closed_at is not None and run.closed_at < horizon
            ]:
                del self._runs[task]
