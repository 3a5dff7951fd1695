"""ObjectRef — a future naming an immutable object in the cluster.

Mirrors the reference's ObjectRef (reference: python/ray/_raylet.pyx:269
ObjectRef class): holds the binary object id, supports `get`-via-API,
equality/hashing by id, and releases its reference on garbage
collection so the owner can free the object (reference:
core_worker/reference_count.h owner-based refcounting).
"""

from __future__ import annotations

from collections import deque

from ._private.ids import ObjectID


class ObjectRef:
    __slots__ = ("_id", "_owner", "__weakref__")

    def __init__(self, object_id: ObjectID, owner=None, skip_adding_ref=False):
        self._id = object_id
        self._owner = owner
        if owner is not None and not skip_adding_ref:
            owner.add_local_ref(object_id)

    def id(self) -> ObjectID:
        return self._id

    def binary(self) -> bytes:
        return self._id.binary()

    def hex(self) -> str:
        return self._id.hex()

    def __hash__(self):
        return hash(self._id)

    def __eq__(self, other):
        return isinstance(other, ObjectRef) and other._id == self._id

    def __repr__(self):
        return f"ObjectRef({self._id.hex()})"

    def __del__(self):
        owner = self._owner
        if owner is not None:
            try:
                owner.remove_local_ref(self._id)
            except Exception:
                pass

    def __reduce__(self):
        # Refs serialized into task args / object values re-attach to
        # the receiving process's worker on deserialization. A ref
        # escaping its owner must first be globally visible: direct
        # transport results live only in the owner's futures until
        # published to the daemon's object table.
        owner = self._owner
        if owner is not None:
            visible = getattr(owner, "ensure_globally_visible", None)
            if visible is not None:
                visible(self._id)
        return (_deserialize_ref, (self._id.binary(),))

    # `await ref` support for async drivers.
    def __await__(self):
        from . import api

        result = yield from _async_get(self).__await__()
        return result

    def future(self):
        """Return a concurrent.futures.Future resolving to the value."""
        import concurrent.futures
        import threading

        from . import api

        fut: concurrent.futures.Future = concurrent.futures.Future()

        def _resolve():
            try:
                fut.set_result(api.get(self))
            except BaseException as e:  # noqa: BLE001
                fut.set_exception(e)

        threading.Thread(target=_resolve, daemon=True).start()
        return fut


async def _async_get(ref: ObjectRef):
    import asyncio

    return await asyncio.wrap_future(ref.future())


class ObjectRefGenerator:
    """Iterator over the ObjectRefs a generator task produces.

    Mirrors the reference's streaming/dynamic generator protocol
    (reference: python/ray/_raylet.pyx:269 ObjectRefGenerator;
    remote_function.py:385-391 num_returns="dynamic"/"streaming"):

    - Item object ids are **deterministic** — item *i* is
      ``ObjectID.for_return(task_id, i + 2)`` (index 1 is the task's
      primary return, the completion marker).
    - Streaming mode (no count): the items travel as the task's
      run of serialized values (_private/stream_runs.py). ``__next__``
      keeps ONE request parked at the daemon, "everything after item
      i", and is answered with every item appended since, bytes
      included, and with the end of the stream once it has come: the
      count, or the task's error behind the items sealed before it.
      The ref it returns resolves from the owner's inline cache; the
      item becomes an object of the directory only if the ref leaves
      this process.
    - Dynamic mode: the completion marker's VALUE is this generator
      (count pre-resolved, every item a sealed object), so
      ``get(ref)`` on a dynamic task returns an ObjectRefGenerator,
      per the reference's API.
    """

    def __init__(self, task_id, owner=None, count=None, primary_ref=None):
        self._task_id = task_id
        self._owner = owner
        self._count = count
        self._index = 0
        #: Held for the generator's lifetime while streaming: the
        #: owner-side future of the completion marker is what reports
        #: a lost producer to the run (CoreWorker.watch_stream_marker).
        self._primary_ref: ObjectRef | None = primary_ref
        #: Serialized items fetched and not handed out yet.
        self._fetched: deque = deque()
        #: {"count", "error"} once the daemon has said so, with what
        #: the producer noted of the end, if it noted anything
        #: (stream_runs.STREAM_END_NOTE).
        self._end: dict | None = None
        self._closed = False
        #: What the consumer counts of its transport: items received
        #: and requests they took (1.0 item a fetch where it keeps up
        #: with the producer, more where it does not).
        self.stream_items = 0
        self.stream_fetches = 0
        #: The producer's epoch time on the stream's first item, from
        #: the answer that brought it (None before, and where the
        #: producer stamped none).
        self.first_item_ts: float | None = None

    @property
    def end_note(self) -> dict:
        """What the producer noted of the stream's end ({} before the
        end, and for a producer that noted nothing)."""
        return {
            k: v for k, v in (self._end or {}).items()
            if k not in ("count", "error")
        }

    def _ref(self, object_id: ObjectID) -> ObjectRef:
        return ObjectRef(object_id, owner=self._owner)

    def _item_id(self, i: int) -> ObjectID:
        return ObjectID.for_return(self._task_id, i + 2)

    @property
    def completed_ref(self) -> ObjectRef:
        """Ref of the completion marker (resolves once the whole
        generator has run: to the item count of a streaming task;
        errors if the task failed)."""
        if self._primary_ref is None:
            self._primary_ref = self._ref(
                ObjectID.for_return(self._task_id, 1)
            )
        return self._primary_ref

    def __iter__(self):
        return self

    def _next_item(self) -> tuple:
        """(id, serialized bytes) of the next streamed item, the bytes
        None for an item that is a store object. Parks at the daemon
        when nothing is at hand."""
        if self._owner is None:
            from ._private.worker import global_worker

            self._owner = global_worker()
        while not self._fetched:
            if self._end is not None:
                if self._end["error"] is not None:
                    from ._private.task_spec import raise_from_payload

                    raise_from_payload(self._end["error"])
                raise StopIteration
            reply = self._owner.call(
                "stream_fetch",
                task=self._task_id.binary(),
                after=self.stream_items,
            )
            self.stream_fetches += 1
            self.stream_items += len(reply["items"])
            self._fetched.extend(reply["items"])
            self._end = reply["end"]
            if "first_ts" in reply:
                self.first_item_ts = reply["first_ts"]
        oid = self._item_id(self._index)
        self._index += 1
        return oid, self._fetched.popleft()

    def __next__(self) -> ObjectRef:
        if self._count is not None:
            if self._index >= self._count:
                raise StopIteration
            ref = self._ref(self._item_id(self._index))
            self._index += 1
            return ref
        oid, data = self._next_item()
        if data is not None:
            self._owner.adopt_stream_item(oid, data)
        return self._ref(oid)

    next = __next__

    def next_value(self):
        """The next item's VALUE (a streaming generator's): what
        ``get(next(gen))`` returns, without the ref."""
        oid, data = self._next_item()
        if data is None:
            return self._owner.get([self._ref(oid)])[0]
        return self._owner.serialization.deserialize(data)

    def close(self) -> None:
        """Tell the daemon that nobody will ask for the rest of the
        stream (a consumer that saw its end has no need to)."""
        if self._count is not None or self._closed:
            return
        self._closed = True
        if self._end is None and self._owner is not None:
            self._owner.notify(
                "stream_close", task=self._task_id.binary()
            )

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def __reduce__(self):
        return (
            _deserialize_generator,
            (self._task_id.binary(), self._count),
        )

    def __repr__(self):
        return (
            f"ObjectRefGenerator(task={self._task_id.hex()}, "
            f"count={self._count}, index={self._index})"
        )


def _deserialize_generator(task_binary: bytes, count):
    from ._private.ids import TaskID
    from ._private.worker import global_worker

    return ObjectRefGenerator(
        TaskID(task_binary), owner=global_worker(), count=count
    )


def _deserialize_ref(binary: bytes) -> ObjectRef:
    from ._private.worker import global_worker

    oid = ObjectID(binary)
    worker = global_worker()
    if worker is not None:
        worker.notify_borrowed_ref(oid)
        return ObjectRef(oid, owner=worker)
    return ObjectRef(oid)
