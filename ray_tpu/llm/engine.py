"""Continuous-batching LLM inference engine over a PAGED KV cache.

PR 10 built the batching loop on fixed slot arenas; this engine keeps
the loop and swaps the memory system (ISSUE 11 tentpole): requests
now hold refcounted `block_len`-sized pages of ONE shared pool
(kv_slots.PagedKVCache) instead of each reserving a max_len arena
row, so long-context and short-chat requests share memory, and a
request whose prompt prefix is already pooled (same system prompt)
SKIPS prefill for the covered blocks entirely. The background step
loop, every iteration:

  1. reaps cancellations and frees their slots + blocks immediately;
  2. admits the FIFO head of the waiting queue — gated on KV-block
     availability (not enough blocks: the head WAITS, no skip-ahead,
     no crash) — pinning any prefix-cache hit and reserving the rest
     of its pages, then DISPATCHES its prefill's next chunk, written
     straight into its pages (starting AFTER the shared prefix).
     Every chunk is `prefill_chunk` tokens but a prompt's last, which
     is the chunk, its half or its quarter, the smallest that holds
     the tokens left (kv_slots.bucket_for): a function of the
     prompt's length alone, so a hit and a miss run the same last
     chunk. The loop runs every such shape once before its first
     admission (`_warm_chunk_shapes`), so no prompt length compiles
     anything later. An iteration's prefill is a BUDGET of tokens,
     the `prefill_chunk` the configuration states (ISSUE 60): it goes
     on, admitting and dispatching, WHILE the chunks it has
     dispatched hold fewer tokens than that. A whole chunk ends it,
     as it always did; a prompt's short last chunk leaves room, so
     the next prompt is admitted behind it in the same iteration and
     its first chunk dispatched, and so on until the budget is met,
     the queue is empty, no slot is free or the gate refuses. Still
     ONE prompt mid-prompt at a time (only a finished prompt lets the
     iteration go on), strict FIFO, the gate asked per admission.
     What it bounds: an iteration dispatches under
     2 x `prefill_chunk` prefill tokens (at most `prefill_chunk` less
     the smallest shape, then one whole chunk), so the longest gap
     between two tokens of a decoding row is a step and under two
     chunks, where one chunk an iteration made it a step and one.
     Why not otherwise: a strict budget (the next chunk only if it
     fits) never joins a whole chunk to a remainder, which is the
     common case; prefilling whenever a slot is free stalls every
     decoding row for whole prompts; two prompts mid-prompt at once
     save no device work;
  3. DISPATCHES one jitted paged decode step over the FULL slot batch,
     every row step 2 started among its rows
     (static shapes: full-width block tables, dead rows masked and
     parked on the null block; attention walks the tables only as far
     as the longest alive row reaches) —
     `models/generate.paged_engine_step`, the pool donated and
     written in place on accelerator backends;
  4. only then RETIRES the programs of the iteration before: waits
     for its chunk, fetches its step's tokens, streams each live
     row's token to its consumer queue, and releases EOS/budget rows
     (full prompt blocks stay cached for future prefix hits until
     memory pressure evicts them).

Dispatch ahead, retire behind (ISSUE 27). The device always has the
next iteration's programs queued while the host emits, because a
step needs nothing from the host that the step before it decides:
block tables, positions, the alive mask, each row's EOS id and token
budget and the step counter are device arrays (`_state`, laid out in
models/generate.py) which the step program advances itself, ending a
row by the two rules the host releases it by. The host keeps numpy
mirrors (`_tables`, `_positions`, `_alive`, `_eos`, `_budget`: the
allocator and the counters read them) and patches the device's copy
one slot at a time where it decides something: a table row at
admission, a row's start after its prompt's last chunk, a
cancellation. What makes this safe is ORDER: one device stream runs
programs in the order the loop dispatched them. So a block may go
back to the allocator, or into the prefix cache, while a program that
writes it is still queued: whoever gets the block next reads or
writes it in a program dispatched later. And a row that ended in
step N is dead on the device in step N+1, dispatched before the host
saw N's tokens, and writes nothing there. The price: a finished
row's slot and blocks come back one iteration after its last step.
Depth is one iteration, a constant of this loop.

Why the queue stands (ISSUE 59). While a request waits, the queue
stands for exactly ONE cause, that of its FIFO head (admission is
strict FIFO, so the head's cause is every waiter's), decided in this
order from what the loop reads anyway: `no_slot` (the scheduler has no
free slot: nothing else could admit the head) -> `behind_prefill` (a
slot is free and another prompt is mid-prompt: the one-prompt-at-a-time
rule and the iteration's budget hold the head, the time a second
prefilling prompt or a larger `prefill_chunk` would win back) ->
`no_pages` / `no_window_pages` / `no_state_slots` (the pool for which
the gate refused the head at the loop's last attempt; the gate's word
stands until the gate is asked again) -> `admissible` (nothing is
known to be in the way and the loop has not asked yet: behind a
prompt's short last chunk the moment until the same iteration asks,
behind a whole last chunk, whose tokens met the budget, the rest of
the iteration). `queue_ms` is the time each cause has stood
and `slot_ms` the slots x time in each state (`decoding`, `prefilling`,
`empty_queued`: empty while the queue stands, what any admission
change can win, `empty_idle`: empty with no waiter), both advanced
under the lock at the transitions that change them (a submission, an
admission attempt, a release, a prompt's last chunk, a cancelled
waiter) with one `perf_counter` reading each, the clock the loop's
phases and their profiler annotations run on: O(1) an iteration,
nothing per token or per waiter. A request takes the clocks' reading
at `submit()`; what they gained by its admission is ITS wait by cause
(`admit_wait_by_cause_ms_total`, whose values sum to
`admit_wait_ms_total`: the same readings).

Requests are host-side objects; per-request device state is the pages
its table points at, one row of `last_logits` and one row of the step
state. Sampling parameters stay engine-level statics (jit statics in
the shared kernel; greedy is the serving default).

Threading: submit()/cancel() may be called from any thread; all
scheduler/allocator/request state is guarded by one lock, JAX work
runs outside it. One engine = one step thread = one model family.

Failure: if the step loop dies, every in-flight and queued request is
failed with the loop's exception (consumers raise, never hang) and
subsequent submits raise EngineDead.

ISSUE 13 additions — the engine as the inference half of a decoupled
RL dataflow:

* **Drainless versioned weight sync** (`update_weights`): a weight
  push installs a new parameter GENERATION without stopping the step
  loop. Every request pins the generation that was latest at its
  ADMISSION and decodes on it to completion — a push mid-decode
  leaves in-flight streams token-exact on the old weights — while the
  next admission (and every policy batch) uses the new generation.
  During the transient mixed window the decode batch partitions by
  generation and runs one masked decode step per generation (disjoint
  alive masks over the same pool; `last_logits` rows merge back), so
  no REQUEST is drained, shed or errored on account of the push (the
  window's steps run one at a time, nothing dispatched ahead). Old
  generations are dropped the moment their last pinned request
  retires.
* **Pluggable batch program** (`program=`, `submit_policy`): ragged
  per-env action requests are the same problem as ragged chat traffic,
  so the same step loop serves them — callers submit small row
  batches of observations from any thread, the loop coalesces
  everything pending into one padded bucket and runs the program's
  jitted forward ONCE (batched logits/action outputs), then scatters
  the rows back to their tickets. A policy-only engine passes
  ``cfg=None`` and skips the KV cache/slot machinery entirely; an LLM
  engine may serve both paths (the RLHF shape: rollout generation and
  scoring on one engine).
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
import uuid
from collections import deque
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

from .._private import compile_watch
from .._private.step_telemetry import phase_timer, take_phases
from ..serve.observability import QUEUE_CAUSES
from ..util import tracing
from .kv_slots import NULL_BLOCK, PagedKVCache
from .scheduler import EngineDead, EngineOverloaded, SlotScheduler

#: The causes the gate gives (module docstring: why the queue
#: stands), and what a slot is doing.
_POOL_CAUSES = frozenset(("no_pages", "no_window_pages", "no_state_slots"))
SLOT_STATES = ("decoding", "prefilling", "empty_queued", "empty_idle")

__all__ = [
    "EngineConfig",
    "InferenceEngine",
    "TokenStream",
    "PolicyTicket",
    "BatchProgram",
    "EngineOverloaded",
    "EngineDead",
]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine admission/cache knobs (README "Paged KV & prefix
    caching")."""

    #: Decode-batch width = max concurrently-decoding sequences. With
    #: the paged cache this is DECOUPLED from KV memory: extra slots
    #: cost one block-table row + one logits row, not max_len of KV.
    slots: int = 4
    #: Per-REQUEST KV cap; prompt_len + max_new_tokens must fit. No
    #: longer a per-slot memory reservation — just the admission bound
    #: and the logical block-table width.
    max_len: int = 256
    #: Prefill chunk length. Long prompts prefill chunk-by-chunk
    #: interleaved with decode steps; a prompt's last chunk pads to
    #: this, its half or its quarter (kv_slots.bucket_for).
    prefill_chunk: int = 32
    #: KV block (page) length in tokens; 0 = auto (largest divisor of
    #: prefill_chunk up to 16). Must divide prefill_chunk and max_len.
    kv_block_len: int = 0
    #: Physical KV pool size in blocks (one extra is reserved as the
    #: null block); 0 = auto: slots x max_len worth — the same memory
    #: the PR 10 arenas held, now shared on demand.
    kv_blocks: int = 0
    #: Prefix caching: full prompt blocks register under their exact
    #: token prefix; a later request with the same prefix pins the
    #: blocks and skips prefill for them. Kill switch (also
    #: RT_serve_prefix_cache_enabled via build_llm_app).
    prefix_cache: bool = True
    #: Waiting-queue bound; past it submit() raises EngineOverloaded.
    #: Size it so worst-case queue wait stays under the serve layer's
    #: 60 s per-chunk stream timeout (≈ max_waiting x max_new_tokens
    #: / batched-tokens-per-s) — a deeper queue just converts shed-
    #: fast errors into slow client timeouts that waste a slot.
    max_waiting: int = 64
    #: Default per-request token budget (requests may pass their own).
    max_new_tokens: int = 64
    #: Engine-level sampling statics (0.0 = greedy).
    temperature: float = 0.0
    top_k: int = 0
    #: Default EOS token id (-1 = none); requests may override.
    eos_token: int = -1
    #: RNG seed for sampled decoding (ignored when greedy).
    seed: int = 0
    #: Idle-loop park time waiting for work.
    idle_wait_s: float = 0.02
    #: Bound on pending policy-path rows (submit_policy sheds with
    #: EngineOverloaded past it); only meaningful with a `program`.
    max_policy_rows: int = 4096


class _Request:
    __slots__ = (
        "request_id", "prompt", "max_new_tokens", "eos_token",
        "out", "cancelled", "submitted_ts", "first_token_ts",
        "emitted", "slot", "bucket", "offset", "padded",
        "prefix_keys", "total_blocks", "block_ids", "n_shared",
        "skip", "gen", "submitted_ns", "admitted_ts", "decoding_ts",
        "trace_parent", "serve_request_id", "table", "dispatched",
        "window_copy", "state_read", "queue_snap", "queue_cause_ms",
    )

    def __init__(
        self,
        request_id: str,
        prompt: List[int],
        max_new_tokens: int,
        eos_token: int,
    ):
        self.request_id = request_id
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.eos_token = eos_token
        #: Consumer stream: ("tok", id) | ("end", reason) |
        #: ("err", exc). Unbounded — the engine must never block on a
        #: slow consumer (that would head-of-line block the whole
        #: decode batch); depth is bounded in practice by max_new.
        self.out: "queue.Queue" = queue.Queue()
        self.cancelled = threading.Event()
        self.submitted_ts = time.perf_counter()
        self.first_token_ts: Optional[float] = None
        self.emitted = 0
        # What the `engine.request` span is made of when the request
        # finishes: epoch start, slot granted, prefill done (both
        # perf_counter, None if never reached), and the caller's span
        # and serve request id as they stood at submit().
        self.submitted_ns = time.time_ns()
        self.admitted_ts: Optional[float] = None
        self.decoding_ts: Optional[float] = None
        #: `queue_ms` as it stood at `submitted_ts`, and from admission
        #: what it gained since: this request's wait by cause.
        self.queue_snap: Optional[Dict[str, float]] = None
        self.queue_cause_ms: Dict[str, float] = {}
        self.trace_parent: Optional[dict] = None
        self.serve_request_id = ""
        # prefill progress (engine thread only)
        self.slot: Optional[int] = None
        self.bucket = 0
        self.offset = 0
        self.padded = None
        # paged-cache bookkeeping
        self.prefix_keys: List[tuple] = []
        self.total_blocks = 0
        self.block_ids: List[int] = []
        #: (from pages, to pages) of a prefix hit's window tail, until
        #: it is dispatched (models with window layers)
        self.window_copy = None
        #: The snapshot's state slot a prefix hit's first chunk starts
        #: from, until that chunk is dispatched (models with conv
        #: layers, llm/kv_state.py)
        self.state_read = None
        self.n_shared = 0
        self.skip = 0
        #: The request's table row on the device, [1, width], uploaded
        #: once at admission: what every chunk of its prompt takes.
        self.table = None
        #: Decode steps dispatched with this row alive, retired or
        #: not: the host knows a budget's end without seeing a token.
        self.dispatched = 0
        #: Weight generation pinned at ADMISSION (None until then):
        #: the request prefils and decodes on this generation to
        #: completion even if update_weights lands mid-stream.
        self.gen: Optional[int] = None


class _ChunkInFlight:
    """A prefill chunk the loop dispatched and has not retired."""

    __slots__ = ("started", "fence", "t0")

    def __init__(self, started: Optional[_Request], fence, t0: float):
        #: The request whose row began to decode behind this chunk, its
        #: prompt's last; None for any other chunk.
        self.started = started
        #: Ready when the chunk is (`generate.finish_chunk`); a MoE
        #: config's picks of the chunk.
        self.fence = fence
        self.t0 = t0


class _StepInFlight:
    """A decode step the loop dispatched and has not retired."""

    __slots__ = ("rows", "fetch", "state", "t0")

    def __init__(self, rows, fetch, state, t0: float):
        #: (slot, request) of the rows the HOST held alive at dispatch.
        #: The device may know better: a row that met its EOS in the
        #: step before was dead in this one, and is skipped at
        #: retirement because its request has been released by then.
        self.rows = rows
        #: The step's tokens, the step counter, a MoE config's picks.
        self.fetch = fetch
        #: The device's step state as this step left it.
        self.state = state
        self.t0 = t0


class TokenStream:
    """Consumer side of one request: iterate token ids as they are
    sampled. Ends at EOS/budget/cancel; raises if the engine failed
    the request. `finish_reason` is set once exhausted."""

    def __init__(self, engine: "InferenceEngine", req: _Request):
        self._engine = engine
        self._req = req
        self.finish_reason: Optional[str] = None

    @property
    def request_id(self) -> str:
        return self._req.request_id

    @property
    def first_token_ts(self) -> Optional[float]:
        """The loop's `perf_counter` when it emitted this request's
        first token; None until then."""
        return self._req.first_token_ts

    def __iter__(self) -> Iterator[int]:
        return self

    def __next__(self) -> int:
        if self.finish_reason is not None:
            raise StopIteration
        while True:
            try:
                kind, value = self._req.out.get(timeout=1.0)
                break
            except queue.Empty:
                # Belt-and-braces: a dead engine fails every request
                # with a sentinel, but if this request somehow missed
                # one the consumer must raise, not hang forever.
                if (
                    self._engine._dead is not None
                    and self._req.out.empty()
                ):
                    self.finish_reason = "error"
                    raise EngineDead(
                        "engine died mid-stream"
                    ) from self._engine._dead
        if kind == "tok":
            return value
        if kind == "end":
            self.finish_reason = value
            raise StopIteration
        self.finish_reason = "error"
        raise value

    def cancel(self) -> None:
        self._engine.cancel(self._req.request_id)


class BatchProgram:
    """Pluggable batch-program hook for the engine's policy path.

    A program turns one PADDED row batch of inputs into a dict of
    per-row output arrays with ONE (jitted) call; the engine's step
    loop owns batching — it coalesces every pending `submit_policy`
    request into the smallest bucket that fits and scatters the
    output rows back to their tickets. Subclasses (e.g.
    rl.dataflow.PolicyProgram) override `run`; `buckets` is the
    ascending set of padded batch sizes (the compile-once shape set,
    exactly like the prefill length buckets on the LLM path).
    """

    #: Ascending padded batch sizes; a single submit may not exceed
    #: buckets[-1] rows.
    buckets: tuple = (8, 16, 32, 64, 128, 256)

    def bucket_for(self, rows: int) -> int:
        for b in self.buckets:
            if rows <= b:
                return b
        return self.buckets[-1]

    def run(self, params, inputs, key) -> Dict[str, Any]:
        """(params, padded inputs [bucket, ...], PRNG key) -> dict of
        [bucket, ...] output arrays. Must be shape-stable per bucket
        (jit compiles once per bucket)."""
        raise NotImplementedError


class _PolicyRequest:
    __slots__ = (
        "inputs", "n", "done", "result", "error", "version",
        "submitted_ts",
    )

    def __init__(self, inputs: np.ndarray):
        self.inputs = inputs
        self.n = int(len(inputs))
        self.done = threading.Event()
        self.result: Optional[Dict[str, np.ndarray]] = None
        self.error: Optional[BaseException] = None
        self.version: Optional[int] = None
        self.submitted_ts = time.perf_counter()


class PolicyTicket:
    """Consumer side of one policy-path request: `result()` blocks
    until the engine's step loop has served the rows (raising, never
    hanging, if the engine dies first). `version` is the weight
    version the reply was computed with — the staleness signal the
    RL dataflow's `max_weight_lag` throttle reads."""

    def __init__(self, engine: "InferenceEngine", req: _PolicyRequest):
        self._engine = engine
        self._req = req

    @property
    def version(self) -> Optional[int]:
        return self._req.version

    def result(
        self, timeout: Optional[float] = None
    ) -> Dict[str, np.ndarray]:
        deadline = (
            None if timeout is None else time.perf_counter() + timeout
        )
        while True:
            wait = 1.0
            if deadline is not None:
                wait = min(wait, deadline - time.perf_counter())
                if wait <= 0:
                    raise TimeoutError(
                        "policy request not served in time"
                    )
            if self._req.done.wait(wait):
                break
            # Belt-and-braces (same contract as TokenStream): a dead
            # engine fails every ticket, but if this one somehow
            # missed the sentinel the consumer must raise, not hang.
            if (
                self._engine._dead is not None
                and not self._req.done.is_set()
            ):
                raise EngineDead(
                    "engine died with policy request pending"
                ) from self._engine._dead
        if self._req.error is not None:
            raise self._req.error
        assert self._req.result is not None
        return self._req.result


class InferenceEngine:
    def __init__(
        self,
        params: Dict[str, Any],
        cfg,
        engine_config: Optional[EngineConfig] = None,
        *,
        family: str = "",
        app: str = "",
        deployment: str = "",
        program: Optional[BatchProgram] = None,
    ):
        import jax

        ec = engine_config or EngineConfig()
        self.params = params
        self.cfg = cfg
        self.config = ec
        self.family = family
        self._program = program
        if cfg is None and program is None:
            raise ValueError(
                "cfg=None (policy-only engine) requires a `program`"
            )
        self._tags = {
            "app": app, "deployment": deployment,
            "family": family or "default",
        }
        self._lock = threading.Lock()
        self._wake = threading.Event()
        # Versioned weight generations (drainless sync): generation
        # index -> {version, params, refs}. `refs` counts the LLM
        # requests pinned at admission; a non-latest generation is
        # dropped the moment its count returns to zero. The policy
        # path always reads the latest generation and pins nothing
        # (one batch = one forward, no stream to keep token-exact).
        self._gens: Dict[int, Dict[str, Any]] = {
            0: {"version": 0, "params": params, "refs": 0}
        }
        self._gen_latest = 0
        self._weight_version = 0
        if cfg is not None:
            self._kv = PagedKVCache.for_engine(
                cfg, slots=ec.slots, max_len=ec.max_len,
                prefill_chunk=ec.prefill_chunk,
                kv_block_len=ec.kv_block_len, kv_blocks=ec.kv_blocks,
            )
            self._sched = SlotScheduler(ec.slots, ec.max_waiting)
            # Keys of one attention tile in a decode step, and whether
            # the step's kernel reads each cache's pool where it lies
            # (for the `kv_keys_read` counter).
            from ..models.generate import paged_tile_keys, step_reads_in_place

            self._kv_in_place = step_reads_in_place(cfg, self._kv.pool)
            self._kv_tile_keys = paged_tile_keys(
                self._kv.block_len, self._kv.max_blocks, 1,
                self._kv_in_place.get("full", False),
            )
        else:
            self._kv = None
            self._sched = None
        # Per-slot decode state: on the device (`_state`, advanced by
        # the step program; `_last_logits`) and mirrored here in numpy
        # for the allocator, the counters and `stats()`. The mirrors
        # follow the device one retirement behind.
        import jax.numpy as jnp

        self._steps = 0
        if cfg is not None:
            self._positions = np.zeros(ec.slots, np.int32)
            self._alive = np.zeros(ec.slots, bool)
            self._eos = np.full(ec.slots, -1, np.int32)
            self._budget = np.zeros(ec.slots, np.int32)
            # (every slot's table rows as the cache lays them out:
            # `tables`, and a window pool's `window_rings`)
            self._mirror = self._kv.host_rows([None] * ec.slots)
            self._tables = self._mirror["tables"]
            self._last_logits = jnp.zeros(
                (ec.slots, cfg.vocab_size), jnp.float32
            )
            self._null_row = self._kv.row_table(0, None)
            self._push_state()
        # Programs dispatched and not retired, oldest first, and the
        # clock at the last retirement (where the next program's
        # fenced timer starts if it was dispatched before that).
        self._inflight: "deque" = deque()
        self._retired_ts = 0.0
        self._programs = 0
        self._programs_ahead = 0
        self._state_patches = 0
        self._pipeline_drains = 0
        self._base_key = jax.random.PRNGKey(ec.seed)
        # Where this engine's programs run, as JAX reports it from
        # inside this process: every serving number is read against
        # it (engine_stats, /api/serve).
        device = jax.devices()[0]
        self._device = {
            "platform": device.platform,
            "device_kind": device.device_kind,
            "devices": len(jax.devices()),
        }
        # The loop's own clock: milliseconds per phase and iterations,
        # cumulative, written by the loop's thread once an iteration
        # and read under the lock by stats(); and two exact counters
        # taken at admission.
        self._loop_ms: Dict[str, float] = {}
        self._loop_iterations = 0
        self._admitted = 0
        self._admit_wait_ms_total = 0.0
        # The queue by cause and slot-time by state (module
        # docstring): the standing cause and the slots in each state
        # as `_settle_locked` left them, and the reading the clocks
        # stand at.
        self._queue_cause: Optional[str] = None
        self._gate_refusal: Optional[str] = None
        self._queue_ms = dict.fromkeys(QUEUE_CAUSES, 0.0)
        self._admit_wait_by_cause = dict.fromkeys(QUEUE_CAUSES, 0.0)
        self._slot_ms = dict.fromkeys(SLOT_STATES, 0.0)
        self._slots_by_state = (0, 0, 0, ec.slots if cfg is not None else 0)
        self._acct_t0 = self._acct_ts = time.perf_counter()
        self._first_tokens = 0
        self._prefill_ms_total = 0.0
        # How much of what the chunks compute the prompts need: chunks
        # dispatched, those at a shape under `prefill_chunk`, those
        # dispatched in an iteration that had dispatched one already
        # (behind a short last chunk), the positions they span (padding
        # too), and the positions the admitted prompts had beyond their
        # prefix hits.
        self._prefill_chunks = 0
        self._prefill_short_chunks = 0
        self._prefill_joined_chunks = 0
        self._prefill_tokens_computed = 0
        self._prefill_tokens_needed = 0
        # What decode's attention touches, per step, from the lengths
        # the host holds: keys inside alive rows' `valid_len`, and
        # keys the step's program attends over all rows (whole tiles
        # to the longest alive row, the rule the program itself runs).
        self._kv_keys_live = 0
        self._kv_keys_read = 0
        # Where the model has window layers (`LlamaConfig.layer_kinds`):
        # the keys they walked, in steps and chunks, and what they
        # would have walked over the whole row (what the full layers
        # did walk); ring pages overwritten in place; each pool's peak
        # of pinned pages; and what the full pool alone would have let
        # prefix hits skip (beside `prefix_tokens_saved`, what was
        # skipped: the rest went with an evicted window tail).
        self._window: Dict[str, int] = dict.fromkeys(
            (
                "swa_keys_read", "swa_keys_unwindowed",
                "window_pages_recycled", "window_pool_used",
                "full_pool_used", "prefix_tokens_full_hit",
            ) if self._kv is not None and self._kv.window is not None
            else (),
            0,
        )
        # Where the model has conv layers (a state slot a row,
        # llm/kv_state.py, whose `stats()` holds the snapshots'
        # counters): what the pages alone would have let prefix hits
        # skip, counted as beside a window pool (the rest went with an
        # evicted snapshot).
        if self._kv is not None and self._kv.state is not None:
            self._window["prefix_tokens_full_hit"] = 0
        # What the expert layers did, from the picks per layer and
        # expert each paged forward leaves in the pool (MoE configs
        # only; a dense engine has none of these keys in stats()).
        self._moe: Dict[str, int] = dict.fromkeys(
            (
                "moe_picks_prefill", "moe_chunk_layers",
                "moe_chunk_max_load", "moe_chunk_experts",
                "moe_picks_decode", "moe_step_layers",
                "moe_experts_touched",
            ) if cfg is not None and cfg.moe_experts else (),
            0,
        )
        # What only some configurations' forwards count, by the pool's
        # counter leaves: the picks the router made over ALL its
        # outputs where the experts held here are fewer (the `moe_picks`
        # above are then the picks that met a held expert), the expert
        # layers' forwards whose held picks passed the row budget and
        # took every row (ops/moe.py `held_row_budget`), and the
        # (query, key) pairs a learned selection could see and kept.
        if self._kv is not None:
            if "moe_routed" in self._kv.pool:
                self._moe["moe_picks_routed"] = 0
            if "moe_spilled" in self._kv.pool:
                self._moe["moe_forwards_spilled"] = 0
            if "dsa_counts" in self._kv.pool:
                # (the chunks' share apart: theirs is the attention
                # kernel's work, ops/selected_attention.py)
                self._moe.update(
                    dsa_keys_visible=0, dsa_keys_selected=0,
                    dsa_chunk_keys_visible=0, dsa_chunk_keys_selected=0,
                )
        self._prefilling: Optional[_Request] = None
        self._by_id: Dict[str, _Request] = {}
        self._policy_pending: "deque[_PolicyRequest]" = deque()
        self._policy_rows_pending = 0
        self._policy_steps = 0
        self._policy_rows_served = 0
        self._tokens_emitted = 0
        self._requests_done = 0
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._prefix_tokens_saved = 0
        self._dead: Optional[BaseException] = None
        self._stopping = False
        self._thread = threading.Thread(
            target=self._run,
            daemon=True,
            name=f"llm-engine:{family or 'default'}",
        )
        self._thread.start()
        self._observe_device()

    # -- public --------------------------------------------------------
    def submit(
        self,
        prompt: List[int],
        *,
        max_new_tokens: Optional[int] = None,
        eos_token: Optional[int] = None,
        request_id: Optional[str] = None,
    ) -> TokenStream:
        ec = self.config
        if self._kv is None:
            raise ValueError(
                "policy-only engine (cfg=None) has no LLM path; use "
                "submit_policy()"
            )
        max_new = int(
            ec.max_new_tokens if max_new_tokens is None
            else max_new_tokens
        )
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        prompt = [int(t) for t in prompt]
        bucket = self._kv.bucket_for(len(prompt))
        if len(prompt) + max_new > ec.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new}) "
                f"exceeds per-request capacity max_len={ec.max_len}"
            )
        if eos_token is not None and eos_token != int(eos_token):
            raise ValueError(
                f"eos_token must be integral, got {eos_token!r}"
            )
        total_blocks = self._kv.blocks_for(
            max(bucket, len(prompt) + max_new)
        )
        if total_blocks > self._kv.full.capacity():
            # OOM is a SHED, not a crash or an unserviceable queue
            # entry: this request could never be admitted.
            raise EngineOverloaded(
                f"request needs {total_blocks} KV blocks but the pool "
                f"holds {self._kv.full.capacity()}; shed"
            )
        req = _Request(
            request_id or uuid.uuid4().hex[:16],
            prompt,
            max_new,
            ec.eos_token if eos_token is None else int(eos_token),
        )
        req.bucket = bucket
        req.total_blocks = total_blocks
        from ..serve.observability import (
            get_request_id,
            observe_handler_submit,
        )

        observe_handler_submit(req.submitted_ts)
        req.trace_parent = tracing.inject_context()
        req.serve_request_id = get_request_id()  # "" outside serve
        if ec.prefix_cache:
            req.prefix_keys = self._kv.prefix_keys(prompt)
        with self._lock:
            if self._dead is not None or self._stopping:
                raise EngineDead(
                    "engine is shut down"
                ) from self._dead
            if req.request_id in self._by_id:
                raise ValueError(
                    f"duplicate request_id {req.request_id!r}"
                )
            self._sched.submit(req)
            self._by_id[req.request_id] = req
            # The request's own reading serves the clocks, unless a
            # transition has read the clock since: what lies between
            # the two goes to the cause that stands as it queues.
            now = max(req.submitted_ts, self._acct_ts)
            self._tick_locked(now)
            self._settle_locked()
            req.queue_snap = dict(self._queue_ms)
            req.queue_snap[self._queue_cause] -= (
                now - req.submitted_ts
            ) * 1e3
        self._wake.set()
        return TokenStream(self, req)

    def cancel(self, request_id: str) -> bool:
        """Cancel a queued or in-flight request. Queued requests end
        immediately; running ones are reaped (slot + blocks freed) at
        the top of the next engine iteration — mid-decode, not at
        stream end."""
        with self._lock:
            req = self._by_id.get(request_id)
            if req is None:
                return False
            req.cancelled.set()
            if self._sched.remove_waiting(req):
                # Its share of the wait goes with it; the queue's
                # cause is its next head's.
                self._tick_locked(time.perf_counter())
                self._finish_locked(req, "cancelled")
                self._settle_locked()
        self._wake.set()
        return True

    def update_weights(
        self, params: Dict[str, Any], *, version: Optional[int] = None
    ) -> int:
        """Install a new weight generation WITHOUT draining the
        engine (ISSUE 13 tentpole): in-flight LLM requests keep the
        generation they were admitted under and finish token-exact on
        it; the next admission — and the next policy batch — serves
        the new weights. Returns the installed weight version
        (monotonic; pass `version` to carry the learner's own
        numbering onto /metrics)."""
        if version is not None and version != int(version):
            raise ValueError(
                f"version must be integral, got {version!r}"
            )
        with self._lock:
            if self._dead is not None or self._stopping:
                raise EngineDead(
                    "engine is shut down"
                ) from self._dead
            v = (
                int(version) if version is not None
                else self._weight_version + 1
            )
            if v <= self._weight_version:
                raise ValueError(
                    f"weight version must increase: got {v}, "
                    f"serving {self._weight_version}"
                )
            self._gen_latest += 1
            self._gens[self._gen_latest] = {
                "version": v, "params": params, "refs": 0,
            }
            self._weight_version = v
            self.params = params
            self._prune_gens_locked()
        self._wake.set()
        return v

    def _prune_gens_locked(self) -> None:
        for gen in [
            g for g, e in self._gens.items()
            if g != self._gen_latest and e["refs"] <= 0
        ]:
            del self._gens[gen]

    def submit_policy(self, inputs) -> PolicyTicket:
        """Queue one row batch for the policy batch program; the step
        loop coalesces everything pending into one padded bucket and
        runs the program's jitted forward once. Ragged per-env
        requests from many callers batch exactly like ragged chat
        traffic on the LLM path."""
        if self._program is None:
            raise ValueError(
                "engine was built without a policy batch program"
            )
        inputs = np.asarray(inputs)
        if inputs.ndim < 1 or len(inputs) < 1:
            raise ValueError("submit_policy needs >= 1 input row")
        if len(inputs) > self._program.buckets[-1]:
            raise ValueError(
                f"policy batch of {len(inputs)} rows exceeds the "
                f"program's largest bucket "
                f"{self._program.buckets[-1]}; split it"
            )
        req = _PolicyRequest(inputs)
        with self._lock:
            if self._dead is not None or self._stopping:
                raise EngineDead(
                    "engine is shut down"
                ) from self._dead
            if (
                self._policy_rows_pending + req.n
                > self.config.max_policy_rows
            ):
                raise EngineOverloaded(
                    f"policy backlog full "
                    f"({self.config.max_policy_rows} rows); shed"
                )
            self._policy_pending.append(req)
            self._policy_rows_pending += req.n
        self._wake.set()
        return PolicyTicket(self, req)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out = (
                self._sched.stats() if self._sched is not None
                else {"slots_total": 0, "slots_used": 0, "waiting": 0}
            )
            if self._sched is not None:
                self._tick_locked(time.perf_counter())
            out.update(
                family=self.family,
                steps=self._steps,
                tokens_emitted=self._tokens_emitted,
                requests_done=self._requests_done,
                prefilling=self._prefilling is not None,
                prefix_hits=self._prefix_hits,
                prefix_misses=self._prefix_misses,
                prefix_tokens_saved=self._prefix_tokens_saved,
                weight_version=self._weight_version,
                weight_gens=len(self._gens),
                policy_steps=self._policy_steps,
                policy_rows_served=self._policy_rows_served,
                dead=self._dead is not None,
                # Where the loop's thread spent its time, by phase
                # (cumulative ms; the phases partition the loop's wall
                # time, so deltas over a window are shares of it), and
                # how long admitted requests waited for their slot.
                loop_ms=dict(self._loop_ms),
                loop_iterations=self._loop_iterations,
                admitted=self._admitted,
                admit_wait_ms_total=self._admit_wait_ms_total,
                # Why they waited, how long each cause has stood, and
                # what the slots did meanwhile (module docstring).
                admit_wait_by_cause_ms_total=dict(
                    self._admit_wait_by_cause
                ),
                queue_ms=dict(self._queue_ms),
                slot_ms=dict(self._slot_ms),
                # Admission to first token, of the requests that
                # reached one: the chunks of their prompts AND the
                # decode steps that ran between them.
                first_tokens=self._first_tokens,
                prefill_ms_total=self._prefill_ms_total,
                # Chunks dispatched, those shorter than
                # `prefill_chunk`, those that joined an iteration
                # behind another, the positions they span and the
                # positions the admitted prompts needed (a prompt less
                # its prefix hit): the rest is padding.
                prefill_chunks=self._prefill_chunks,
                prefill_short_chunks=self._prefill_short_chunks,
                prefill_joined_chunks=self._prefill_joined_chunks,
                prefill_tokens_computed=self._prefill_tokens_computed,
                prefill_tokens_needed=self._prefill_tokens_needed,
                kv_keys_live=self._kv_keys_live,
                kv_keys_read=self._kv_keys_read,
                # The pipeline: chunks and steps dispatched, those
                # dispatched while an earlier one was not retired yet,
                # patches of the device's step state, and the times
                # the loop retired everything before going on (a
                # mixed-generation window, shutdown).
                programs=self._programs,
                programs_ahead=self._programs_ahead,
                state_patches=self._state_patches,
                pipeline_drains=self._pipeline_drains,
                **self._moe,
                **self._window,
                **self._device,
            )
            if self._kv is not None:
                # Compile counts of the programs the LLM path runs,
                # as the compile watch credits them: under the names
                # models/generate.py registers (one wrapper a
                # program; a second one here was credited nothing).
                # Process-wide, like the jitted programs themselves.
                # Each compiles once a shape: `prefill` once for
                # each of the last chunk's shapes, as the engine
                # starts, and `finish_chunk` once for them all (a
                # chunk hands it one row). Steady state after
                # warmup is a FIXED number —
                # movement under traffic is a recompile bug. (A
                # mixed-generation window runs a fifth,
                # `generate.paged_decode_step`.)
                out["compiles"] = {
                    kind: compile_watch.program_stats(
                        f"generate.{program}"
                    )
                    for kind, program in (
                        ("prefill", "paged_prefill"),
                        ("decode", "paged_engine_step"),
                        ("patch", "patch_step_slot"),
                        ("finish_chunk", "finish_chunk"),
                    )
                }
                out.update(
                    kv_block_len=self._kv.block_len,
                    **self._kv.full.stats(),
                )
                if self._kv.window is not None:
                    out.update(self._kv.window.stats())
                if self._kv.state is not None:
                    # (and the pages' bytes beside the states', held
                    # by rows or by the prefix cache)
                    pages = self._kv.full
                    out.update(
                        self._kv.state.stats(),
                        kv_bytes_in_use=self._kv.block_bytes
                        * (pages.used() + pages.cached()),
                    )
        return out

    def close(self) -> None:
        """Stop the loop and fail everything in flight (the multiplex
        LRU calls this on eviction). In-flight consumers get an ERROR,
        not a clean end — a truncated response must be detectable."""
        with self._lock:
            self._stopping = True
        self._wake.set()
        self._thread.join(timeout=30)
        with self._lock:
            if self._dead is None:
                self._dead = EngineDead("engine unloaded")
            self._fail_all_locked(
                EngineDead("engine unloaded with request in flight")
            )

    # Multiplex eviction hook (serve/multiplex.py looks for it).
    __serve_unload__ = close

    # -- engine loop ---------------------------------------------------
    def _run(self) -> None:
        """The loop is always in one `engine.*` phase or another: one
        `phase_timer` is open for the thread's whole life and every
        boundary is a `switch`, so the phases partition the loop's
        wall time (a bucket each, and an annotation in a running
        `jax.profiler` trace). The buckets are drained once an
        iteration into the totals `stats()` returns as `loop_ms`."""
        try:
            if self._kv is not None:
                # Before the first admission, in no phase or counter.
                self._warm_chunk_shapes()
            with phase_timer("engine.reap") as phase:
                self._phase = phase
                while True:
                    phase.switch("engine.reap")
                    self._drain_phases()
                    with self._lock:
                        stopping = self._stopping
                    if stopping:
                        self._drain()
                        return
                    worked = self._reap_cancelled()
                    if self._program is not None:
                        phase.switch("engine.policy")
                        worked = self._policy_step() or worked
                    if self._sched is not None:
                        # Prefill before decode: chunks until
                        # `prefill_chunk` tokens are dispatched (one
                        # whole chunk, or a prompt's short last chunk
                        # and the next prompt's first behind it), then
                        # the whole batch decodes one step, the rows
                        # those chunks started among them. All are
                        # only dispatched; what is retired
                        # after them is what the iteration before
                        # dispatched, so the device has this
                        # iteration's programs queued while the host
                        # emits that one's tokens.
                        held = len(self._inflight)
                        self._advance_prefill()
                        worked = self._decode() or worked
                        ahead = max(len(self._inflight) - held, 0)
                        worked = (
                            self._retire(keep=ahead) or ahead > 0
                            or worked
                        )
                    if not worked:
                        phase.switch("engine.idle")
                        self._wake.wait(self.config.idle_wait_s)
                        self._wake.clear()
        except BaseException as e:  # noqa: BLE001 — forwarded to
            # every consumer; the loop must never die silently.
            failure = EngineDead(f"engine loop died: {e!r}")
            failure.__cause__ = e
            with self._lock:
                self._dead = e
                self._fail_all_locked(failure)

    def _drain_phases(self) -> None:
        phases = take_phases()
        with self._lock:
            self._loop_iterations += 1
            for name, ms in phases.items():
                # Only the loop's own: this thread's bucket also
                # collects `compile_ms` from the compile watch.
                if name.startswith("engine."):
                    self._loop_ms[name] = (
                        self._loop_ms.get(name, 0.0) + ms
                    )

    # -- policy path ---------------------------------------------------
    def _policy_step(self) -> bool:
        """Serve every pending policy request that fits the largest
        bucket in ONE padded batched forward on the LATEST weight
        generation; scatter output rows back to their tickets. Policy
        batches go before the LLM path: their callers are blocked
        env-runner threads, and one batched forward is cheap next to
        a decode step over the full slot batch."""
        with self._lock:
            if not self._policy_pending:
                return False
            cap = self._program.buckets[-1]
            batch: List[_PolicyRequest] = []
            rows = 0
            while (
                self._policy_pending
                and rows + self._policy_pending[0].n <= cap
            ):
                req = self._policy_pending.popleft()
                self._policy_rows_pending -= req.n
                batch.append(req)
                rows += req.n
            entry = self._gens[self._gen_latest]
            params, version = entry["params"], entry["version"]
        import jax

        bucket = self._program.bucket_for(rows)
        sample = batch[0].inputs
        padded = np.zeros(
            (bucket, *sample.shape[1:]), dtype=sample.dtype
        )
        cursor = 0
        for req in batch:
            padded[cursor:cursor + req.n] = req.inputs
            cursor += req.n
        key = jax.random.fold_in(
            jax.random.fold_in(self._base_key, 0x9E37),
            self._policy_steps,
        )
        try:
            outs = self._program.run(params, padded, key)
            host = {k: np.asarray(v) for k, v in outs.items()}
        except BaseException as e:
            # A program failure fails THIS batch's tickets (the
            # callers must not hang) and then the loop: a broken
            # program cannot serve the next batch either.
            for req in batch:
                req.error = EngineDead(
                    f"policy batch program failed: {e!r}"
                )
                req.error.__cause__ = e
                req.done.set()
            raise
        cursor = 0
        for req in batch:
            req.result = {
                k: v[cursor:cursor + req.n] for k, v in host.items()
            }
            req.version = version
            req.done.set()
            cursor += req.n
        self._policy_steps += 1
        self._policy_rows_served += rows
        return True

    # -- cancellation / completion ------------------------------------
    def _reap_cancelled(self) -> bool:
        if self._sched is None:
            return False
        reaped = []
        with self._lock:
            # The prefilling request is ALSO in sched.running (its
            # slot was claimed at admission), so one pass releases it
            # too, once (release() on an already-freed slot raises and
            # would kill the whole loop).
            if (
                self._prefilling is not None
                and self._prefilling.cancelled.is_set()
            ):
                self._prefilling = None
            for slot, req in list(self._sched.running.items()):
                if req.cancelled.is_set():
                    self._release_locked(slot, req, "cancelled")
                    reaped.append(slot)
        # The device cannot know of a cancellation: tell it, before
        # the next step is dispatched. A step in flight still decodes
        # the row; its token is dropped at retirement, and what it
        # writes into the released blocks lands before anything their
        # next owner dispatches.
        for slot in reaped:
            self._patch_slot(slot, self._null_row)
        return bool(reaped)

    def _release_locked(
        self, slot: int, req: _Request, reason: str
    ) -> None:
        """Give back a request's slot and blocks, in the mirrors. An
        EOS or a spent budget the device has applied itself already
        (its table row there goes stale, which a dead row's never
        matters: the step program reads the null block for it); a
        cancellation the caller patches in (`_patch_slot`)."""
        self._tick_locked(time.perf_counter())
        self._sched.release(slot)
        self._alive[slot] = False
        for rows in self._mirror.values():
            rows[slot, :] = NULL_BLOCK
        if req.block_ids:
            if self._kv.window is not None:
                # Blocks the row wrote beyond its ring's pages each
                # overwrote one in place.
                written = max(req.offset, int(self._positions[slot]))
                self._window["window_pages_recycled"] += max(
                    0, self._kv.blocks_for(written)
                    - len(req.block_ids["window"]),
                )
            # Unpin: full prompt blocks stay in the prefix cache
            # (refcount 0, LRU-evictable); private blocks go back to
            # the free list. block_ids cleared so no path can double-
            # free (the allocator would raise and kill the loop).
            # Programs still queued may write these blocks (the step
            # in flight when a row is cancelled): safe, by dispatch
            # order (module docstring).
            self._kv.alloc.release(req.block_ids)
            req.block_ids = []
        self._unpin_gen_locked(req)
        self._finish_locked(req, reason)
        self._settle_locked()

    # -- the queue by cause, slot-time by state (module docstring) -----
    def _tick_locked(self, now: float) -> None:
        """Advance the cause and slot clocks to `now`, by the state
        `_settle_locked` left: what stood SINCE the reading before."""
        dt = (now - self._acct_ts) * 1e3
        if dt <= 0.0:
            return
        self._acct_ts = now
        if self._queue_cause is not None:
            self._queue_ms[self._queue_cause] += dt
        for state, n in zip(SLOT_STATES, self._slots_by_state):
            if n:
                self._slot_ms[state] += n * dt

    def _settle_locked(self, refused: Optional[str] = None) -> None:
        """After a transition: the cause the queue stands for from
        here on, in the module docstring's order, and the slots by
        state. `refused` is the gate's word of the attempt just made;
        without an attempt a pool's refusal stands as it stood."""
        sched = self._sched
        waiting = len(sched.waiting)
        prefilling = self._prefilling is not None
        if not waiting:
            cause = None
        elif not sched.free_slots:
            cause = "no_slot"
        elif prefilling:
            cause = "behind_prefill"
        elif refused is not None:
            cause = refused
        elif self._queue_cause in _POOL_CAUSES:
            cause = self._queue_cause
        else:
            cause = "admissible"
        self._queue_cause = cause
        used, empty = len(sched.running), sched.free_slots
        self._slots_by_state = (
            used - prefilling, int(prefilling),
            empty if waiting else 0, 0 if waiting else empty,
        )

    def _unpin_gen_locked(self, req: _Request) -> None:
        if req.gen is None:
            return
        entry = self._gens.get(req.gen)
        req.gen = None
        if entry is not None:
            entry["refs"] -= 1
            self._prune_gens_locked()

    def _finish_locked(self, req: _Request, reason: str) -> None:
        self._by_id.pop(req.request_id, None)
        self._requests_done += 1
        req.out.put(("end", reason))
        self._record_request_span(req, reason)
        # Push occupancy from the retirement itself: cancellation/
        # drain may leave no alive rows, so no decode step would ever
        # publish the freed slots (the gauge throttle keeps this
        # cheap; a slots_used zero-crossing always goes out).
        self._observe_occupancy()

    def _record_request_span(self, req: _Request, reason: str) -> None:
        """One `engine.request` span per request, when it ends (never
        per token): a child of the span that was current at submit()
        (`serve.handle` under serve), so an HTTP request's spans share
        one trace id from proxy to engine. Recording is one append to
        the tracing ring; the metrics flusher ships it."""
        now = time.perf_counter()
        admitted = req.admitted_ts if req.admitted_ts is not None else now
        decoding = req.decoding_ts if req.decoding_ts is not None else now
        first = (
            {} if req.first_token_ts is None else {"first_token_ms": round(
                (req.first_token_ts - req.submitted_ts) * 1e3, 3
            )}
        )
        tracing.record_span(
            "engine.request",
            req.submitted_ns,
            time.time_ns(),
            req.trace_parent,
            request_id=req.serve_request_id,
            engine_request_id=req.request_id,
            family=self._tags["family"],
            queue_ms=round((admitted - req.submitted_ts) * 1e3, 3),
            queue_cause_ms=",".join(
                f"{cause}={ms:.3f}"
                for cause, ms in req.queue_cause_ms.items()
            ),
            prefill_ms=round((decoding - admitted) * 1e3, 3),
            decode_ms=round((now - decoding) * 1e3, 3),
            tokens=req.emitted,
            finish_reason=reason,
            **first,
        )

    def _fail_all_locked(self, error: BaseException) -> None:
        if self._prefilling is not None:
            doomed = [self._prefilling]
            self._prefilling = None
        else:
            doomed = []
        if self._sched is not None:
            doomed.extend(self._sched.drain())
            self._alive[:] = False
            self._tables[:, :] = NULL_BLOCK
        for req in doomed:
            if req.block_ids:
                try:
                    self._kv.alloc.release(req.block_ids)
                except Exception:
                    pass  # dying anyway; never mask the real failure
                req.block_ids = []
            req.gen = None
            self._by_id.pop(req.request_id, None)
            req.out.put(("err", error))
            self._record_request_span(req, "error")
        # Pending policy tickets fail FAST too: their callers are
        # synchronously blocked env-runner threads — an engine death
        # must turn into EngineDead there, never a hang.
        while self._policy_pending:
            preq = self._policy_pending.popleft()
            self._policy_rows_pending -= preq.n
            preq.error = error
            preq.done.set()
        if self._sched is not None:
            self._tick_locked(time.perf_counter())
            self._settle_locked()
        self._observe_occupancy()

    # -- admission / block allocation ---------------------------------
    def _skip_for(self, req: _Request, hit_blocks: int) -> int:
        """Prefill tokens a prefix hit lets this request skip: capped
        at len(prompt) - 1 (the LAST prompt token is always computed —
        its logits seed decoding) and rounded down to a whole prefill
        chunk, so at most where the prompt's last chunk starts: a hit
        runs the last chunk a miss runs, same positions, same shape,
        same program, and their greedy tokens are equal. (A skip to
        the block would leave a hit a shorter last chunk over other
        positions: another program than the miss's.)"""
        bl = self._kv.block_len
        chunk = self._kv.prefill_chunk
        usable = min(hit_blocks * bl, len(req.prompt) - 1)
        return (usable // chunk) * chunk

    def _window_skip(self, req: _Request, skip: int) -> int:
        """What of `_skip_for`'s tokens the window layers can skip too:
        the longest whole-chunk boundary whose tail, the window's worth
        of keys before it, the window pool still holds
        (llm/kv_window.py); all of it for a model without such
        layers."""
        window, state = self._kv.window, self._kv.state
        if window is not None and skip:
            skip = window.usable_skip(req.prefix_keys, skip)
        if state is not None and skip:
            # (and the conv layers: as far as a snapshot of their
            # state is still held, llm/kv_state.py)
            skip = state.usable_skip(req.prefix_keys, skip)
        return skip

    def _gate_locked(self, req: _Request) -> bool:
        """`admit_next`'s gate; the pool that refused stays in
        `_gate_refusal` for the cause clocks."""
        self._gate_refusal = self._gate_cause_locked(req)
        return self._gate_refusal is None

    def _gate_cause_locked(self, req: _Request) -> Optional[str]:
        """Admission gate: can the FIFO head get its blocks NOW (None)
        and, if not, which pool refuses it (a queue cause)? The
        reservation needs `total - skip` fresh blocks, and pinning the
        hit additionally consumes `cached` availability — only the
        hit blocks that are currently refcount-0 (cached-free) leave
        `available()` when pinned; hits already pinned by a live
        request are free to share. A gated admission can therefore
        never fail its reservation one line later, and sharing a
        LIVE request's prefix genuinely relaxes admission. With a
        window pool the gate covers both: the row's ring of window
        pages is reserved here and never grows (kv_window.py), and a
        hit reaches only as far as both pools can serve it."""
        alloc = self._kv.full
        hits = alloc.peek_prefix(req.prefix_keys)
        skip = self._window_skip(req, self._skip_for(req, hits))
        skip_blocks = skip // self._kv.block_len
        cached = alloc.peek_cached(req.prefix_keys, skip_blocks)
        if self._kv.window is not None and not self._kv.window.gate(
            req.prefix_keys, skip, req.total_blocks
        ):
            return "no_window_pages"
        if self._kv.state is not None and not self._kv.state.gate(
            req.prefix_keys, skip
        ):
            return "no_state_slots"
        if alloc.available() - cached < req.total_blocks - skip_blocks:
            return "no_pages"
        return None

    def _allocate_locked(self, req: _Request) -> None:
        """Pin the request's prefix-cache hit (if any) and reserve the
        rest of its pages; build its table row. Runs under the lock in
        the same critical section as the gate."""
        alloc = self._kv.full
        shared = alloc.match_prefix(req.prefix_keys)
        full_skip = self._skip_for(req, len(shared))
        skip = self._window_skip(req, full_skip)
        skip_blocks = skip // self._kv.block_len
        if len(shared) > skip_blocks:
            # Hit blocks beyond the chunk-aligned usable window: unpin
            # them again (they stay cached).
            alloc.release(shared[skip_blocks:])
            shared = shared[:skip_blocks]
        req.skip = skip
        req.offset = skip
        self._prefill_tokens_needed += len(req.prompt) - skip
        req.n_shared = skip_blocks
        req.block_ids = shared + alloc.reserve(
            req.total_blocks - skip_blocks
        )
        window = self._kv.window
        if window is not None:
            ring, req.window_copy = window.admit(
                req.prefix_keys, skip, req.total_blocks
            )
            req.block_ids = {"full": req.block_ids, "window": ring}
            counted = self._window
            for pool, pages in (("full", alloc), ("window", window.alloc)):
                counted[f"{pool}_pool_used"] = max(
                    counted[f"{pool}_pool_used"], pages.used()
                )
        state = self._kv.state
        if state is not None:
            own, req.state_read = state.admit(req.prefix_keys, skip)
            req.block_ids = {"full": req.block_ids, "state": own}
        if "prefix_tokens_full_hit" in self._window:
            # (what the pages alone would have let this hit skip)
            self._window["prefix_tokens_full_hit"] += full_skip
        for name, rows in self._kv.host_rows([req.block_ids]).items():
            self._mirror[name][req.slot] = rows[0]
        if skip:
            self._prefix_hits += 1
            self._prefix_tokens_saved += skip
        else:
            self._prefix_misses += 1
        self._observe_prefix(skip)

    # -- the device's step state --------------------------------------
    def _push_state(self) -> None:
        """Make the device's step state from the mirrors, whole: at
        start, and after a mixed-generation window's serial steps."""
        blocks = [None] * self.config.slots
        with self._lock:
            for slot, req in self._sched.running.items():
                blocks[slot] = req.block_ids
        self._state = self._kv.step_state(
            blocks, self._positions, self._alive, self._eos,
            self._budget, self._steps,
        )

    def _patch_slot(self, slot: int, table_row) -> None:
        """The device's row of `slot`: this table row, and dead."""
        from ..models.generate import patch_step_slot

        self._state = patch_step_slot(
            self._state, np.int32(slot), table_row
        )
        self._state_patches += 1

    def _dispatching(self) -> float:
        """Count a chunk or step about to be dispatched; -> the clock
        its fenced timer starts at."""
        self._programs += 1
        if self._inflight:
            self._programs_ahead += 1
        return time.perf_counter()

    def _owed_ms(self, t0: float) -> float:
        """A program's fenced time at its retirement (now): from the
        later of its dispatch and the retirement of the program before
        it. Programs run in dispatch order, so that is what the device
        owed THIS program, its wait behind the one before not counted
        twice."""
        now = time.perf_counter()
        ms = (now - max(t0, self._retired_ts)) * 1e3
        self._retired_ts = now
        return ms

    # -- prefill -------------------------------------------------------
    def _advance_prefill(self) -> None:
        """An iteration's prefill: chunks are dispatched, each behind
        the one before, WHILE those dispatched so far hold fewer than
        `prefill_chunk` tokens (module docstring, step 2). A whole
        chunk therefore ends it; a prompt's short last chunk leaves
        room, the prompt has left `_prefilling` by then, and the next
        call admits the FIFO head behind it."""
        dispatched = 0
        while dispatched < self.config.prefill_chunk:
            chunk = self._prefill_chunk(joined=dispatched > 0)
            if not chunk:
                return
            dispatched += chunk

    def _prefill_chunk(self, joined: bool) -> int:
        """Admit (if no prompt is prefilling) and dispatch the current
        prefill's next chunk, written straight into the request's
        pages, with `finish_chunk` behind it; neither is waited for.
        `joined`: this iteration has dispatched a chunk already. -> the
        chunk's tokens, 0 when there was nothing to do (an empty
        queue, no free slot, the gate's refusal)."""
        phase = self._phase
        phase.switch("engine.admit")
        with self._lock:
            req = self._prefilling
            admitting = req is None
            if admitting:
                self._gate_refusal = None
                admitted = self._sched.admit_next(
                    gate=self._gate_locked
                )
                if admitted is None:
                    if self._sched.waiting:
                        # The attempt's word on why the queue stands.
                        self._tick_locked(time.perf_counter())
                        self._settle_locked(self._gate_refusal)
                    return 0
                req, slot = admitted
                req.slot = slot
                req.admitted_ts = time.perf_counter()
                self._tick_locked(req.admitted_ts)
                self._admitted += 1
                self._admit_wait_ms_total += (
                    req.admitted_ts - req.submitted_ts
                ) * 1e3
                for cause, ms in self._queue_ms.items():
                    waited = ms - req.queue_snap[cause]
                    if waited:
                        req.queue_cause_ms[cause] = waited
                        self._admit_wait_by_cause[cause] += waited
                # Pin the weight generation at ADMISSION: everything
                # this request computes — every prefill chunk and
                # every decode step — uses these params, even if a
                # weight push lands mid-stream (drainless sync's
                # token-exactness contract).
                req.gen = self._gen_latest
                self._gens[req.gen]["refs"] += 1
                self._allocate_locked(req)
                self._prefilling = req
                self._settle_locked()
        slot = req.slot
        if admitting:
            req.table = self._kv.row_table(slot, req.block_ids)
            self._patch_slot(slot, req.table)
            if req.window_copy is not None:
                # A hit's window tail, from the prefix cache's pages
                # into the row's ring, before its first chunk.
                self._copy_window_pages(*req.window_copy)
                req.window_copy = None
        phase.switch("engine.prefill.prepare")
        if req.padded is None:
            padded = np.zeros((1, req.bucket), np.int32)
            padded[0, : len(req.prompt)] = req.prompt
            req.padded = padded
        # `prefill_chunk` tokens, or what the plan leaves the prompt's
        # last chunk (`bucket_for`: offsets are whole chunks, so only
        # the last is ever shorter).
        start = req.offset
        chunk = min(self.config.prefill_chunk, req.bucket - start)
        tokens = req.padded[:, start:start + chunk]
        # (read before a cancelled last chunk's release unpins them)
        params = self._gens[req.gen]["params"]
        table = req.table
        if self._kv.state is not None:
            table = self._state_table(req, start, chunk)
        req.offset += chunk
        last_chunk = req.offset >= req.bucket
        started = cancelled = False
        if last_chunk:
            # The host's side of a row's start happens HERE, at
            # dispatch, so that this iteration's decode step takes the
            # row along as it always did, and the next iteration
            # admits the next prompt.
            req.padded = None
            with self._lock:
                self._tick_locked(time.perf_counter())
                self._prefilling = None
                # Cancelled during the prompt: reap now rather than
                # decoding a dead row for one step.
                cancelled = req.cancelled.is_set()
                if cancelled:
                    self._release_locked(slot, req, "cancelled")
                else:
                    started = True
                    if self.config.prefix_cache:
                        # Publish the full prompt blocks this request
                        # computed (not the ones it shared) for future
                        # prefix hits; first writer wins on races.
                        # The chunk that fills the last of them is
                        # only queued: a request that hits them reads
                        # them in a program dispatched after it.
                        self._kv.publish(
                            req.block_ids, req.n_shared, req.prefix_keys
                        )
                    self._positions[slot] = len(req.prompt)
                    self._alive[slot] = True
                    self._budget[slot] = req.max_new_tokens
                    # An id no token can be ends no row, here as in
                    # `_emit`.
                    self._eos[slot] = (
                        req.eos_token
                        if 0 <= req.eos_token < self.cfg.vocab_size
                        else -1
                    )
                    self._settle_locked()
        phase.switch("engine.prefill.dispatch")
        t0 = self._dispatching()
        self._prefill_chunks += 1
        self._prefill_short_chunks += int(chunk < self.config.prefill_chunk)
        self._prefill_joined_chunks += int(joined)
        self._prefill_tokens_computed += chunk
        # Next-token logits come from the prompt's LAST REAL position
        # (inside the last chunk by bucket construction: it starts at
        # the last whole chunk under len(prompt) — prefix skip never
        # reaches the final chunk, it is capped at len(prompt) - 1).
        fence = self._dispatch_chunk(
            params, tokens, table, start,
            slot, len(req.prompt) - 1 - start if last_chunk else 0,
            started,
        )
        if started:
            self._state_patches += 1
        if self._kv.window is not None and not cancelled:
            self._after_window_chunk(req, start, chunk)
        if cancelled:
            self._patch_slot(slot, self._null_row)
        self._inflight.append(
            _ChunkInFlight(req if started else None, fence, t0)
        )
        return chunk

    def _state_table(self, req: _Request, start: int, chunk: int):
        """The `table` of the chunk over [start, start + chunk) of
        `req`, a model with conv layers: the row's pages and, of its
        state, the slot the chunk starts from (a hit's first chunk: its
        snapshot's; otherwise the row's own), the slot a copy goes to
        where the chunk ends on a whole-chunk boundary of the prompt
        (kept for later hits, kv_state.py `keep`), and how far the
        prompt reaches into a padded last chunk."""
        end, snapshot = start + chunk, NULL_BLOCK
        with self._lock:
            if (
                self.config.prefix_cache
                and end % self._kv.prefill_chunk == 0
                and end <= len(req.prompt) and req.block_ids
            ):
                snapshot = self._kv.state.keep(req.prefix_keys, end)
        read, req.state_read = req.state_read, None
        return self._kv.row_table(
            req.slot, req.block_ids, read=read, snapshot=snapshot,
            length=min(end, len(req.prompt)), pages=req.table.full,
        )

    def _copy_window_pages(self, src: List[int], dst: List[int]) -> None:
        """Dispatch `copy_window_pages` behind what is queued."""
        from ..models.generate import copy_window_pages

        self._kv.pool = copy_window_pages(
            self._kv.pool, np.asarray(src, np.int32),
            np.asarray(dst, np.int32),
        )

    def _after_window_chunk(self, req: _Request, start: int, chunk: int) -> None:
        """A chunk over [start, start + chunk) of `req` was dispatched:
        count what its window layers walked, and where it ends on a
        whole-chunk boundary of the prompt, keep that boundary's tail
        for later hits (kv_window.py `keep_tail`)."""
        from ..models.generate import (
            paged_tile_keys, paged_tiles_read, window_first_key,
            window_view_blocks,
        )

        window, bl = self._kv.window, self._kv.block_len
        end = start + chunk
        first = window_first_key(np.int32(start), window.window, bl)
        counted = {}
        for name, length, width in (
            ("swa_keys_read", end - int(first),
             window_view_blocks(window.window, bl, chunk)),
            ("swa_keys_unwindowed", end, self._kv.max_blocks),
        ):
            tile = paged_tile_keys(bl, width, chunk)
            counted[name] = tile * int(paged_tiles_read(
                np.asarray([length]), True, tile
            ))
        copy = None
        with self._lock:
            for name, keys in counted.items():
                self._window[name] += keys
            if (
                self.config.prefix_cache
                and end % self._kv.prefill_chunk == 0
                and end <= len(req.prompt) and req.block_ids
            ):
                copy = window.keep_tail(
                    req.prefix_keys, req.block_ids["window"], end
                )
        if copy is not None:
            self._copy_window_pages(*copy)

    def _dispatch_chunk(
        self, params, tokens, table, start: int, slot: int, local: int,
        started: bool,
    ):
        """Dispatch `paged_prefill` over `tokens` [1, t], positions
        [start, start + t) of the row whose table is `table`, with its
        head over the chunk's `local` position alone, and `finish_chunk`
        behind it, which starts the row from those logits if `started`
        (from the mirrors' values of `slot`); neither is waited for.
        -> the chunk's fence."""
        from ..models.generate import (
            counter_leaves, finish_chunk, paged_prefill,
        )

        logits, pool = paged_prefill(
            params,
            self.cfg,
            tokens,
            self._kv.pool,
            table,
            np.int32(start),
            np.int32(start + tokens.shape[1]),
            row=np.int32(local),
        )
        self._kv.pool = pool
        # `logits` is the one row `finish_chunk` may keep, [1, vocab]:
        # the chunk ran its final norm and head for no other position.
        self._state, self._last_logits, fence = finish_chunk(
            self._state,
            self._last_logits,
            logits,
            counter_leaves(pool),
            np.int32(slot),
            np.bool_(started),
            self._positions[slot],
            self._budget[slot],
            self._eos[slot],
        )
        return fence

    def _warm_chunk_shapes(self) -> None:
        """Run the chunk's two programs once at every shape a prompt's
        last chunk can have (`kv_slots.chunk_shapes`), as the loop
        starts: against the null table row, which starts no row and
        writes only the null block, whose contents are garbage by
        design. Which shapes the first requests happen to need then
        compiles nothing later. Counted in no counter, timer or
        span. (On the loop's thread, which traces every other program
        of the engine: on a replica's handler thread a chunk program's
        first call held the host 1.0-1.1 s, here 0.7-0.9; PERF.md,
        PR 43.)"""
        import jax

        for shape in self._kv.chunk_shapes():
            jax.block_until_ready(
                self._dispatch_chunk(
                    self.params, np.zeros((1, shape), np.int32),
                    self._null_row, 0, 0, 0, False,
                )
            )
        if self._kv.window is not None:
            null = [NULL_BLOCK] * self._kv.window.tail_blocks
            self._copy_window_pages(null, null)

    # -- decode --------------------------------------------------------
    def _live_rows(self) -> List[tuple]:
        """(slot, request) of the rows a step dispatched now would
        decode, as far as the host knows: alive in the mirror (so not
        seen to end yet) and with budget left after the steps already
        dispatched."""
        with self._lock:
            return [
                (slot, req)
                for slot, req in sorted(self._sched.running.items())
                if self._alive[slot]
                and req.dispatched < req.max_new_tokens
            ]

    def _decode(self) -> bool:
        """Dispatch one decode step if a row is alive; not waited for.
        -> whether there was anything to decode."""
        from ..models.generate import paged_engine_step

        phase = self._phase
        phase.switch("engine.decode.prepare")
        def mixed(rows):
            return len({req.gen for _, req in rows}) > 1

        rows = self._live_rows()
        if mixed(rows):
            # Rows of two weight generations (the transient window
            # after an update_weights: old streams finishing, new
            # admissions starting): retire what is in flight (a row
            # that ended there may end the window) and, if the mix
            # stands, run this step the serial way.
            self._drain()
            rows = self._live_rows()
            if mixed(rows):
                self._decode_mixed(rows)
                return True
        if not rows:
            return False
        ec = self.config
        phase.switch("engine.decode.dispatch")
        t0 = self._dispatching()
        fetch, pool, self._last_logits, self._state = paged_engine_step(
            self._gens[rows[0][1].gen]["params"],
            self.cfg,
            self._kv.pool,
            self._last_logits,
            self._state,
            self._base_key,
            temperature=ec.temperature,
            top_k=ec.top_k,
        )
        self._kv.pool = pool
        for _, req in rows:
            req.dispatched += 1
        self._inflight.append(_StepInFlight(rows, fetch, self._state, t0))
        return True

    def _decode_mixed(self, rows: List[tuple]) -> None:
        """One decode step over rows of several weight generations,
        with nothing in flight: a masked `paged_decode_step` per
        generation over the SAME pool (masks are disjoint and dead
        rows scatter to the null block, so the groups can't
        cross-talk), the host's mirrors for state, one sync, and the
        device's state made anew from the mirrors after it."""
        import jax
        import jax.numpy as jnp

        from ..models.generate import counter_leaves, paged_decode_step

        phase = self._phase
        ec = self.config
        t0 = time.perf_counter()
        by_gen: Dict[int, List[int]] = {}
        for slot, req in rows:
            by_gen.setdefault(req.gen, []).append(slot)
            req.dispatched += 1
        self._count_kv_keys(by_gen.values())
        key = jax.random.fold_in(self._base_key, self._steps)
        tables = jnp.asarray(self._tables)
        if self._kv.window is not None:
            from ..models.generate import KindTables

            tables = KindTables(
                tables, jnp.asarray(self._mirror["window_rings"])
            )
        if self._kv.state is not None:
            from ..models.generate import step_state_tables

            tables = step_state_tables(
                tables, jnp.asarray(self._mirror["state_slots"])
            )
        positions = jnp.asarray(self._positions)
        # paged_decode_step donates last_logits on accelerator
        # backends, so each group gets a PRIVATE copy of the pre-step
        # logits (`+ 0` forces a fresh buffer) and the surviving rows
        # merge back — a group must never read another group's
        # freshly-written junk rows, and the donated original must
        # never be reused.
        phase.switch("engine.decode.dispatch")
        base_logits = self._last_logits
        merged = base_logits
        pool = self._kv.pool
        # Tokens merge on-device too: a per-group np.asarray here
        # would block the host once per generation inside the hot
        # step loop (static analyzer rule RT303); one sync after the
        # loop costs the same D2H as a single step's.
        merged_tokens = moe_counts = None
        for gen in sorted(by_gen):
            self._dispatching()
            mask = np.zeros(ec.slots, bool)
            mask[by_gen[gen]] = True
            gmask = jnp.asarray(mask)
            token, pool, out_logits = paged_decode_step(
                self._gens[gen]["params"],
                self.cfg,
                pool,
                tables,
                base_logits + 0,
                positions,
                gmask,
                key,
                temperature=ec.temperature,
                top_k=ec.top_k,
            )
            merged = jnp.where(gmask[:, None], out_logits, merged)
            merged_tokens = jnp.where(
                gmask,
                token,
                0 if merged_tokens is None else merged_tokens,
            )
            if self._moe:
                # Each group's program counts its own rows' picks.
                counted = counter_leaves(pool)
                moe_counts = counted if moe_counts is None else (
                    jax.tree.map(jnp.add, counted, moe_counts)
                )
        self._kv.pool = pool
        self._last_logits = merged
        phase.switch("engine.decode.sync")
        # ONE sync for the window
        tokens, moe_counts = jax.device_get((merged_tokens, moe_counts))
        phase.switch("engine.emit")
        step_ms = self._owed_ms(t0)
        self._steps += 1
        if moe_counts is not None:
            self._count_moe("decode", moe_counts)
        self._emit(rows, tokens)
        self._observe_step(step_ms, len(rows), len(rows))
        self._push_state()

    # -- retirement ----------------------------------------------------
    def _retire(self, keep: int) -> bool:
        """Retire the oldest programs in flight until `keep` are left
        (the ones this iteration dispatched). -> whether any was."""
        worked = len(self._inflight) > keep
        while len(self._inflight) > keep:
            program = self._inflight.popleft()
            if isinstance(program, _StepInFlight):
                self._retire_step(program)
            else:
                self._retire_chunk(program)
        return worked

    def _drain(self) -> None:
        """Retire everything in flight: afterwards the mirrors say
        what the device's state says."""
        if self._retire(keep=0):
            self._pipeline_drains += 1

    def _retire_chunk(self, chunk: _ChunkInFlight) -> None:
        import jax

        phase = self._phase
        phase.switch("engine.prefill.wait")
        fence = jax.device_get(chunk.fence)
        phase.switch("engine.emit")
        self._observe_prefill(self._owed_ms(chunk.t0))
        if self._moe:
            self._count_moe("prefill", fence)
        if chunk.started is not None:
            chunk.started.decoding_ts = time.perf_counter()

    def _retire_step(self, step: _StepInFlight) -> None:
        import jax

        phase = self._phase
        phase.switch("engine.decode.sync")
        # device->host sync per step: the tokens and, for a MoE
        # config, the step's picks in the same transfer.
        fetch = jax.device_get(step.fetch)
        phase.switch("engine.emit")
        step_ms = self._owed_ms(step.t0)
        # Rows released since the dispatch (they ended in the step
        # before, or were cancelled) get no token: on the device the
        # first kind was dead in this step anyway.
        running = self._sched.running
        rows = [
            (slot, req) for slot, req in step.rows
            if running.get(slot) is req
        ]
        # The device counts a step if a row was alive in it.
        steps, self._steps = self._steps, int(fetch["step"])
        if self._steps > steps:
            # The mirrors still stand where this step found the rows.
            self._count_kv_keys([[slot for slot, _ in rows]])
            if self._moe:
                self._count_moe("decode", fetch)
        self._emit(rows, fetch["token"])
        self._observe_step(step_ms, len(rows), len(rows))

    def _emit(self, rows: List[tuple], tokens: np.ndarray) -> None:
        """Stream a retired step's token to each of `rows`, advance
        the mirrors as the step program advanced the device's state,
        and release the rows that ended: the same two rules."""
        now = time.perf_counter()
        with self._lock:
            for slot, req in rows:
                tok = int(tokens[slot])
                if req.first_token_ts is None:
                    req.first_token_ts = now
                    self._first_tokens += 1
                    self._prefill_ms_total += (
                        now - req.admitted_ts
                    ) * 1e3
                    self._observe_ttft(
                        (now - req.submitted_ts) * 1e3
                    )
                req.out.put(("tok", tok))
                req.emitted += 1
                self._positions[slot] += 1
                self._budget[slot] -= 1
                if tok == req.eos_token:
                    self._release_locked(slot, req, "stop")
                elif req.emitted >= req.max_new_tokens:
                    self._release_locked(slot, req, "length")
            self._tokens_emitted += len(rows)

    def _count_kv_keys(self, groups) -> None:
        """Add a step's `kv_keys_live` / `kv_keys_read`: one program
        per group of slots (one, or one a weight generation), each
        over its own rows' tiles: each alive row's whole tiles where
        the step's kernel reads the pool in place, else the work
        list's trips, a tile for every slot a trip
        (`generate.paged_keys_read`)."""
        from ..models.generate import paged_keys_read

        tile = self._kv_tile_keys
        valid_len = self._positions + 1
        live = read = 0
        for slots in groups:
            mask = np.zeros_like(self._alive)
            mask[slots] = True
            live += int(valid_len[mask].sum())
            read += int(paged_keys_read(
                valid_len, mask, tile, self._kv_in_place.get("full", False)
            ))
        if self._kv.window is not None:
            live, read = self._count_window_keys(groups, live, read)
        with self._lock:
            self._kv_keys_live += live
            self._kv_keys_read += read

    def _count_window_keys(self, groups, live: int, read: int) -> tuple:
        """A step of a model with window layers. Adds `swa_keys_read`
        (what a window layer walked: every row's view of its last
        `window` keys, `generate._window_view`) and
        `swa_keys_unwindowed` (what it would have walked over the whole
        row: `read`, what a full layer did), and -> (live, read) summed
        over the kinds of layer, each kind as many times as the model
        has layers of it: a window layer's live keys are a row's last
        `window`, so `kv_read_amplification` keeps its meaning."""
        from ..models.generate import (
            paged_keys_read, paged_tile_keys, window_first_key,
            window_view_blocks,
        )

        window, bl = self._kv.window.window, self._kv.block_len
        tile = paged_tile_keys(
            bl, window_view_blocks(window, bl, 1), 1,
            self._kv_in_place["window"],
        )
        seen = self._positions + 1 - window_first_key(
            self._positions, window, bl
        )
        w_live = w_read = 0
        for slots in groups:
            mask = np.zeros_like(self._alive)
            mask[slots] = True
            w_live += int(np.minimum(self._positions[mask] + 1, window).sum())
            w_read += int(paged_keys_read(
                seen, mask, tile, self._kv_in_place["window"]
            ))
        layers = {
            kind.cache: len(ls)
            for kind, ls in self.cfg.attn_kinds().values()
        }
        with self._lock:
            self._window["swa_keys_read"] += w_read
            self._window["swa_keys_unwindowed"] += read
        full = layers.get("full", 0)
        return (
            full * live + layers["window"] * w_live,
            full * read + layers["window"] * w_read,
        )

    def _count_moe(self, program: str, counted: dict) -> None:
        """Add what one paged forward counted (the pool's counter
        leaves, fetched). `moe_counts` [expert layers, E]: the picks
        per layer and expert; per layer the experts that
        got any token (the expert weights the forward had to read);
        and, of a chunk, per layer the fullest expert's tokens (how
        uneven the grouped matmuls ran). `moe_routed` [expert layers]:
        the picks over all the router's outputs; `moe_spilled` [expert
        layers]: 1 where a layer's held picks passed its row budget;
        `dsa_counts` [layers, 2]: the pairs its attention could see
        and kept."""
        added: Dict[str, int] = {}
        if "moe_routed" in counted:
            added["moe_picks_routed"] = int(counted["moe_routed"].sum())
        if "moe_spilled" in counted:
            added["moe_forwards_spilled"] = int(counted["moe_spilled"].sum())
        if "dsa_counts" in counted:
            visible, kept = map(int, counted["dsa_counts"].sum(axis=0))
            added.update(dsa_keys_visible=visible, dsa_keys_selected=kept)
            if program == "prefill":
                added.update(
                    dsa_chunk_keys_visible=visible,
                    dsa_chunk_keys_selected=kept,
                )
        counts = counted.get("moe_counts")
        with self._lock:
            moe = self._moe
            for name, value in added.items():
                moe[name] += value
            if counts is None:
                return
            picks, layers = int(counts.sum()), counts.shape[0]
            touched = int((counts > 0).sum())
            if program == "prefill":
                moe["moe_picks_prefill"] += picks
                moe["moe_chunk_layers"] += layers
                moe["moe_chunk_max_load"] += int(counts.max(axis=1).sum())
                moe["moe_chunk_experts"] += touched
            else:
                moe["moe_picks_decode"] += picks
                moe["moe_step_layers"] += layers
                moe["moe_experts_touched"] += touched

    # -- metrics -------------------------------------------------------
    # All hooks are guarded no-ops on failure: observability must
    # never fail a decode (serve/observability.py owns the metric
    # definitions; the engine just reports).

    def _block_stats(self) -> Dict[str, Any]:
        """What the occupancy gauges take beside the slots: the pool's
        blocks and the cause the queue stands for."""
        if self._kv is None:
            return {"kv_used": 0, "kv_total": 0}
        alloc = self._kv.full
        return {
            "kv_used": alloc.used(),
            "kv_total": alloc.capacity(),
            "queue_cause": self._queue_cause,
        }

    def _observe_step(
        self, step_ms: float, batch: int, tokens: int
    ) -> None:
        try:
            from ..serve.observability import observe_engine_step

            stats = self._sched.stats()
            observe_engine_step(
                self._tags, step_ms, batch, tokens,
                stats["slots_used"], stats["slots_total"],
                stats["waiting"], **self._block_stats(),
            )
        except Exception:
            pass

    def _observe_prefill(self, chunk_ms: float) -> None:
        try:
            from ..serve.observability import observe_engine_prefill

            observe_engine_prefill(self._tags, chunk_ms)
        except Exception:
            pass

    def _observe_prefix(self, skip_tokens: int) -> None:
        try:
            from ..serve.observability import observe_engine_prefix

            observe_engine_prefix(self._tags, skip_tokens)
        except Exception:
            pass

    def _observe_ttft(self, ttft_ms: float) -> None:
        try:
            from ..serve.observability import observe_engine_ttft

            observe_engine_ttft(self._tags, ttft_ms)
        except Exception:
            pass

    def _observe_occupancy(self) -> None:
        try:
            from ..serve.observability import (
                observe_engine_occupancy,
            )

            if self._sched is None:
                return
            stats = self._sched.stats()
            observe_engine_occupancy(
                self._tags, stats["slots_used"],
                stats["slots_total"], stats["waiting"],
                **self._block_stats(),
            )
        except Exception:
            pass

    def _observe_device(self) -> None:
        try:
            from ..serve.observability import observe_engine_device

            observe_engine_device(self._tags, **self._device)
        except Exception:
            pass

