"""The engine behind `ray_tpu.serve`: a multiplexed, streaming LLM
deployment.

Wiring (ISSUE 10 tentpole):

* replicas host `InferenceEngine`s — one engine per loaded model
  family, created through ``@serve.multiplexed`` so the router's
  model-warmth ranking and the per-replica LRU apply unchanged;
  per-family slot accounting falls out of one-engine-per-family;
* ``__call__`` is a GENERATOR, so the deployment is a streaming
  ingress: each sampled token goes out as its own chunk while the
  engine keeps decoding (proxy chunked transfer-encoding, handle
  ``options(stream=True)``);
* admission: the router/proxy queue feeds the replica; the replica
  hands the request to the engine's FIFO scheduler, which rejects
  with ``EngineOverloaded`` past its waiting bound;
* cancellation: a consumer that abandons the stream
  (`DeploymentResponseGenerator.close()`, proxy client disconnect)
  triggers `Replica.cancel_stream` -> ``__serve_cancel_stream__``
  here -> `engine.cancel` — the slot frees mid-decode instead of
  decoding to the token budget for nobody.

Request payload (HTTP body JSON or a plain dict via handle):

    {"prompt": [token ids], "max_new_tokens": 16,
     "model": "family-id" (optional; `serve_multiplexed_model_id`
      header / handle option wins), "eos_token": optional}

Response stream: one chunk per token, ASCII decimal + trailing space
(a client sums and parses it trivially, and times chunk arrivals).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional

from .._private import compile_watch
from ..serve.multiplex import get_multiplexed_model_id, multiplexed
from .engine import EngineConfig, InferenceEngine

#: Engines a single replica keeps loaded (multiplex LRU bound).
MAX_FAMILIES_PER_REPLICA = 4


def _resolve_dtype(name: Any):
    import jax.numpy as jnp

    if not isinstance(name, str):
        return name
    return {
        "float32": jnp.float32,
        "bfloat16": jnp.bfloat16,
        "float16": jnp.float16,
    }[name]


def build_model(spec: Dict[str, Any]):
    """Model-family spec -> (params, LlamaConfig).

    kind "init": randomly initialized from a config dict (tests, the
    benchmark — every HF family shares the Llama compute graph, so a
    family here is a (config, seed) point);
    kind "hf": a converted HF checkpoint directory
    (models/hf_convert.load_hf_llama — the six parity-proven
    families)."""
    import jax

    kind = spec.get("kind", "init")
    if kind == "hf":
        from ..models.hf_convert import load_hf_llama

        return load_hf_llama(spec["path"])
    if kind != "init":
        raise ValueError(f"unknown model spec kind {kind!r}")
    from ..models.llama import LlamaConfig, init_params

    kwargs = dict(spec.get("config") or {})
    if "dtype" in kwargs:
        kwargs["dtype"] = _resolve_dtype(kwargs["dtype"])
    cfg = LlamaConfig(**kwargs)
    # One program for the whole tree: made op by op, each float32
    # draw and its scaled copy sat in device memory beside the
    # weights already cast (15.3 GB at the peak for 6.8 GB of bf16
    # weights); fused, a tensor is drawn, scaled and cast in one pass.
    params = compile_watch.instrument(
        "serving.init_params", jax.jit(init_params, static_argnums=1)
    )(jax.random.PRNGKey(int(spec.get("seed", 0))), cfg)
    return params, cfg


class LLMServer:
    """The deployment class `build_llm_app` wraps (usable directly:
    ``serve.deployment(LLMServer).bind(families, ...)``)."""

    def __init__(
        self,
        families: Dict[str, Dict[str, Any]],
        default_family: Optional[str] = None,
        engine: Optional[Dict[str, Any]] = None,
    ):
        if not families:
            raise ValueError("families must name at least one model")
        self._families = dict(families)
        self._default = default_family or next(iter(self._families))
        self._engine_cfg = EngineConfig(**(engine or {}))
        # serve request_id -> [(engine, engine_request_id), ...] for
        # cancel_stream propagation. A LIST per id: the serve id is
        # CLIENT-controlled (x-request-id), so concurrent requests may
        # collide on it — each stream keeps its own engine-minted
        # unique id and cancel hits every stream under the serve id.
        self._streams: Dict[str, list] = {}
        # Cancels that arrived BEFORE their stream handler ran (the
        # cancel RPC can beat the streaming call through the actor
        # mailbox): serve_id -> arrival ts, consulted right after
        # submit. Entries expire; the map stays tiny.
        self._early_cancels: Dict[str, float] = {}
        self._streams_lock = threading.Lock()

    # -- engines -------------------------------------------------------
    @multiplexed(max_num_models_per_replica=MAX_FAMILIES_PER_REPLICA)
    def get_engine(self, family: str) -> InferenceEngine:
        """Loader the multiplex LRU calls on a cold family: builds the
        params and an engine with its OWN slots + step thread, so a
        swap (this load) blocks only requests for THIS family."""
        from ..serve.observability import current_request_context

        spec = self._spec(family)
        params, cfg = build_model(spec)
        ctx = current_request_context() or {}
        return InferenceEngine(
            params,
            cfg,
            self._engine_cfg,
            family=family,
            app=str(ctx.get("app", "")),
            deployment=str(ctx.get("deployment", "")),
        )

    def _spec(self, family: str) -> Dict[str, Any]:
        spec = self._families.get(family)
        if spec is None:
            raise ValueError(
                f"unknown model family {family!r}; serving "
                f"{sorted(self._families)}"
            )
        return spec

    # -- request path --------------------------------------------------
    def __call__(self, request):
        """Streaming ingress: yields one chunk per sampled token."""
        payload = (
            request.json() if hasattr(request, "json") else request
        ) or {}
        family = (
            get_multiplexed_model_id()
            or str(payload.get("model") or "")
            or self._default
        )
        prompt = payload.get("prompt")
        if not prompt:
            raise ValueError("payload needs a non-empty 'prompt'")
        max_new = payload.get("max_new_tokens")
        max_new = None if max_new is None else int(max_new)
        eos = payload.get("eos_token")
        eos = None if eos is None else int(eos)
        from ..serve.observability import (
            get_request_id,
            observe_first_item,
        )

        engine = self.get_engine(family)
        # The engine mints its own UNIQUE id (a client-controlled
        # x-request-id may collide); the serve id only keys the
        # cancel map.
        stream = engine.submit(
            prompt, max_new_tokens=max_new, eos_token=eos
        )
        serve_id = get_request_id()
        entry = (engine, stream.request_id)
        cancelled_early = False
        if serve_id:
            with self._streams_lock:
                self._streams.setdefault(serve_id, []).append(entry)
                # The consumer may have abandoned us before this
                # handler even ran (cancel RPC beat the streaming
                # call through the mailbox).
                cancelled_early = (
                    self._early_cancels.pop(serve_id, None)
                    is not None
                )
        if cancelled_early:
            stream.cancel()
        try:
            # The first token is B6 of the request's first-token
            # stages: one reading, then the same iterator goes on.
            for token in stream:
                observe_first_item(stream.first_token_ts)
                yield f"{token} ".encode()
                break
            for token in stream:
                yield f"{token} ".encode()
        finally:
            # Abnormal generator exit (consumer gone) must not leave
            # the engine decoding the rest of the budget for nobody.
            if stream.finish_reason is None:
                stream.cancel()
            if serve_id:
                with self._streams_lock:
                    entries = self._streams.get(serve_id)
                    if entries is not None:
                        try:
                            entries.remove(entry)
                        except ValueError:
                            pass
                        if not entries:
                            self._streams.pop(serve_id, None)

    def __serve_cancel_stream__(self, request_id: str) -> bool:
        """Replica cancel hook: the consumer abandoned the stream.
        Cancels EVERY live stream under the serve request id (ids are
        client-controlled and may collide; each entry still cancels
        by its own engine-minted id). A miss is remembered briefly —
        the cancel may have outrun its own stream handler."""
        now = time.time()
        with self._streams_lock:
            entries = list(self._streams.get(request_id, ()))
            if not entries:
                self._early_cancels[request_id] = now
                # Expire stale entries so the map stays bounded even
                # under cancel floods for requests that never arrive.
                for rid, ts in list(self._early_cancels.items()):
                    if now - ts > 60.0:
                        del self._early_cancels[rid]
        cancelled = False
        for engine, engine_request_id in entries:
            cancelled = engine.cancel(engine_request_id) or cancelled
        return cancelled

    # -- introspection -------------------------------------------------
    def engine_stats(self) -> Dict[str, Any]:
        """Per-loaded-family engine stats for this replica (also the
        smoke-bench's concurrency witness)."""
        wrapper = getattr(self, "__serve_multiplex_get_engine", None)
        if wrapper is None:
            return {}
        return {
            family: engine.stats()
            for family, engine in wrapper.models().items()
        }


def build_llm_app(
    families: Dict[str, Dict[str, Any]],
    *,
    default_family: Optional[str] = None,
    engine: Optional[Dict[str, Any]] = None,
    num_replicas: int = 1,
    max_ongoing_requests: Optional[int] = None,
    name: str = "llm",
):
    """Bind the engine deployment.

    Each replica hosts one single-device `InferenceEngine`, so it
    leases one chip when the cluster advertises any: the daemon then
    scopes its worker to that chip (two replicas, two chips). With no
    chips in the cluster the replica is a CPU worker, as in tests."""
    from .._private.config import Config
    from ..serve.deployment import deployment as serve_deployment
    from ..util.accelerators.tpu import cluster_tpu_chips

    engine = dict(engine or {})
    if "prefix_cache" not in engine:
        # Resolved HERE (driver-side) so the decision ships in the
        # replica init args instead of depending on worker-process
        # environments.
        engine["prefix_cache"] = bool(
            Config.from_env().serve_prefix_cache_enabled
        )
    engine_cfg = EngineConfig(**engine)
    if max_ongoing_requests is None:
        # Streams hold a replica thread for their whole lifetime:
        # admit enough for every slot plus a queueing margin so the
        # engine's FIFO — not the actor mailbox — orders waiters.
        max_ongoing_requests = engine_cfg.slots * 4
    dep = serve_deployment(
        name=name,
        num_replicas=num_replicas,
        max_ongoing_requests=max_ongoing_requests,
        ray_actor_options=(
            {"num_tpus": 1} if cluster_tpu_chips() else None
        ),
    )(LLMServer)
    return dep.bind(
        dict(families),
        default_family=default_family,
        engine=engine,
    )
