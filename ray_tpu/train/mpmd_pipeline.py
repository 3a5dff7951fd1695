"""MPMD pipeline-parallel training: 1F1B stage gangs over channels.

The second half of the pipeline story. `train/pipeline_step.py` runs
GPipe INSIDE one jitted SPMD program — every device executes every
schedule tick (invalid ticks masked, so the bubble is paid as real
FLOPs) and one giant program compiles for the whole stack. This
module is the MPMD mode the PAPERS.md "Scaling Deep Learning Training
with MPMD Pipeline Parallelism" paper argues for, built the way the
reference builds pipelines (compiled actor DAGs over channels,
dag/compiled_dag_node.py): the layer stack is partitioned into
chunks, each PHYSICAL stage is an actor running its OWN small jitted
fwd/bwd programs (compile time stays flat in stage size, not model
size), and activations/activation-gradients ride ahead-of-time wired
channel edges (`dag/edges.py`: shm same-host, TCP cross-host, bounded
capacity = backpressure) under a 1F1B schedule from
`parallel/schedule.py` — warmup fills, steady state alternates
one-forward-one-backward so the activation stash stays O(n_stages),
cooldown drains, then every stage applies its LOCAL optimizer shard.
No cross-stage traffic exists beyond the boundary hops.

Numerics contract (the parity test pins it): with the same init, the
accumulated gradient equals the single-program baseline's exactly —
each microbatch's backward uses the objective
``nll_sum_mb / count_total + (moe_aux_weight / num_mb) * aux_mb``
whose per-microbatch sum telescopes to the baseline loss
``nll_total / count_total + moe_aux_weight * aux_total / num_mb``
(count_total is known up front: targets are host data). Backward is
remat-style — each stage stashes only its chunk INPUT and the vjp
recomputes the chunk forward — so stash memory is
O(stash_depth * microbatch activation), with stash_depth <= n_stages
by the 1F1B invariant.

Optimizer locality: the update runs per stage on that stage's shard.
Anything inside the optax chain that wants a GLOBAL reduction (e.g.
clip_by_global_norm) sees only the local shard — use per-stage
clipping or a clip-free optimizer when cross-stage-exact optimizer
semantics matter (README "Pipeline-parallel training (MPMD)").
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import api as rt
from .._private.config import Config
from ..dag.channels import ShmChannel
from ..dag.edges import Edge
from ..dag.tcp_channel import TcpChannel
from ..exceptions import GetTimeoutError, RayTpuError
from ..parallel.schedule import (
    interleaved_1f1b,
    max_stash_depth,
    partition_layers,
    theoretical_efficiency,
    validate_schedule,
)

__all__ = ["MPMDPipeline", "MPMDPipelineError"]


class MPMDPipelineError(RayTpuError):
    """A pipeline step failed (stage death, channel timeout, protocol
    desync). The pipeline is broken afterwards — build a new one."""


# ---------------------------------------------------------------------------
# stage programs (jit-compiled inside the stage actor)
# ---------------------------------------------------------------------------

def _remat_body(cfg, body):
    import jax

    if not cfg.remat:
        return body
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies
            .dots_with_no_batch_dims_saveable,
        )
    if cfg.remat_policy == "dots_flash":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(
                    "flash_out", "flash_lse"
                ),
            ),
        )
    return jax.checkpoint(body)


def _make_chunk_fwd(cfg, first: bool):
    """fwd(params, x) -> (y, aux_sum) for one chunk. `first` chunks
    take token ids and embed them; later chunks take activations.
    RoPE cos/sin recompute inside the jit from absolute positions —
    cheap next to the stack, and it keeps the channel payload to the
    activation alone."""
    import jax.numpy as jnp
    from jax import lax

    from ..models.llama import _layer, embed_tokens
    from ..ops.norms import rotary_embedding

    def fwd(params, x):
        b, t = x.shape[0], x.shape[1]
        if first:
            x = embed_tokens(cfg, params, x)
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
        cos, sin = rotary_embedding(
            positions, cfg.head_dim, cfg.rope_theta,
            getattr(cfg, "rope_scaling", None),
        )

        def body(xc, layer):
            return _layer(cfg, xc, layer, cos, sin, None, None)

        h, auxs = lax.scan(
            _remat_body(cfg, body), x, params["layers"]
        )
        return h, jnp.sum(auxs)

    return fwd


def _make_last_objective(cfg):
    """objective(params, x, targets, inv_count, aux_scale) for the
    LAST chunk: its layers + final norm + lm_head + masked xent. The
    scaling makes per-microbatch objectives sum to the exact baseline
    loss (see module docstring)."""
    import jax.numpy as jnp
    from jax import lax

    from ..models.llama import _layer, masked_xent, model_norm
    from ..ops.norms import rotary_embedding

    def objective(params, x, targets, inv_count, aux_scale):
        b, t = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
        cos, sin = rotary_embedding(
            positions, cfg.head_dim, cfg.rope_theta,
            getattr(cfg, "rope_scaling", None),
        )

        def body(xc, layer):
            return _layer(cfg, xc, layer, cos, sin, None, None)

        h, auxs = lax.scan(
            _remat_body(cfg, body), x, params["layers"]
        )
        h = model_norm(cfg, h, params["final_norm"])
        logits = (h @ params["lm_head"]).astype(jnp.float32)
        nll, count = masked_xent(logits, targets)
        aux = jnp.sum(auxs)
        obj = nll * inv_count + aux_scale * aux
        return obj, (nll, count, aux)

    return objective


# ---------------------------------------------------------------------------
# the stage actor
# ---------------------------------------------------------------------------

class _PipelineStage:
    """One physical pipeline stage: owns its chunks' params + optimizer
    shard, its jitted programs, its channel endpoints, and executes its
    slice of the 1F1B schedule per `run_step` call."""

    def __init__(
        self,
        stage_idx: int,
        n_stages: int,
        cfg,
        chunk_specs: Sequence[Tuple[int, int, int]],
        n_chunks_total: int,
        num_microbatches: int,
        ops: Sequence[Tuple[str, int, int]],
        optimizer_factory: Optional[Callable],
        hop_timeout_s: float,
    ):
        self.stage = int(stage_idx)
        self.n_stages = int(n_stages)
        self.cfg = cfg
        self.chunk_specs = [tuple(s) for s in chunk_specs]
        self.V = int(n_chunks_total)
        self.num_mb = int(num_microbatches)
        self.ops = [tuple(op) for op in ops]
        self.hop_timeout = float(hop_timeout_s)
        self.aux_scale = float(
            getattr(cfg, "moe_aux_weight", 0.0)
        ) / self.num_mb
        self._optimizer = (
            optimizer_factory() if optimizer_factory else None
        )
        self._params: Dict[int, Any] = {}
        self._opt_state = None
        self._programs: Dict[str, Any] = {}
        self._edges: Dict[str, Dict[int, Optional[Edge]]] = {}
        # Session wiring: stage rank telemetry rides the same
        # per-(step, rank) records gang training uses, so doctor /
        # goodput / gang-skew read pipeline stages like data ranks.
        from .session import TrainContext, init_session

        init_session(
            TrainContext(
                world_rank=self.stage, world_size=self.n_stages
            )
        )

    # -- wiring --------------------------------------------------------
    def wire(self, fwd_in, fwd_out, bwd_in, bwd_out) -> int:
        """Install this stage's channel endpoints (dict: chunk ->
        Edge | None). Called once at build; edges are REUSED across
        every subsequent step — wiring is off the step path."""
        self._edges = {
            "fwd_in": dict(fwd_in),
            "fwd_out": dict(fwd_out),
            "bwd_in": dict(bwd_in),
            "bwd_out": dict(bwd_out),
        }
        return self.stage

    def set_params(self, chunk_params: Dict[int, Any]) -> int:
        """Install per-chunk param trees (host arrays), build the
        optimizer shard over ALL this stage's chunks."""
        import jax

        self._params = {
            int(c): jax.tree.map(jax.numpy.asarray, tree)
            for c, tree in chunk_params.items()
        }
        if self._optimizer is not None:
            self._opt_state = self._optimizer.init(self._params)
        self._build_programs()
        return self.stage

    def _build_programs(self) -> None:
        import jax

        from .._private import compile_watch

        # Every stage program registers with the compile watcher by
        # name (mpmd.s<stage>.<fwd|bwd>:<chunk> — bounded by the
        # pipeline topology): per-chunk fwd/bwd must compile once at
        # warmup and NEVER again, and a microbatch-shape drift that
        # re-traces a stage mid-training now convicts itself in
        # `doctor` verdict.compile instead of reading as a slow
        # stage.
        def _jit(key: str, fn):
            return compile_watch.instrument(
                f"mpmd.s{self.stage}.{key}", jax.jit(fn)
            )

        cfg = self.cfg
        for c, _lo, _hi in self.chunk_specs:
            first = c == 0
            last = c == self.V - 1
            if last:
                objective = _make_last_objective(cfg)

                def last_bwd(p, x, t, ic, ascale, _obj=objective):
                    return jax.value_and_grad(
                        _obj, argnums=(0, 1), has_aux=True
                    )(p, x, t, ic, ascale)

                self._programs[f"bwd:{c}"] = _jit(f"bwd:{c}", last_bwd)
            else:
                fwd = _make_chunk_fwd(cfg, first)
                self._programs[f"fwd:{c}"] = _jit(f"fwd:{c}", fwd)
                if first:

                    def first_bwd(p, tokens, gy, aux_ct, _fwd=fwd):
                        (y, aux), vjp = jax.vjp(
                            lambda pp: _fwd(pp, tokens), p
                        )
                        (dp,) = vjp((gy, aux_ct.astype(aux.dtype)))
                        return dp, aux

                    self._programs[f"bwd:{c}"] = _jit(f"bwd:{c}", first_bwd)
                else:

                    def mid_bwd(p, x, gy, aux_ct, _fwd=fwd):
                        (y, aux), vjp = jax.vjp(_fwd, p, x)
                        dp, dx = vjp((gy, aux_ct.astype(aux.dtype)))
                        return dp, dx, aux

                    self._programs[f"bwd:{c}"] = _jit(f"bwd:{c}", mid_bwd)
        self._programs["acc"] = _jit(
            "acc", lambda a, b: jax.tree.map(jax.numpy.add, a, b)
        )
        if self._optimizer is not None:
            import optax

            def opt_update(params, opt_state, grads):
                updates, new_opt = self._optimizer.update(
                    grads, opt_state, params
                )
                return optax.apply_updates(params, updates), new_opt

            self._programs["opt"] = _jit("opt", opt_update)

    # -- the step ------------------------------------------------------
    def run_step(
        self,
        step_index: int,
        tokens_mbs: Optional[List[np.ndarray]] = None,
        targets_mbs: Optional[List[np.ndarray]] = None,
    ) -> dict:
        """Execute this stage's 1F1B op list once: recv/compute/send
        per op, accumulate grads, then apply the local optimizer
        shard. Returns loss pieces + the per-op timing and edge-wait
        numbers a pipeline-efficiency account reads (`simulate_schedule`)."""
        import jax
        import jax.numpy as jnp

        t_wall0 = time.monotonic()
        V, last_c = self.V, self.V - 1
        stash: Dict[Tuple[int, int], Any] = {}
        stash_peak = 0
        grads: Dict[int, Any] = {}
        op_ms: Dict[str, List[float]] = {}
        # Loss pieces stay device-side until the schedule drains —
        # a float() per op would insert m extra D2H syncs into the
        # schedule's critical path.
        nll_parts: List[Any] = []
        cnt_parts: List[Any] = []
        aux_parts: List[Any] = []
        obj_parts: List[Any] = []
        inv_count = aux_scale_arr = None
        if targets_mbs is not None:
            count = float(
                sum(int((t >= 0).sum()) for t in targets_mbs)
            )
            inv_count = jnp.asarray(
                1.0 / max(count, 1.0), jnp.float32
            )
        aux_scale_arr = jnp.asarray(self.aux_scale, jnp.float32)

        def _time(key: str, t0: float) -> None:
            op_ms.setdefault(key, []).append(
                (time.monotonic() - t0) * 1e3
            )

        for kind, c, mb in self.ops:
            if kind == "F":
                if c == 0:
                    x = tokens_mbs[mb]
                else:
                    tag, x = self._recv("fwd_in", c, ("F", c, mb))
                stash[(c, mb)] = x
                stash_peak = max(stash_peak, len(stash))
                if c != last_c:
                    t0 = time.monotonic()
                    y, aux = self._programs[f"fwd:{c}"](
                        self._params[c], x
                    )
                    y = np.asarray(y)
                    _time(f"F:{c}", t0)
                    aux_parts.append(aux)
                    self._send("fwd_out", c, ("F", c + 1, mb), y)
                # Last chunk: forward happens inside its backward's
                # vjp (remat) — F just lands the stash.
            else:  # B
                x = stash.pop((c, mb))
                if c == last_c:
                    t0 = time.monotonic()
                    (obj, (nll, cnt, aux)), (dp, dx) = self._programs[
                        f"bwd:{c}"
                    ](
                        self._params[c], x, targets_mbs[mb],
                        inv_count, aux_scale_arr,
                    )
                    dx = np.asarray(dx)
                    _time(f"B:{c}", t0)
                    nll_parts.append(nll)
                    cnt_parts.append(cnt)
                    aux_parts.append(aux)
                    obj_parts.append(obj)
                    if c > 0:
                        self._send(
                            "bwd_out", c, ("B", c - 1, mb), dx
                        )
                elif c == 0:
                    tag, gy = self._recv("bwd_in", c, ("B", c, mb))
                    t0 = time.monotonic()
                    dp, _aux = self._programs[f"bwd:{c}"](
                        self._params[c], x,
                        jnp.asarray(gy), aux_scale_arr,
                    )
                    jax.block_until_ready(jax.tree.leaves(dp)[0])
                    _time(f"B:{c}", t0)
                else:
                    tag, gy = self._recv("bwd_in", c, ("B", c, mb))
                    t0 = time.monotonic()
                    dp, dx, _aux = self._programs[f"bwd:{c}"](
                        self._params[c], x,
                        jnp.asarray(gy), aux_scale_arr,
                    )
                    dx = np.asarray(dx)
                    _time(f"B:{c}", t0)
                    self._send("bwd_out", c, ("B", c - 1, mb), dx)
                grads[c] = (
                    dp if c not in grads
                    else self._programs["acc"](grads[c], dp)
                )
        if stash:
            raise MPMDPipelineError(
                f"stage {self.stage}: {len(stash)} unretired "
                "stashes after the schedule — schedule bug"
            )
        opt_ms = 0.0
        if self._optimizer is not None:
            t0 = time.monotonic()
            self._params, self._opt_state = self._programs["opt"](
                self._params, self._opt_state, grads
            )
            jax.block_until_ready(
                jax.tree.leaves(self._params)[0]
            )
            opt_ms = (time.monotonic() - t0) * 1e3

        nll_total = float(sum(float(x) for x in nll_parts))
        cnt_total = float(sum(float(x) for x in cnt_parts))
        aux_total = float(sum(float(x) for x in aux_parts))
        obj_total = float(sum(float(x) for x in obj_parts))
        wall_ms = (time.monotonic() - t_wall0) * 1e3
        busy_ms = (
            sum(sum(v) for v in op_ms.values()) + opt_ms
        )
        edges = [
            e.take_stats()
            for group in self._edges.values()
            for e in group.values()
            if e is not None
        ]
        # Session heartbeat: one per-(step, rank=stage) record with
        # send_wait/recv_wait phases (billed by Edge) riding the
        # metrics pipe — the doctor's bubble attribution.
        from .session import get_session

        session = get_session()
        if session is not None:
            session.report(
                {"step_ms": busy_ms, "pipeline_stage": self.stage}
            )
        return {
            "stage": self.stage,
            "nll": nll_total,
            "count": cnt_total,
            "aux": aux_total,
            "objective": obj_total,
            "busy_ms": round(busy_ms, 3),
            "opt_ms": round(opt_ms, 3),
            "wall_ms": round(wall_ms, 3),
            "op_ms": {
                k: [round(v, 3) for v in vals]
                for k, vals in op_ms.items()
            },
            "edges": edges,
            "stash_peak": stash_peak,
        }

    def _recv(self, group: str, chunk: int, want: tuple):
        edge = self._edges[group][chunk]
        record = edge.get_value(timeout=self.hop_timeout)
        tag, payload = record
        if tuple(tag) != want:
            raise MPMDPipelineError(
                f"stage {self.stage} edge {edge.name}: got record "
                f"{tag}, schedule expected {want}"
            )
        return tag, payload

    def _send(self, group: str, chunk: int, tag: tuple,
              payload) -> None:
        edge = self._edges[group][chunk]
        edge.put_value((tag, payload), timeout=self.hop_timeout)

    # -- params / checkpoints -----------------------------------------
    def get_params(self) -> Dict[int, Any]:
        return {
            c: jax_tree_to_numpy(tree)
            for c, tree in self._params.items()
        }

    def save(self, root: str, step: int,
             async_save: bool = True) -> str:
        from .checkpoint import save_checkpoint

        path = os.path.join(
            root, f"step-{step:08d}", f"stage-{self.stage}"
        )
        save_checkpoint(
            path,
            {"params": self._params, "opt_state": self._opt_state},
            metadata={
                "stage": self.stage,
                "chunks": [c for c, _l, _h in self.chunk_specs],
                "step": int(step),
            },
            async_save=async_save,
        )
        return path

    def wait_ckpt(self) -> None:
        """PR 4 durability barrier, stage-side: pending async saves
        must persist before the driver trusts the checkpoint."""
        from .checkpoint import wait_for_checkpoints

        wait_for_checkpoints()

    def restore(self, root: str, step: int) -> int:
        from .checkpoint import restore_checkpoint

        path = os.path.join(
            root, f"step-{step:08d}", f"stage-{self.stage}"
        )
        state = restore_checkpoint(
            path,
            {"params": self._params, "opt_state": self._opt_state},
        )
        self._params = state["params"]
        self._opt_state = state["opt_state"]
        return self.stage

    def ping(self) -> int:
        return self.stage


def jax_tree_to_numpy(tree):
    import jax

    return jax.tree.map(np.asarray, tree)


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------

class MPMDPipeline:
    """Driver for MPMD pipeline-parallel training of the flagship
    Llama stack.

    Build once (spawns the stage actors, wires channel edges, installs
    params), then call `step(tokens, targets)` per global batch.
    Geometry: ``global batch = num_microbatches * microbatch_size``,
    layer partition from `partition_layers` (pass `layer_ms` /
    `embed_ms` / `head_ms` as measured for one layer, the embedding
    and the head + loss to balance the asymmetric ends; uniform otherwise).
    """

    def __init__(
        self,
        cfg,
        n_stages: int,
        *,
        num_microbatches: int,
        microbatch_size: int,
        seq_len: int,
        chunks_per_stage: int = 1,
        optimizer_factory: Optional[Callable] = None,
        layer_ms: Optional[Sequence[float]] = None,
        embed_ms: float = 0.0,
        head_ms: float = 0.0,
        channel_depth: Optional[int] = None,
        hop_timeout_s: Optional[float] = None,
        step_timeout_s: Optional[float] = None,
        init_key: int = 0,
        params: Optional[dict] = None,
        num_cpus_per_stage: int = 1,
    ):
        if n_stages < 2:
            raise ValueError("MPMD pipeline needs >= 2 stages")
        config = Config.from_env()
        self.cfg = cfg
        self.n = int(n_stages)
        self.v = int(chunks_per_stage)
        self.V = self.n * self.v
        self.m = int(num_microbatches)
        self.mb = int(microbatch_size)
        self.seq = int(seq_len)
        self.depth = int(
            channel_depth or config.pipeline_channel_depth
        )
        self.hop_timeout = float(
            hop_timeout_s or config.pipeline_hop_timeout_s
        )
        self.step_timeout = float(
            step_timeout_s or config.pipeline_step_timeout_s
        )
        if isinstance(layer_ms, (int, float)):
            # One measured `layer_ms` stands for every layer of a
            # homogeneous stack — broadcast it.
            layer_ms = [float(layer_ms)] * cfg.n_layers
        self.bounds = partition_layers(
            cfg.n_layers,
            self.V,
            layer_ms,
            embed_ms=embed_ms,
            head_ms=head_ms,
        )
        self.schedules = interleaved_1f1b(self.n, self.m, self.v)
        # Bounded-edge validation: a schedule too deep for the
        # configured channel depth must die HERE (ValueError naming
        # the depth), never as an all-stages hang at hop-timeout.
        validate_schedule(
            self.schedules, self.n, self.m, self.v,
            channel_depth=self.depth,
        )
        self.stash_bound = max(
            max_stash_depth(ops) for ops in self.schedules
        )
        self._broken = False
        self._edges_by_boundary: Dict[
            Tuple[int, str], Edge
        ] = {}
        self._spawn(optimizer_factory, num_cpus_per_stage)
        self._wire()
        self._install_params(params, init_key)
        self._step_index = 0

    # -- build ---------------------------------------------------------
    def _spawn(self, optimizer_factory, num_cpus: int) -> None:
        stage_cls = rt.remote(num_cpus=num_cpus)(_PipelineStage)
        chunk_of_stage = {
            s: [
                (c, *self.bounds[c])
                for c in range(s, self.V, self.n)
            ]
            for s in range(self.n)
        }
        self.stages = [
            stage_cls.remote(
                s,
                self.n,
                self.cfg,
                chunk_of_stage[s],
                self.V,
                self.m,
                self.schedules[s],
                optimizer_factory,
                self.hop_timeout,
            )
            for s in range(self.n)
        ]
        rt.get(
            [a.ping.remote() for a in self.stages], timeout=120
        )

    def _placements(self) -> Dict[int, Optional[str]]:
        """stage index -> node id hex (shared compiled-DAG placement
        wait — a just-created actor may still be leasing)."""
        from ..dag.compiled import wait_actor_placements

        by_id = wait_actor_placements(
            [a for a in self.stages], timeout=60.0
        )
        return {
            s: by_id[a.actor_id.binary()]
            for s, a in enumerate(self.stages)
        }

    def _channel_capacity(self) -> int:
        import jax.numpy as jnp

        itemsize = jnp.dtype(self.cfg.dtype).itemsize
        record = (
            self.mb * self.seq * self.cfg.dim * itemsize + 4096
        )
        # Bounded depth IS the backpressure: a stage can run at most
        # `depth` records ahead of its consumer before put() blocks.
        return self.depth * record + 8192

    def _wire(self) -> None:
        placements = self._placements()
        capacity = self._channel_capacity()

        def new_edge(boundary: int, direction: str,
                     src: int, dst: int) -> Edge:
            same = placements.get(src) == placements.get(dst)
            chan = (
                ShmChannel(capacity)
                if same
                else TcpChannel(capacity)
            )
            edge = Edge(
                chan,
                f"s{src}->s{dst}:b{boundary}",
                direction,
            )
            self._edges_by_boundary[(boundary, direction)] = edge
            return edge

        fwd_in: List[Dict[int, Optional[Edge]]] = [
            {} for _ in range(self.n)
        ]
        fwd_out = [dict() for _ in range(self.n)]
        bwd_in = [dict() for _ in range(self.n)]
        bwd_out = [dict() for _ in range(self.n)]
        for c in range(self.V):
            src, dst = c % self.n, (c + 1) % self.n
            if c < self.V - 1:
                f_edge = new_edge(c, "fwd", src, dst)
                fwd_out[src][c] = f_edge
                fwd_in[dst][c + 1] = f_edge
                g_edge = new_edge(c, "grad", dst, src)
                bwd_out[dst][c + 1] = g_edge
                bwd_in[src][c] = g_edge
            # chunk 0 has no fwd_in/bwd_out; last chunk no
            # fwd_out/bwd_in — run_step never touches those keys.
        rt.get(
            [
                a.wire.remote(
                    fwd_in[s], fwd_out[s], bwd_in[s], bwd_out[s]
                )
                for s, a in enumerate(self.stages)
            ],
            timeout=120,
        )

    def _install_params(self, params, init_key) -> None:
        if params is None:
            import jax

            from ..models.llama import init_params

            params = init_params(
                jax.random.PRNGKey(init_key), self.cfg
            )
        params = jax_tree_to_numpy(params)
        per_stage: List[Dict[int, Any]] = [
            {} for _ in range(self.n)
        ]
        for c, (lo, hi) in enumerate(self.bounds):
            tree: Dict[str, Any] = {
                "layers": {
                    k: v[lo:hi]
                    for k, v in params["layers"].items()
                }
            }
            if c == 0:
                tree["embed"] = params["embed"]
            if c == self.V - 1:
                tree["final_norm"] = params["final_norm"]
                tree["lm_head"] = params["lm_head"]
            per_stage[c % self.n][c] = tree
        rt.get(
            [
                a.set_params.remote(per_stage[s])
                for s, a in enumerate(self.stages)
            ],
            timeout=300,
        )

    # -- stepping ------------------------------------------------------
    def step(self, tokens: np.ndarray,
             targets: np.ndarray) -> dict:
        """One global-batch training step. tokens/targets: [B, T]
        host arrays with B == num_microbatches * microbatch_size.
        Returns {"loss", "stages": [per-stage telemetry]}; raises
        MPMDPipelineError (never hangs) when a stage dies or a
        channel times out."""
        if self._broken:
            raise MPMDPipelineError(
                "pipeline is broken (a previous step failed)"
            )
        B = tokens.shape[0]
        if B != self.m * self.mb:
            raise ValueError(
                f"batch {B} != num_microbatches {self.m} x "
                f"microbatch_size {self.mb}"
            )
        tokens_mbs = [
            np.ascontiguousarray(
                tokens[i * self.mb : (i + 1) * self.mb]
            )
            for i in range(self.m)
        ]
        targets_mbs = [
            np.ascontiguousarray(
                targets[i * self.mb : (i + 1) * self.mb]
            )
            for i in range(self.m)
        ]
        self._step_index += 1
        refs = []
        for s, actor in enumerate(self.stages):
            refs.append(
                actor.run_step.remote(
                    self._step_index,
                    tokens_mbs if s == 0 else None,
                    targets_mbs if s == self.n - 1 else None,
                )
            )
        results = self._gather(refs)
        last = results[self.n - 1]
        count = max(last["count"], 1.0)
        aux_total = sum(r["aux"] for r in results)
        aux_w = float(getattr(self.cfg, "moe_aux_weight", 0.0))
        loss = (
            last["nll"] / count + aux_w * aux_total / self.m
        )
        return {"loss": loss, "stages": results}

    def _gather(self, refs) -> List[dict]:
        """Collect every stage's result; the FIRST failure aborts the
        pipeline: all channel edges close (same-host shm peers
        unblock with ChannelClosedError immediately instead of
        waiting out hop timeouts; cross-host TCP stages that stay
        blocked past the drain deadline are force-killed), then
        raises with the root cause. Bounded by step_timeout + drain
        end to end."""
        deadline = time.monotonic() + self.step_timeout
        results: List[Optional[dict]] = [None] * len(refs)
        pending = dict(enumerate(refs))
        first_err: Optional[BaseException] = None
        while pending and time.monotonic() < deadline:
            for i in list(pending):
                try:
                    results[i] = rt.get(pending[i], timeout=0.25)
                    del pending[i]
                except GetTimeoutError:
                    continue
                except Exception as e:  # noqa: BLE001 — stage death
                    first_err = first_err or e
                    del pending[i]
                    self._abort()
            if first_err:
                # Straight to the bounded drain + force-kill below —
                # polling stuck survivors here would stretch recovery
                # to hop/step timeouts instead of the 15s drain.
                break
        if pending and first_err is None:
            first_err = MPMDPipelineError(
                f"step exceeded step_timeout_s={self.step_timeout:g} "
                f"with {len(pending)} stage(s) outstanding"
            )
            self._abort()
        if first_err is not None:
            # Same-host edges are closed (ShmChannel's shared flag
            # unblocks peers immediately); drain the survivors so no
            # ref leaks.
            drain_deadline = time.monotonic() + 15.0
            stuck: List[int] = []
            for i in list(pending):
                try:
                    rt.get(
                        pending[i],
                        timeout=max(
                            0.1, drain_deadline - time.monotonic()
                        ),
                    )
                except GetTimeoutError:
                    stuck.append(i)
                except Exception:  # noqa: BLE001 — draining
                    pass
            # A stage still blocked past the drain deadline is on a
            # CROSS-HOST edge: the driver's TcpChannel copy owns no
            # socket (roles bind on first use), so edge.close() above
            # couldn't reach it — force-kill the actor; its dying
            # sockets unblock ITS peers in turn.
            for i in stuck:
                try:
                    rt.kill(self.stages[i])
                except Exception:  # noqa: BLE001 — teardown
                    pass
                try:
                    rt.get(pending[i], timeout=10)
                except Exception:  # noqa: BLE001 — draining
                    pass
            raise MPMDPipelineError(
                f"pipeline step failed: {first_err!r}"
            ) from first_err
        return results  # type: ignore[return-value]

    def _abort(self) -> None:
        self._broken = True
        for edge in self._edges_by_boundary.values():
            try:
                edge.close()
            except Exception:  # noqa: BLE001 — teardown
                pass

    # -- checkpoints (PR 4 async barrier compose) ---------------------
    def save_checkpoint(self, root: str,
                        async_save: bool = True) -> List[str]:
        """Each stage saves its shard (params + optimizer state);
        with async_save the host snapshot happens now and persistence
        overlaps the next steps — `wait_for_checkpoints()` is the
        durability barrier."""
        return rt.get(
            [
                a.save.remote(root, self._step_index, async_save)
                for a in self.stages
            ],
            timeout=300,
        )

    def wait_for_checkpoints(self) -> None:
        rt.get(
            [a.wait_ckpt.remote() for a in self.stages],
            timeout=600,
        )

    def restore_checkpoint(self, root: str, step: int) -> None:
        rt.get(
            [a.restore.remote(root, step) for a in self.stages],
            timeout=300,
        )
        self._step_index = int(step)

    # -- introspection -------------------------------------------------
    def collect_params(self) -> dict:
        """Reassemble the full model tree from the stage shards
        (tests / export; the layer stack concatenates in chunk
        order)."""
        per_stage = rt.get(
            [a.get_params.remote() for a in self.stages],
            timeout=300,
        )
        by_chunk: Dict[int, Any] = {}
        for shard in per_stage:
            by_chunk.update(shard)
        layers = {
            k: np.concatenate(
                [by_chunk[c]["layers"][k] for c in range(self.V)]
            )
            for k in by_chunk[0]["layers"]
        }
        return {
            "embed": by_chunk[0]["embed"],
            "layers": layers,
            "final_norm": by_chunk[self.V - 1]["final_norm"],
            "lm_head": by_chunk[self.V - 1]["lm_head"],
        }

    def theoretical_efficiency(self) -> float:
        return theoretical_efficiency(self.n, self.m, self.v)

    def shutdown(self) -> None:
        for edge in self._edges_by_boundary.values():
            try:
                edge.close()
                edge.unlink()
            except Exception:  # noqa: BLE001 — teardown
                pass
        for actor in getattr(self, "stages", []):
            try:
                rt.kill(actor)
            except Exception:  # noqa: BLE001 — teardown
                pass
