"""Pipeline-parallel training step for the flagship model.

Runs the FULL Llama training step with its layer stack partitioned
over the `pp` mesh axis (GPipe schedule inside one SPMD program,
parallel/pipeline.py), composing with sequence parallelism (ring
attention over `sp`) and expert parallelism (MoE all_to_all over `ep`)
in the same shard_map. The reference's pipeline story is runtime
channels between actor stages (reference: dag/compiled_dag_node.py:691
+ NCCL channels); here stage hops are `lax.ppermute` over ICI and the
optimizer update runs outside the shard_map under GSPMD, sharded
exactly like the parameters.

Mesh contract: axes ("pp", "sp", "ep"), any of them size 1. The batch
dim shards over `ep` (which doubles as the data axis — experts are
sharded over the same devices that hold different batch shards, the
standard DeepSeek/GShard layout), the sequence dim over `sp`, and the
layer stack over `pp`.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.llama import (
    LlamaConfig,
    _layer,
    embed_tokens,
    masked_xent,
    model_norm,
    param_annotations,
)
from ..ops.norms import rotary_embedding
from ..parallel.collective import pcast_varying
from ..parallel.pipeline import broadcast_from_last_stage, spmd_pipeline
from ..parallel.sharding import Annotated, checked_shard_map
from .train_step import TrainState, infer_opt_shardings


def to_pipeline_params(params: Any, pp: int) -> Any:
    """Reshape the stacked layer tree [L, ...] -> [pp, L/pp, ...] so
    the leading stage axis shards over `pp`."""
    out = dict(params)
    out["layers"] = jax.tree.map(
        lambda a: a.reshape(pp, a.shape[0] // pp, *a.shape[1:]),
        params["layers"],
    )
    return out


def _pipeline_param_specs(cfg: LlamaConfig) -> Any:
    """PartitionSpecs for to_pipeline_params' tree: stage axis on pp,
    expert axis on ep, everything else replicated (embed/lm_head are
    small at flagship scale relative to the layer stack; tp composes
    later if needed)."""
    ann = param_annotations(cfg)

    def layer_spec(a: Annotated) -> P:
        parts = ["pp", None]  # [stage, layers/stage, ...]
        for name in a.logical_axes[1:]:
            parts.append("ep" if name == "expert" else None)
        return P(*parts)

    return {
        "embed": P(),
        "layers": jax.tree.map(
            layer_spec, ann["layers"],
            is_leaf=lambda x: isinstance(x, Annotated),
        ),
        "final_norm": P(),
        "lm_head": P(),
    }


def make_pp_train_step(
    cfg: LlamaConfig,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    *,
    num_microbatches: Optional[int] = None,
    donate: bool = True,
) -> Tuple[Callable, Callable]:
    """Build (init_fn, step_fn) for pipeline-parallel training.

    init_fn(key, init_params_fn) -> sharded TrainState (layer stack
    pre-reshaped to [pp, L/pp, ...]).
    step_fn(state, tokens, targets) -> (state, metrics); tokens are the
    GLOBAL batch [B, T] with B % (ep * num_microbatches) == 0 and
    T % sp == 0.
    """
    pp = mesh.shape["pp"]
    sp = mesh.shape.get("sp", 1)
    ep = mesh.shape.get("ep", 1)
    if cfg.n_layers % pp != 0:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pp={pp}"
        )
    num_mb = num_microbatches or max(2 * pp, 2)
    sp_axis = "sp" if sp > 1 else None
    ep_axis = "ep" if ep > 1 else None

    param_specs = _pipeline_param_specs(cfg)
    param_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    batch_spec = P("ep", "sp")  # [batch, seq]
    batch_sharding = NamedSharding(mesh, batch_spec)
    repl = NamedSharding(mesh, P())

    def pp_loss(params, tokens, targets):
        # Local shapes: tokens [b_loc, t_loc]; b_loc = B/ep, t_loc = T/sp.
        b_loc, t_loc = tokens.shape
        mb = b_loc // num_mb
        # Global positions of this rank's sequence shard drive RoPE and
        # ring attention's causal masking.
        sp_rank = lax.axis_index("sp") if sp > 1 else 0
        positions = sp_rank * t_loc + jnp.arange(t_loc)
        cos, sin = rotary_embedding(
            jnp.broadcast_to(positions, (mb, t_loc)),
            cfg.head_dim, cfg.rope_theta,
            getattr(cfg, "rope_scaling", None),
        )

        # Embedding runs on every pp rank (cheap vs the stack); only
        # rank 0's result is injected into the pipeline. Shared helper
        # so family conventions (Gemma sqrt(dim) scale) apply here too.
        x = embed_tokens(cfg, params, tokens)
        microbatches = x.reshape(num_mb, mb, t_loc, -1)
        stage_layers = jax.tree.map(lambda a: a[0], params["layers"])

        def stage_fn(layers, h):
            def body(xc, layer):
                return _layer(cfg, xc, layer, cos, sin, sp_axis, ep_axis)

            if cfg.remat:
                if cfg.remat_policy == "dots":
                    body = jax.checkpoint(
                        body,
                        policy=jax.checkpoint_policies
                        .dots_with_no_batch_dims_saveable,
                    )
                else:
                    body = jax.checkpoint(body)
            h, auxs = lax.scan(body, h, layers)
            # The pipeline carry is a single activation array, so the
            # MoE aux loss rides spmd_pipeline's rank-local accumulator
            # instead; it must vary over at most pp — average the data
            # axes here.
            # Varying over sp/ep before the psum/pmean below (the
            # varying-manual-axes check requires it).
            aux = pcast_varying(jnp.sum(auxs), ("sp", "ep"))
            return h, lax.pmean(aux, ("sp", "ep"))

        outs, aux_local = spmd_pipeline(
            stage_fn, stage_layers, microbatches,
            axis_name="pp", stacked_params=False, with_aux=True,
        )
        # Stage ranks each accumulated their own layers' aux over all
        # microbatches: sum stages, average microbatches to match the
        # non-pp loss_fn scale.
        aux = lax.psum(aux_local, "pp") / num_mb
        outs = broadcast_from_last_stage(outs, "pp")
        h = outs.reshape(b_loc, t_loc, -1)
        h = model_norm(cfg, h, params["final_norm"])
        logits = (h @ params["lm_head"]).astype(jnp.float32)

        nll_sum, count = masked_xent(logits, targets)
        # Reduce over BOTH data axes unconditionally (even size-1 axes
        # carry a formal varying mark from the batch in_spec, and
        # out_specs=P() demands a fully unvarying scalar).
        local = pcast_varying(
            jnp.stack([nll_sum, count]), ("sp", "ep")
        )
        local = lax.psum(local, ("sp", "ep"))
        xent = local[0] / jnp.maximum(local[1], 1.0)
        return xent + cfg.moe_aux_weight * aux

    smapped = checked_shard_map(
        pp_loss,
        mesh,
        (param_specs, batch_spec, batch_spec),
        P(),
    )

    def init_fn(key, init_params_fn) -> TrainState:
        from .._private import compile_watch

        def build(k):
            return to_pipeline_params(init_params_fn(k), pp)

        params = compile_watch.instrument(
            "train.pipeline.init_params",
            jax.jit(build, out_shardings=param_shardings),
        )(key)
        opt_shardings = infer_opt_shardings(
            optimizer, params, param_shardings, repl
        )
        opt_state = compile_watch.instrument(
            "train.pipeline.init_opt_state",
            jax.jit(optimizer.init, out_shardings=opt_shardings),
        )(params)
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            opt_state=opt_state,
        )

    def _step(state: TrainState, tokens, targets):
        loss, grads = jax.value_and_grad(smapped)(
            state.params, tokens, targets
        )
        updates, new_opt = optimizer.update(
            grads, state.opt_state, state.params
        )
        new_params = optax.apply_updates(state.params, updates)
        metrics = {"loss": loss, "grad_norm": optax.global_norm(grads)}
        return (
            TrainState(
                step=state.step + 1, params=new_params, opt_state=new_opt
            ),
            metrics,
        )

    # Same compile-watch contract as make_train_step's "train.step":
    # one compile per geometry, recompile storms convicted by name.
    from .._private import compile_watch

    step_fn = compile_watch.instrument(
        "train.pp_step",
        jax.jit(
            _step,
            in_shardings=(None, batch_sharding, batch_sharding),
            out_shardings=(None, repl),
            donate_argnums=(0,) if donate else (),
        ),
    )
    return init_fn, step_fn
