"""Pipeline parallelism over the `pp` mesh axis.

The reference's building blocks for pipelining are compiled actor
DAGs over NCCL channels (reference: dag/compiled_dag_node.py:691,
experimental/channel/torch_tensor_nccl_channel.py) — i.e. stage hops
travel through the runtime. The TPU-native design keeps the whole
pipeline INSIDE one jitted SPMD program: every pp rank holds its
stage's parameters, microbatch activations hop stages via
`lax.ppermute` over ICI, and the classic GPipe skew schedule
(num_microbatches + num_stages - 1 ticks) keeps all stages busy.
XLA overlaps the neighbor hop with stage compute; no runtime channel
is involved. The cross-host version of the same schedule rides the
compiled actor DAG (ray_tpu.dag) with one SPMD program per stage gang.

Use inside shard_map: the wrapper `spmd_pipeline` masks the pipeline
bubble, injects microbatch i into stage 0 at tick i, and emits stage
N-1's output at tick i+N-1. Differentiable end to end (ppermute has a
transpose rule), so pipeline-parallel training composes with jax.grad.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .collective import axis_size as _axis_size, pcast_varying


def spmd_pipeline(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,
    *,
    axis_name: str = "pp",
    stacked_params: bool = True,
    with_aux: bool = False,
):
    """Run a stage-partitioned function over microbatches.

    stage_fn(stage_params, x) — this rank's stage; all ranks call it
    every tick (SPMD), invalid ticks are masked. With `with_aux=True`
    it must return (y, aux_scalar); aux from valid ticks is summed
    rank-locally across ticks (aux never travels between stages — sum
    it over `axis_name` with a psum to get the pipeline total).
    stage_params — a stacked [n_stages, ...] param tree sharded
    P('pp', ...); shard_map hands each rank its [1, ...] slice and the
    singleton stage axis is stripped here (pass stacked_params=False
    if the tree is already per-rank).
    microbatches — [num_mb, mb, ...] input, same on every rank (only
    stage 0 actually consumes it).

    Returns [num_mb, mb, ...] outputs (or (outputs, aux_sum) with
    with_aux), valid on the LAST stage's ranks (other ranks hold
    zeros); use `broadcast_from_last_stage` if every rank needs them.
    """
    if stacked_params:
        stage_params = jax.tree.map(lambda a: a[0], stage_params)
    n = _axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    num_mb = microbatches.shape[0]
    ticks = num_mb + n - 1
    # Stage hop: rank i's output becomes rank i+1's input.
    perm = [(i, (i + 1) % n) for i in range(n)]

    def tick(t, carry):
        state, outputs, aux_acc = carry
        # state: activation entering this rank's stage this tick.
        mb_index = t - rank  # microbatch this stage works on
        inject = jnp.take(
            microbatches,
            jnp.clip(t, 0, num_mb - 1),
            axis=0,
        )
        x = jnp.where(rank == 0, inject, state)
        if with_aux:
            y, aux = stage_fn(stage_params, x)
        else:
            y, aux = stage_fn(stage_params, x), 0.0
        valid = (mb_index >= 0) & (mb_index < num_mb)
        y = jnp.where(valid, y, jnp.zeros_like(y))
        aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
        # Last stage banks its finished microbatch.
        out_index = jnp.clip(t - (n - 1), 0, num_mb - 1)
        write = valid & (rank == n - 1)
        outputs = jnp.where(
            write,
            outputs.at[out_index].set(y),
            outputs,
        )
        state = lax.ppermute(y, axis_name, perm)
        return state, outputs, aux_acc

    # The carry is device-varying over pp (each rank holds different
    # activations); mark the zero initializers so the loop carry's
    # varying-manual-axes type matches. zeros_like inherits any OTHER
    # varying axes (sp/ep) the activations already carry when the
    # pipeline composes with sequence/expert parallelism.
    state = pcast_varying(
        jnp.zeros_like(jnp.take(microbatches, 0, axis=0)),
        axis_name,
    )
    outputs = pcast_varying(jnp.zeros_like(microbatches), axis_name)
    aux_acc = pcast_varying(jnp.zeros((), jnp.float32), axis_name)
    _, outputs, aux_acc = lax.fori_loop(
        0, ticks, tick, (state, outputs, aux_acc)
    )
    return (outputs, aux_acc) if with_aux else outputs


def broadcast_from_last_stage(
    outputs: jax.Array, axis_name: str = "pp"
) -> jax.Array:
    """All ranks get the last stage's outputs (zeros elsewhere make a
    psum a broadcast)."""
    return lax.psum(outputs, axis_name)


def stack_stage_params(per_stage_params: list) -> Any:
    """[stage0_tree, stage1_tree, ...] -> one tree with a leading
    stage axis, ready to shard over pp (P('pp', ...))."""
    return jax.tree.map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params
    )
