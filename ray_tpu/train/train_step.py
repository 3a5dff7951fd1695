"""Sharded training-step construction.

The TPU-native replacement for the reference's DDP wrapping
(reference: train/torch/train_loop_utils.py:162 prepare_model wraps in
DistributedDataParallel; config.py:115 inits the NCCL group): here
parameters/optimizer state are laid out on the mesh via logical-axis
rules and the step is one `jax.jit` whose gradient/psum collectives
XLA inserts from the shardings (GSPMD). dp+fsdp+tp+sp all come from
the same code path — the MeshSpec decides which are active.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.sharding import (
    ACT_RULES,
    PARAM_RULES,
    Rules,
    spec_for,
    tree_shardings,
)


@dataclasses.dataclass
class TrainState:
    """Param + optimizer-state pytree (registered below)."""

    step: jax.Array
    params: Any
    opt_state: Any


jax.tree_util.register_dataclass(
    TrainState, data_fields=["step", "params", "opt_state"], meta_fields=[]
)


def default_optimizer(
    learning_rate: float = 3e-4,
    weight_decay: float = 0.1,
    warmup_steps: int = 100,
    total_steps: int = 10000,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
) -> optax.GradientTransformation:
    """AdamW + cosine schedule + global-norm clipping — the standard
    pretraining recipe (reference parity: the configs its release
    train_tests use for Llama-2 pretraining)."""
    warmup_steps = min(warmup_steps, max(1, total_steps // 10))
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=learning_rate,
        warmup_steps=warmup_steps,
        decay_steps=max(total_steps, warmup_steps + 1),
        end_value=learning_rate * 0.1,
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay),
    )


def _path_keys(path) -> Tuple[str, ...]:
    """Dict/attribute names along a pytree key path (indices dropped)."""
    keys = []
    for entry in path:
        name = getattr(entry, "key", None) or getattr(entry, "name", None)
        if isinstance(name, str):
            keys.append(name)
    return tuple(keys)


def infer_opt_shardings(
    optimizer: optax.GradientTransformation,
    params: Any,
    param_shardings: Any,
    replicated: NamedSharding,
) -> Any:
    """Sharding tree for optimizer.init's output: each moment leaf
    (e.g. adam mu/nu at path (..., 'mu', <param path>)) inherits the
    sharding of the parameter whose key-path is a suffix of its own;
    everything else (step counters) is replicated."""
    by_path: Dict[Tuple[str, ...], Any] = {}
    for path, sharding in jax.tree_util.tree_flatten_with_path(
        param_shardings
    )[0]:
        by_path[_path_keys(path)] = sharding
    abstract = jax.eval_shape(optimizer.init, params)

    def leaf_sharding(path, leaf):
        keys = _path_keys(path)
        for start in range(len(keys)):
            match = by_path.get(keys[start:])
            if match is not None:
                return match
        return replicated

    return jax.tree_util.tree_map_with_path(leaf_sharding, abstract)


def make_train_step(
    loss_fn: Callable[..., jax.Array],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    param_annotations: Any,
    *,
    param_rules: Rules = PARAM_RULES,
    batch_logical_axes: Tuple[Optional[str], ...] = ("batch", "seq"),
    act_rules: Rules = ACT_RULES,
    donate: bool = True,
):
    """Build (init_fn, step_fn).

    loss_fn(params, tokens, targets) -> scalar loss.
    init_fn(key, init_params_fn) -> sharded TrainState.
    step_fn(state, tokens, targets) -> (state, metrics) — jitted, with
    params/opt-state donated so the update is in-place in HBM.
    """
    param_shardings = tree_shardings(mesh, param_annotations, param_rules)
    batch_sharding = NamedSharding(
        mesh, spec_for(batch_logical_axes, act_rules)
    )
    repl = NamedSharding(mesh, P())

    def init_fn(key, init_params_fn) -> TrainState:
        from .._private import compile_watch

        # jit with out_shardings lays parameters out directly on the
        # mesh — no host-side full copy of the model is ever built.
        params = compile_watch.instrument(
            "train.init_params",
            jax.jit(init_params_fn, out_shardings=param_shardings),
        )(key)
        # Optimizer moments must shard exactly like their parameters
        # (the ZeRO-3 property); jit's inference doesn't guarantee it,
        # so derive explicit out_shardings by param-path matching.
        opt_shardings = infer_opt_shardings(
            optimizer, params, param_shardings, repl
        )
        opt_state = compile_watch.instrument(
            "train.init_opt_state",
            jax.jit(optimizer.init, out_shardings=opt_shardings),
        )(params)
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params, opt_state=opt_state
        )

    def _step(state: TrainState, tokens, targets):
        with jax.named_scope("grad"):
            loss, grads = jax.value_and_grad(loss_fn)(
                state.params, tokens, targets
            )
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
            gnorm = optax.global_norm(grads)
        metrics = {"loss": loss, "grad_norm": gnorm}
        return (
            TrainState(
                step=state.step + 1, params=new_params, opt_state=new_opt
            ),
            metrics,
        )

    # Registered with the XLA compile watcher by name: a training
    # loop's step must compile once per (state, batch) geometry and
    # never again — a drifting batch shape that re-traces it every
    # iteration now convicts itself in `doctor` verdict.compile
    # (recompile_storm) instead of reading as a mysteriously slow
    # loop, and the cold-compile step bills compile_ms as a stall.
    from .._private import compile_watch

    step_fn = compile_watch.instrument(
        "train.step",
        jax.jit(
            _step,
            in_shardings=(None, batch_sharding, batch_sharding),
            out_shardings=(None, repl),
            donate_argnums=(0,) if donate else (),
        ),
    )
    return init_fn, step_fn


def shard_batch(batch, mesh: Mesh, logical_axes=("batch", "seq"),
                rules: Rules = ACT_RULES):
    """Device-put host batches onto the mesh data axes."""
    sharding = NamedSharding(mesh, spec_for(logical_axes, rules))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)


def prefetch_to_device(
    batches,
    mesh: Mesh,
    *,
    buffer_size: int = 2,
    logical_axes: Tuple[Optional[str], ...] = ("batch", "seq"),
    rules: Rules = ACT_RULES,
):
    """Double-buffer host batches onto the mesh: batch N+1's
    device_put is dispatched before batch N is consumed, so its H2D
    transfer overlaps step N's compute (flax.jax_utils
    prefetch_to_device pattern; device_put is an async dispatch on
    TPU/GPU backends).

    `batches` is any iterator of pytrees (e.g. Dataset.iter_batches
    output); each leaf is device_put with the same sharding
    shard_batch would use. buffer_size=2 is classic double buffering;
    1 degenerates to put-then-yield with no overlap.
    """
    from collections import deque

    from .._private import step_telemetry

    if buffer_size < 1:
        raise ValueError(f"buffer_size must be >= 1, got {buffer_size}")
    sharding = NamedSharding(mesh, spec_for(logical_axes, rules))

    def put(batch):
        # H2D dispatch time, attributed per step (device_put is an
        # async dispatch on TPU/GPU — what's measured is the stall the
        # loop pays, which is exactly the number the doctor wants).
        with step_telemetry.phase_timer("h2d_ms"):
            return jax.tree.map(
                lambda x: jax.device_put(x, sharding), batch
            )

    window: "deque" = deque()
    iterator = iter(batches)

    def pull():
        # data_wait is timed at this outermost consumer boundary;
        # phase_timer's reentrancy guard keeps a telemetry-wrapped
        # source — even one buried under user transforms, e.g.
        # (augment(b) for b in ds.iter_batches(...)) — from billing
        # the same stall twice.
        with step_telemetry.phase_timer("data_wait_ms"):
            return next(iterator)

    while True:
        while len(window) < buffer_size:
            try:
                window.append(put(pull()))
            except StopIteration:
                while window:
                    yield window.popleft()
                return
        yield window.popleft()
