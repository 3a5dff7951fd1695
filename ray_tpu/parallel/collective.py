"""Explicit collective API compiling to XLA collectives.

API modeled on the reference's `ray.util.collective` (reference:
python/ray/util/collective/collective.py:258-615 — allreduce,
allgather, reducescatter, broadcast, send/recv, barrier over NCCL/GLOO
groups). TPU-native difference (SURVEY.md §5.8): these are *traced*
primitives used inside `shard_map`-decorated functions over a named
mesh axis, so XLA schedules them on ICI — there is no runtime
communicator object to manage and no NCCL.

Example:

    mesh = MeshSpec(fsdp=8).build()
    @partial(shard_map, mesh=mesh, in_specs=P("fsdp"), out_specs=P("fsdp"))
    def step(x):
        g = allreduce(local_grad(x), "fsdp")
        ...
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

Axis = Union[str, Sequence[str]]


def allreduce(x, axis: Axis, op: str = "sum"):
    """Reduce across the mesh axis; all members get the result
    (reference: collective.py:258 allreduce)."""
    if op == "sum":
        return lax.psum(x, axis)
    if op == "mean":
        return lax.pmean(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    if op == "prod":
        # Gather-then-multiply handles zeros and negatives exactly
        # (a log/exp trick would NaN on them).
        gathered = lax.all_gather(x, axis)
        return jnp.prod(gathered, axis=0)
    raise ValueError(f"unsupported reduce op: {op}")


def allgather(x, axis: Axis, *, concat_axis: int = 0, tiled: bool = True):
    """Gather shards from every member of the axis
    (reference: collective.py:371 allgather)."""
    return lax.all_gather(x, axis, axis=concat_axis, tiled=tiled)


def reducescatter(x, axis: Axis, *, scatter_axis: int = 0, op: str = "sum"):
    """Reduce then scatter shards (reference: collective.py:443)."""
    if op not in ("sum", "mean"):
        raise ValueError(f"unsupported reducescatter op: {op}")
    out = lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=True)
    if op == "mean":
        out = out / axis_size(axis)
    return out


def broadcast(x, axis: Axis, root: int = 0):
    """Every member receives root's value (reference: collective.py:300).

    Non-root values are discarded with `where` (not multiplied by 0,
    which would propagate their NaN/Inf) before a psum that XLA lowers
    to an ICI broadcast.
    """
    idx = lax.axis_index(axis)
    selected = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(selected, axis)


def send_recv(x, axis: Axis, *, shift: int = 1):
    """Neighbor exchange on a ring: each member sends its value
    `shift` steps forward and receives from `shift` steps back
    (reference p2p: collective.py:531 send / :594 recv; here a single
    fused ppermute, which is how rings ride ICI)."""
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def barrier(axis: Axis):
    """Synchronize members of the axis (reference: collective.py:615).

    Under XLA a barrier is a collective with trivial payload.
    """
    return lax.psum(jnp.zeros((), dtype=jnp.float32), axis)


def all_to_all(
    x,
    axis: Axis,
    *,
    split_axis: int,
    concat_axis: int,
):
    """All-to-all reshard — the Ulysses sequence-parallelism primitive
    (SURVEY.md §5.7): swap which array dimension is sharded over the
    mesh axis."""
    return lax.all_to_all(x, axis, split_axis, concat_axis, tiled=True)


def axis_index(axis: Axis):
    return lax.axis_index(axis)


def axis_size(axis: Axis):
    """Static size of a named mesh axis (a Python int at trace
    time)."""
    return lax.axis_size(axis)


def pcast_varying(x, axes):
    """Mark `x` varying over `axes` for the varying-manual-axes type
    check. Axes `x` already varies over are left alone (`lax.pcast`
    rejects a repeat)."""
    if isinstance(axes, str):
        axes = (axes,)
    missing = tuple(
        ax for ax in axes if ax not in jax.typeof(x).vma
    )
    return lax.pcast(x, missing, to="varying") if missing else x
