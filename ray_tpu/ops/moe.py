"""Mixture-of-experts: one router, one gated expert function, two ways
of bringing tokens to experts.

Absent from the reference (SURVEY.md §2.4 EP row — no MoE sharding
anywhere in Ray core or its libraries); built TPU-first.

  * `route`: float32 softmax over all experts, the `k` largest
    probabilities and their experts, renormalised to sum to one or
    left as they are (OLMoE's `norm_topk_prob: false`).
  * `route_grouped_sigmoid`: DeepSeek-V3's router: sigmoid scores, a
    correction bias that decides the choice and not the gates, the
    choice limited to the best groups of experts.
  * `gated_experts`: `down(act(gate(x)) * up(x))` for rows already
    grouped by expert; how a row finds its expert's matrices is the
    caller's matmul.
  * `moe_ffn_dropless` — every expert on this device (training on one
    device, the serve forwards): the `t * k` picks are sorted by
    expert and the three matmuls run as grouped matmuls over the
    sorted rows (`lax.ragged_dot`, which XLA compiles to its own
    grouped-matmul kernel on the TPU), so each token meets only its
    `k` experts and NOTHING is dropped at any skew: all tokens to one
    expert is one group of `t` rows. Where the device holds a share
    of the experts its router deals over (one rank of an
    expert-parallel layer), the picks that meet a held expert are the
    first of the sorted picks and only `held_row_budget` rows are
    gathered, multiplied, summed and differentiated; a load over the
    budget takes every row, so the layer is exact at every load.
  * `moe_ffn_ep` — experts sharded over the `ep` mesh axis inside a
    `shard_map`: dispatch/return are `lax.all_to_all` hops over ICI
    with GShard/Switch fixed-capacity buffers (static shapes for the
    exchange; picks past an expert's capacity drop to the residual
    path). Its dropless rewrite belongs to the four-chip training
    cell (ROADMAP.md Reach 1).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.collective import axis_size as _axis_size
from .norms import swiglu


def init_moe_params(
    key,
    num_experts: int,
    d_model: int,
    d_ff: int,
    dtype=jnp.float32,
) -> Dict[str, jax.Array]:
    k_router, k_gate, k_up, k_down = jax.random.split(key, 4)
    scale_in = 1.0 / math.sqrt(d_model)
    scale_out = 1.0 / math.sqrt(d_ff)

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    return {
        "router": normal(k_router, (d_model, num_experts), scale_in),
        "w_gate": normal(k_gate, (num_experts, d_model, d_ff), scale_in),
        "w_up": normal(k_up, (num_experts, d_model, d_ff), scale_in),
        "w_down": normal(k_down, (num_experts, d_ff, d_model), scale_out),
    }


def route(
    x: jax.Array, router: jax.Array, k: int, renormalise: bool = True
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x [t, d], router [d, E] -> (gates [t, k] float32, experts
    [t, k], aux_loss). Logits and softmax are float32 whatever the
    model's dtype: which expert comes 8th is decided here.

    aux_loss is the Switch/GShard load-balancing loss: mean expert
    probability x mean assignment fraction, scaled by num_experts.
    """
    logits = jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = lax.top_k(probs, k)
    if renormalise:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    num_experts = logits.shape[-1]
    assign = jnp.sum(
        jax.nn.one_hot(experts[:, 0], num_experts), axis=0
    ) / logits.shape[0]
    importance = jnp.mean(probs, axis=0)
    aux_loss = num_experts * jnp.sum(assign * importance)
    return gates, experts, aux_loss


def route_grouped_sigmoid(
    x: jax.Array,
    router: jax.Array,
    bias: jax.Array,
    k: int,
    n_groups: int,
    top_groups: int,
    scale: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """DeepSeek-V3's router (`scoring_func: sigmoid`, `topk_method:
    noaux_tc`, `norm_topk_prob`): x [t, d], router [d, E], bias [E]
    -> (gates [t, k] float32, experts [t, k]).

    A score is `sigmoid(x . router_e)`, float32. WHICH experts a token
    gets is decided on `score + bias` (the correction bias that
    balances load without an auxiliary loss): the experts stand in
    `n_groups` groups of neighbours, a group's mark is the sum of its
    two largest corrected scores, the `top_groups` best groups stay
    and the `k` largest corrected scores inside them win. The GATES
    are the winners' plain scores, the bias left out, over their sum,
    times `scale` (`routed_scaling_factor`)."""
    logits = jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    scores = jax.nn.sigmoid(logits)
    corrected = scores + bias.astype(jnp.float32)
    t, num_experts = scores.shape
    by_group = corrected.reshape(t, n_groups, num_experts // n_groups)
    marks = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)  # [t, groups]
    _, kept = lax.top_k(marks, top_groups)
    in_kept = jnp.any(
        kept[:, :, None] == jnp.arange(n_groups), axis=1
    )  # [t, groups]
    corrected = jnp.where(
        in_kept[:, :, None], by_group, -jnp.inf
    ).reshape(t, num_experts)
    _, experts = lax.top_k(corrected, k)
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True) * scale
    return gates, experts


@jax.custom_vjp
def grouped_matmul(
    rows: jax.Array, weights: jax.Array, groups: jax.Array
) -> jax.Array:
    """`lax.ragged_dot(rows [n, d], weights [G, d, f], groups [G])`
    whose gradient is the mathematics' wherever the rows lie: a row
    behind the last group is in no product, so its cotangent is ZERO.

    XLA's grouped-matmul kernel on the TPU leaves the rows behind the
    last group as they were in memory, in its transposes as in the
    forward (the CPU's writes zeros there and shows nothing). The
    forward's callers select those rows of the OUTPUT away; the
    cotangent to `rows` came back with the same garbage, and the
    gather's transpose added it into real tokens' gradients (PR 55, on
    the chip, one rank's share with seven picks of eight held
    elsewhere: a third of the runs reached NaN inside 48 s and the
    others trained on noise). The select lives here, in the backward
    pass of the product itself, so it holds for every caller that
    differentiates (a layer's slice or a whole stack, dead rows or
    picks held elsewhere), and a forward that is never differentiated
    lowers to `lax.ragged_dot` alone, as before."""
    return lax.ragged_dot(rows, weights, groups)


def _grouped_matmul_fwd(rows, weights, groups):
    return lax.ragged_dot(rows, weights, groups), (rows, weights, groups)


def _grouped_matmul_bwd(saved, cotangent):
    rows, weights, groups = saved
    d_rows, d_weights = jax.vjp(
        lambda r, w: lax.ragged_dot(r, w, groups), rows, weights
    )[1](cotangent)
    grouped = jnp.arange(rows.shape[0]) < jnp.sum(groups)
    return jnp.where(grouped[:, None], d_rows, 0), d_weights, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def gated_experts(
    params: Dict, rows: jax.Array, matmul: Callable, glu: Callable
) -> jax.Array:
    """`down(glu(up(rows), gate(rows)))` with the caller's grouped
    `matmul(rows, expert_weights)`; `glu(x, gate) = act(gate) * x`."""
    hidden = glu(
        matmul(rows, params["w_up"]), matmul(rows, params["w_gate"])
    )
    return matmul(hidden, params["w_down"])


def held_row_budget(picks: int, held: int, routed_over: int) -> int:
    """The rows an expert layer computes of its `picks` = tokens x k
    sorted picks, where it holds `held` of the `routed_over` experts
    its router deals over: twice the held experts' even share of the
    picks, in whole 512s, and never more than the picks. A layer that
    holds every expert it routes over, or so few picks that the round
    number reaches them (a decode step's rows), computes all of them:
    the budget IS the picks, and `moe_ffn_dropless` is the program it
    was. A shape's rule, with nothing to set: the layer is exact at
    every load (`moe_ffn_dropless`), the budget only says which of
    its two programs a load takes."""
    if routed_over <= held:
        return picks
    share = -(-2 * picks * held // routed_over)
    return min(picks, -(-share // 512) * 512)


def _flat(w):
    """A stack's two leading axes merged (free): `[layers x E, ., .]`."""
    return w.reshape((-1,) + w.shape[-2:])


def _all_rows(params, x, gates, picks, k, glu, masked):
    """Every one of the `t * k` sorted picks through the experts and
    back to its token, the ones behind the last group selected away
    (`masked`: there are picks of experts held elsewhere). `picks` are
    `(picked, order, groups, live)`."""
    picked, order, groups, live = picks
    t = x.shape[0]
    num_experts = params["w_gate"].shape[-3]
    with jax.named_scope("moe/experts"):
        out = gated_experts(
            params,
            x[order // k],
            lambda rows, w: grouped_matmul(rows, _flat(w), groups),
            glu,
        )
    with jax.named_scope("moe/combine"):
        # Back to token order by the inverse permutation (a gather; a
        # scatter-add of t*k rows serialises on the TPU), then the
        # weighted sum of each token's k rows in float32.
        out = out[jnp.argsort(order)].reshape(t, k, -1)
        if live is not None:
            # Rows behind the last group are whatever the kernel left.
            out = jnp.where(live[:, None, None], out, 0)
        if masked:
            out = jnp.where(
                (picked < num_experts).reshape(t, k, 1), out, 0
            )
        out = jnp.sum(out * gates[:, :, None], axis=1)
    return out


def _held_picks(picks, rows, k):
    """Of the first `rows` sorted picks: -> (their index into the
    `t * k` picks, their tokens, [rows, 1] whether a row is a held
    pick or filler behind the last group)."""
    _, order, groups, _ = picks
    first = order[:rows]
    return first, first // k, (jnp.arange(rows) < jnp.sum(groups))[:, None]


def _held_rows(params, x, gates, picks, rows, k, glu):
    """The first `rows` sorted picks alone: the picks that met a held
    expert, in expert order, then filler behind the last group ->
    (out [t, d] float32, what `_held_rows_bwd` needs of it). Each pass
    of the layer (the gather of the tokens' rows, the three grouped
    matmuls, the GLU, the gates, the sum back into the tokens) runs
    over `rows` rows and not `t * k`."""
    groups = picks[2]
    first, token, filled = _held_picks(picks, rows, k)
    with jax.named_scope("moe/experts"):
        xs = x[token]
        up = lax.ragged_dot(xs, _flat(params["w_up"]), groups)
        gate = lax.ragged_dot(xs, _flat(params["w_gate"]), groups)
        out = lax.ragged_dot(glu(up, gate), _flat(params["w_down"]), groups)
    with jax.named_scope("moe/combine"):
        # The filler is whatever the kernel left (garbage on the chip):
        # selected away BEFORE the product with the gate. A filler
        # row's token is a real one and takes a zero. The sum is a
        # scatter-add of `rows` rows in float32: at a quarter of the
        # picks it costs less than the gather of `t x k` rows and the
        # float32 product over `[t, k, d]` it replaces (PERF.md
        # section 6, PR 57).
        y = jnp.where(filled, out, 0) * gates.reshape(-1)[first][:, None]
        y = jnp.zeros(x.shape, y.dtype).at[token].add(y)
    return y, (xs, up, gate, out)


def _held_rows_bwd(saved, params, x, gates, picks, rows, k, glu, dy):
    """`_held_rows`' cotangents to (the expert matrices, x, gates)
    from what its forward saved: every pass over `rows` rows. Written
    out because the residuals have to cross a `lax.cond` by hand
    (`_budgeted_rows`); the parametrised test holds it to `jax.grad`
    of the all-rows program and of a plain loop. A row behind the last
    group is garbage in every saved array and in the kernel's
    cotangents to rows (`_grouped_matmul_bwd` selects those away), and
    its own cotangent is zero."""
    xs, up, gate, out = saved
    groups = picks[2]
    first, token, filled = _held_picks(picks, rows, k)
    with jax.named_scope("moe/combine"):
        d_y = dy[token]
        d_gates = jnp.zeros(gates.size, gates.dtype).at[first].add(
            jnp.sum(jnp.where(filled, out, 0) * d_y, axis=-1)
        ).reshape(gates.shape)
        weight = gates.reshape(-1)[first][:, None]
        d_out = jnp.where(filled, d_y * weight, 0).astype(out.dtype)
    with jax.named_scope("moe/experts"):
        hidden, glu_bwd = jax.vjp(glu, up, gate)
        d_hidden, d_down, _ = _grouped_matmul_bwd(
            (hidden, _flat(params["w_down"]), groups), d_out
        )
        d_up, d_gate = glu_bwd(d_hidden)
        d_xs_up, d_w_up, _ = _grouped_matmul_bwd(
            (xs, _flat(params["w_up"]), groups), d_up
        )
        d_xs_gate, d_w_gate, _ = _grouped_matmul_bwd(
            (xs, _flat(params["w_gate"]), groups), d_gate
        )
        d_x = jnp.zeros(x.shape, x.dtype).at[token].add(d_xs_up + d_xs_gate)
    d_params = {
        "w_gate": d_w_gate.reshape(params["w_gate"].shape),
        "w_up": d_w_up.reshape(params["w_up"].shape),
        "w_down": d_down.reshape(params["w_down"].shape),
    }
    return d_params, d_x, d_gates


def _fits(picks, rows):
    """Whether the picks that met a held expert fit `rows` rows."""
    return jnp.sum(picks[2]) <= rows


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _budgeted_rows(rows, k, glu, masked, params, x, gates, picks):
    """The expert part of a layer whose router is wider than the
    experts held, exact at every load: `_held_rows` where the held
    picks fit `rows`, `_all_rows` where they do not. `picks` are
    `(picked, order, groups, live)`.

    Why a `custom_vjp` and not the `lax.cond` alone: the backward of
    a `cond` keeps the residuals of EVERY branch (zeros for the one
    not taken), so the all-rows branch's `t x k` rows would be written
    and held a layer a step although it never runs; at
    `trinity-mini-ep8` the compiler counts that step at 16.44 of
    15.75 GB (PERF.md section 6, PR 57). Here the forward hands the
    backward `_held_rows`' residuals alone (zeros of that size where
    the load spilled), and the backward's own `cond` either uses them
    or, over the budget, differentiates `_all_rows` from the inputs."""
    return lax.cond(
        _fits(picks, rows),
        lambda *inputs: _held_rows(*inputs, rows, k, glu)[0],
        lambda *inputs: _all_rows(*inputs, k, glu, masked),
        params, x, gates, picks,
    )


def _budgeted_rows_fwd(rows, k, glu, masked, params, x, gates, picks):
    def held(*inputs):
        return _held_rows(*inputs, rows, k, glu)

    inputs = (params, x, gates, picks)
    _, saved = jax.eval_shape(held, *inputs)

    def spilled(*inputs):
        zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), saved)
        return _all_rows(*inputs, k, glu, masked), zeros

    out, saved = lax.cond(_fits(picks, rows), held, spilled, *inputs)
    return out, (saved, *inputs)


def _budgeted_rows_bwd(rows, k, glu, masked, residuals, dy):
    def held(saved, *inputs):
        return _held_rows_bwd(saved, *inputs, rows, k, glu, dy)

    def spilled(saved, params, x, gates, picks):
        return jax.vjp(
            lambda params, x, gates: _all_rows(
                params, x, gates, picks, k, glu, masked
            ),
            params, x, gates,
        )[1](dy)

    picks = residuals[-1]
    cotangents = lax.cond(_fits(picks, rows), held, spilled, *residuals)
    return (*cotangents, None)


_budgeted_rows.defvjp(_budgeted_rows_fwd, _budgeted_rows_bwd)


def moe_ffn_dropless(
    params: Dict,
    x: jax.Array,
    *,
    k: int = 2,
    renormalise: bool = True,
    glu: Callable = swiglu,
    live: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
    routed: Optional[Tuple[jax.Array, jax.Array]] = None,
    first_expert: int = 0,
    routed_over: int = 0,
):
    """Every expert local, no capacity. x: [tokens, d] ->
    (out [tokens, d], aux_loss, counts [E] int32: picks per expert).

    `routed`: the `(gates, experts)` of a router of the caller's
    (`route_grouped_sigmoid`) in the place of `route`'s; the auxiliary
    loss is then 0. With it the router may be WIDER than the experts
    held here, which are `first_expert` and those after it, as many as
    the expert matrices hold, of the `routed_over` the router deals
    over: one rank's share of an expert-parallel layer, run without
    its exchange. A pick of an expert held elsewhere sorts past the
    last group, as a dead row's does, reads no weight and adds
    nothing; `counts` are the picks that met a held expert. The held
    picks are the first of the sorted picks, and where the router is
    wider than the experts held only `held_row_budget` of the `t * k`
    sorted rows are gathered, multiplied, gated, summed and
    differentiated (`_held_rows`): an eighth of the router's experts
    meet about an eighth of the picks, and the budget is twice that
    share. How many picks ARE held is data, so the layer holds both
    programs (`_budgeted_rows`): a load over the budget takes every
    row (`_all_rows`), as a layer that holds all its experts does.
    Nothing is dropped, clipped or approximated at any load; a load
    only chooses how many dead rows ride along.

    `layer`: the experts' matrices are whole stacks `[layers, E, ., .]`
    and this is the layer to use. A loop over layers that slices its
    layer's experts out of the stack makes the compiler COPY them
    before the grouped-matmul kernel (a custom call cannot read a
    slice in place): 805 MB a layer at OLMoE's widths, 22 ms of a
    39 ms forward on the v5e (PERF.md, PR 26). With the stack seen as
    `layers x E` groups of which only this layer's hold rows, the
    kernel reads the experts where they lie and visits no empty group.
    The serve forwards pass it; training slices (its gradient would
    otherwise be the whole stack's, once a layer).

    `live` [tokens] bool marks the rows that are real (a decode step
    carries dead slots): a row that is not live picks no expert, so it
    is in no group, reads no expert's weights, counts nowhere and
    comes out zero.
    """
    t, _ = x.shape
    num_experts = params["w_gate"].shape[-3]
    with jax.named_scope("moe/route"):
        if routed is None:
            gates, experts, aux = route(
                x, params["router"], k, renormalise
            )
            picked = experts.reshape(-1)  # [t*k], token-major
        else:
            (gates, experts), aux = routed, jnp.zeros((), jnp.float32)
            picked = experts.reshape(-1) - first_expert
            picked = jnp.where(
                (picked >= 0) & (picked < num_experts), picked,
                num_experts,
            )
        if live is not None:
            # Past the last expert: sorted behind every group.
            picked = jnp.where(jnp.repeat(live, k), picked, num_experts)
        order = jnp.argsort(picked)  # stable: pick j of sorted row i
        counts = jnp.bincount(picked, length=num_experts).astype(jnp.int32)
        groups = counts
        if layer is not None:
            n_layers = params["w_gate"].shape[0]
            groups = lax.dynamic_update_slice(
                jnp.zeros(n_layers * num_experts, jnp.int32),
                counts, (layer * num_experts,),
            )
    weights = {name: params[name] for name in ("w_gate", "w_up", "w_down")}
    masked = routed is not None
    rows = held_row_budget(t * k, num_experts, routed_over if masked else 0)
    picks = (picked, order, groups, live)
    if rows == t * k:
        out = _all_rows(weights, x, gates, picks, k, glu, masked)
    else:
        out = _budgeted_rows(rows, k, glu, masked, weights, x, gates, picks)
    return out.astype(x.dtype), aux, counts


def _dispatch_tensors(
    indices: jax.Array,
    gates: jax.Array,
    num_experts: int,
    capacity: int,
):
    """Capacity-based dispatch (Switch-style) for the all_to_all
    exchange: per (token, choice), its position in the target expert's
    buffer; picks past capacity drop. Returns dispatch one-hot
    [t, E, C] and combine [t, E, C]."""
    t, k = indices.shape
    flat_expert = indices.reshape(-1)  # [t*k], choice-major rows
    onehot = jax.nn.one_hot(flat_expert, num_experts, dtype=jnp.int32)
    # Position of each (token, choice) within its expert queue.
    position = jnp.cumsum(onehot, axis=0) * onehot - 1  # [t*k, E]
    pos_in_expert = jnp.sum(position * onehot, axis=-1)  # [t*k]
    keep = pos_in_expert < capacity
    pos_clipped = jnp.clip(pos_in_expert, 0, capacity - 1)
    slot = (
        jax.nn.one_hot(flat_expert, num_experts)[:, :, None]
        * jax.nn.one_hot(pos_clipped, capacity)[:, None, :]
    )  # [t*k, E, C]

    def per_token(weight):
        return (
            (slot * weight[:, None, None])
            .reshape(t, k, num_experts, capacity)
            .sum(axis=1)
        )

    return per_token(keep), per_token(keep * gates.reshape(-1))


def moe_ffn_ep(
    params: Dict,
    x: jax.Array,
    *,
    axis_name: str = "ep",
    k: int = 2,
    capacity_factor: float = 2.0,
    renormalise: bool = True,
    glu: Callable = swiglu,
):
    """Expert-parallel MoE inside shard_map.

    Each rank holds E_local = E/ep experts (params sharded on the
    expert axis) and a token shard x: [t_local, d]. Dispatch:
    one all_to_all sends each rank's per-expert buffers to the expert's
    owner; experts run batched; a second all_to_all returns outputs.
    """
    ep = _axis_size(axis_name)
    e_local = params["w_gate"].shape[0]
    num_experts = e_local * ep
    t_local, d = x.shape
    capacity = int(
        math.ceil(k * t_local * capacity_factor / num_experts)
    )
    capacity = max(capacity, 1)

    # The router is tiny ([d, E]) and replicated on every rank; only
    # the expert FFN weights shard over ep.
    gates, indices, aux = route(x, params["router"], k, renormalise)
    dispatch, combine = _dispatch_tensors(
        indices, gates, num_experts, capacity
    )
    # Expert-major buffers: [E, C, d] = tokens this rank sends to each
    # expert, then all_to_all regroups by owner rank.
    expert_inputs = jnp.einsum(
        "tec,td->ecd", dispatch.astype(x.dtype), x
    )  # [E, C, d]
    # [E, C, d] -> [ep, E_local, C, d] -> a2a -> [ep, E_local, C, d]
    # where now the leading axis indexes SOURCE rank.
    expert_inputs = expert_inputs.reshape(ep, e_local, capacity, d)
    expert_inputs = lax.all_to_all(
        expert_inputs, axis_name, split_axis=0, concat_axis=0, tiled=True
    ).reshape(ep, e_local, capacity, d)
    # Local experts over all source ranks' buffers: [E_local, ep*C, d].
    h = expert_inputs.transpose(1, 0, 2, 3).reshape(
        e_local, ep * capacity, d
    )
    h = gated_experts(
        params, h, lambda rows, w: jnp.einsum("ecd,edf->ecf", rows, w), glu
    )
    # Return trip: back to source ranks.
    h = h.reshape(e_local, ep, capacity, d).transpose(1, 0, 2, 3)
    h = lax.all_to_all(
        h, axis_name, split_axis=0, concat_axis=0, tiled=True
    ).reshape(num_experts, capacity, d)
    out = jnp.einsum(
        "tec,ecd->td", combine.astype(h.dtype), h
    )
    return out.astype(x.dtype), aux
