"""One rank's share of an expert layer computes the rows its held
experts read (`ops/moe.py` `moe_ffn_dropless` under `routed=` with a
router wider than the experts held: `held_row_budget`, `_held_rows`),
and is the dropless layer at EVERY load: at no held pick, at the even
share, at exactly the budget, at the budget plus one (where the layer
takes every row, `_all_rows`) and with every pick held, with and
without dead rows, sliced or on a stack, the output and the gradients
to the rows, the gates and each expert leaf are those of the all-rows
program and of a plain float32 loop over picks and held experts.

The grouped matmul is the chip's here: XLA's kernel on the TPU leaves
the rows behind the last group as they lay in memory, forward and
transposes, and the CPU's writes zeros there. `chip_like` writes NaN
in both places, so a read of a dead row fails on the CPU too."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.ops import moe
from ray_tpu.ops.norms import swiglu

T, K, D, F = 192, 4, 16, 32
HELD, ROUTED_OVER, FIRST = 4, 16, 8
PICKS = T * K
BUDGET = 512  # twice the even share of 768 / 4 = 192, in whole 512s
LAYERS, LAYER = 3, 1
#: held LIVE picks of a case, by name
LOADS = {
    "none": 0, "even_share": PICKS * HELD // ROUTED_OVER, "budget": BUDGET,
    "budget_plus_one": BUDGET + 1, "every_pick": None,
}


@pytest.fixture
def chip_like(monkeypatch):
    """`lax.ragged_dot` as the TPU's kernel leaves its results: NaN in
    every row behind the last group, of the product and of the
    cotangent to the rows (the weights' gradient sums a group's rows
    and never reads the others)."""
    real = lax.ragged_dot

    def behind(rows, groups):
        return (jnp.arange(rows.shape[0]) >= jnp.sum(groups))[:, None]

    @jax.custom_vjp
    def ragged_dot(rows, weights, groups):
        return jnp.where(behind(rows, groups), jnp.nan, real(rows, weights, groups))

    def fwd(rows, weights, groups):
        return ragged_dot(rows, weights, groups), (rows, weights, groups)

    def bwd(saved, cotangent):
        rows, weights, groups = saved
        dead = behind(rows, groups)
        d_rows, d_weights = jax.vjp(
            lambda r, w: real(r, w, groups), rows, weights
        )[1](jnp.where(dead, 0, cotangent))
        return jnp.where(dead, jnp.nan, d_rows), d_weights, None

    ragged_dot.defvjp(fwd, bwd)
    monkeypatch.setattr(moe.lax, "ragged_dot", ragged_dot)


def _case(load, with_live, stacked):
    keys = jax.random.split(jax.random.PRNGKey(7), 8)
    shape = (LAYERS,) if stacked else ()
    params = {
        "w_gate": jax.random.normal(keys[0], shape + (HELD, D, F)) / 4,
        "w_up": jax.random.normal(keys[1], shape + (HELD, D, F)) / 4,
        "w_down": jax.random.normal(keys[2], shape + (HELD, F, D)) / 6,
    }
    x = jax.random.normal(keys[3], (T, D))
    gates = jax.nn.softmax(jax.random.normal(keys[4], (T, K)), axis=-1)
    live = None
    live_picks = np.ones(PICKS, bool)
    if with_live:
        live = jax.random.uniform(keys[5], (T,)) > 0.2
        live = live.at[:4].set(False)
        live_picks = np.repeat(np.asarray(live), K)
    # Pick j of a token is held expert j, or expert j of a rank behind
    # this one: a token's picks are distinct either way.
    held = np.zeros(PICKS, bool)
    n_held = LOADS[load]
    if n_held is None:
        held[:] = True  # (a dead row's too: they must count for nothing)
        n_held = int(live_picks.sum())
    else:
        chosen = np.asarray(jax.random.permutation(
            keys[6], np.flatnonzero(live_picks)
        ))[:n_held]
        held[chosen] = True
        # (and dead rows pick held experts, for nothing)
        held |= ~live_picks
    assert n_held <= live_picks.sum()
    pick = np.tile(np.arange(K), T)
    experts = np.where(held, FIRST + pick, FIRST + HELD + pick)
    target = jax.random.normal(keys[7], (T, D))
    return params, x, gates, jnp.asarray(experts.reshape(T, K)), live, target, n_held


def _layer(routed_over, experts, live, stacked):
    def run(params, x, gates):
        return moe.moe_ffn_dropless(
            params, x, k=K, routed=(gates, experts), first_expert=FIRST,
            routed_over=routed_over, live=live,
            layer=jnp.asarray(LAYER) if stacked else None,
        )

    return run


def _loop(experts, live, stacked):
    """The layer as its equations read, float32, a held expert and a
    pick at a time over all tokens."""

    def run(params, x, gates):
        if stacked:
            params = {name: w[LAYER] for name, w in params.items()}
        out = jnp.zeros_like(x)
        for e in range(HELD):
            y = swiglu(x @ params["w_up"][e], x @ params["w_gate"][e])
            y = y @ params["w_down"][e]
            for j in range(K):
                met = experts[:, j] == FIRST + e
                if live is not None:
                    met = met & live
                out = out + jnp.where(met, gates[:, j], 0)[:, None] * y
        return out

    return run


@pytest.mark.parametrize("stacked", [False, True], ids=["sliced", "stack"])
@pytest.mark.parametrize("with_live", [False, True], ids=["all_live", "dead_rows"])
@pytest.mark.parametrize("load", list(LOADS))
def test_the_compacted_layer_is_the_dropless_layer_at_every_load(
    chip_like, load, with_live, stacked
):
    params, x, gates, experts, live, target, n_held = _case(
        load, with_live, stacked
    )
    assert moe.held_row_budget(PICKS, HELD, ROUTED_OVER) == BUDGET < PICKS
    compacted = _layer(ROUTED_OVER, experts, live, stacked)
    all_rows = _layer(0, experts, live, stacked)
    # (the budget decides by shape which program a layer IS: one with a
    # branch for a load over the budget, or the program it has been)
    assert " cond[" in str(jax.make_jaxpr(compacted)(params, x, gates))
    assert " cond[" not in str(jax.make_jaxpr(all_rows)(params, x, gates))

    def value_and_grads(layer):
        def loss(params, x, gates):
            out = layer(params, x, gates)
            out = out[0] if isinstance(out, tuple) else out
            return jnp.sum(out * target), out

        (_, out), grads = jax.jit(  # rt: noqa[RT301] — once a program a case
            jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
        )(params, x, gates)
        return out, grads

    got, got_grads = value_and_grads(compacted)
    counts = compacted(params, x, gates)[2]
    assert int(counts.sum()) == n_held  # none dropped, none clipped
    for name, layer in (
        ("all rows", all_rows), ("loop", _loop(experts, live, stacked))
    ):
        want, want_grads = value_and_grads(layer)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5, err_msg=name)
        for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
            assert bool(jnp.isfinite(g).all()), name
            np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5, err_msg=name)
    if live is not None:
        assert float(jnp.abs(got[:4]).max()) == 0.0  # a dead row comes out zero
    if load == "none":
        assert float(jnp.abs(got).max()) == 0.0
    else:
        assert float(jnp.abs(got).max()) > 0.1
