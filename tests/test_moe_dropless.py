"""The dropless single-device MoE (`ops/moe.py` `moe_ffn_dropless`)
against an all-experts float32 oracle: nothing is dropped at any
routing, and the gradients are the oracle's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.moe import init_moe_params, moe_ffn_dropless

from moe_oracle import all_experts_ffn

E, D, F = 8, 16, 32


def _params(seed=0):
    return init_moe_params(jax.random.PRNGKey(seed), E, D, F)


def _to_one_expert(params, expert):
    """A router that sends every token to `expert` first: its column
    dominates whatever the token is."""
    x_bias = jnp.zeros((D, E)).at[:, expert].set(5.0)
    return dict(params, router=params["router"] * 0.01 + x_bias)


@pytest.mark.parametrize("tokens,k,routing", [
    (64, 2, "even"), (64, 2, "one_expert"), (37, 3, "even"),
    (1, 2, "even"), (131, 8, "one_expert"),
])
def test_dropless_matches_the_oracle_at_any_skew(tokens, k, routing):
    params = _params()
    x = jax.random.normal(jax.random.PRNGKey(1), (tokens, D))
    if routing == "one_expert":
        params = _to_one_expert(params, 3)
        x = jnp.abs(x)  # so that x @ bias is large for every token
    out, aux, counts = jax.jit(
        lambda p, x: moe_ffn_dropless(p, x, k=k)
    )(params, x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(all_experts_ffn(params, x, k)),
        rtol=1e-5, atol=1e-5,
    )
    counts = np.asarray(counts)
    assert counts.sum() == tokens * k and counts.dtype == np.int32
    if routing == "one_expert":
        assert counts[3] == tokens  # far past any capacity: none dropped
    assert float(aux) > 0


def test_gates_are_left_alone_when_the_config_says_so():
    params = _params(2)
    x = jax.random.normal(jax.random.PRNGKey(3), (40, D))
    raw, _, _ = moe_ffn_dropless(params, x, k=2, renormalise=False)
    want = all_experts_ffn(params, x, 2, renormalise=False)
    np.testing.assert_allclose(raw, want, rtol=1e-5, atol=1e-5)
    renormed, _, _ = moe_ffn_dropless(params, x, k=2, renormalise=True)
    assert not np.allclose(raw, renormed, rtol=1e-3)


def test_gradients_are_the_oracles():
    params = _params(4)
    x = jax.random.normal(jax.random.PRNGKey(5), (29, D))
    y = jax.random.normal(jax.random.PRNGKey(6), (29, D))

    def loss(ffn):
        return lambda p, x: jnp.mean((ffn(p, x) - y) ** 2)

    got = jax.grad(
        loss(lambda p, x: moe_ffn_dropless(p, x, k=2)[0]), argnums=(0, 1)
    )(params, x)
    want = jax.grad(
        loss(lambda p, x: all_experts_ffn(p, x, 2)), argnums=(0, 1)
    )(params, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6)


def test_a_row_that_is_not_live_picks_no_expert():
    params = _params(7)
    x = jax.random.normal(jax.random.PRNGKey(8), (12, D))
    live = jnp.arange(12) % 3 != 0
    out, _, counts = jax.jit(
        lambda p, x, live: moe_ffn_dropless(p, x, k=2, live=live)
    )(params, x, live)
    want = all_experts_ffn(params, x, 2)
    np.testing.assert_allclose(
        np.asarray(out)[np.asarray(live)], np.asarray(want)[np.asarray(live)],
        rtol=1e-5, atol=1e-5,
    )
    assert not np.asarray(out)[~np.asarray(live)].any()
    assert int(counts.sum()) == 2 * int(live.sum())
    alone, _, alone_counts = moe_ffn_dropless(params, x[live], k=2)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(alone_counts))


def test_experts_found_in_the_whole_stack_give_the_sliced_answer():
    """`layer=`: the experts' matrices stay stacked `[layers, E, ., .]`
    (the serve forwards' loop does not slice them) and only that
    layer's groups hold rows."""
    layers = [_params(seed) for seed in (10, 11, 12)]
    stack = {
        name: jnp.stack([p[name] for p in layers])
        for name in ("w_gate", "w_up", "w_down")
    }
    x = jax.random.normal(jax.random.PRNGKey(13), (37, D))
    live = jnp.arange(37) % 5 != 0
    run = jax.jit(lambda router, i: moe_ffn_dropless(
        dict(stack, router=router), x, k=3, live=live, layer=i
    ))
    for i, params in enumerate(layers):
        want, _, want_counts = moe_ffn_dropless(params, x, k=3, live=live)
        got, _, counts = run(params["router"], i)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(counts, want_counts)
