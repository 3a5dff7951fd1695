"""Pipeline-parallel + MoE/expert-parallel tests on the virtual
8-device CPU mesh (test model per SURVEY.md §4: hermetic sharding
coverage without TPU hardware)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

try:
    from jax import shard_map
except ImportError:  # older jax
    from jax.experimental.shard_map import shard_map


def _mesh(axes):
    devices = np.array(jax.devices()[: np.prod(list(axes.values()))])
    return Mesh(devices.reshape(tuple(axes.values())), tuple(axes))


def test_spmd_pipeline_matches_sequential():
    from ray_tpu.parallel.pipeline import (
        broadcast_from_last_stage,
        spmd_pipeline,
        stack_stage_params,
    )

    n_stages, num_mb, mb, d = 4, 8, 2, 16
    mesh = _mesh({"pp": n_stages})
    key = jax.random.PRNGKey(0)
    stages = []
    for i in range(n_stages):
        k1, k2, key = jax.random.split(key, 3)
        stages.append(
            {
                "w": jax.random.normal(k1, (d, d)) * 0.3,
                "b": jax.random.normal(k2, (d,)) * 0.1,
            }
        )
    stacked = stack_stage_params(stages)
    x = jax.random.normal(key, (num_mb, mb, d))

    def stage_fn(params, h):
        return jnp.tanh(h @ params["w"] + params["b"])

    def pipelined(params, microbatches):
        out = spmd_pipeline(stage_fn, params, microbatches)
        return broadcast_from_last_stage(out)

    run = jax.jit(
        shard_map(
            pipelined,
            mesh=mesh,
            in_specs=(P("pp"), P()),
            out_specs=P(),
        )
    )
    got = run(stacked, x)

    expected = x
    for params in stages:
        expected = jnp.tanh(expected @ params["w"] + params["b"])
    np.testing.assert_allclose(got, expected, rtol=2e-5, atol=2e-5)


def test_spmd_pipeline_differentiable():
    from ray_tpu.parallel.pipeline import (
        broadcast_from_last_stage,
        spmd_pipeline,
        stack_stage_params,
    )

    n_stages, num_mb, mb, d = 2, 4, 2, 8
    mesh = _mesh({"pp": n_stages})
    key = jax.random.PRNGKey(1)
    stages = [
        {"w": jax.random.normal(jax.random.fold_in(key, i), (d, d)) * 0.3}
        for i in range(n_stages)
    ]
    stacked = stack_stage_params(stages)
    x = jax.random.normal(key, (num_mb, mb, d))

    def stage_fn(params, h):
        return jnp.tanh(h @ params["w"])

    def loss_fn(params, microbatches):
        out = spmd_pipeline(stage_fn, params, microbatches)
        out = broadcast_from_last_stage(out)
        return jnp.mean(out**2)

    def sequential_loss(params_list, microbatches):
        h = microbatches
        for p in params_list:
            h = jnp.tanh(h @ p["w"])
        return jnp.mean(h**2)

    from ray_tpu.parallel.sharding import checked_shard_map

    sharded_loss = jax.jit(
        checked_shard_map(loss_fn, mesh, (P("pp"), P()), P())
    )
    grads = jax.grad(lambda p: sharded_loss(p, x))(stacked)
    ref_grads = jax.grad(lambda ps: sequential_loss(ps, x))(stages)
    for i in range(n_stages):
        np.testing.assert_allclose(
            np.asarray(grads["w"][i]),
            np.asarray(ref_grads[i]["w"]),
            rtol=2e-4,
            atol=2e-5,
        )


#: The expert-parallel layout of `init_moe_params`' tree: the router
#: replicated, the experts' matrices sharded on the expert axis.
_EP_SPECS = {
    "router": P(), "w_gate": P("ep"), "w_up": P("ep"), "w_down": P("ep"),
}


def test_moe_dense_routes_topk():
    from ray_tpu.ops.moe import init_moe_params, moe_ffn_dropless

    params = init_moe_params(jax.random.PRNGKey(0), 4, 16, 32)
    x = jax.random.normal(jax.random.PRNGKey(1), (10, 16))
    out, aux, counts = moe_ffn_dropless(params, x, k=2)
    assert out.shape == (10, 16)
    assert np.isfinite(np.asarray(out)).all()
    assert float(aux) > 0
    assert int(counts.sum()) == 10 * 2


def test_moe_expert_parallel_matches_dense():
    """EP sharded MoE == dense MoE when capacity never overflows."""
    from ray_tpu.ops.moe import init_moe_params, moe_ffn_ep

    from moe_oracle import all_experts_ffn

    ep, e_local, d, ff = 4, 2, 16, 32
    num_experts = ep * e_local
    t_local = 8
    mesh = _mesh({"ep": ep})
    params = init_moe_params(
        jax.random.PRNGKey(0), num_experts, d, ff
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (ep * t_local, d))

    def ep_fn(params, tokens):
        out, aux = moe_ffn_ep(
            params,
            tokens,
            k=2,
            capacity_factor=float(num_experts),  # no drops
        )
        return out

    run = jax.jit(
        shard_map(
            ep_fn,
            mesh=mesh,
            in_specs=(_EP_SPECS, P("ep")),
            out_specs=P("ep"),
        )
    )
    got = run(params, x)

    # The all-experts oracle over every token (routing is per-token,
    # so the shard split doesn't change assignments).
    want = all_experts_ffn(params, x, 2)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_moe_ep_sharded_gradients_finite():
    from ray_tpu.ops.moe import init_moe_params, moe_ffn_ep

    ep, d, ff = 4, 8, 16
    mesh = _mesh({"ep": ep})
    params = init_moe_params(jax.random.PRNGKey(0), 8, d, ff)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, d))

    def loss(params, tokens):
        out, aux = moe_ffn_ep(params, tokens, k=2)
        from jax import lax

        return lax.pmean(jnp.mean(out**2) + 0.01 * aux, "ep")

    run = shard_map(
        loss,
        mesh=mesh,
        in_specs=(_EP_SPECS, P("ep")),
        out_specs=P(),
    )
    grads = jax.jit(jax.grad(lambda p: run(p, x)))(params)
    for g in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(g)).all()
        assert float(jnp.abs(g).sum()) > 0