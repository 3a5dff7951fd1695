"""DeepSeek-V3's router and one chip's share of an expert layer
(ops/moe.py `route_grouped_sigmoid`, `moe_ffn_dropless(routed=,
first_expert=)`, `llama._mlp`), against float32 loops written from the
equations; YaRN's frequencies against the closed form."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import LlamaConfig, _mlp
from ray_tpu.ops.moe import moe_ffn_dropless, route_grouped_sigmoid
from ray_tpu.ops.norms import rope_frequencies, yarn_mscale

E, GROUPS, TOP_GROUPS, K, D, F = 32, 4, 2, 4, 16, 8


def _router(seed=0, tokens=64):
    rng = np.random.default_rng(seed)
    return (
        rng.normal(size=(tokens, D)).astype(np.float32),
        rng.normal(size=(D, E)).astype(np.float32) * D ** -0.5,
        rng.normal(size=(E,)).astype(np.float32) * 0.1,
    )


def _loop_route(x, router, bias, scale):
    """Token by token, as the equations say."""
    gates, experts = [], []
    per = E // GROUPS
    for h in x:
        s = 1.0 / (1.0 + np.exp(-(h.astype(np.float64) @ router)))
        c = s + bias
        marks = [
            np.sort(c[g * per:(g + 1) * per])[-2:].sum() for g in range(GROUPS)
        ]
        kept = np.argsort(marks)[-TOP_GROUPS:]
        allowed = [e for e in range(E) if e // per in kept]
        chosen = sorted(allowed, key=lambda e: -c[e])[:K]
        total = sum(s[e] for e in chosen)
        experts.append(chosen)
        gates.append([scale * s[e] / total for e in chosen])
    return np.array(gates), np.array(experts)


def test_the_router_chooses_inside_the_best_groups_by_corrected_scores():
    x, router, bias = _router()
    gates, experts = route_grouped_sigmoid(
        jnp.asarray(x), jnp.asarray(router), jnp.asarray(bias),
        K, GROUPS, TOP_GROUPS, 2.5,
    )
    want_gates, want_experts = _loop_route(x, router, bias, 2.5)
    assert np.array_equal(np.asarray(experts), want_experts)
    np.testing.assert_allclose(np.asarray(gates), want_gates, rtol=1e-5)
    # at most TOP_GROUPS groups a token, and the gates sum to the scale
    groups = np.asarray(experts) // (E // GROUPS)
    assert max(len(set(row)) for row in groups) <= TOP_GROUPS
    np.testing.assert_allclose(np.asarray(gates).sum(axis=1), 2.5, rtol=1e-5)


def test_the_bias_moves_the_choice_and_never_the_gates():
    x, router, bias = _router(1)
    args = (K, GROUPS, TOP_GROUPS, 1.0)
    plain_g, plain_e = route_grouped_sigmoid(
        jnp.asarray(x), jnp.asarray(router), jnp.zeros(E), *args
    )
    # a bias that lifts group 3's experts over everything: every token
    # chooses there, and its gates are still its plain scores' shares
    lifted = np.zeros(E, np.float32)
    lifted[24:] = 10.0
    gates, experts = route_grouped_sigmoid(
        jnp.asarray(x), jnp.asarray(router), jnp.asarray(lifted), *args
    )
    assert not np.array_equal(np.asarray(experts), np.asarray(plain_e))
    assert (np.asarray(experts) >= 24).all()
    scores = 1.0 / (1.0 + np.exp(-(x @ router)))
    picked = np.take_along_axis(scores, np.asarray(experts), axis=1)
    np.testing.assert_allclose(
        np.asarray(gates), picked / picked.sum(axis=1, keepdims=True), rtol=1e-5
    )
    assert np.asarray(gates).max() <= 1.0  # no 10 got into a gate
    del plain_g


def _layer(seed, held_first=0, held=E):
    """An expert layer's leaves: router over E, experts `held_first`..."""
    rng = np.random.default_rng(seed)

    def w(*shape, fan):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32) * fan ** -0.5)

    full = {
        "mlp_norm": jnp.asarray(1 + 0.1 * rng.normal(size=D).astype(np.float32)),
        "router": w(D, E, fan=D),
        "router_bias": jnp.asarray(0.1 * rng.normal(size=E).astype(np.float32)),
        "w_gate": w(E, D, F, fan=D), "w_up": w(E, D, F, fan=D),
        "w_down": w(E, F, D, fan=F),
        "shared_gate": w(D, F, fan=D), "shared_up": w(D, F, fan=D),
        "shared_down": w(F, D, fan=F),
    }
    share = dict(full)
    for name in ("w_gate", "w_up", "w_down"):
        share[name] = full[name][held_first:held_first + held]
    return full, share


def _cfg(held, first=0, shared=True):
    return LlamaConfig(
        vocab_size=8, dim=D, n_layers=2, n_heads=2, n_kv_heads=2,
        intermediate=F, dtype=jnp.float32, kv_lora_rank=4,
        moe_experts=held, moe_top_k=K, moe_router="sigmoid_groups",
        moe_router_experts=E, moe_first_expert=first, moe_groups=GROUPS,
        moe_top_groups=TOP_GROUPS, moe_route_scale=2.5,
        moe_shared_intermediate=F if shared else 0,
    )


def _ffn_only(cfg, x, layer):
    """What `_mlp` adds to the residual stream."""
    out, _, counts = _mlp(cfg, x, layer)
    return np.asarray(out - x), counts


def test_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The guide's share test: the routed parts that all the shares
    give, with what every chip computes alike (the shared expert)
    counted once, add up to the uncut layer."""
    full, _ = _layer(3)
    x = jnp.asarray(
        np.random.default_rng(4).normal(size=(1, 48, D)).astype(np.float32)
    )
    whole, whole_counts = _ffn_only(_cfg(E), x, full)
    no_shared = {k: v for k, v in full.items() if not k.startswith("shared")}
    shared_part = whole - _ffn_only(_cfg(E, shared=False), x, no_shared)[0]
    assert np.abs(shared_part).max() > 0.01  # it is not nothing
    shares, per, picks = 16, E // 16, 0
    total = np.zeros_like(whole)
    for rank in range(shares):
        _, share = _layer(3, rank * per, per)
        share = {k: v for k, v in share.items() if not k.startswith("shared")}
        part, counts = _ffn_only(
            _cfg(per, rank * per, shared=False), x, share
        )
        assert counts.shape == (per,)
        assert np.array_equal(
            np.asarray(counts), np.asarray(whole_counts)[rank * per:(rank + 1) * per]
        )
        picks += int(counts.sum())
        total += part
    assert picks == 48 * K  # every pick met exactly one share
    np.testing.assert_allclose(total + shared_part, whole, atol=2e-5)


def test_a_share_is_the_loop_over_its_held_experts():
    """One share against a float32 loop: gates normalised over ALL the
    chosen, the held experts' parts alone, the shared expert added."""
    first, held = 8, 8
    full, share = _layer(5, first, held)
    x = np.random.default_rng(6).normal(size=(40, D)).astype(np.float32)
    got, counts = _ffn_only(_cfg(held, first), jnp.asarray(x)[None], share)
    norm = np.asarray(full["mlp_norm"])
    h = x / np.sqrt((x * x).mean(axis=1, keepdims=True) + 1e-6) * norm
    gates, experts = _loop_route(
        h, np.asarray(full["router"]), np.asarray(full["router_bias"]), 2.5
    )

    def silu(v):
        return v / (1.0 + np.exp(-v))

    def expert(v, gate, up, down):
        return (silu(v @ np.asarray(gate)) * (v @ np.asarray(up))) @ np.asarray(down)

    want = np.zeros_like(x)
    met = np.zeros(held, int)
    for t in range(len(x)):
        for gate, e in zip(gates[t], experts[t]):
            if first <= e < first + held:
                met[e - first] += 1
                want[t] += gate * expert(
                    h[t], full["w_gate"][e], full["w_up"][e], full["w_down"][e]
                )
        want[t] += expert(
            h[t], full["shared_gate"], full["shared_up"], full["shared_down"]
        )
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    assert np.array_equal(np.asarray(counts), met)


def test_a_dead_rows_picks_meet_no_held_expert():
    full, _ = _layer(7)
    x = jnp.asarray(
        np.random.default_rng(8).normal(size=(6, D)).astype(np.float32)
    )
    routed = route_grouped_sigmoid(
        x, full["router"], full["router_bias"], K, GROUPS, TOP_GROUPS, 2.5
    )
    live = jnp.asarray([True, False, True, True, False, True])
    out, _, counts = moe_ffn_dropless(full, x, k=K, routed=routed, live=live)
    alone, _, _ = moe_ffn_dropless(full, x[live], k=K, routed=(
        routed[0][live], routed[1][live]
    ))
    assert int(counts.sum()) == 4 * K
    assert np.abs(np.asarray(out)[~np.asarray(live)]).max() == 0.0
    np.testing.assert_allclose(
        np.asarray(out)[np.asarray(live)], np.asarray(alone), atol=1e-5
    )


@pytest.mark.parametrize("dim, theta, factor, beta_fast, beta_slow, original", [
    (64, 10000.0, 40.0, 32.0, 1.0, 4096),   # DeepSeek-V3.2's rope dims
    (32, 50000.0, 8.0, 16.0, 2.0, 2048),
])
def test_yarn_frequencies_are_the_closed_form(
    dim, theta, factor, beta_fast, beta_slow, original
):
    got = np.asarray(rope_frequencies(
        dim, theta, ("yarn", factor, beta_slow, beta_fast, original)
    ))

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    assert 0 < low < high < dim // 2
    for j in range(dim // 2):
        plain = theta ** (-2.0 * j / dim)
        if j <= low:
            want = plain  # turns often enough inside the trained span
        elif j >= high:
            want = plain / factor  # interpolated whole
        else:
            blend = (j - low) / (high - low)
            want = plain / factor * blend + plain * (1 - blend)
        assert got[j] == pytest.approx(want, rel=1e-5), j
    # DeepSeek's temperature: 0.1 ln(factor) + 1, squared into the scale
    assert yarn_mscale(40.0) == pytest.approx(0.1 * math.log(40.0) + 1.0)
    assert yarn_mscale(1.0) == 1.0


def test_a_list_from_a_json_file_is_a_hashable_scaling():
    cfg = LlamaConfig(rope_scaling=["yarn", 40, 1, 32, 4096])
    assert cfg.rope_scaling == ("yarn", 40, 1, 32, 4096) and hash(cfg)
    with pytest.raises(ValueError, match="index_topk"):
        LlamaConfig(index_topk=4)
    with pytest.raises(ValueError, match="dense_layers"):
        LlamaConfig(dense_layers=1)


@pytest.mark.parametrize("outputs, shares, reference", [
    (32, 16, None), (64, 8, "lfm2_moe_ref"),
], ids=["mimo_v2_ep16_of_32", "lfm2_ep8_4_of_64"])
def test_the_shares_of_a_one_group_router_are_the_uncut_layer(
    outputs, shares, reference
):
    """MiMo-V2's and LFM2's layer (one group, no shared expert, a
    route scale of 1.0, top-4): the shares of EP16 / EP8, each the
    router whole and its own `outputs / shares` experts, add up to the
    uncut layer, and every pick meets exactly one share; the uncut
    layer is the float32 loop with the 4 largest of score + bias out
    of ALL the outputs, and where the configuration's plain reference
    is named, that reference's layer with every expert held."""
    def cfg(held, first=0):
        return LlamaConfig(
            vocab_size=8, dim=D, n_layers=2, n_heads=2, n_kv_heads=2,
            intermediate=F, dtype=jnp.float32, moe_experts=held,
            moe_top_k=K, moe_router="sigmoid_groups",
            moe_router_experts=outputs, moe_first_expert=first, moe_groups=1,
            moe_top_groups=1, moe_route_scale=1.0,
        )

    rng = np.random.default_rng(9 + outputs)

    def w(*shape, fan):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32) * fan ** -0.5)

    full = {
        "mlp_norm": jnp.asarray(1 + 0.1 * rng.normal(size=D).astype(np.float32)),
        "router": w(D, outputs, fan=D),
        "router_bias": jnp.asarray(
            0.1 * rng.normal(size=outputs).astype(np.float32)
        ),
        "w_gate": w(outputs, D, F, fan=D), "w_up": w(outputs, D, F, fan=D),
        "w_down": w(outputs, F, D, fan=F),
    }
    x = jnp.asarray(
        np.random.default_rng(10).normal(size=(1, 48, D)).astype(np.float32)
    )
    whole, whole_counts = _ffn_only(cfg(outputs), x, full)
    per, picks = outputs // shares, 0
    total = np.zeros_like(whole)
    for rank in range(shares):
        share = dict(full)
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = full[name][rank * per:(rank + 1) * per]
        part, counts = _ffn_only(cfg(per, rank * per), x, share)
        assert np.array_equal(
            np.asarray(counts),
            np.asarray(whole_counts)[rank * per:(rank + 1) * per],
        )
        picks += int(counts.sum())
        total += part
    assert picks == 48 * K  # every pick met exactly one share
    np.testing.assert_allclose(total, whole, atol=2e-5)
    # and the uncut layer is the equations': no group is left out
    h = np.asarray(x[0])
    h = h / np.sqrt((h * h).mean(axis=1, keepdims=True) + 1e-6) * np.asarray(
        full["mlp_norm"]
    )
    scores = 1.0 / (1.0 + np.exp(-(h @ np.asarray(full["router"]))))
    chosen = np.argsort(-(scores + np.asarray(full["router_bias"])), axis=1)[:, :K]
    assert np.array_equal(
        np.bincount(chosen.ravel(), minlength=outputs), np.asarray(whole_counts)
    )
    gates = np.take_along_axis(scores, chosen, axis=1)
    gates = gates / gates.sum(axis=1, keepdims=True)

    def expert(v, e):
        gate, up, down = (np.asarray(full[n][e]) for n in ("w_gate", "w_up", "w_down"))
        g = v @ gate
        return ((g / (1.0 + np.exp(-g))) * (v @ up)) @ down

    want = np.stack([
        sum(gates[t, j] * expert(h[t], chosen[t, j]) for j in range(K))
        for t in range(len(h))
    ])
    np.testing.assert_allclose(whole[0], want, atol=2e-5)
    if reference is None:
        return
    # the eight shares against the benchmark's own reference, uncut
    import importlib
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    ref = importlib.import_module(f"benchmark.reference.{reference}")
    numbers = ref._numbers(dict(
        dim=D, n_heads=2, norm_eps=1e-6, moe_top_k=K, moe_route_scale=1.0,
        moe_first_expert=0,
    ))
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(ref._ffn(x[0], full, m=numbers) - x[0])
    np.testing.assert_allclose(total[0], uncut, atol=2e-5)


# ---- the share tied to the model through the TRAINING layer (ISSUE 55):
# Trinity-Mini's expert layer, one group, a shared expert, a norm on the
# layer's output. The 8 shares of a small layer through `llama._mlp`
# (what `loss_fn` differentiates), the shared expert counted once, add
# up to what the uncut reference gives for the whole layer, and so do
# their gradients with respect to the layer's input.

SHARES = 8


def _trinity_cfg(held, first=0, shared=True):
    return LlamaConfig(
        vocab_size=8, dim=D, n_layers=2, n_heads=2, n_kv_heads=2,
        intermediate=F, dtype=jnp.float32, norm_eps=1e-5,
        layer_kinds=[[4, 2, 10000.0, False], [0, 2, 0, False]],
        attn_gate=True, post_norms=True, dense_layers=0,
        moe_experts=held, moe_top_k=K, moe_router="sigmoid_groups",
        moe_router_experts=E, moe_first_expert=first, moe_groups=1,
        moe_top_groups=1, moe_route_scale=2.826,
        moe_shared_intermediate=F if shared else 0,
    )


def _summed_shares(x, full):
    """sum over the 8 shares of what `_mlp` adds for its held experts,
    and the shared expert's part once (the first share carries it)."""
    per = E // SHARES
    total, picks = jnp.zeros_like(x), []
    for rank in range(SHARES):
        layer = {
            k: v for k, v in full.items()
            if rank == 0 or not k.startswith("shared")
        }
        for name in ("w_gate", "w_up", "w_down"):
            layer[name] = full[name][rank * per:(rank + 1) * per]
        out, aux, counts = _mlp(
            _trinity_cfg(per, rank * per, shared=rank == 0), x, layer
        )
        total = total + (out - x)
        picks.append(counts)
    return total, jnp.concatenate(picks)


def _uncut_reference(x, full, post_norm):
    """x [t, d] -> the whole layer of `trinity_ref`: x + N_post(f)."""
    from benchmark.reference import trinity_ref

    numbers = trinity_ref._Numbers(
        eps=1e-5, moe_top_k=K, route_scale=2.826, first_expert=0
    )
    with jax.default_matmul_precision("highest"):
        return trinity_ref._ffn(
            x, dict(full, mlp_post_norm=post_norm), m=numbers
        )


@pytest.mark.parametrize("seed", [11, 12])
def test_eight_shares_through_the_training_layer_are_the_uncut_reference(seed):
    from benchmark.reference.llama_ref import _rms_norm

    full, _ = _layer(seed)
    rng = np.random.default_rng(seed + 100)
    x = jnp.asarray(rng.normal(size=(40, D)).astype(np.float32))
    post_norm = jnp.asarray(1 + 0.1 * rng.normal(size=D).astype(np.float32))
    summed, picks = _summed_shares(x[None], full)
    assert int(picks.sum()) == 40 * K  # every pick met exactly one share
    assert picks.shape == (E,)
    # the shared expert is there, and once: twice would be far off
    shared = _mlp(
        _trinity_cfg(E), x[None], full
    )[0] - _mlp(
        _trinity_cfg(E, shared=False), x[None],
        {k: v for k, v in full.items() if not k.startswith("shared")},
    )[0]
    assert float(jnp.abs(shared).max()) > 0.01
    want = _uncut_reference(x, full, post_norm) - x
    got = _rms_norm(summed[0], post_norm, 1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)
    twice = _rms_norm((summed + shared)[0], post_norm, 1e-5)
    assert float(jnp.abs(twice - want).max()) > 1e-2
    # and the whole layer through `_mlp` itself, post-norm and all
    whole = _mlp(
        _trinity_cfg(E), x[None], dict(full, mlp_post_norm=post_norm)
    )[0][0]
    np.testing.assert_allclose(
        np.asarray(whole - x), np.asarray(want), atol=3e-5
    )


@pytest.mark.parametrize("seed", [11, 12])
def test_the_shares_gradients_to_the_layers_input_add_up_too(seed):
    from benchmark.reference.llama_ref import _rms_norm

    full, _ = _layer(seed)
    rng = np.random.default_rng(seed + 100)
    x = jnp.asarray(rng.normal(size=(40, D)).astype(np.float32))
    post_norm = jnp.asarray(1 + 0.1 * rng.normal(size=D).astype(np.float32))
    weight = jnp.asarray(rng.normal(size=(40, D)).astype(np.float32))

    def through_the_shares(x):
        summed, _ = _summed_shares(x[None], full)
        return jnp.sum(weight * _rms_norm(summed[0], post_norm, 1e-5))

    def through_the_reference(x):
        return jnp.sum(weight * (_uncut_reference(x, full, post_norm) - x))

    got = jax.grad(through_the_shares)(x)
    want = jax.grad(through_the_reference)(x)
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_a_shares_gradient_takes_nothing_from_the_rows_behind_the_last_group(
    monkeypatch,
):
    """On the chip `lax.ragged_dot` leaves the rows behind the last
    group as they were in memory, forward AND backward (the CPU's writes
    zeros, which hides it). Simulated here: a grouped matmul whose
    output and whose cotangent to its rows are NaN behind the groups.
    The gradient of one rank's share to the layer's input and to its
    weights is the true one all the same (ISSUE 55: the picks held
    elsewhere, seven of eight, must add nothing to a real token)."""
    from jax import lax

    import ray_tpu.ops.moe as moe

    real = lax.ragged_dot

    def behind(rows, groups):
        return (jnp.arange(rows.shape[0]) >= jnp.sum(groups))[:, None]

    @jax.custom_vjp
    def garbage_dot(rows, w, groups):
        return jnp.where(behind(rows, groups), jnp.nan, real(rows, w, groups))

    def fwd(rows, w, groups):
        return garbage_dot(rows, w, groups), (rows, w, groups)

    def bwd(saved, cotangent):
        rows, w, groups = saved
        clean = jnp.where(behind(rows, groups), 0, cotangent)
        d_rows, d_w = jax.vjp(
            lambda r, w: real(r, w, groups), rows, w
        )[1](clean)
        return jnp.where(behind(rows, groups), jnp.nan, d_rows), d_w, None

    garbage_dot.defvjp(fwd, bwd)
    full, share = _layer(21, 8, 4)
    x = jnp.asarray(
        np.random.default_rng(22).normal(size=(1, 24, D)).astype(np.float32)
    )
    cfg = _trinity_cfg(4, 8)

    def through_the_layer(x, layer):
        out, _, _ = _mlp(cfg, x, layer)
        return jnp.sum(out * out)

    # The same for a caller that hands the grouped matmul a whole STACK
    # and a layer's index, dead rows among the live ones: the contract
    # is the product's own, not one caller's (REVIEW 55).
    live = jnp.arange(24) % 5 != 0
    stack = {
        k: jnp.stack([v, v + 1.0]) for k, v in share.items()
        if k in ("w_gate", "w_up", "w_down")
    }

    def through_a_stack(x, stack):
        routed = moe.route_grouped_sigmoid(
            x[0], share["router"], share["router_bias"], K,
            n_groups=1, top_groups=1, scale=2.826,
        )
        out, _, _ = moe.moe_ffn_dropless(
            stack, x[0], k=K, live=live, layer=jnp.int32(1),
            routed=routed, first_expert=8,
        )
        return jnp.sum(out * out)

    for loss, weights in ((through_the_layer, share), (through_a_stack, stack)):
        want = jax.grad(loss, (0, 1))(x, weights)
        with monkeypatch.context() as patched:
            patched.setattr(moe.lax, "ragged_dot", garbage_dot)
            got = jax.grad(loss, (0, 1))(x, weights)
        assert float(jnp.abs(want[0]).max()) > 0.01
        assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(got))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
