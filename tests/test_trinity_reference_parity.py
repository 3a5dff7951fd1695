"""Trinity-Mini's mechanisms on the TRAIN path against their plain
reference (`benchmark/reference/trinity_ref.py`), CPU, small sizes,
float32: `forward`, `loss_fn` and `jax.grad(loss_fn)` leaf by leaf, on
seeded weights with every norm and the expert bias DRAWN
(`weights.make` of the reference's plan), with both kinds of layer, a
leading dense layer, a sequence longer than the window, one rank's
share of the experts (2 of 8) and the whole layer; and three cases
where leaving a term out of the reference's equations fails the
comparison."""

import jax
import jax.numpy as jnp
import pytest

from benchmark.reference import trinity_ref, weights
from ray_tpu.models import llama

WINDOW = [8, 2, 10000.0, False]
FULL = [0, 2, 0, False]
#: a leading dense layer, a whole period and one layer more
KINDS = [WINDOW] + [WINDOW, WINDOW, WINDOW, FULL] + [WINDOW]
SEQ = 20  # over two windows


def _model(**changes) -> dict:
    model = dict(
        vocab_size=64, dim=32, n_layers=len(KINDS), n_heads=4, n_kv_heads=2,
        custom_head_dim=16, intermediate=16, max_seq_len=64, norm_eps=1e-5,
        embed_scale=True, qk_norm="head", layer_kinds=KINDS, attn_gate=True,
        post_norms=True, moe_router="sigmoid_groups", moe_experts=2,
        moe_router_experts=8, moe_first_expert=2, moe_top_k=3, moe_groups=1,
        moe_top_groups=1, moe_route_scale=2.826, moe_shared_intermediate=16,
        dense_layers=1, dense_intermediate=48, moe_aux_weight=0.0,
    )
    model.update(changes)
    return model


SHARE = _model()
WHOLE = _model(moe_experts=8, moe_first_expert=0)


def _build(model, seed=3, **cfg_keys):
    cfg = llama.LlamaConfig(
        **model, dtype=jnp.float32, attention="reference", **cfg_keys
    )
    params = weights.make(model, "float32", seed, trinity_ref)
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (SEQ + 1,), 0, model["vocab_size"]
    )
    return cfg, params, tokens


@pytest.fixture(scope="module", params=["share", "whole"])
def case(request):
    model = SHARE if request.param == "share" else WHOLE
    cfg, params, tokens = _build(model)
    with jax.default_matmul_precision("highest"):
        # (each jitted once a case, by a module-scoped fixture: eager,
        # the two gradients take three times as long)
        got = jax.jit(  # rt: noqa[RT301] — once a case, module-scoped fixture
            lambda p: llama.forward(p, tokens[None, :-1], cfg)[0]
        )(params)
        loss, grads = jax.jit(jax.value_and_grad(  # rt: noqa[RT301] — once a case, module-scoped fixture
            lambda p: llama.loss_fn(p, tokens[None, :-1], tokens[None, 1:], cfg)
        ))(params)
    want_loss, want_grads = jax.jit(jax.value_and_grad(  # rt: noqa[RT301] — once a case, module-scoped fixture
        lambda p: trinity_ref.loss(p, tokens[:-1], tokens[1:], model, q_block=8)
    ))(params)
    return dict(
        model=model, cfg=cfg, params=params, tokens=tokens, got=got,
        grads=grads, want_grads=want_grads, loss=loss, want_loss=want_loss,
    )


def test_the_program_and_the_plan_build_one_tree(case):
    shapes = jax.eval_shape(
        lambda k: llama.init_params(k, case["cfg"]), jax.random.PRNGKey(0)
    )
    assert jax.tree.map(lambda x: x.shape, shapes) == jax.tree.map(
        lambda x: x.shape, case["params"]
    )
    annotations = llama.param_annotations(case["cfg"])
    assert set(annotations) == set(shapes)
    for stack in ("dense_layers", "layers", "attn_window", "attn_full"):
        assert set(annotations[stack]) == set(shapes[stack])
    assert annotations["layers"]["w_gate"].logical_axes == (
        "layers", "expert", "embed", "mlp"
    )
    assert annotations["layers"]["wg"].logical_axes == annotations["layers"]["wq"].logical_axes


def test_forward_matches_the_reference_to_1e5(case):
    want = trinity_ref.forward(
        case["params"], case["tokens"][:-1], case["model"], q_block=8
    )
    assert float(jnp.abs(case["got"] - want).max()) < 1e-5 * float(
        jnp.abs(want).max()
    ) + 1e-5
    rows = trinity_ref.forward(
        case["params"], case["tokens"][:-1], case["model"], rows=(5, 9)
    )
    assert float(jnp.abs(rows - want[5:9]).max()) < 1e-5


def test_loss_matches_the_references(case):
    assert abs(float(case["loss"]) - float(case["want_loss"])) < 1e-5


def _leaves(tree):
    return {
        jax.tree_util.keystr(path): leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


LEAVES = sorted(_leaves(jax.eval_shape(
    lambda: weights.make(SHARE, "float32", 0, trinity_ref)
)))


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_matches_the_references(case, leaf):
    got, want = _leaves(case["grads"])[leaf], _leaves(case["want_grads"])[leaf]
    scale = float(jnp.abs(want).max())
    if leaf.endswith("['router_bias']"):
        # the expert bias decides the choice, not the gates: no gradient
        assert scale == 0.0 and float(jnp.abs(got).max()) == 0.0
        return
    assert scale > 1e-4, "the leaf takes no part in the loss"
    assert float(jnp.abs(got - want).max()) < 2e-5 * max(scale, 1.0), leaf


def test_gradients_through_the_compacted_layer_match_over_all_positions():
    """ISSUE 57. A sequence long enough that the rank's expert layers
    compute their budget of rows and not every pick (400 tokens x 3
    picks = 1,200 rows, 2 of 8 experts held: `held_row_budget` 1,024):
    the gradient of the real `loss_fn` to an expert leaf and to the
    EMBEDDED INPUT, position by position, against `jax.grad` of the
    reference's loss. Every token id occurs once, so a row of the
    embedding's gradient IS one position's gradient to the embedded
    input, and the error is pooled over all 400 of them: the cell's own
    `correct` reads one position's logits and holds no precision
    (ROADMAP.md 1 g), so a wrong row would pass it."""
    from ray_tpu.ops.moe import held_row_budget

    seq = 400
    model = _model(vocab_size=512, max_seq_len=512)
    budget = held_row_budget(
        seq * model["moe_top_k"], model["moe_experts"],
        model["moe_router_experts"],
    )
    assert budget == 1024 < seq * model["moe_top_k"]
    cfg = llama.LlamaConfig(**model, dtype=jnp.float32, attention="reference")
    params = weights.make(model, "float32", 11, trinity_ref)
    tokens = jax.random.permutation(jax.random.PRNGKey(12), 512)[:seq + 1]
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: llama.loss_fn(p, tokens[None, :-1], tokens[None, 1:], cfg)
        ))(params)
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p: trinity_ref.loss(p, tokens[:-1], tokens[1:], model, q_block=8)
    ))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    # (a target's row takes no gradient through the embedding: untied)
    got = {
        "input": grads["embed"][tokens[:-1]],
        "experts": grads["layers"]["w_up"],
        "gates": grads["layers"]["router"],
    }
    want = {
        "input": want_grads["embed"][tokens[:-1]],
        "experts": want_grads["layers"]["w_up"],
        "gates": want_grads["layers"]["router"],
    }
    for name in got:
        scale = float(jnp.sqrt(jnp.mean(want[name] ** 2)))
        assert scale > 1e-6, name
        pooled = float(jnp.sqrt(jnp.mean((got[name] - want[name]) ** 2)))
        assert pooled < 1e-4 * scale, (name, pooled / scale)
    # every position's own row, not the pool alone
    rows = jnp.sqrt(jnp.mean((got["input"] - want["input"]) ** 2, axis=-1))
    assert float(rows.max()) < 1e-3 * float(
        jnp.sqrt(jnp.mean(want["input"] ** 2))
    )


#: what the program would compute with a term of the block left out:
#: the reference's own equations with that term dropped must NOT agree
def _no_attn_post_norm(params):
    out = jax.tree.map(lambda x: x, params)
    for stack in ("dense_layers", "layers"):
        out[stack] = dict(out[stack])
        out[stack].pop("attn_post_norm")
    return out


@pytest.mark.parametrize("dropped", ["gate", "post_norm", "full_layer_rotary"])
def test_leaving_a_term_out_fails_the_comparison(dropped):
    """The program with the gate dropped, with the norm on the
    attention's output dropped, or with a full layer's q and k turned
    like a sliding layer's: each is far from the reference (which the
    untouched program meets to 1e-5)."""
    model = SHARE
    cfg, params, tokens = _build(model)
    want = trinity_ref.forward(params, tokens[:-1], model, q_block=8)
    if dropped == "gate":
        cfg = llama.LlamaConfig(
            **dict(model, attn_gate=False), dtype=jnp.float32,
            attention="reference",
        )
    elif dropped == "post_norm":
        # a layer without the leaf adds the half's output as it is
        params = _no_attn_post_norm(params)
    else:
        turned = [WINDOW] + [
            kind if kind[0] else [0, 2, 10000.0, False] for kind in KINDS[1:]
        ]
        cfg = llama.LlamaConfig(
            **dict(model, layer_kinds=turned), dtype=jnp.float32,
            attention="reference",
        )
    with jax.default_matmul_precision("highest"):
        got = jax.jit(
            lambda p: llama.forward(p, tokens[None, :-1], cfg)[0]
        )(params)
    error = float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want ** 2)))
    assert error > 0.05, (dropped, error)


def test_the_flash_path_with_a_window_meets_the_reference():
    """`attention="flash"` with the kernels interpreted: the training
    forward hands each kind's window to the kernel."""
    from unittest import mock

    from ray_tpu.ops import attention

    model = _model(n_layers=5, layer_kinds=KINDS[:5])
    cfg, params, tokens = _build(model)
    flash = llama.LlamaConfig(**model, dtype=jnp.float32, attention="flash")
    windows = []

    def interpreted(q, k, v, **kw):
        windows.append(kw.get("window", 0))
        return attention.flash_attention(
            q, k, v, block_q=16, block_k=16, force_pallas=True, **kw
        )

    with mock.patch.object(llama, "flash_attention", interpreted):
        with jax.default_matmul_precision("highest"):
            got = llama.forward(params, tokens[None, :-1], flash)[0]
    want = trinity_ref.forward(params, tokens[:-1], model, q_block=8)
    assert sorted(set(windows)) == [0, 8]
    assert float(jnp.abs(got - want).max()) < 2e-5


def test_what_still_has_no_training_equations_is_refused_by_name():
    sink = llama.LlamaConfig.tiny(
        layer_kinds=[[8, 2, 10000.0, True], [0, 2, 10000.0, False]],
        moe_experts=2, moe_router="sigmoid_groups",
    )
    conv = llama.LlamaConfig(
        vocab_size=64, dim=32, n_layers=2, n_heads=2, intermediate=16,
        custom_head_dim=16, moe_experts=2, moe_router="sigmoid_groups",
        layer_kinds=[[0, 0, 0, False, 3], [0, 2, 10000.0, False]],
    )
    tokens = jnp.zeros((1, 8), jnp.int32)
    for cfg, word in ((sink, "sink"), (conv, "conv")):
        with pytest.raises(NotImplementedError, match=word):
            llama.param_annotations(cfg)
        with pytest.raises(NotImplementedError, match=word):
            llama.forward_and_aux({}, tokens, cfg)
    narrow = llama.LlamaConfig.tiny(
        layer_kinds=[[8, 2, 10000.0, False], [0, 2, 10000.0, False]],
        moe_experts=2, moe_router="sigmoid_groups", v_head_dim=8,
    )
    with pytest.raises(NotImplementedError, match="v_head_dim"):
        llama.param_annotations(narrow)
    with pytest.raises(ValueError, match="layer_kinds"):
        llama.LlamaConfig.tiny(attn_gate=True)


@pytest.mark.parametrize("key", ["attn_gate", "post_norms"])
def test_the_paged_forwards_refuse_the_trinity_keys_by_name(key):
    from ray_tpu.models import generate

    keys = dict(SHARE, attn_gate=False, post_norms=False)
    keys[key] = True
    cfg = llama.LlamaConfig(**keys, dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match=key):
        generate.init_block_pool(cfg, 8, 4)
