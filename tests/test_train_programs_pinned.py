"""The older train programs are the parent's (ISSUE 55): the step of
the two Mistral configurations (`pretrain_8k`, `pretrain_8k_fsdp4`;
one model file apart in depth) and the flash kernels at `window=0`
trace to the SAME jaxpr they traced to before `layer_kinds`, a window,
a gate and post-norms reached the train path: nothing of those is in
them, no `cond` a kind, no second mask, no new operand. Since ISSUE 57
the step is pinned on a mesh of four devices too, and so are the
expert layer's programs where it holds every expert it routes over
(`ops/moe.py` `moe_ffn_dropless`: OLMoE's, `doc_score_moe`), while a
layer under a router wider than its held experts computes
`held_row_budget` rows and no pass over `t x k` outside its fall-back.

The pins are digests of the traced programs' text, kernel bodies
included, with addresses and line numbers taken out; they were read off
the parent commit (PR 54, 14afb43) and off this tree by the same lines
and are the same. A change of JAX moves them on both sides: read them
again off a tree known to be sound (`_digest` below prints through
`pytest -s`) before taking a difference here for a fault."""

import hashlib
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import llama
from ray_tpu.ops import attention

ROOT = pathlib.Path(__file__).parent.parent

#: jax 0.9.0; parent and change alike
FLASH_GRAD_AT_THE_BENCHMARKS_WIDTHS = "384b1d801178e4da"
MISTRAL_REHEARSAL_STEP = "13b31ace8bf7cf5d"
#: the same step on `MeshSpec(fsdp=4)` over four devices, four
#: sequences a step (`pretrain_8k_fsdp4`'s mesh): read off the parent
#: commit (re-anchor at PR 55, 3bafcb2) and off this tree (ISSUE 57)
MISTRAL_REHEARSAL_STEP_FSDP4 = "7a28173ebfe2d64d"


def _digest(jaxpr) -> str:
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr))
    text = re.sub(r"/[^ :\"']*/ray_tpu/", "ray_tpu/", text)
    text = re.sub(r"\.py:\d+", ".py", text)
    # (a set prints in the order of its hashes, another every process:
    # a `shard_map`'s `manual_axes` on a mesh of several devices)
    text = re.sub(
        r"frozenset\(\{([^}]*)\}\)",
        lambda m: "frozenset({%s})" % ", ".join(sorted(m[1].split(", "))),
        text,
    )
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """The kernels ask `jax.default_backend()` whether to be kernels:
    steered here, for a trace (nothing is compiled or run)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attention, "_interpret", lambda: False)


def _flash_grad(**kw):
    # `mistral-7b-v0.3-l4`: 32 heads (kv repeated) x 128, 8,192 tokens
    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16)

    def loss(q, k, v):
        out = attention.flash_attention(q, k, v, causal=True, **kw)
        return out.astype(jnp.float32).sum()

    return jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, q, q)


def test_flash_without_a_window_is_the_parents_kernels(as_on_the_chip):
    plain = _flash_grad()
    print("flash grad:", _digest(plain))
    assert _digest(plain) == FLASH_GRAD_AT_THE_BENCHMARKS_WIDTHS
    # window=0, and a window the sequence fits, are that program
    assert _digest(_flash_grad(window=0)) == _digest(plain)
    assert _digest(_flash_grad(window=8192)) == _digest(plain)
    # and a real window is another: the second inequality, the clamps
    windowed = _flash_grad(window=2048)
    assert _digest(windowed) != _digest(plain)
    assert str(windowed).count("pallas_call") == str(plain).count(
        "pallas_call"
    ) == 2
    for name in ("flash_fwd", "flash_bwd"):
        assert f"name={name}" in str(windowed) and f"name={name}" in str(plain)


def _mistral_step(depth_of: str, fsdp: int = 1):
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.train_step import (
        TrainState, default_optimizer, make_train_step,
    )

    config = json.loads(
        (ROOT / "benchmark" / "configs" / f"{depth_of}.json").read_text()
    )
    model = {**config["model"], **config["rehearsal"]["model"]}
    trainer = config["trainer"]
    cfg = llama.LlamaConfig(
        **model, dtype=jnp.bfloat16, attention=trainer["attention"],
        remat_policy=trainer["remat_policy"],
    )
    mesh = MeshSpec(fsdp=fsdp).build(jax.devices()[:fsdp])
    optimizer = default_optimizer(**trainer["optimizer"])
    _, step_fn = make_train_step(
        lambda p, t, y: llama.loss_fn(p, t, y, cfg, mesh=mesh),
        optimizer, mesh, llama.param_annotations(cfg),
    )
    shapes = jax.eval_shape(
        lambda k: llama.init_params(k, cfg), jax.random.PRNGKey(0)
    )
    state = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32), params=shapes,
        opt_state=jax.eval_shape(optimizer.init, shapes),
    )
    tokens = jax.ShapeDtypeStruct((fsdp, model["max_seq_len"]), jnp.int32)
    return jax.make_jaxpr(step_fn.wrapped)(state, tokens, tokens)


@pytest.mark.parametrize(
    "config", ["mistral-7b-v0.3-l4", "mistral-7b-v0.3-l8"]
)
def test_the_mistral_step_is_the_parents_program(as_on_the_chip, config):
    step = _mistral_step(config)
    text = str(step)
    print(config, "step:", _digest(step))
    # (the two files' rehearsals are one model: one digest)
    assert _digest(step) == MISTRAL_REHEARSAL_STEP
    # (a `cond` there is a kernel's `pl.when`; a kind's would be a
    # `switch` around `layer/attention`)
    assert "switch" not in text and "layer/attn_gate" not in text
    assert text.count("name=flash_fwd") == 1  # dots_flash: never re-run
    assert text.count("name=flash_bwd") == 1
    assert "ragged_dot" not in text


@pytest.mark.parametrize(
    "config", ["mistral-7b-v0.3-l4", "mistral-7b-v0.3-l8"]
)
def test_the_mistral_step_on_four_devices_is_the_parents_program(
    as_on_the_chip, config
):
    """ISSUE 57: two PRs (48, 56) died on `pretrain_8k_fsdp4` with a
    change that was said not to reach it. This says it of the program
    a mesh of four devices traces: the flash kernels per shard
    (`shard_map`), the parameters' shardings, the step's outputs."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices (tests/conftest.py gives eight)")
    step = _mistral_step(config, fsdp=4)
    text = str(step)
    print(config, "step on fsdp=4:", _digest(step))
    assert _digest(step) == MISTRAL_REHEARSAL_STEP_FSDP4
    assert "shard_map" in text and "ragged_dot" not in text
    assert [a.shape for a in step.out_avals[-2:]] == [(), ()]


def test_a_dense_models_metrics_are_loss_and_grad_norm_as_before():
    from ray_tpu.train.train_step import TrainState

    step = _mistral_step("mistral-7b-v0.3-l4")
    state_leaves = len(step.in_avals) - 2  # the state, tokens, targets
    # out: the new state's leaves, then `grad_norm` and `loss` alone
    assert len(step.out_avals) == state_leaves + 2
    assert [a.shape for a in step.out_avals[-2:]] == [(), ()]
    assert TrainState.__dataclass_fields__.keys() == {
        "step", "params", "opt_state"
    }


#: `moe_ffn_dropless` with every expert held, at `olmoe-1b-7b-l8`'s
#: widths: a chunk and a step of the serve forwards (the experts a
#: stack, a step's dead rows), and a sliced layer differentiated. Read
#: off the parent commit (3bafcb2) and off this tree (ISSUE 57).
OLMOE_EXPERT_LAYER = {
    "chunk": "4eea1ae590a3060a", "step": "0acddec02160e863",
    "grad": "9a351b15f4fd6a39",
}


def _olmoe_expert_layer(form: str):
    from ray_tpu.ops.moe import moe_ffn_dropless

    config = json.loads(
        (ROOT / "benchmark" / "configs" / "olmoe-1b-7b-l8.json").read_text()
    )
    model, engine = config["model"], config["engine"]
    d, f, experts = model["dim"], model["intermediate"], model["moe_experts"]
    stack = () if form == "grad" else (model["n_layers"],)
    rows = {
        "chunk": engine["prefill_chunk"], "step": engine["slots"], "grad": 8192
    }[form]
    params = {
        "router": jax.ShapeDtypeStruct((d, experts), jnp.bfloat16),
        "w_gate": jax.ShapeDtypeStruct(stack + (experts, d, f), jnp.bfloat16),
        "w_up": jax.ShapeDtypeStruct(stack + (experts, d, f), jnp.bfloat16),
        "w_down": jax.ShapeDtypeStruct(stack + (experts, f, d), jnp.bfloat16),
    }
    x = jax.ShapeDtypeStruct((rows, d), jnp.bfloat16)
    live = jax.ShapeDtypeStruct((rows,), jnp.bool_)
    layer = jax.ShapeDtypeStruct((), jnp.int32)
    keys = dict(
        k=model["moe_top_k"], renormalise=model["moe_router"] == "softmax_renorm"
    )
    if form == "grad":
        def loss(params, x):
            out, aux, _ = moe_ffn_dropless(params, x, **keys)
            return out.astype(jnp.float32).sum() + aux

        return jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, x)
    return jax.make_jaxpr(
        lambda params, x, live, layer: moe_ffn_dropless(
            params, x, layer=layer, live=live if form == "step" else None,
            **keys,
        )
    )(params, x, live, layer)


@pytest.mark.parametrize("form", list(OLMOE_EXPERT_LAYER))
def test_a_layer_that_holds_every_expert_is_the_parents_program(form):
    program = _olmoe_expert_layer(form)
    print("olmoe expert layer,", form, _digest(program))
    assert _digest(program) == OLMOE_EXPERT_LAYER[form]
    assert "cond[" not in str(program)


def _passes_over_every_pick(jaxpr, picks, tokens, k, inside_fallback=False):
    """(primitive, shape) of every value a program computes that is a
    ROW a pick (`[t x k, width]`, or `[t, k, width]`), outside the
    `cond` branch a load over the budget takes; a vector of `t x k`
    numbers (the picks, their order) is not one."""
    found = []
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            rows = len(shape) == 2 and shape[0] == picks and shape[1] > 1
            rows |= len(shape) == 3 and shape[:2] == (tokens, k)
            if rows and not inside_fallback:
                found.append((eqn.primitive.name, shape))
        if eqn.primitive.name == "cond":
            # `lax.cond(fits, held, all)`: the branches are (all, held)
            fallback, held = eqn.params["branches"]
            found += _passes_over_every_pick(
                held.jaxpr, picks, tokens, k, inside_fallback
            )
            found += _passes_over_every_pick(fallback.jaxpr, picks, tokens, k, True)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _passes_over_every_pick(
                sub, picks, tokens, k, inside_fallback
            )
    return found


@pytest.mark.parametrize("differentiated", [False, True], ids=["forward", "grad"])
def test_a_rank_of_ep8_passes_over_its_budget_of_rows_alone(differentiated):
    """`trinity-mini-ep8`'s expert layer (16 of 128 experts held, 8,192
    tokens x 8 picks): outside the branch a load over the budget takes,
    no gather, no product, no select and no cotangent has a row a pick;
    they have `held_row_budget` = 16,384 rows, a quarter."""
    from ray_tpu.ops.moe import held_row_budget, moe_ffn_dropless

    config = json.loads(
        (ROOT / "benchmark" / "configs" / "trinity-mini-ep8.json").read_text()
    )
    model = config["model"]
    t, d, f = model["max_seq_len"], model["dim"], model["intermediate"]
    held, over, k = (
        model["moe_experts"], model["moe_router_experts"], model["moe_top_k"]
    )
    budget = held_row_budget(t * k, held, over)
    assert budget == 16384 == 2 * t * k * held // over
    params = {
        "w_gate": jax.ShapeDtypeStruct((held, d, f), jnp.bfloat16),
        "w_up": jax.ShapeDtypeStruct((held, d, f), jnp.bfloat16),
        "w_down": jax.ShapeDtypeStruct((held, f, d), jnp.bfloat16),
    }
    x = jax.ShapeDtypeStruct((t, d), jnp.bfloat16)
    gates = jax.ShapeDtypeStruct((t, k), jnp.float32)
    experts = jax.ShapeDtypeStruct((t, k), jnp.int32)

    def layer(params, x, gates, experts, routed_over=over):
        out, _, _ = moe_ffn_dropless(
            params, x, k=k, routed=(gates, experts), routed_over=routed_over
        )
        return out.astype(jnp.float32).sum()

    program = jax.grad(layer, (0, 1, 2)) if differentiated else layer
    found = _passes_over_every_pick(
        jax.make_jaxpr(program)(params, x, gates, experts).jaxpr, t * k, t, k
    )
    assert found == []
    text = str(jax.make_jaxpr(program)(params, x, gates, experts))
    assert f"[{budget},{d}]" in text and "ragged_dot" in text
    # and the reader does find them in the program that has them
    all_rows = _passes_over_every_pick(
        jax.make_jaxpr(
            lambda p, x, g, e: layer(p, x, g, e, routed_over=0)
        )(params, x, gates, experts).jaxpr, t * k, t, k,
    )
    assert {name for name, _ in all_rows} >= {"gather", "mul"}
