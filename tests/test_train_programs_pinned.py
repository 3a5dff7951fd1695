"""The older train programs are the parent's (ISSUE 55): the step of
the two Mistral configurations (`pretrain_8k`, `pretrain_8k_fsdp4`;
one model file apart in depth) and the flash kernels at `window=0`
trace to the SAME jaxpr they traced to before `layer_kinds`, a window,
a gate and post-norms reached the train path: nothing of those is in
them, no `cond` a kind, no second mask, no new operand.

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


def _digest(jaxpr) -> str:
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr))
    text = re.sub(r"/[^ :\"']*/ray_tpu/", "ray_tpu/", text)
    text = re.sub(r"\.py:\d+", ".py", text)
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


def _mistral_step(depth_of: str):
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
    mesh = MeshSpec(fsdp=1).build(jax.devices()[:1])
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
    tokens = jax.ShapeDtypeStruct((1, model["max_seq_len"]), jnp.int32)
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
