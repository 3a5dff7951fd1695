"""The flash kernels at the benchmark's training widths, compiled for a
described TPU v5e (no chip attached; nothing runs): the device trace
names a kernel by its HLO instruction, so the names the benchmark's
`flash_kernel_share` sums by are pinned here, with the `named_scope`
the model puts around attention.

The topology is described inside a module-scoped fixture of this file
and nowhere else (on-chip-measurement guide, section 2): only one
process may load the TPU's library, and only a test that has started
may try."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import llama

#: `benchmark/configs/mistral-7b-v0.3-l4.json`: 32 heads / 8 KV heads
#: x 128, one 8,192-token sequence a chip.
HEADS, KV_HEADS, HEAD_DIM, SEQ = 32, 8, 128, 8192


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def kernel_calls(one_chip):
    """{instruction name: op_name} of the Mosaic calls in the compiled
    gradient of the model's attention block."""
    from jax.experimental.compilation_cache import compilation_cache

    cfg = llama.LlamaConfig(
        vocab_size=32768, dim=HEADS * HEAD_DIM, n_layers=1,
        n_heads=HEADS, n_kv_heads=KV_HEADS, intermediate=14336,
        max_seq_len=SEQ, dtype=jnp.bfloat16, attention="flash",
    )

    def loss(q, k, v):
        out = llama._attention(cfg, q, k, v, None)
        return out.astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct(
        (1, HEADS, SEQ, HEAD_DIM), jnp.bfloat16, sharding=one_chip
    )
    kv = jax.ShapeDtypeStruct(
        (1, KV_HEADS, SEQ, HEAD_DIM), jnp.bfloat16, sharding=one_chip
    )
    # `flash_attention` asks jax.default_backend(), which is the CPU
    # here, and would take its reference branch: steered in the test,
    # not through an option of the program. A compile for a described
    # chip cannot be read back from the persistent cache, so it is
    # kept out of it.
    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(  # rt: noqa[RT301] — compiled once, for a described chip, by a module-scoped fixture; nothing is called
            jax.grad(loss, argnums=(0, 1, 2))
        ).lower(q, kv, kv).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
        patch.undo()
    calls = {}
    for line in text.splitlines():
        if "tpu_custom_call" not in line or " = " not in line:
            continue
        name = line.strip().split(" = ")[0].removeprefix("ROOT ")
        op_name = re.search(r'op_name="([^"]*)"', line)
        calls[name.lstrip("%")] = op_name.group(1) if op_name else ""
    return calls


def test_two_mosaic_kernels_and_nothing_unnamed(kernel_calls):
    families = sorted(re.sub(r"[.\d]+$", "", n) for n in kernel_calls)
    assert families == ["flash_bwd", "flash_fwd"], kernel_calls


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd"])
def test_kernel_is_named_and_scoped(kernel_calls, kernel):
    (name, op_name), = [
        kv for kv in kernel_calls.items() if kv[0].startswith(kernel)
    ]
    assert re.fullmatch(rf"{kernel}(\.\d+)?", name)
    assert "layer/attention" in op_name
    assert f"/{kernel}/" in op_name
