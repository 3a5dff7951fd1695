"""Numerical tests for the compute ops. The Pallas kernels run in
interpreter mode on CPU (tiling/precision semantics preserved), so
these validate the same code path that runs on TPU."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import (
    apply_rotary,
    flash_attention,
    mha_reference,
    ring_attention,
    rms_norm,
    rotary_embedding,
    swiglu,
    ulysses_attention,
)
from ray_tpu.parallel import MeshSpec


def _qkv(key, b=1, h=2, t=256, d=128, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, t, d), dtype)
    k = jax.random.normal(kk, (b, h, t, d), dtype)
    v = jax.random.normal(kv, (b, h, t, d), dtype)
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_reference(self, causal):
        q, k, v = _qkv(jax.random.PRNGKey(0))
        ref = mha_reference(q, k, v, causal=causal)
        out = flash_attention(
            q, k, v, causal=causal, block_q=128, block_k=128,
            force_pallas=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_multiple_kv_blocks(self):
        q, k, v = _qkv(jax.random.PRNGKey(1), t=512)
        ref = mha_reference(q, k, v, causal=True)
        out = flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128,
            force_pallas=True,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    @pytest.mark.parametrize("causal", [True, False])
    def test_gradients_match_reference(self, causal):
        q, k, v = _qkv(jax.random.PRNGKey(2), h=1, t=256)

        def loss_flash(q, k, v):
            out = flash_attention(
                q, k, v, causal=causal, block_q=128, block_k=128,
                force_pallas=True,
            )
            return jnp.sum(out * out)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
                err_msg=f"d{name} mismatch",
            )

    @pytest.mark.parametrize("blocks", [(128, 256), (256, 128)])
    def test_gradients_unequal_blocks(self, blocks):
        """Non-square tiles take the slow masking path and have no
        exact-diagonal structure — the regime where any square-block
        assumption in the fused backward (per-tile scale placement,
        bias fast path gating) breaks (review r5 finding)."""
        bq, bk = blocks
        q, k, v = _qkv(jax.random.PRNGKey(7), h=1, t=256)

        def loss_flash(q, k, v):
            out = flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk,
                force_pallas=True,
            )
            return jnp.sum(out * out)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
                err_msg=f"d{name} mismatch (bq={bq}, bk={bk})",
            )

    def test_bf16_inputs(self):
        q, k, v = _qkv(jax.random.PRNGKey(3), dtype=jnp.bfloat16)
        ref = mha_reference(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
        )
        out = flash_attention(
            q, k, v, block_q=128, block_k=128, force_pallas=True
        )
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, dtype=np.float32), np.asarray(ref),
            atol=2e-2, rtol=2e-2,
        )


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, causal):
        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs 8 virtual devices")
        mesh = MeshSpec(sp=8).build()
        b, h, t, d = 1, 2, 128, 32
        q, k, v = _qkv(jax.random.PRNGKey(4), b=b, h=h, t=t, d=d)
        ref = mha_reference(q, k, v, causal=causal)
        out = shard_map(
            partial(ring_attention, axis_name="sp", causal=causal),
            mesh=mesh,
            in_specs=P(None, None, "sp", None),
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        )(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_grad_flows(self):
        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs 8 virtual devices")
        mesh = MeshSpec(sp=8).build()
        q, k, v = _qkv(jax.random.PRNGKey(5), t=64, d=16)

        @jax.jit
        def loss(q, k, v):
            out = shard_map(
                partial(ring_attention, axis_name="sp", causal=True),
                mesh=mesh,
                in_specs=P(None, None, "sp", None),
                out_specs=P(None, None, "sp", None),
                check_vma=False,
            )(q, k, v)
            return jnp.sum(out**2)

        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        def ref_loss(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

        ref_grads = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for g, r in zip(grads, ref_grads):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), atol=1e-4, rtol=1e-4
            )


class TestUlysses:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, causal):
        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs 8 virtual devices")
        mesh = MeshSpec(sp=8).build()
        b, h, t, d = 1, 8, 128, 32  # heads divisible by sp
        q, k, v = _qkv(jax.random.PRNGKey(6), b=b, h=h, t=t, d=d)
        ref = mha_reference(q, k, v, causal=causal)
        out = shard_map(
            partial(ulysses_attention, axis_name="sp", causal=causal),
            mesh=mesh,
            in_specs=P(None, None, "sp", None),
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        )(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )


class TestNorms:
    def test_rms_norm(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
        w = jnp.ones(64) * 2.0
        out = rms_norm(x, w)
        expected = (
            x / np.sqrt(np.mean(np.asarray(x) ** 2, -1, keepdims=True) + 1e-6)
        ) * 2.0
        np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5)

    def test_rope_rotation_preserves_norm(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 16, 64))
        pos = jnp.arange(16)[None, :]
        cos, sin = rotary_embedding(pos, 64)
        out = apply_rotary(x, cos, sin)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(out), axis=-1),
            np.linalg.norm(np.asarray(x), axis=-1),
            atol=1e-4,
        )

    def test_rope_position_zero_identity(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 32))
        cos, sin = rotary_embedding(jnp.zeros((1, 1)), 32)
        out = apply_rotary(x, cos, sin)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=1e-6)

    def test_swiglu(self):
        x = jnp.array([1.0, 2.0])
        g = jnp.array([0.0, 10.0])
        out = swiglu(x, g)
        np.testing.assert_allclose(
            np.asarray(out), [0.0, 2.0 * 10.0 / (1 + np.exp(-10.0))],
            rtol=1e-5,
        )


class TestFlashAttentionPadding:
    """Sequence lengths not divisible by block sizes must be exact
    (kernels mask padded KV columns and padded q rows)."""

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("t", [100, 300])
    def test_ragged_lengths_forward_and_grad(self, causal, t):
        q, k, v = _qkv(jax.random.PRNGKey(7), h=1, t=t, d=128)

        def loss_flash(q, k, v):
            out = flash_attention(
                q, k, v, causal=causal, block_q=128, block_k=128,
                force_pallas=True,
            )
            return jnp.sum(out * out)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

        np.testing.assert_allclose(
            float(loss_flash(q, k, v)), float(loss_ref(q, k, v)),
            rtol=1e-4,
        )
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3,
                err_msg=f"d{name}",
            )


def test_every_pallas_call_is_named():
    """A device trace names a kernel by its HLO instruction, which is
    the `name=` of its `pl.pallas_call`: one without it shows up as
    whatever JAX wrapper it sits in (`checkpoint`, `closed_call`,
    `shard_map`), and the benchmark's kernel metrics cannot find it."""
    import ast
    import glob
    import os

    import ray_tpu.ops

    unnamed, seen = [], 0
    root = os.path.dirname(ray_tpu.ops.__file__)
    for path in sorted(glob.glob(os.path.join(root, "**", "*.py"), recursive=True)):
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "pallas_call"
            ):
                continue
            seen += 1
            name = next(
                (k.value for k in node.keywords if k.arg == "name"), None
            )
            if not (isinstance(name, ast.Constant) and name.value):
                unnamed.append(f"{path}:{node.lineno}")
    assert seen >= 2  # the flash forward and the fused backward
    assert not unnamed, unnamed
