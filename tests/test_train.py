"""Training-stack tests: sharded train step (dp+fsdp+tp on the virtual
mesh), JaxTrainer fit, sessions, checkpointing, worker gangs."""

import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import (
    LlamaConfig,
    forward,
    init_params,
    loss_fn,
    param_annotations,
)
from ray_tpu.parallel.mesh import MeshSpec
from ray_tpu.train import (
    CheckpointManager,
    JaxTrainer,
    Result,
    RunConfig,
    ScalingConfig,
    default_optimizer,
    make_train_step,
    report,
    restore_checkpoint,
    save_checkpoint,
    shard_batch,
)


def _tiny_cfg():
    return LlamaConfig.tiny()


def _mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return MeshSpec(dp=2, fsdp=2, tp=2).build()


class TestTrainStep:
    def test_loss_decreases_sharded(self):
        mesh = _mesh()
        cfg = _tiny_cfg()
        opt = default_optimizer(learning_rate=1e-2, total_steps=50)
        init_fn, step_fn = make_train_step(
            lambda p, t, y: loss_fn(p, t, y, cfg),
            opt,
            mesh,
            param_annotations(cfg),
        )
        state = init_fn(jax.random.PRNGKey(0), lambda k: init_params(k, cfg))
        toks = jax.random.randint(
            jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size
        )
        toks = shard_batch(toks, mesh, logical_axes=("batch", None))
        inp, tgt = toks[:, :-1], toks[:, 1:]
        first = None
        for _ in range(10):
            state, metrics = step_fn(state, inp, tgt)
            if first is None:
                first = float(metrics["loss"])
        last = float(metrics["loss"])
        assert last < first, (first, last)
        assert int(state.step) == 10

    def test_params_are_sharded(self):
        mesh = _mesh()
        cfg = _tiny_cfg()
        opt = default_optimizer(total_steps=10)
        init_fn, _ = make_train_step(
            lambda p, t, y: loss_fn(p, t, y, cfg),
            opt,
            mesh,
            param_annotations(cfg),
        )
        state = init_fn(jax.random.PRNGKey(0), lambda k: init_params(k, cfg))
        # w1 [L, embed(dim), mlp] must be sharded over fsdp and tp.
        spec = state.params["layers"]["w1"].sharding.spec
        assert tuple(spec) == (None, "fsdp", "tp")
        # Optimizer state inherits the same layout (ZeRO-3 analog).
        adam_mu = jax.tree.leaves(state.opt_state)
        assert any(
            getattr(leaf, "sharding", None) is not None
            and leaf.sharding.spec == state.params["layers"]["w1"].sharding.spec
            for leaf in adam_mu
            if hasattr(leaf, "shape")
            and leaf.shape == state.params["layers"]["w1"].shape
        )

    def test_sp_ring_attention_training(self):
        """Sequence parallelism end-to-end: loss under ring attention
        on an sp-sharded mesh matches the reference-attention loss."""
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        from functools import partial

        from jax import shard_map
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = MeshSpec(sp=4).build(jax.devices()[:4])
        cfg_ring = LlamaConfig.tiny(attention="ring")
        cfg_ref = LlamaConfig.tiny(attention="reference")
        params = init_params(jax.random.PRNGKey(0), cfg_ref)
        toks = jax.random.randint(
            jax.random.PRNGKey(1), (2, 64), 0, cfg_ref.vocab_size
        )
        inp, tgt = toks[:, :-1], toks[:, 1:]  # seq 63... need divisible
        inp, tgt = toks[:, :64][:, :-4], toks[:, 1:61]  # len 60 -> /4
        ref_loss = float(loss_fn(params, inp, tgt, cfg_ref))

        def sp_loss(params, inp, tgt):
            b, t = inp.shape
            positions = jnp.broadcast_to(jnp.arange(t), (b, t))

            def local(params, inp, tgt, positions):
                return loss_fn(
                    params, inp, tgt, cfg_ring,
                    positions=positions, sp_axis="sp",
                )[None]

            losses = shard_map(
                local,
                mesh=mesh,
                in_specs=(P(), P(None, "sp"), P(None, "sp"), P(None, "sp")),
                out_specs=P("sp"),
                check_vma=False,
            )(params, inp, tgt, positions)
            # Each shard's mean is over its local tokens; all tokens
            # unmasked and shards equal-sized, so the mean of means is
            # the global mean.
            return jnp.mean(losses)

        ring_loss = float(sp_loss(params, inp, tgt))
        np.testing.assert_allclose(ring_loss, ref_loss, rtol=2e-4)


class TestJaxTrainer:
    def test_fit_local_reports(self):
        cfg = _tiny_cfg()

        def train_loop(config):
            mesh = MeshSpec(fsdp=1).build(jax.devices()[:1])
            opt = default_optimizer(learning_rate=1e-2, total_steps=20)
            init_fn, step_fn = make_train_step(
                lambda p, t, y: loss_fn(p, t, y, cfg),
                opt, mesh, param_annotations(cfg),
            )
            state = init_fn(
                jax.random.PRNGKey(0), lambda k: init_params(k, cfg)
            )
            toks = jax.random.randint(
                jax.random.PRNGKey(1), (2, 33), 0, cfg.vocab_size
            )
            for step in range(config["steps"]):
                state, metrics = step_fn(state, toks[:, :-1], toks[:, 1:])
                report({"loss": float(metrics["loss"]), "step": step})

        trainer = JaxTrainer(
            train_loop,
            train_loop_config={"steps": 3},
            scaling_config=ScalingConfig(num_workers=1),
        )
        result = trainer.fit()
        assert isinstance(result, Result)
        assert result.error is None
        assert len(result.metrics_history) == 3
        assert result.metrics["step"] == 2

    def test_fit_failure_captured(self):
        def bad_loop():
            raise RuntimeError("train loop exploded")

        trainer = JaxTrainer(bad_loop)
        result = trainer.fit()
        assert result.error is not None
        assert "exploded" in str(result.error)

    def test_fit_retry_resumes_from_checkpoint(self, tmp_path):
        """A retried attempt must restore from the previous attempt's
        latest checkpoint, not restart from scratch (reference:
        backend_executor._restart:759)."""
        from ray_tpu.train import FailureConfig, get_checkpoint

        marker = tmp_path / "attempts"
        marker.write_text("0")

        def loop():
            attempt = int(marker.read_text())
            marker.write_text(str(attempt + 1))
            ckpt = get_checkpoint()
            start = 0
            if ckpt is not None:
                start = int(
                    (pathlib.Path(ckpt) / "step").read_text()
                )
            assert not (attempt > 0 and start == 0), (
                "retry did not see the previous attempt's checkpoint"
            )
            for step in range(start, 5):
                d = tmp_path / f"ck{step}"
                d.mkdir(exist_ok=True)
                (d / "step").write_text(str(step + 1))
                report({"step": step}, checkpoint=str(d))
                if step == 2 and attempt == 0:
                    raise RuntimeError("boom at step 2")

        trainer = JaxTrainer(
            loop,
            run_config=RunConfig(
                storage_path=str(tmp_path / "storage"),
                failure_config=FailureConfig(max_failures=1),
            ),
        )
        result = trainer.fit()
        assert result.error is None
        assert result.metrics["step"] == 4
        # Second attempt resumed at step 3 → reported only steps 3, 4.
        assert [m["step"] for m in result.metrics_history] == [3, 4]


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        state = {
            "w": jnp.arange(16.0).reshape(4, 4),
            "step": jnp.int32(7),
        }
        path = str(tmp_path / "ckpt")
        save_checkpoint(path, state, {"note": "test"})
        restored = restore_checkpoint(
            path, jax.tree.map(jnp.zeros_like, state)
        )
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.asarray(state["w"])
        )
        assert int(restored["step"]) == 7

    def test_manager_retention(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), num_to_keep=2)
        for step in [1, 2, 3]:
            mgr.save(step, {"x": jnp.float32(step)})
        dirs = sorted(os.listdir(tmp_path))
        assert dirs == ["checkpoint_00000002", "checkpoint_00000003"]
        assert mgr.latest().endswith("checkpoint_00000003")


class TestAsyncCheckpoint:
    def test_async_save_restore_roundtrip(self, tmp_path):
        from ray_tpu.train import load_metadata

        state = {
            "w": jnp.arange(16.0).reshape(4, 4),
            "step": jnp.int32(7),
        }
        path = str(tmp_path / "ckpt")
        save_checkpoint(path, state, {"note": "async"}, async_save=True)
        # restore_checkpoint waits for the in-flight write internally.
        restored = restore_checkpoint(
            path, jax.tree.map(jnp.zeros_like, state)
        )
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.asarray(state["w"])
        )
        assert int(restored["step"]) == 7
        assert load_metadata(path)["note"] == "async"

    def test_step_n_plus_1_runs_while_save_n_persists(
        self, tmp_path, monkeypatch
    ):
        """The overlap proof: gate the background write on an event,
        run (and finish) training compute while the writer is
        provably still inside the save, then release it and assert
        the barrier delivers a durable checkpoint."""
        import threading
        import time

        from ray_tpu.train import checkpoint as ck

        write_started = threading.Event()
        release_write = threading.Event()
        real_write = ck._write_payload

        def gated_write(path, state, metadata):
            write_started.set()
            assert release_write.wait(timeout=30), "writer never released"
            real_write(path, state, metadata)

        monkeypatch.setattr(ck, "_write_payload", gated_write)

        state = {"w": jnp.arange(64.0)}
        path = str(tmp_path / "ck0")
        t0 = time.perf_counter()
        save_checkpoint(state=state, path=path, metadata={"step": 0},
                        async_save=True)
        # save N returned without waiting on the (gated) disk write.
        assert time.perf_counter() - t0 < 5.0
        assert write_started.wait(timeout=10)

        # Step N+1: real jitted compute, completed to a host value
        # while the save is still persisting.
        step = jax.jit(lambda x: jnp.sum(x * x))
        result = float(step(jnp.arange(1000.0)))
        assert result > 0
        assert ck.pending_checkpoints() == [path], (
            "save must still be in flight when step N+1 retires"
        )

        release_write.set()
        ck.wait_for_checkpoints()
        assert ck.pending_checkpoints() == []
        assert (tmp_path / "ck0" / "metadata.json").exists()

    def test_fit_exit_barrier_makes_final_checkpoint_durable(
        self, tmp_path, monkeypatch
    ):
        """fit() must not return while an async save is still in
        flight: the loop issues a slow async save as its final act,
        and the checkpoint must be fully on disk (metadata.json is
        written last) the moment fit() hands back."""
        import time

        from ray_tpu.train import checkpoint as ck

        real_write = ck._write_payload

        def slow_write(path, state, metadata):
            time.sleep(0.8)
            real_write(path, state, metadata)

        monkeypatch.setattr(ck, "_write_payload", slow_write)
        ckpt_dir = str(tmp_path / "final_ck")

        def loop():
            save_checkpoint(
                ckpt_dir,
                {"w": jnp.ones(8)},
                {"step": 1},
                async_save=True,
            )
            report({"step": 1}, checkpoint=ckpt_dir)

        result = JaxTrainer(loop).fit()
        assert result.error is None
        assert result.checkpoint_path == ckpt_dir
        assert ck.pending_checkpoints() == []
        assert os.path.exists(os.path.join(ckpt_dir, "metadata.json"))

    def test_write_error_surfaces_at_barrier(self, tmp_path, monkeypatch):
        from ray_tpu.train import checkpoint as ck

        def boom(path, state, metadata):
            raise RuntimeError("disk full")

        monkeypatch.setattr(ck, "_write_payload", boom)
        save_checkpoint(
            str(tmp_path / "x"), {"w": jnp.ones(2)}, async_save=True
        )
        with pytest.raises(RuntimeError, match="disk full"):
            ck.wait_for_checkpoints()
        assert ck.pending_checkpoints() == []

    def test_manager_async_retention(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), num_to_keep=2)
        for step in [1, 2, 3]:
            mgr.save(step, {"x": jnp.float32(step)}, async_save=True)
        mgr.wait()
        dirs = sorted(
            d
            for d in os.listdir(tmp_path)
            if d.startswith("checkpoint_")
        )
        assert dirs == ["checkpoint_00000002", "checkpoint_00000003"]
        assert mgr.latest().endswith("checkpoint_00000003")


class TestDeviceBatchPrefetch:
    def test_prefetch_to_device_order_and_residency(self):
        from ray_tpu.train import prefetch_to_device

        mesh = MeshSpec(fsdp=1).build(jax.devices()[:1])
        host = [
            {"id": np.full((4,), i, dtype=np.int32)} for i in range(7)
        ]
        out = list(
            prefetch_to_device(
                iter(host), mesh, buffer_size=2, logical_axes=("batch",)
            )
        )
        assert len(out) == 7
        for i, batch in enumerate(out):
            assert isinstance(batch["id"], jax.Array)  # on device
            np.testing.assert_array_equal(
                np.asarray(batch["id"]), np.full((4,), i)
            )

    def test_trainer_device_batches_end_to_end(self):
        """datasets= -> get_device_batches: the whole overlapped input
        path (host prefetch thread + device double buffer) feeds a
        train loop and covers every row exactly once."""
        from ray_tpu import data
        from ray_tpu.train import get_device_batches

        import ray_tpu as rt

        rt.init(num_cpus=4, ignore_reinit_error=True)
        try:
            ds = data.range(96, parallelism=4)

            def loop(config):
                mesh = MeshSpec(fsdp=1).build(jax.devices()[:1])
                total, count = 0, 0
                for batch in get_device_batches(
                    "train",
                    mesh=mesh,
                    batch_size=32,
                    prefetch_batches=2,
                    buffer_size=2,
                ):
                    assert isinstance(batch["id"], jax.Array)
                    total += int(jnp.sum(batch["id"]))
                    count += int(batch["id"].shape[0])
                report({"total": total, "count": count})

            result = JaxTrainer(
                loop, train_loop_config={}, datasets={"train": ds}
            ).fit()
            assert result.error is None
            assert result.metrics["count"] == 96
            assert result.metrics["total"] == sum(range(96))
        finally:
            rt.shutdown()


class TestWorkerGroup:
    def test_gang_ranks(self):
        import ray_tpu as rt

        rt.init(num_cpus=4, ignore_reinit_error=True)
        try:
            from ray_tpu.train.worker_group import WorkerGroup

            group = WorkerGroup(num_workers=2)

            def whoami(tag):
                return tag

            outs = group.run_per_rank(
                whoami, lambda rank: (f"worker-{rank}",)
            )
            assert outs == ["worker-0", "worker-1"]

            def loop():
                from ray_tpu.train.session import get_context, report

                context = get_context()
                report({"rank": context.world_rank})
                return context.world_size

            results = group.run_train_loop(loop)
            assert [r["result"] for r in results] == [2, 2]
            assert results[0]["reported"] == [{"rank": 0}]
            assert results[1]["reported"] == [{"rank": 1}]
            group.shutdown()
        finally:
            rt.shutdown()


class TestMultiSlice:
    def test_two_slice_gang_hybrid_mesh_matches_single_slice(self):
        """review r3 item 2: a 2-worker gang (distinct processes,
        REAL jax.distributed rendezvous over a coordinator) where each
        worker models one 4-device slice. The flagship train step runs
        over the hybrid mesh (outer dcn_dp=2 over DCN, fsdp=4 inside
        each slice) and its losses must match the single-process flat
        fsdp=8 mesh — cross-slice pure-dp is mathematically invisible
        (reference analog: dp over the multi-node NCCL world,
        train/torch/config.py:66-116)."""
        import socket

        import ray_tpu as rt

        rt.init(num_cpus=4, ignore_reinit_error=True)
        try:
            from ray_tpu.train.backend import JaxBackend
            from ray_tpu.train.worker_group import WorkerGroup

            group = WorkerGroup(num_workers=2)

            # Stage 1 (before any jax import in the workers): each
            # worker becomes a virtual 4-device "slice".
            def setup_env():
                import os

                os.environ["XLA_FLAGS"] = (
                    "--xla_force_host_platform_device_count=4"
                )
                os.environ["JAX_PLATFORMS"] = "cpu"
                return os.getpid()

            pids = group.run_all(setup_env)
            assert pids[0] != pids[1], "gang must span processes"

            # Stage 2: one jax.distributed world across both slices.
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                port = s.getsockname()[1]
            JaxBackend().on_start(
                group,
                {
                    "coordinator_address": f"127.0.0.1:{port}",
                    "slices": 2,
                },
            )

            def train_two_steps():
                import os

                import jax

                from ray_tpu.models.llama import (
                    LlamaConfig,
                    init_params,
                    loss_fn,
                    param_annotations,
                )
                from ray_tpu.parallel.mesh import MeshSpec
                from ray_tpu.train.train_step import (
                    default_optimizer,
                    make_train_step,
                    shard_batch,
                )

                assert jax.device_count() == 8
                assert os.environ["RT_SLICE_ID"] in ("0", "1")
                cfg = LlamaConfig.tiny()
                mesh = MeshSpec(dcn_dp=2, fsdp=4).build()
                init_fn, step_fn = make_train_step(
                    lambda p, t, y: loss_fn(p, t, y, cfg),
                    default_optimizer(learning_rate=1e-2, total_steps=50),
                    mesh,
                    param_annotations(cfg),
                )
                state = init_fn(
                    jax.random.PRNGKey(0), lambda k: init_params(k, cfg)
                )
                toks = jax.random.randint(
                    jax.random.PRNGKey(1), (8, 33), 0, cfg.vocab_size
                )
                toks = shard_batch(
                    toks, mesh, logical_axes=("batch", None)
                )
                losses = []
                for _ in range(2):
                    state, metrics = step_fn(
                        state, toks[:, :-1], toks[:, 1:]
                    )
                    losses.append(float(metrics["loss"]))
                return losses

            gang_losses = group.run_all(train_two_steps)
            assert gang_losses[0] == pytest.approx(gang_losses[1])
            group.shutdown()

            # Single-process flat fsdp=8 reference on this process's
            # own 8 virtual devices: same seeds -> same math.
            cfg = _tiny_cfg()
            mesh = MeshSpec(fsdp=8).build()
            init_fn, step_fn = make_train_step(
                lambda p, t, y: loss_fn(p, t, y, cfg),
                default_optimizer(learning_rate=1e-2, total_steps=50),
                mesh,
                param_annotations(cfg),
            )
            state = init_fn(
                jax.random.PRNGKey(0), lambda k: init_params(k, cfg)
            )
            toks = jax.random.randint(
                jax.random.PRNGKey(1), (8, 33), 0, cfg.vocab_size
            )
            toks = shard_batch(toks, mesh, logical_axes=("batch", None))
            flat_losses = []
            for _ in range(2):
                state, metrics = step_fn(state, toks[:, :-1], toks[:, 1:])
                flat_losses.append(float(metrics["loss"]))
            assert gang_losses[0] == pytest.approx(
                flat_losses, abs=2e-3
            ), f"hybrid {gang_losses[0]} vs flat {flat_losses}"
        finally:
            rt.shutdown()
