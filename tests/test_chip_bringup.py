"""Bring-up contracts that must hold before anything touches a chip:
where the compile cache lives, which chips a worker process is scoped
to, who asks for `TPU` resources, and that the flash kernel never
quietly becomes the reference. Pure (no runtime, no compile) — the
chip itself is `chip_smoke.py`'s job."""

import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- compile cache -----------------------------------------------------

def test_cache_dir_from_env_is_untouched(monkeypatch):
    from ray_tpu._private import compile_cache

    monkeypatch.setenv(compile_cache.ENV_VAR, "/x")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.ensure_compile_cache() == "/x"
    assert os.environ[compile_cache.ENV_VAR] == "/x"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_default_is_fixed_across_calls_and_processes():
    # In children: the unset case configures jax in the calling
    # process, which must not leak a persistent cache into this suite.
    code = (
        "import os\n"
        "from ray_tpu._private.compile_cache import ensure_compile_cache\n"
        "a = ensure_compile_cache(); b = ensure_compile_cache()\n"
        "assert a == b == os.environ['JAX_COMPILATION_CACHE_DIR']\n"
        "print(a)"
    )
    env = {
        k: v for k, v in os.environ.items()
        if k != "JAX_COMPILATION_CACHE_DIR"
    }
    env["PYTHONPATH"] = REPO
    paths = [
        subprocess.run(
            [sys.executable, "-c", code], env=env, cwd=cwd,
            capture_output=True, text=True, check=True, timeout=60,
        ).stdout.strip()
        for cwd in (REPO, "/")
    ]
    assert paths[0] == paths[1] == os.path.join(REPO, ".jax_cache")


# -- chip scoping ------------------------------------------------------

def _worker_env(chips, chips_on_node=4):
    from ray_tpu._private.daemon import NodeDaemon

    daemon = types.SimpleNamespace(
        socket_path="/tmp/sock", resources={"TPU": float(chips_on_node)}
    )
    return NodeDaemon._worker_env(daemon, chips)


def test_two_tpu_leases_are_scoped_to_different_chips(monkeypatch):
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    a, b = _worker_env((0,)), _worker_env((1,))
    assert (a["TPU_VISIBLE_CHIPS"], b["TPU_VISIBLE_CHIPS"]) == ("0", "1")
    for env in (a, b):
        assert env["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"
        assert env["TPU_HOST_BOUNDS"] == "1,1,1"
        assert env["JAX_PLATFORMS"] == "tpu,cpu"
    pair = _worker_env((2, 3))
    assert pair["TPU_VISIBLE_CHIPS"] == "2,3"
    assert pair["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,2,1"
    # Every chip on the node: libtpu's own description of the host.
    whole = _worker_env((0, 1, 2, 3))
    assert "TPU_VISIBLE_CHIPS" not in whole
    assert whole["TPU_CHIPS_PER_HOST_BOUNDS"] == "2,2,1"
    assert whole["RT_WORKER_CHIPS"] == "0,1,2,3"
    # A CPU worker never sees a chip.
    cpu = _worker_env(())
    assert cpu["TPU_VISIBLE_CHIPS"] == "" and cpu["JAX_PLATFORMS"] == "cpu"
    with pytest.raises(ValueError):
        _worker_env((0, 1, 2))


def test_pick_chips_takes_aligned_free_blocks():
    from ray_tpu._private.accelerators.tpu import pick_chips

    assert pick_chips({0, 1, 2, 3}, 1, 4) == (0,)
    assert pick_chips({1, 2, 3}, 1, 4) == (1,)
    # Chips 1-2 are idle but are not neighbours on the host grid.
    assert pick_chips({1, 2, 3}, 2, 4) == (2, 3)
    assert pick_chips({1, 2}, 2, 4) is None
    assert pick_chips({0, 1, 2, 3}, 4, 4) == (0, 1, 2, 3)
    assert pick_chips({0, 1, 2}, 4, 4) is None


# -- who asks for TPU --------------------------------------------------

@pytest.mark.parametrize("chips,options", [(4, {"num_tpus": 1}), (0, {})])
def test_llm_replicas_lease_a_chip_when_the_cluster_has_one(
    monkeypatch, chips, options
):
    from ray_tpu.llm import build_llm_app
    from ray_tpu.util.accelerators import tpu

    monkeypatch.setattr(tpu, "cluster_tpu_chips", lambda: chips)
    app = build_llm_app({"m": {"kind": "init", "config": {}}})
    assert app.deployment.ray_actor_options == options


@pytest.mark.parametrize(
    "chips,workers,explicit,expected",
    [(4, 1, None, 4), (8, 2, None, 4), (0, 2, None, 0),
     (4, 1, {"TPU": 2}, 2)],
)
def test_train_workers_lease_their_share_of_the_chips(
    monkeypatch, chips, workers, explicit, expected
):
    from ray_tpu.train import worker_group

    asked = {}

    def fake_remote(**options):
        asked.update(options)
        return lambda cls: types.SimpleNamespace(remote=lambda *a: None)

    monkeypatch.setattr(worker_group, "cluster_tpu_chips", lambda: chips)
    monkeypatch.setattr(
        worker_group, "rt", types.SimpleNamespace(remote=fake_remote)
    )
    worker_group.WorkerGroup(workers, explicit)
    assert asked["num_tpus"] == expected


@pytest.mark.parametrize(
    "chips,worker_chips,where",
    [(0, "", "local"), (4, "", "gang"), (4, "0,1,2,3", "local")],
)
def test_single_worker_fit_runs_where_the_chips_are(
    monkeypatch, chips, worker_chips, where
):
    """No chips advertised: in process, as ever. Chips advertised: in
    a gang worker that leases them — unless this process already is
    the worker that holds them."""
    from ray_tpu.train import JaxTrainer, trainer

    monkeypatch.setattr(trainer, "cluster_tpu_chips", lambda: chips)
    monkeypatch.setenv("RT_WORKER_CHIPS", worker_chips)
    monkeypatch.setattr(
        JaxTrainer, "_fit_local", lambda self, *a: "local"
    )
    monkeypatch.setattr(JaxTrainer, "_fit_gang", lambda self, *a: "gang")
    assert JaxTrainer(lambda: None)._fit_once("n", "/tmp") == where


# -- no silent reference -----------------------------------------------

@pytest.mark.parametrize("backend,force", [("cpu", True), ("tpu", False)])
def test_flash_attention_never_returns_the_reference(
    monkeypatch, backend, force
):
    """Forced off-TPU, or by default on a TPU, the op IS the Pallas
    kernel (traced only here: no interpreter run, no compile)."""
    from ray_tpu.ops import attention

    def no_reference(*a, **k):
        raise AssertionError("fell back to mha_reference")

    monkeypatch.setattr(attention, "mha_reference", no_reference)
    monkeypatch.setattr(attention.jax, "default_backend", lambda: backend)
    q = jnp.zeros((1, 2, 128, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda q: attention.flash_attention(q, q, q, force_pallas=force)
    )(q)
    assert "pallas_call" in str(jaxpr)
