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


def _fake_proc(root, pid, state, threads, fds=()):
    import os

    os.makedirs(f"{root}/{pid}/fd")
    fields = ["0"] * 50
    fields[0], fields[17] = state, str(threads)
    with open(f"{root}/{pid}/stat", "w") as f:
        f.write(f"{pid} (python3) " + " ".join(fields) + "\n")
    with open(f"{root}/{pid}/cmdline", "wb") as f:
        f.write(b"python3\0run.py\0" if state != "Z" else b"")
    with open(f"{root}/{pid}/comm", "w") as f:
        f.write("python3\n")
    for i, target in enumerate(fds):
        os.symlink(target, f"{root}/{pid}/fd/{i}")


@pytest.mark.parametrize(
    "chips,expected",
    [
        # the open file of chip 2, and the zombie whose threads live on
        ((2,), [(11, "Sl", "python3 run.py"), (13, "Zl", "[python3]")]),
        ((0, 1), [(13, "Zl", "[python3]")]),
        ((0, 1, 2, 3), [(11, "Sl", "python3 run.py"), (12, "S", "python3 run.py"),
                        (13, "Zl", "[python3]")]),
    ],
)
def test_foreign_holders_of_a_chip_are_its_open_files_and_zl(
    tmp_path, chips, expected
):
    """`chip_holders` (ISSUE 59): what a TPU spawn waits for on a host
    whose previous session left a worker behind."""
    import os

    from ray_tpu._private.accelerators.tpu import (
        chip_device_files,
        chip_holders,
    )

    dev, proc = str(tmp_path / "dev"), str(tmp_path / "proc")
    os.makedirs(f"{dev}/vfio")
    for group in ("7", "3", "12", "9", "vfio"):  # IOMMU groups, not chips
        open(f"{dev}/vfio/{group}", "w").close()
    assert chip_device_files((0, 3), dev) == {
        f"{dev}/vfio/3", f"{dev}/vfio/12"
    }
    _fake_proc(proc, 11, "S", 40, [f"{dev}/vfio/vfio", f"{dev}/vfio/9"])
    _fake_proc(proc, 12, "S", 1, [f"{dev}/vfio/12", "/dev/null"])
    _fake_proc(proc, 13, "Z", 2)     # files closed, threads in the kernel
    _fake_proc(proc, 14, "Z", 1)     # a plain zombie holds nothing
    _fake_proc(proc, 15, "S", 8, ["/dev/null"])
    _fake_proc(proc, 16, "S", 8, [f"{dev}/vfio/9"])  # one of ours
    got = chip_holders(chips, skip=[16], proc=proc, dev=dev)
    assert sorted(got) == expected
    # a host without the device files (CPU, fake chips): nobody
    assert chip_holders(chips, proc=proc, dev=str(tmp_path / "none")) == []


class _Leftover:
    """A killed worker whose threads take `lasts` seconds over the
    device's teardown (ForkedProc's view of a `Zl`)."""

    def __init__(self, pid, lasts):
        import time

        self.pid, self._gone_at = pid, time.monotonic() + lasts

    def poll(self):
        import time

        return 0 if time.monotonic() >= self._gone_at else None

    def state(self):
        return None if self.poll() == 0 else "Zl"


@pytest.mark.parametrize(
    "scoped,lasts,waits", [(True, 1.3, True), (False, 0.0, False)]
)
def test_shutdown_returns_when_a_chip_workers_threads_are_gone(
    capsys, scoped, lasts, waits
):
    import time
    import types

    from ray_tpu._private.daemon import NodeDaemon

    proc = _Leftover(4242, lasts)
    daemon = types.SimpleNamespace(
        _chip_procs=[(proc, (0, 1, 2, 3))] if scoped else [],
        _CHIP_WAIT_S=NodeDaemon._CHIP_WAIT_S,
    )
    t0 = time.monotonic()
    NodeDaemon._await_killed(daemon, [proc])
    assert (time.monotonic() - t0 >= lasts) and proc.poll() == 0
    err = capsys.readouterr().err
    assert ("pid 4242 (Zl, held chips)" in err) == waits


def test_a_tpu_worker_waits_for_another_sessions_holder_at_tpu_init(
    monkeypatch, capsys
):
    """A worker scoped to chips waits for what another session left
    on them when ITS JAX initialises the TPU backend, not before (its
    own start runs beside the leftover's teardown), for a bounded
    time, and standard error says what was waited for."""
    from jax._src import xla_bridge

    from ray_tpu._private.accelerators import tpu

    holders = [(99, "Zl", "[python3]")]
    calls = []

    def fake_holders(chips, **where):
        calls.append("holders")
        if calls.count("holders") >= 3:
            holders.clear()
        return list(holders)

    monkeypatch.setattr(tpu, "chip_holders", fake_holders)
    monkeypatch.setattr(
        xla_bridge, "_init_backend",
        lambda platform: calls.append(platform) or platform,
    )
    before = xla_bridge._init_backend
    tpu.wait_for_chips_at_tpu_init((0, 1, 2, 3))  # a host without chips
    assert xla_bridge._init_backend is before
    monkeypatch.setattr(tpu, "chip_device_files", lambda chips: {"/dev/x"})
    tpu.wait_for_chips_at_tpu_init((0, 1, 2, 3))
    assert calls == []  # nothing waited for at the worker's start
    assert xla_bridge._init_backend("cpu") == "cpu" and calls == ["cpu"]
    assert xla_bridge._init_backend("tpu") == "tpu"
    assert calls == ["cpu", "holders", "holders", "holders", "tpu"]
    assert capsys.readouterr().err == ""  # under a second: no line
    # still held at the deadline: the line, and JAX finds out itself
    holders.append((99, "Zl", "[python3]"))
    calls[:] = [None] * 3
    monkeypatch.setattr(tpu, "chip_holders", lambda chips, **w: holders)
    assert tpu.await_chip_holders((0,), timeout=0.25) >= 0.25
    err = capsys.readouterr().err
    assert "pid 99 (Zl) [python3]" in err and "still held" in err


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
