"""Train cells: `JaxTrainer.fit` -> the benchmark's own loop in the
gang worker that leases the chips -> `make_train_step`, fed by the
runtime's data plane (`JaxTrainer(datasets=..)` ->
`session.get_device_batches`).

The driver process never touches JAX. The loop (in the worker) makes
the weights on the device in one jitted call from the seed, checks one
seeded sequence against `benchmark/reference` (loss and last-position
logits), warms the one step shape, then measures whole steps for
`seconds`: step k+1 is dispatched before step k's loss is awaited, so
the device always has a program queued, and every step's completion
is stamped on the host clock.
"""

from __future__ import annotations

import os
import time

from ..harness import BenchmarkError, check
from ..stats import median


def train_loop(spec: dict) -> None:
    """Runs in the worker that holds the chips."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu._private import compile_watch, step_telemetry
    from ray_tpu.models.llama import (
        LlamaConfig, forward, init_params, loss_fn, masked_xent,
        param_annotations,
    )
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train import get_device_batches, report
    from ray_tpu.train.train_step import (
        default_optimizer, make_train_step, shard_batch,
    )

    from benchmark import harness
    from benchmark.reference import compare
    from benchmark.trace import xplane

    devices = jax.devices()
    device = harness.describe(devices)
    if not spec["rehearse"] and device["platform"] != "tpu":
        raise RuntimeError(f"no TPU: JAX reports {device}")
    if device["count"] != spec["chips"]:
        raise RuntimeError(
            f"cell needs {spec['chips']} chip(s), JAX sees {device}"
        )

    model = spec["model"]
    trainer = spec["trainer"]
    cfg = LlamaConfig(
        **model, dtype=jnp.dtype(spec["dtype"]),
        attention=trainer["attention"], remat_policy=trainer["remat_policy"],
    )
    mesh = MeshSpec(**trainer["mesh"]).build(devices)
    init_fn, step_fn = make_train_step(
        lambda p, t, y: loss_fn(p, t, y, cfg, mesh=mesh),
        default_optimizer(**trainer["optimizer"]),
        mesh,
        param_annotations(cfg),
    )
    state = jax.block_until_ready(
        init_fn(jax.random.PRNGKey(spec["seed"]), lambda k: init_params(k, cfg))
    )

    # -- correctness: one seeded sequence against the plain reference,
    # through the same sharded forward + loss the step differentiates.
    batch, seq = spec["batch"], spec["seq_len"]
    probe = np.asarray(spec["probe_tokens"], np.int32)
    rows = shard_batch(
        jnp.asarray(np.tile(probe[None], (batch, 1))), mesh,
        logical_axes=("batch", None),
    )

    @jax.jit
    def program(params, tokens, targets):
        logits = forward(params, tokens, cfg, mesh=mesh)
        nll, count = masked_xent(logits, targets)
        return nll / count, logits[0, -1]

    got_loss, got_last = jax.block_until_ready(
        program(state.params, rows[:, :-1], rows[:, 1:])
    )
    on_one = jax.tree.map(
        lambda x: jax.device_put(x, devices[0]), state.params
    ) if len(devices) > 1 else state.params
    reference = compare.load(spec.get("reference"))
    want = reference.forward(on_one, jnp.asarray(probe[:-1]), model)
    want_loss = float(compare.mean_xent(want, jnp.asarray(probe[1:])))
    logit_err = compare.relative_rms_error(got_last, want[-1])
    loss_err = abs(float(got_loss) - want_loss)
    del want, on_one, rows

    # -- warm the one step shape through the real input path
    batches = get_device_batches(
        "train", mesh=mesh, batch_size=batch, drop_last=True,
        prefetch_batches=2, buffer_size=2, logical_axes=("batch", None),
    )

    def next_step(state):
        tokens = next(batches)["tokens"]
        return step_fn(state, tokens[:, :-1], tokens[:, 1:])

    losses = []
    for _ in range(spec["warmup_steps"]):
        state, metrics = next_step(state)
        losses.append(float(metrics["loss"]))

    def compiles() -> int:
        return sum(r["compiles"] for r in compile_watch.snapshot().values())

    compiles_before = compiles()
    step_telemetry.take_phases()

    # -- the window
    trace_dir = spec.get("trace_dir")
    trace_from, trace_steps = 3, int(spec["trace_steps"])
    steps = []  # [completed_at_s, wall_ms, data_wait_ms, h2d_ms]
    window_start_epoch = time.time()
    t0 = last = time.perf_counter()
    state, pending = next_step(state)
    dispatched = 1
    tracing = False
    while True:
        if trace_dir and dispatched == trace_from and not tracing:
            jax.block_until_ready(pending)
            jax.profiler.start_trace(trace_dir)
            tracing = True
        state, metrics = next_step(state)
        dispatched += 1
        jax.block_until_ready(pending["loss"])
        now = time.perf_counter()
        phases = step_telemetry.take_phases()
        steps.append([
            now - t0, (now - last) * 1e3,
            phases.get("data_wait_ms", 0.0), phases.get("h2d_ms", 0.0),
        ])
        last = now
        pending = metrics
        if tracing and dispatched == trace_from + trace_steps:
            jax.block_until_ready(pending)
            jax.profiler.stop_trace()
            tracing = False
        if now - t0 >= spec["seconds"]:
            break
    jax.block_until_ready((state, pending))
    if tracing:
        jax.profiler.stop_trace()
    losses.append(float(pending["loss"]))
    steady_compiles = compiles() - compiles_before

    trace = None
    if trace_dir and device["platform"] != "cpu":
        trace = xplane.summarize(xplane.read(xplane.find_xplane(trace_dir)))
    report({
        "device": device,
        "reference": reference.__name__,
        "logit_err": logit_err,
        "loss_err": loss_err,
        "probe_loss": float(got_loss),
        "losses": losses,
        "finite": bool(np.all(np.isfinite(losses))),
        "steps": steps,
        "steady_compiles": steady_compiles,
        "window_start_epoch": window_start_epoch,
        "memory_peak_bytes": harness.peak_bytes(devices),
        "trace": trace,
        "pid": os.getpid(),
    })


def run(ctx: dict) -> dict:
    """ctx: cell, config, traffic, generator, seed, seconds, trace,
    rehearse, started_epoch, scratch. Returns the run record."""
    import numpy as np

    import ray_tpu as rt
    from ray_tpu import data
    from ray_tpu.train import JaxTrainer, ScalingConfig

    config, traffic, chips = ctx["config"], ctx["traffic"], ctx["cell"]["chips"]
    model = config["model"]
    stream = ctx["generator"].generate(
        traffic, ctx["seed"], chips, model["vocab_size"]
    )
    probe = np.random.default_rng([ctx["seed"], 0xC0DE]).integers(
        0, model["vocab_size"], size=stream["seq_len"] + 1
    )
    spec = {
        "model": model, "dtype": config["dtype"],
        "reference": config.get("reference"),
        "trainer": config["trainer"],
        "chips": chips, "seed": ctx["seed"], "seconds": ctx["seconds"],
        "batch": stream["batch"], "seq_len": stream["seq_len"],
        "warmup_steps": int(traffic["warmup_steps"]),
        "trace_steps": int(traffic.get("trace_steps", 4)),
        "probe_tokens": probe.tolist(),
        "rehearse": ctx["rehearse"],
        "trace_dir": (
            os.path.join(ctx["scratch"], "trace") if ctx["trace"] else None
        ),
    }
    rt.init(num_tpus=chips if ctx["rehearse"] else None)
    try:
        have = int(rt.cluster_resources().get("TPU", 0))
        if have < chips:
            raise BenchmarkError(
                f"cell needs {chips} chip(s); the runtime found {have}"
            )
        result = JaxTrainer(
            train_loop,
            train_loop_config=spec,
            datasets={"train": data.from_numpy({"tokens": stream["tokens"]})},
            # one gang worker that leases exactly the cell's chips
            scaling_config=ScalingConfig(
                num_workers=1, resources_per_worker={"TPU": chips}
            ),
        ).fit()
    finally:
        rt.shutdown()
    if result.error is not None:
        raise BenchmarkError(f"JaxTrainer.fit failed: {result.error!r}")
    m = result.metrics
    if m["pid"] == os.getpid():
        raise BenchmarkError("the train loop ran in the driver process")
    steps = m["steps"]
    tolerance = config["tolerance"]
    checks = [
        check("logits_rel_rms", m["logit_err"], tolerance["logits_rel_rms"]),
        check("loss_abs", m["loss_err"], tolerance["loss_abs"]),
        check("finite", m["finite"], 1, at_least=True),
        check("steady_compiles", m["steady_compiles"], 0),
    ]
    return {
        "kind": "train",
        "device": dict(m["device"], memory_peak_bytes=m["memory_peak_bytes"]),
        "correct": all(c["ok"] for c in checks),
        "checks": checks,
        "attempted": len(steps),
        "failed": 0 if m["finite"] else len(steps),
        "setup_s": m["window_start_epoch"] - ctx["started_epoch"],
        "steps": steps,
        "tokens_per_step": stream["batch"] * stream["seq_len"],
        "seq_len": stream["seq_len"],
        "trace": m["trace"],
        "notes": dict(
            {k: m[k] for k in (
                "reference", "logit_err", "loss_err", "probe_loss", "losses",
                "steady_compiles",
            )},
            # a stall shows here: the rate is over all the window's time
            step_ms_median=median([s[1] for s in steps]),
            step_ms_max=max(s[1] for s in steps),
        ),
    }
