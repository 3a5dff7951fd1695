"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

Drives the two main paths once, through the entry points a user calls,
at the full width of `LlamaConfig.bench_410m` in bf16 with random
weights from a seed:

  device  platform, device kind and count as JAX reports them; a
          platform other than `tpu` is a failure, not a downgrade.
  kernel  `flash_attention` forward and grad against `mha_reference`,
          plain and under a window, and the serve step's `paged_attn` over ragged rows of a block
          pool against dense attention, at one GQA and one MHA
          geometry, compiled by Mosaic (never interpreted on a chip);
          one rank's share of an expert layer at `trinity-mini-ep8`'s
          widths, its budget of rows against every pick, output and
          input-gradient, at the even load and at one over the budget.
  train   `JaxTrainer.fit` -> `make_train_step`, batch 8 x seq 2048,
          mesh over every chip; loss finite and falling, zero
          steady-state compiles. On several chips: `fsdp=n`, then
          `dp x tp`, with the flash kernel's per-device operands
          checked against the shard.
  serve   `serve.run(build_llm_app(...))` -> HTTP proxy -> router ->
          replica -> `InferenceEngine`: concurrent streaming requests,
          greedy determinism, a prefix-cache hit, and the replica
          reporting `tpu` from inside its own process (two one-chip
          replicas on two distinct chips when there are several).

Who owns the chip: one process at a time. This process is the driver
and never initialises a JAX backend. The device and kernel phases run
in a child that exits; the trainer runs in a gang worker that leases
every chip and is gone when `fit` returns; each serve replica leases
one chip. Every wait is bounded and any failed phase exits non-zero.

    python chip_smoke.py             # on the chip (fails without one)
    python chip_smoke.py --rehearse  # CPU walk-through at tiny sizes;
                                     # says platform=cpu on every line

Step time, TTFT and tokens/s are printed as information, labelled
with the device; nothing here is a benchmark.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# What the chip run uses, and the cut the CPU rehearsal uses to walk
# the same code in seconds (four virtual devices stand in for a
# four-chip host so the sharded paths are rehearsed too).
REAL = {
    # (queries, keys, causal, window): the last two under a window, one
    # equal to a forward block and one that cuts blocks of a sequence
    # that is no block multiple
    "kernel_shapes": [
        (2048, 2048, True, 0), (512, 2048, False, 0),
        (4096, 4096, True, 1024), (2304, 2304, True, 600),
    ],
    "model": dict(
        vocab_size=32000, dim=1024, n_layers=24, n_heads=8,
        n_kv_heads=8, intermediate=2816, max_seq_len=2048,
    ),
    "batch": 8, "seq": 2048, "warmup": 3, "steps": 5,
    "engine": dict(
        slots=8, max_len=2048, prefill_chunk=128, max_new_tokens=32
    ),
    "prompt_lens": [64, 128, 200, 320, 512, 640, 800, 1024],
    "new_tokens": 32,
}
REHEARSAL = {
    "kernel_shapes": [
        (256, 256, True, 0), (128, 256, False, 0), (256, 256, True, 96),
    ],
    "model": dict(
        vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
        intermediate=128, max_seq_len=64,
    ),
    "batch": 8, "seq": 64, "warmup": 3, "steps": 5,
    "engine": dict(
        slots=8, max_len=256, prefill_chunk=16, max_new_tokens=8
    ),
    "prompt_lens": [8, 16, 24, 40, 64, 80, 100, 128],
    "new_tokens": 8,
}
REHEARSAL_DEVICES = 4

PHASE_BUDGET_S = {"kernel": 480, "train": 420, "serve": 480}
_LABEL = "platform=? "


class SmokeFailure(RuntimeError):
    """A phase did not establish what it must."""


def say(phase: str, message: str) -> None:
    print(f"[chip_smoke] {_LABEL}| {phase}: {message}", flush=True)


def require(condition: bool, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


class phase_deadline:
    """Bound one phase: SIGALRM raises in the main thread, which is
    where every wait of this script blocks."""

    def __init__(self, name: str):
        self.name = name

    def _on_alarm(self, signum, frame):
        raise TimeoutError(
            f"{self.name} phase exceeded {PHASE_BUDGET_S[self.name]}s"
        )

    def __enter__(self):
        self._old = signal.signal(signal.SIGALRM, self._on_alarm)
        signal.alarm(PHASE_BUDGET_S[self.name])

    def __exit__(self, *exc):
        signal.alarm(0)
        signal.signal(signal.SIGALRM, self._old)


def count_cache_hits():
    """Counter of persistent-compilation-cache hits in this process."""
    import jax

    hits = [0]

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            hits[0] += 1

    jax.monitoring.register_event_listener(on_event)
    return hits


# ---------------------------------------------------------------------
# device + kernel: a child process that owns the chip and exits
# ---------------------------------------------------------------------

def device_and_kernel_phase(rehearse: bool) -> dict:
    """Runs in the child (`--phase kernel`); prints one JSON line."""
    import importlib.metadata

    import jax
    import jax.numpy as jnp
    import jaxlib

    hits = count_cache_hits()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu" and not rehearse:
        raise SmokeFailure(
            f"JAX found no TPU (platform={device['platform']!r}); "
            "pass --rehearse for the CPU walk-through"
        )
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "absent"
    out = {
        "device": device,
        "versions": {
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu,
        },
        "kernel": [],
    }

    from ray_tpu.ops import flash_attention, mha_reference

    def diff(a, b):
        return float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)
        )))

    sizes = REHEARSAL if rehearse else REAL
    for t_q, t_k, causal, window in sizes["kernel_shapes"]:
        q = 0.5 * jax.random.normal(
            jax.random.PRNGKey(1), (1, 2, t_q, 128), jnp.bfloat16
        )
        kv = 0.5 * jax.random.normal(
            jax.random.PRNGKey(2), (1, 2, t_k, 128), jnp.bfloat16
        )

        def flash(a, b):
            # force_pallas: the rehearsal runs the same kernel in the
            # interpreter; on a chip the flag changes nothing.
            return flash_attention(
                a, b, b, causal=causal, force_pallas=True, window=window
            )

        def reference(a, b):
            return mha_reference(a, b, b, causal=causal, window=window)

        def loss_of(fn):
            return lambda a, b: jnp.sum(
                fn(a, b).astype(jnp.float32) * 0.01
            )

        t0 = time.perf_counter()
        forward = jax.jit(flash)
        mosaic = "tpu_custom_call" in forward.lower(q, kv).as_text()
        fwd_err = diff(forward(q, kv), jax.jit(reference)(q, kv))
        grads = jax.jit(jax.grad(loss_of(flash), argnums=(0, 1)))(q, kv)
        ref_grads = jax.jit(
            jax.grad(loss_of(reference), argnums=(0, 1))
        )(q, kv)
        grad_err = max(diff(a, b) for a, b in zip(grads, ref_grads))
        row = {
            "shape": f"{t_q}/{t_k} causal={causal}" + (
                f" window={window}" if window else ""
            ),
            "mosaic": mosaic,
            "fwd_err": fwd_err,
            "grad_err": grad_err,
            "wall_s": round(time.perf_counter() - t0, 2),
        }
        out["kernel"].append(row)
        require(
            mosaic or device["platform"] != "tpu",
            f"{row['shape']}: kernel was not lowered to a Mosaic "
            "custom call on a TPU",
        )
        require(fwd_err < 0.05, f"{row['shape']}: fwd err {fwd_err}")
        require(grad_err < 0.01, f"{row['shape']}: grad err {grad_err}")
    out["paged"] = [
        paged_attn_row(heads, kv_heads, rehearse, device)
        for heads, kv_heads in ((16, 2), (8, 8))
    ]
    out["experts"] = expert_layer_rows(rehearse)
    out["cache_hits"] = hits[0]
    return out


def expert_layer_rows(rehearse: bool) -> list:
    """One rank's share of an expert layer (ops/moe.py
    `moe_ffn_dropless` under a router wider than the experts held) at
    `trinity-mini-ep8`'s widths: the layer that computes its budget of
    rows against the one that computes every pick, output and gradient
    to the input, at the even load and at a load over the budget
    (where the layer takes every row). The rows behind the last group
    are garbage on the chip and zeros on the CPU, so this is where a
    read of one shows."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.moe import held_row_budget, moe_ffn_dropless

    t, k, d, f, held, over = (
        (192, 4, 32, 64, 4, 16) if rehearse else (8192, 8, 2048, 1024, 16, 128)
    )
    budget = held_row_budget(t * k, held, over)
    require(budget < t * k, f"expert layer: no budget under {t * k} picks")
    keys = jax.random.split(jax.random.PRNGKey(57), 5)
    params = {
        name: (jax.random.normal(key, shape) / shape[1] ** 0.5).astype(
            jnp.bfloat16
        )
        for name, key, shape in (
            ("w_gate", keys[0], (held, d, f)), ("w_up", keys[1], (held, d, f)),
            ("w_down", keys[2], (held, f, d)),
        )
    }
    x = jax.random.normal(keys[3], (t, d), jnp.bfloat16)
    scores = jax.random.normal(keys[4], (t, over))

    def both(routed_over):
        def loss(x, gates, experts):
            out, _, counts = moe_ffn_dropless(
                params, x, k=k, routed=(gates, experts),
                routed_over=routed_over,
            )
            return jnp.sum(out.astype(jnp.float32) * 0.01), (out, counts)

        return jax.jit(jax.value_and_grad(loss, has_aux=True))

    compacted, all_rows = both(over), both(0)

    def far(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    rows = []
    for load, lift in (("even", 0.0), ("over_budget", 2.5)):
        t0 = time.perf_counter()
        lifted = scores.at[:, :held].add(lift)
        gates, experts = jax.lax.top_k(jax.nn.sigmoid(lifted), k)
        (_, (got, counts)), got_dx = compacted(x, gates, experts)
        (_, (want, _)), want_dx = all_rows(x, gates, experts)
        picks = int(counts.sum())
        row = {
            "load": load, "held_picks": picks, "budget": budget,
            "picks": t * k, "out_err": far(got, want),
            "dx_err": far(got_dx, want_dx),
            "finite": bool(
                jnp.isfinite(got.astype(jnp.float32)).all()
                & jnp.isfinite(got_dx.astype(jnp.float32)).all()
            ),
            "wall_s": round(time.perf_counter() - t0, 2),
        }
        rows.append(row)
        require(
            (picks > budget) == (load == "over_budget") and picks > 0,
            f"expert layer {load}: {picks} held picks, budget {budget}",
        )
        require(row["finite"], f"expert layer {load}: not finite")
        # bf16 rounds to 2^-8; the two sum in float32 in another order
        require(row["out_err"] < 0.02, f"expert layer {load}: {row}")
        require(row["dx_err"] < 0.03, f"expert layer {load}: {row}")
    return rows


def paged_attn_row(heads: int, kv_heads: int, rehearse: bool, device) -> dict:
    """The serve step's attention kernel (ops/paged_attention.py) over
    ragged rows of a block pool, one dead, against dense float32
    attention over each row's own keys."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import generate as g

    rows, block, lanes = (4, 16, 128)
    width = 8 if rehearse else 64
    lengths = np.linspace(1, width * block, rows).astype(np.int32)
    alive = np.arange(rows) != 1
    n_blocks = rows * width + 1
    rng = np.random.default_rng(heads)
    tables = 1 + rng.permutation(rows * width).astype(np.int32).reshape(
        rows, width
    )
    keys = jax.random.split(jax.random.PRNGKey(heads), 3)
    q = 0.5 * jax.random.normal(keys[0], (rows, heads, 1, lanes), jnp.bfloat16)
    k_pool, v_pool = (
        0.5 * jax.random.normal(
            key, (2, n_blocks, kv_heads, block, lanes), jnp.bfloat16
        ) for key in keys[1:]
    )

    def kernel(q, k_pool, v_pool):
        plan = g._paged_plan(
            jnp.asarray(tables), jnp.asarray(lengths)[:, None] - 1,
            jnp.asarray(lengths), jnp.asarray(alive), n_blocks, block,
            heads // kv_heads, in_place=True,
        )
        return g._attend_pages(q, k_pool, v_pool, 1, plan)

    t0 = time.perf_counter()
    forward = jax.jit(kernel)
    mosaic = "tpu_custom_call" in forward.lower(q, k_pool, v_pool).as_text()
    got = np.asarray(forward(q, k_pool, v_pool))
    err = 0.0

    def f32(a):
        return np.asarray(a, np.float32)

    for row in np.flatnonzero(alive):
        n = int(lengths[row])
        k, v = (
            f32(pool[1])[tables[row]].transpose(1, 0, 2, 3)
            .reshape(kv_heads, -1, lanes)[:, :n] for pool in (k_pool, v_pool)
        )
        kv_of = np.arange(heads) // (heads // kv_heads)
        s = np.einsum("hd,hkd->hk", f32(q)[row, :, 0], k[kv_of]) / lanes ** 0.5
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        want = np.einsum("hk,hkd->hd", p / p.sum(-1, keepdims=True), v[kv_of])
        err = max(err, float(np.abs(got[row, :, 0] - want).max()))
    shape = f"{heads}/{kv_heads} heads x {width * block} keys"
    require(
        mosaic or device["platform"] != "tpu",
        f"paged_attn {shape}: not lowered to a Mosaic custom call on a TPU",
    )
    require(err < 0.02, f"paged_attn {shape}: err {err}")
    require(
        bool((got[~alive] == 0).all()), f"paged_attn {shape}: a dead row's output"
    )
    return {
        "shape": shape, "mosaic": mosaic, "err": err,
        "wall_s": round(time.perf_counter() - t0, 2),
    }


def run_kernel_child(rehearse: bool) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", "kernel"]
    if rehearse:
        cmd.append("--rehearse")
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True,
            timeout=PHASE_BUDGET_S["kernel"], cwd=HERE,
        )
    except subprocess.TimeoutExpired as e:
        raise SmokeFailure(
            f"device/kernel child exceeded {e.timeout}s"
        ) from None
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-4000:])
        raise SmokeFailure(
            f"device/kernel child exited {proc.returncode}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------
# train: runs inside the JaxTrainer's gang worker
# ---------------------------------------------------------------------

def train_loop(config: dict) -> None:
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import compile_watch
    from ray_tpu.models.llama import (
        LlamaConfig, init_params, loss_fn, param_annotations,
    )
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train import report
    from ray_tpu.train.train_step import (
        default_optimizer, make_train_step, shard_batch,
    )

    hits = count_cache_hits()
    devices = jax.devices()
    cfg = LlamaConfig(
        **config["model"], dtype=jnp.bfloat16, attention="flash",
        remat_policy="dots_flash",
    )
    mesh = MeshSpec(**config["mesh"]).build(devices)
    init_fn, step_fn = make_train_step(
        lambda p, t, y: loss_fn(p, t, y, cfg, mesh=mesh),
        default_optimizer(
            learning_rate=1e-3, warmup_steps=2, total_steps=50
        ),
        mesh,
        param_annotations(cfg),
    )
    t0 = time.perf_counter()
    state = jax.block_until_ready(
        init_fn(jax.random.PRNGKey(0), lambda k: init_params(k, cfg))
    )
    init_s = time.perf_counter() - t0
    leaves = jax.tree.leaves(state.params)
    param_bytes = sum(leaf.nbytes for leaf in leaves)
    param_bytes_device0 = sum(
        leaf.addressable_shards[0].data.nbytes for leaf in leaves
    )

    batch, seq = config["batch"], config["seq"]
    tokens = shard_batch(
        jax.random.randint(
            jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size
        ),
        mesh, logical_axes=("batch", None),
    )
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    def unregistered() -> int:
        row = compile_watch.snapshot().get("(unregistered)", {})
        return row.get("compiles", 0)

    losses = []
    t0 = time.perf_counter()
    state, metrics = step_fn(state, inputs, targets)
    losses.append(float(metrics["loss"]))
    first_step_s = time.perf_counter() - t0
    for _ in range(config["warmup"] - 1):
        state, metrics = step_fn(state, inputs, targets)
        losses.append(float(metrics["loss"]))
    warm = step_fn.stats()["compiles"], unregistered()
    t0 = time.perf_counter()
    for _ in range(config["steps"]):
        state, metrics = step_fn(state, inputs, targets)
    jax.block_until_ready((state, metrics))
    step_ms = (time.perf_counter() - t0) / config["steps"] * 1e3
    losses.append(float(metrics["loss"]))
    steady = (
        step_fn.stats()["compiles"] - warm[0], unregistered() - warm[1]
    )

    # What each device's flash kernel was handed, from the compiled
    # (post-partitioning) program: the leading dim of its bf16
    # [batch*heads, seq, head_dim] operands.
    kernel_rows = None
    if devices[0].platform == "tpu":
        text = step_fn.wrapped.lower(
            state, inputs, targets
        ).compile().as_text()
        kernel_rows = sorted({
            int(m.group(1))
            for line in text.splitlines()
            if "tpu_custom_call" in line
            for m in re.finditer(
                rf"bf16\[(\d+),{seq},{cfg.head_dim}\]", line
            )
        })
    memory = devices[0].memory_stats() or {}
    report({
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "chips": os.environ.get("RT_WORKER_CHIPS", ""),
        "pid": os.getpid(),
        "losses": losses,
        "init_s": init_s,
        "first_step_s": first_step_s,
        "step_ms": step_ms,
        "steady_compiles": steady[0],
        "steady_unregistered_compiles": steady[1],
        "param_bytes": param_bytes,
        "param_bytes_device0": param_bytes_device0,
        "kernel_rows": kernel_rows,
        "peak_bytes_in_use": memory.get("peak_bytes_in_use"),
        "cache_hits": hits[0],
    })


def train_phase(sizes: dict, device: dict, mesh_axes: dict) -> None:
    from ray_tpu.train import JaxTrainer, ScalingConfig

    layout = "x".join(f"{k}{v}" for k, v in mesh_axes.items())
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={
            "model": sizes["model"], "mesh": mesh_axes,
            "batch": sizes["batch"], "seq": sizes["seq"],
            "warmup": sizes["warmup"], "steps": sizes["steps"],
        },
        scaling_config=ScalingConfig(num_workers=1),
    )
    result = trainer.fit()
    if result.error is not None:
        raise SmokeFailure(
            f"JaxTrainer.fit({layout}) failed: {result.error!r}"
        ) from result.error
    m = result.metrics
    say(
        f"train[{layout}]",
        f"worker pid={m['pid']} reports platform={m['platform']} "
        f"kind={m['device_kind']!r} devices={m['device_count']} "
        f"chips=[{m['chips']}]",
    )
    require(m["pid"] != os.getpid(), "train loop ran in the driver")
    require(
        (m["platform"], m["device_count"])
        == (device["platform"], device["count"]),
        f"trainer worker saw {m['platform']} x{m['device_count']}, "
        f"the device phase saw {device}",
    )
    losses = m["losses"]
    require(
        all(x == x and abs(x) != float("inf") for x in losses),
        f"non-finite loss: {losses}",
    )
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    require(
        m["steady_compiles"] == 0
        and m["steady_unregistered_compiles"] == 0,
        f"compiles in the steady window: {m['steady_compiles']} "
        f"train.step, {m['steady_unregistered_compiles']} unregistered",
    )
    if m["kernel_rows"] is not None:
        data = mesh_axes.get("dp", 1) * mesh_axes.get("fsdp", 1)
        shard_rows = (sizes["batch"] // data) * (
            sizes["model"]["n_heads"] // mesh_axes.get("tp", 1)
        )
        require(
            m["kernel_rows"] == [shard_rows],
            f"flash kernel operands have {m['kernel_rows']} "
            f"batch*heads rows per device; the shard is {shard_rows}",
        )
    tokens = sizes["batch"] * sizes["seq"]
    say(
        f"train[{layout}]",
        f"loss {losses[0]:.3f} -> {losses[-1]:.3f} over "
        f"{sizes['warmup'] + sizes['steps']} steps; step "
        f"{m['step_ms']:.1f} ms ({tokens / m['step_ms'] * 1e3:.0f} "
        f"tokens/s, information only); compile+first step "
        f"{m['first_step_s']:.1f} s, init {m['init_s']:.1f} s; "
        f"steady-state compiles 0; cache hits {m['cache_hits']}",
    )
    say(
        f"train[{layout}]",
        f"params {m['param_bytes'] / 2**20:.0f} MiB total, "
        f"{m['param_bytes_device0'] / 2**20:.0f} MiB on device 0 "
        f"({m['param_bytes_device0'] / m['param_bytes']:.2f} of "
        f"total); flash kernel rows per device {m['kernel_rows']}; "
        f"peak device memory {m['peak_bytes_in_use']}",
    )


# ---------------------------------------------------------------------
# serve: HTTP -> proxy -> router -> replica -> engine
# ---------------------------------------------------------------------

def stream_request(port: int, prompt: list, new_tokens: int,
                   timeout_s: float) -> dict:
    """POST one prompt and read the chunked token stream to its end,
    as a well-behaved client: the router's admission control answers
    503 + `retry_after_s` when every replica's queue is past its
    token threshold, and the request is sent again after that pause
    (TTFT counts from the first send)."""
    body = json.dumps({"prompt": prompt, "max_new_tokens": new_tokens})
    t0 = time.perf_counter()
    sheds = 0
    while True:
        conn = http.client.HTTPConnection(
            "127.0.0.1", port, timeout=timeout_s
        )
        try:
            conn.request(
                "POST", "/llm", body=body,
                headers={"Content-Type": "application/json"},
            )
            resp = conn.getresponse()
            data, first = b"", None
            while True:
                chunk = resp.read1(65536)
                if not chunk:
                    break
                if first is None:
                    first = time.perf_counter()
                data += chunk
        finally:
            conn.close()
        if resp.status == 200:
            return {
                "tokens": [int(t) for t in data.split()],
                "ttft_ms": (first - t0) * 1e3,
                "sheds": sheds,
            }
        if (
            resp.status != 503
            or time.perf_counter() - t0 > timeout_s
        ):
            raise SmokeFailure(f"http {resp.status}: {data[:300]!r}")
        sheds += 1
        time.sleep(float(json.loads(data).get("retry_after_s", 1)))


def serve_phase(sizes: dict, device: dict, replicas: int) -> None:
    import random

    import ray_tpu as rt
    import ray_tpu.serve as serve
    from ray_tpu.llm import build_llm_app
    from ray_tpu.serve.controller import CONTROLLER_NAME

    family = "bench410m"
    app = build_llm_app(
        {family: {
            "kind": "init", "seed": 0,
            "config": dict(sizes["model"], dtype="bfloat16"),
        }},
        engine=sizes["engine"],
        num_replicas=replicas,
    )
    serve.run(app, name="llm", route_prefix="/llm")
    port = serve.start(http_port=0)
    rng = random.Random(0)
    vocab = sizes["model"]["vocab_size"]
    new = sizes["new_tokens"]
    prompts = [
        [rng.randrange(1, vocab) for _ in range(n)]
        for n in sizes["prompt_lens"]
    ]

    controller = rt.get_actor(CONTROLLER_NAME, namespace="serve")

    def replica_reports() -> list:
        rows = rt.get(
            controller.get_replicas.remote("llm", "llm"), timeout=30
        )
        stats = rt.get(
            [r["actor"].stats.remote() for r in rows]
            + [
                r["actor"].handle_request.remote("engine_stats", (), {})
                for r in rows
            ],
            timeout=60,
        )
        return [
            {**stats[i], "engine": stats[len(rows) + i].get(family)}
            for i in range(len(rows))
        ]

    # Warm every replica: the first request to each loads the weights
    # and compiles prefill + decode. A cold first token may outlast
    # the router's 60 s per-chunk bound, so a failed warm-up request
    # is retried (the load carries on replica-side) until each replica
    # has an engine, inside the phase's own bound.
    t0 = time.perf_counter()
    attempts = 0
    while True:
        attempts += 1
        wave = [
            threading.Thread(
                target=lambda: stream_request(
                    port, prompts[0], 2, timeout_s=240
                ),
                daemon=True,
            )
            for _ in range(2 * replicas if replicas > 1 else 1)
        ]
        for t in wave:
            t.start()
        for t in wave:
            t.join()
        reports = replica_reports()
        if all(r["engine"] for r in reports):
            break
    warm_s = time.perf_counter() - t0
    say(
        "serve",
        f"{len(reports)} replica(s) warm after {attempts} wave(s), "
        f"{warm_s:.1f} s (weights + prefill/decode compile)",
    )
    require(len(reports) == replicas, f"replicas: {reports}")
    for r in reports:
        engine = r["engine"]
        say(
            "serve",
            f"replica {r['replica_id']} pid={r['pid']} chips="
            f"{r['chips']} reports platform={engine['platform']} "
            f"kind={engine['device_kind']!r} "
            f"devices={engine['devices']}",
        )
        require(
            engine["platform"] == device["platform"],
            f"replica runs on {engine['platform']!r}, not "
            f"{device['platform']!r}",
        )
        require(r["pid"] != os.getpid(), "replica runs in the driver")
    if replicas > 1:
        chips = [tuple(r["chips"]) for r in reports]
        require(
            all(len(c) == 1 for c in chips)
            and len(set(chips)) == replicas,
            f"replicas do not hold distinct single chips: {chips}",
        )

    # Concurrent streaming traffic: every request completes.
    results: list = [None] * len(prompts)
    errors: list = []

    def fire(i: int) -> None:
        try:
            results[i] = stream_request(
                port, prompts[i], new, timeout_s=240
            )
        except BaseException as e:  # reported below, fails the phase
            errors.append((i, repr(e)))

    t0 = time.perf_counter()
    threads = [
        threading.Thread(target=fire, args=(i,), daemon=True)
        for i in range(len(prompts))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    require(not errors, f"requests failed: {errors}")
    require(
        all(len(r["tokens"]) == new for r in results),
        f"short streams: {[len(r['tokens']) for r in results]}",
    )
    ttfts = sorted(r["ttft_ms"] for r in results)
    say(
        "serve",
        f"{len(prompts)} concurrent streams (prompts "
        f"{sizes['prompt_lens'][0]}..{sizes['prompt_lens'][-1]} "
        f"tokens, {new} new each) all completed in {wall:.2f} s: "
        f"TTFT median {ttfts[len(ttfts) // 2]:.0f} ms, max "
        f"{ttfts[-1]:.0f} ms; {len(prompts) * new / wall:.0f} "
        f"generated tokens/s (information only); "
        f"{sum(r['sheds'] for r in results)} shed with 503 by "
        "admission control and sent again",
    )

    # Greedy determinism + prefix reuse: one more send than there are
    # replicas, so some replica sees the prompt twice.
    repeat = prompts[3]
    before = sum(r["engine"]["prefix_hits"] for r in replica_reports())
    outs = [
        stream_request(port, repeat, new, timeout_s=240)["tokens"]
        for _ in range(replicas + 1)
    ]
    require(
        all(o == results[3]["tokens"] for o in outs),
        "the same prompt gave different greedy tokens",
    )
    reports = replica_reports()
    hits = sum(r["engine"]["prefix_hits"] for r in reports) - before
    require(hits >= 1, "repeating a prompt never hit the prefix cache")
    for r in reports:
        engine = r["engine"]
        require(not engine["dead"], f"engine died: {engine}")
        # One geometry: the step once, a chunk once a shape (the
        # chunk, its half and its quarter, which the engine's loop
        # runs before its first admission), whatever the prompts.
        require(
            engine["compiles"]["prefill"]["distinct_shapes"] <= 3
            and engine["compiles"]["decode"]["distinct_shapes"] <= 1,
            f"engine compiled more than one geometry: "
            f"{engine['compiles']}",
        )
    say(
        "serve",
        f"same prompt x{replicas + 1}: identical greedy tokens, "
        f"{hits} prefix-cache hit(s); engine steps "
        f"{[r['engine']['steps'] for r in reports]}",
    )

    # The same facts on the operator surface (/api/serve payload);
    # the head folds replica metrics on its flush period.
    deadline = time.monotonic() + 30
    while True:
        row = (
            serve.status_detail().get("llm/llm", {}).get("engine", {})
        ).get(family, {})
        if row.get("platform") or time.monotonic() > deadline:
            break
        time.sleep(0.5)
    require(
        row.get("platform") == device["platform"],
        f"/api/serve names platform {row.get('platform')!r}",
    )
    say(
        "serve",
        f"/api/serve: platform={row['platform']} "
        f"device_kind={row['device_kind']!r}",
    )
    serve.shutdown()


# ---------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------

def main() -> int:
    global _LABEL
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--rehearse", action="store_true",
        help="CPU walk-through at tiny sizes (never a chip result)",
    )
    parser.add_argument("--phase", choices=["kernel"], help=argparse.SUPPRESS)
    args = parser.parse_args()
    sizes = REHEARSAL if args.rehearse else REAL

    if args.phase == "kernel":
        print(json.dumps(device_and_kernel_phase(args.rehearse)))
        return 0

    t_start = time.perf_counter()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={REHEARSAL_DEVICES}"
        ).strip()
    from ray_tpu._private.compile_cache import ensure_compile_cache

    cache = ensure_compile_cache()

    probe = run_kernel_child(args.rehearse)
    device = probe["device"]
    _LABEL = (
        f"platform={device['platform']} kind={device['kind']!r} "
        f"n={device['count']} "
    )
    versions = probe["versions"]
    say(
        "device",
        f"jax {versions['jax']} jaxlib {versions['jaxlib']} libtpu "
        f"{versions['libtpu']}; compile cache {cache}",
    )
    for row in probe["kernel"]:
        say(
            "kernel",
            f"flash {row['shape']}: fwd err {row['fwd_err']:.2e} grad "
            f"err {row['grad_err']:.2e} mosaic={row['mosaic']} "
            f"({row['wall_s']} s with compiles)",
        )
    for row in probe["paged"]:
        say(
            "kernel",
            f"paged_attn {row['shape']}: err {row['err']:.2e} "
            f"mosaic={row['mosaic']} ({row['wall_s']} s with compiles)",
        )
    for row in probe["experts"]:
        say(
            "kernel",
            f"expert layer, {row['load']} load ({row['held_picks']} held "
            f"picks of {row['picks']}, budget {row['budget']}): out err "
            f"{row['out_err']:.2e} dx err {row['dx_err']:.2e} against every "
            f"pick computed ({row['wall_s']} s with compiles)",
        )

    import ray_tpu as rt
    from ray_tpu._native import load_library

    n = device["count"]
    rt.init(num_tpus=n if args.rehearse else None)
    try:
        say(
            "device",
            "object store: "
            + ("native arena" if load_library() else "python segments"),
        )
        chips = int(rt.cluster_resources().get("TPU", 0))
        # A mismatch would leave every num_tpus actor pending forever.
        require(
            chips == n,
            f"the runtime detected {chips} chip(s) but JAX sees {n}",
        )
        layouts = [{"fsdp": n}]
        if n > 1:
            layouts.append({"dp": n // 2, "tp": 2})
        for mesh_axes in layouts:
            with phase_deadline("train"):
                train_phase(sizes, device, mesh_axes)
        with phase_deadline("serve"):
            serve_phase(sizes, device, replicas=min(2, n))
    finally:
        rt.shutdown()

    from jax._src import xla_bridge

    require(
        not xla_bridge.backends_are_initialized(),
        "the driver initialised a JAX backend",
    )
    say(
        "done",
        f"device, kernel, train, serve passed in "
        f"{time.perf_counter() - t_start:.0f} s wall",
    )
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
