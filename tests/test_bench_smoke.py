"""CI gate for the bench harness itself: `bench.py --smoke` must run
the whole bench surface (train step, fixed-cost attribution, async-
checkpoint overhead) in seconds on CPU and emit one well-formed JSON
line — so a broken bench is caught by the test suite, not discovered
at measurement time."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# slow: ~90s of jit compiles on a loaded CPU box — the smoke gate
# belongs in the slow tier, not displacing tier-1 wall-clock.
@pytest.mark.slow
@pytest.mark.timeout(420)
def test_bench_smoke_emits_composite_json():
    # Drop the suite's forced 8-host-device XLA_FLAGS: the smoke gate
    # mirrors `python bench.py --smoke` as a user runs it (1 CPU
    # device), and CPU SPMD across forced devices is pathologically
    # slow.
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    # Keep the checkpoint-overhead phase short: this test checks the
    # bench RUNS and emits the right shape, not the numbers.
    env.setdefault("RT_BENCH_SMOKE_CKPT_STEPS", "6")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "bench.py"),
            "--smoke",
            "--skip-micro",
        ],
        capture_output=True,
        text=True,
        timeout=390,
        env=env,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [
        ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")
    ][-1]
    out = json.loads(line)

    assert out["smoke"] is True
    assert out["vs_baseline"] == 0.0  # smoke numbers never count
    # The CPU gate names its device and carries no device metric.
    assert out["train"]["platform"] == "cpu"
    assert "per_chip" not in out["train"]["metric"]

    breakdown = out["fixed_ms_breakdown"]
    for key in (
        "fixed_step_ms_0l",
        "optimizer_ms",
        "embed_lm_head_ms",
        "dispatch_ms",
        "host_sync_ms",
        "input_stall_ms",
    ):
        assert isinstance(breakdown[key], (int, float)), key
        assert breakdown[key] >= 0, (key, breakdown[key])

    ckpt = out["ckpt_overhead"]
    assert ckpt["every"] == 10
    assert ckpt["base_wall_s"] > 0
    assert ckpt["ckpt_wall_s"] > 0
    assert isinstance(ckpt["ckpt_overhead_pct"], (int, float))


# slow: two pipeline builds + the single-program baseline compiles.
@pytest.mark.slow
@pytest.mark.timeout(600)
def test_bench_pipeline_smoke_efficiency_and_parity():
    """`bench.py --mode pipeline --smoke` must run the MPMD 1F1B
    bench end to end on CPU (2 stages x tiny model): efficiency /
    bubble fields render, per-stage send/recv wait is visible, and
    the MPMD loss matches the single-program GPipe baseline at
    identical geometry."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "bench.py"),
            "--mode",
            "pipeline",
            "--smoke",
        ],
        capture_output=True,
        text=True,
        timeout=570,
        env=env,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [
        ln for ln in proc.stdout.strip().splitlines() if ln.startswith("{")
    ][-1]
    out = json.loads(line)

    assert out["smoke"] is True
    assert out["metric"] == "mpmd_pipeline_tokens_per_s"
    assert out["points"], "no pipeline points measured"
    for point in out["points"]:
        # Efficiency/bubble fields render and are sane.
        assert 0.0 < point["pipeline_efficiency"] <= 1.2
        assert 0.0 < point["theoretical_bound"] <= 1.0
        assert point["bound_ratio"] > 0
        assert point["tokens_per_s"] > 0
        # 1F1B invariant visible in telemetry.
        assert all(
            s["stash_peak"] <= point["stash_bound"]
            for s in point["stages"]
        )
        # Per-stage send/recv wait breakdown present.
        for stage in point["stages"]:
            assert "send_wait_ms" in stage
            assert "recv_wait_ms" in stage
        # Loss parity with the single-program GPipe baseline.
        assert point["loss_matches_baseline"] is True
    # The baseline comparison renders at every compared geometry.
    # (Which side wins at SMOKE scale is box-dependent: on one CPU
    # core the fused program's lower per-op dispatch usually beats
    # MPMD's per-op overhead at tiny compute — the committed
    # PIPEBENCH.json `large` point is where the structural win
    # shows. Parity above is the correctness gate.)
    assert all(
        p["vs_single_program"] > 0
        for p in out["points"]
        if "vs_single_program" in p
    )
