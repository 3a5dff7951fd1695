"""`chat_sessions_conv` walked through on the CPU, end to end: the cell
of `lfm2-24b-a2b-ep8` at its rehearsal sizes through `benchmark/run.py`.
It belongs with `tests/benchmark/test_benchmark_lfm2.py` and lies here
because `tests/benchmark/test_benchmark_grown.py` runs that whole
directory again in ONE process inside 600 s, which the three whole-cell
rehearsals it already holds nearly fill."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "chat_sessions_conv"


@pytest.mark.timeout(900)
def test_the_cell_walks_through_on_the_cpu(tmp_path):
    """`run.py --workload chat_sessions_conv --rehearse --trace 1` on a
    copy of the checkout: HTTP -> proxy -> router -> replica -> engine
    over pages and state slots at the rehearsal's sizes (both kinds of
    layer in the published pattern, sessions past the chunk, so every
    later turn starts from a snapshot), float32, `correct` against the
    reference, and the cell's own readers among the names."""
    import subprocess

    sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
    import manifest_checks as checks  # the benchmark's own checks

    root = checks.checkout(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(
        JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_cpu_multi_thread_eigen=false",
        OMP_NUM_THREADS="1",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 52), "--rehearse",
         "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=800,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["device"]["platform"] == "cpu" and "metrics" not in line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {
        "conv_hit_kept_share", "conv_state_cache_share", "moe_held_pick_share",
        "moe_load_imbalance", "moe_experts_touched_share",
        "prefix_hit_token_share.tput", "kv_read_amplification.tput",
    } <= set(line["metric_names"])
    assert not {"swa_key_share", "window_hit_kept_share"} & set(line["metric_names"])
    notes = json.loads(
        next(x for x in lines if x.startswith("[benchmark] notes "))[18:]
    )
    assert notes["probe"]["reference"].endswith("lfm2_moe_ref")
    assert notes["engine_window"]["prefix_hit_token_share"] > 20.0
    assert notes["compiles_in_window"] == 0
