"""`pretrain_8k_moe_swa` walked through on the CPU, end to end: the cell
of `trinity-mini-ep8` at its rehearsal sizes through `benchmark/run.py`.
It belongs with `tests/benchmark/test_benchmark_trinity.py` and lies
here, as `test_lfm2_cell_walkthrough.py` does, because
`tests/benchmark/test_benchmark_grown.py` runs that whole directory
again in ONE process inside 600 s, which the whole-cell rehearsals it
already holds nearly fill."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "pretrain_8k_moe_swa"


@pytest.mark.timeout(900)
def test_the_cell_walks_through_on_the_cpu(tmp_path):
    """`run.py --workload pretrain_8k_moe_swa --rehearse --trace 1` on
    a copy of the checkout: `JaxTrainer.fit` -> the gang worker ->
    `make_train_step(loss_fn)` over a leading dense layer and a whole
    period of expert layers of both kinds at the rehearsal's sizes (a
    window of 16 under sequences of 64, 2 of 8 experts held, the shared
    expert), `correct` against `trinity_ref`, no compile in the window."""
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
         "--workload", CELL, "--seed", str(2 ** 31 + 55), "--rehearse",
         "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=800,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["device"]["platform"] == "cpu" and "metrics" not in line
    assert line["attempted"] > 0 and line["failed"] == 0
    # (the CPU gives no device trace: the two readers the cell brings
    # leave their names out, as they do on a program without them)
    assert "data_wait_share" in line["metric_names"]
    notes = json.loads(
        next(x for x in lines if x.startswith("[benchmark] notes "))[18:]
    )
    assert notes["reference"].endswith("trinity_ref")
    assert notes["steady_compiles"] == 0
