"""The tests of this directory, run on a copy of the benchmark GROWN
the way a `model_config` PR grows it (`manifest_checks.grow`): a test
that holds the manifest to what it lists today (its configurations,
its cells, the number of either) is red there, and would send the PR
that adds an entry back to edit a file it may not edit. The whole runs
of `test_benchmark_rehearsal.py` and the tests that grow a copy
themselves stay out; this file is on its own so that a parallel runner
can place it beside the quick ones."""

import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import manifest_checks as checks  # noqa: E402  (this directory)

#: What runs whole cells, and what is itself about growing a copy.
LEFT_OUT = ["test_benchmark_rehearsal.py", "test_benchmark_grown.py"]


@pytest.mark.timeout(1000)
def test_the_tests_of_this_directory_pass_on_a_grown_copy(tmp_path):
    root = checks.checkout(tmp_path)
    grown = checks.grow(root, "qwen2.5-3b", "docqa_closed")
    assert "stub-model" in checks.names(grown, "configs")
    tests = os.path.join(root, "tests", "benchmark")
    shutil.copytree(HERE, tests, ignore=shutil.ignore_patterns("__pycache__", *LEFT_OUT))
    for name in ("PERF.md", "pytest.ini"):
        shutil.copy(os.path.join(checks.ROOT, name), root)
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=root)

    def pytest_there(*args):
        return subprocess.run(
            [sys.executable, "-m", "pytest", *args, "-q", "-p", "no:cacheprovider",
             "-p", "no:xdist", "-p", "no:randomly"],
            cwd=root, env=env, capture_output=True, text=True, timeout=600,
        )

    proc = pytest_there(tests, "-k", "not grown", "-rf")
    if proc.returncode != 0:
        # what the grown manifest turns red is red again alone; what
        # the tests running beside this one made late is not
        failed = re.findall(r"^FAILED (\S+)", proc.stdout, re.M)
        assert failed, proc.stdout[-4000:] + proc.stderr[-2000:]
        again = pytest_there(*failed)
        assert again.returncode == 0, again.stdout[-6000:]
    # it ran there, on the grown manifest: the stub's cases were collected
    listed = pytest_there(
        tests, "--collect-only", "-k", "stub-model or stub_cell or stub_steps"
    )
    assert listed.returncode == 0
    assert sum("stub" in line for line in listed.stdout.splitlines()) >= 6
