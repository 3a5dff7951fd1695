"""The benchmark's command end to end on the CPU (`--rehearse`): tiny
sizes, virtual devices, fake chips. In a file of its own so the test
runner can place these slower tests beside the quick ones."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

MANIFEST = harness.load_manifest()


# -- end to end on the CPU --------------------------------------------

def _run(root, *args, timeout=300):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    # tiny models: one thread each, so these runs leave the cores to
    # the tests beside them
    env["XLA_FLAGS"] = "--xla_cpu_multi_thread_eigen=false"
    env["OMP_NUM_THREADS"] = "1"
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout,
    )


def _checkout(tmp_path):
    """A copy that holds what the benchmark owns plus a link to the
    program: what a later PR's checkout looks like to run.py."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    os.symlink(os.path.join(ROOT, "ray_tpu"), os.path.join(root, "ray_tpu"))
    return root


@pytest.mark.timeout(400)
@pytest.mark.parametrize("cell", ["pretrain_8k_fsdp4", "docqa_closed"])
def test_rehearsal_says_cpu_and_prints_no_device_metric(cell, tmp_path):
    root = _checkout(tmp_path)
    proc = _run(root, "--workload", cell, "--seed", "3", "--rehearse", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == harness.find_cell(MANIFEST, cell)["chips"]
    assert "metrics" not in line and "memory_peak_bytes" not in line["device"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metric_names"]


@pytest.mark.timeout(400)
def test_a_fifth_cell_is_three_new_files_and_one_entry(tmp_path):
    root = _checkout(tmp_path)
    bench = os.path.join(root, "benchmark")
    config = harness.load_config(MANIFEST, "mistral-7b-v0.3-l4")
    config["name"] = "dummy-model"
    with open(os.path.join(bench, "configs", "dummy-model.json"), "w") as f:
        json.dump(config, f)
    traffic = dict(harness.load_traffic("stream_8k"), sequences_per_chip=2)
    with open(os.path.join(bench, "traffic", "dummy_stream.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bench, "layer_metrics", "dummy_steps.py"), "w") as f:
        f.write(
            'LAYER, UNIT, SOURCE = "train loop", "steps", "program_counter"\n'
            "def reduce(run):\n    return len(run['steps'])\n"
        )
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append({
        "name": "dummy-model", "source": config["source"],
        "file": "benchmark/configs/dummy-model.json",
        "reduced": ["num_hidden_layers"], "why": "dummy",
    })
    manifest["workloads"].append({
        "name": "dummy_cell", "config": "dummy-model",
        "traffic": "dummy_stream", "chips": 1, "why": "dummy",
    })
    manifest["per_layer"].append({
        "name": "dummy_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train loop",
        "moves": "train_tokens_per_s_chip", "workloads": ["dummy_cell"],
    })
    for metric in manifest["end_to_end"]:
        if metric["name"] == "train_tokens_per_s_chip":
            metric["workloads"].append("dummy_cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    proc = _run(root, "--workload", "dummy_cell", "--rehearse", "--trace", "1")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["metric_names"] == ["dummy_steps"]
    proc = _run(root, "--workload", "dummy_cell", "--rehearse")
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["metric_names"] == ["setup_s", "train_tokens_per_s_chip"]


@pytest.mark.timeout(400)
@pytest.mark.parametrize("base, cell, seed", [
    ("qwen2.5-3b", "docqa_closed", 5), ("mistral-7b-v0.3-l4", "pretrain_8k", 0),
], ids=["serve-probe", "train-check"])
def test_a_configuration_names_its_reference_and_the_driver_calls_it(base, cell, seed, tmp_path):
    """A configuration whose equations differ is new files and entries:
    its file names `reference/<module>.py`, and the probe (serve) or
    the train check decides `correct` against that module."""
    root = _checkout(tmp_path)
    bench = os.path.join(root, "benchmark")
    mark = str(tmp_path / "stub_ref.called")
    with open(os.path.join(bench, "reference", "stub_ref.py"), "w") as f:
        f.write(
            "from benchmark.reference import llama_ref\n"
            "def forward(params, tokens, model):\n"
            f"    with open({mark!r}, 'a') as f:\n"
            "        f.write(f'{tokens.shape[0]}\\n')\n"
            "    return llama_ref.forward(params, tokens, model)\n"
        )
    config = dict(harness.load_config(MANIFEST, base), name="stub-model", reference="stub_ref")
    with open(os.path.join(bench, "configs", "stub-model.json"), "w") as f:
        json.dump(config, f)
    manifest = json.loads(json.dumps(MANIFEST))
    entry = next(c for c in manifest["configs"] if c["name"] == base)
    manifest["configs"].append(dict(entry, name="stub-model", file="benchmark/configs/stub-model.json"))
    traffic = harness.find_cell(MANIFEST, cell)["traffic"]
    manifest["workloads"].append({
        "name": "stub_cell", "config": "stub-model", "traffic": traffic,
        "chips": 1, "why": "stub",
    })
    for section in ("end_to_end", "per_layer"):
        for metric in manifest[section]:
            if cell in metric.get("workloads", ()):
                metric["workloads"].append("stub_cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    proc = _run(root, "--workload", "stub_cell", "--seed", str(seed), "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1])["correct"] is True
    notes = json.loads(next(x for x in lines if x.startswith("[benchmark] notes "))[18:])
    assert (notes.get("probe") or notes)["reference"] == "benchmark.reference.stub_ref"
    with open(mark) as f:
        calls = f.read().split()
    # the probe compares one sequence per probe length, the train check one
    assert len(calls) == (len(harness.apply_rehearsal(config)["probe_lengths"]) if "engine" in config else 1)


def test_without_a_tpu_the_command_fails_and_prints_no_result(tmp_path):
    root = _checkout(tmp_path)
    proc = _run(root, "--workload", "pretrain_8k", "--seconds", "1")
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout and '"correct"' not in proc.stdout


def test_without_the_program_the_command_fails(tmp_path):
    root = _checkout(tmp_path)
    os.unlink(os.path.join(root, "ray_tpu"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "pretrain_8k"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0 and '"metrics"' not in proc.stdout
