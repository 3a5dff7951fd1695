"""The benchmark's command end to end on the CPU (`--rehearse`): tiny
sizes, virtual devices, fake chips. In a file of its own so the test
runner can place these slower tests beside the quick ones."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.reference import weights  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import manifest_checks as checks  # noqa: E402  (this directory)

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


_checkout = checks.checkout


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
    checks.write_manifest(root, manifest)
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
    mark = str(tmp_path / "stub_ref.called")
    checks.grow(root, base, cell, mark)
    config = harness.load_json(os.path.join(root, "benchmark", "configs", "stub-model.json"))
    proc = _run(root, "--workload", "stub_cell", "--seed", str(seed), "--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1])["correct"] is True
    notes = json.loads(next(x for x in lines if x.startswith("[benchmark] notes "))[18:])
    assert (notes.get("probe") or notes)["reference"] == "benchmark.reference.stub_ref"
    with open(mark) as f:
        calls = [json.loads(x) for x in f.read().splitlines()]
    # the serve comparison runs the reference once a sampled request and
    # once a distinct row of the probe, each padded to a multiple of a
    # quarter of max_len; the train check once
    small = harness.apply_rehearsal(config)
    if "engine" not in config:
        assert len(calls) == 1 and calls[0][1] is None
        return
    from benchmark.drivers.serve_probe import DECODE_STEPS, SAMPLE_REQUESTS

    engine, probe = small["engine"], notes["probe"]
    assert len(small["probe_lengths"]) < len(calls) <= SAMPLE_REQUESTS + engine["slots"]
    assert all(fed % (engine["max_len"] // 4) == 0 for fed, _ in calls)
    # the stub's own leaves are there on both sides: in the tree the
    # replica served and in the one the reference's pass made
    leaves = len(weights.shapes(small["model"])) + 3
    assert notes["replica_load_s"]["leaves"] == probe["leaves"] == leaves
    # a served request is asked for the rows that are compared and no
    # other (the last prompt position and every served token but the
    # last), a probe row for every position it holds
    served = [(a, b) for _, (a, b) in calls if a > 0]
    assert sorted(b - a for a, b in served) == sorted(
        r["n_out"] for r in probe["requests_rows"]
    )
    assert sorted(a + 1 for a, _ in served) == sorted(
        r["n_prompt"] for r in probe["requests_rows"]
    )
    assert sum(b - a for a, b in served) == probe["tokens"]
    rows = {b for _, (a, b) in calls if a == 0}
    assert rows == {n + DECODE_STEPS for n in small["probe_lengths"]}


@pytest.mark.timeout(400)
def test_a_leaf_the_reference_names_and_the_tree_lacks_ends_the_run_on_its_name(tmp_path):
    """The grown stub with its `shapes` taken away: both sides make
    `weights.shapes`' tree, the program serves it, and the reference
    refuses it. The run exits 1 on a last line that names the leaf, and
    the probe is not tried again."""
    root = _checkout(tmp_path)
    mark = str(tmp_path / "stub_ref.called")
    checks.grow(root, "qwen2.5-3b", "docqa_closed", mark)
    with open(os.path.join(root, "benchmark", "reference", "stub_ref.py"), "a") as f:
        f.write("\n\ndel shapes\n")
    proc = _run(root, "--workload", "stub_cell", "--seed", "5", "--rehearse")
    assert proc.returncode == 1
    assert '"correct"' not in proc.stdout
    last = proc.stderr.strip().splitlines()[-1]
    assert "serve probe exited 2" in last
    assert "stub_ref: the tree has no leaf layers/w_index" in last
    assert proc.stderr.count("probe failed: ValueError") == 2  # the child's line, and the last
    assert not os.path.exists(mark)


def _last_lines(proc):
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    return line, proc.stderr.strip().splitlines()


OTHER_CHECKS = [
    "compiles_in_window", "replay_equal", "tokens_in_range", "engine_dead",
    "platform", "failed",
]


@pytest.mark.timeout(400)
def test_a_run_made_incorrect_names_the_check_on_its_last_line_and_on_standard_error(tmp_path):
    """A limit under the reading in a scratch configuration: the run
    exits 1, and both the last line of standard output (where the
    driver's ledger looks) and the last lines of standard error say
    which number of the comparison it was, with the reading beside the
    limit; a sound run carries the same keys (the other tests of this
    file read its last line)."""
    root = _checkout(tmp_path)
    path = os.path.join(root, "benchmark", "configs", "qwen2.5-3b.json")
    config = harness.load_json(path)
    config["rehearsal"]["tolerance"]["logits_rel_rms"] = 0.0
    with open(path, "w") as f:
        json.dump(config, f)
    proc = _run(root, "--workload", "docqa_closed", "--seed", "3", "--rehearse")
    assert proc.returncode == 1
    line, errors = _last_lines(proc)
    assert line["correct"] is False and line["failed"] == 0
    worst = line["checks"]["logits_rel_rms"]
    assert worst["ok"] is False and worst["limit"] == 0.0 and 0 < worst["value"] < 1e-4
    assert worst["where"].startswith("prefill ") and "decode " in worst["where"]
    others = {k: v["ok"] for k, v in line["checks"].items() if k != "logits_rel_rms"}
    assert others == dict.fromkeys(
        ["served_gap_max", "logits_rel_rms_row"] + OTHER_CHECKS, True
    )
    assert errors[-1].startswith("[benchmark] failed: logits_rel_rms ")
    assert f"{worst['value']:.6g} against 0" in errors[-1]
    assert sum(x.startswith("[benchmark] check: ") for x in errors[-10:]) == 9


ALTERED_TOKEN = '''

_sound_call = BenchLLMServer.__call__


def _altered(self, request):
    """The timed path broken underneath: every stream's last token is
    altered where it is produced (nothing follows it, so no sound token
    is judged in a context the reference was fed wrong)."""
    held = None
    for chunk in _sound_call(self, request):
        if held is not None:
            yield held
        held = chunk
    if held is not None:
        yield b"%d " % ((int(held) + 1) % 512)


BenchLLMServer.__call__ = _altered
'''


@pytest.mark.timeout(400)
def test_a_token_altered_where_it_is_produced_makes_the_run_incorrect(tmp_path):
    """The whole of a run but the look for a chip, with the replica of
    a scratch checkout altering the last token of every stream:
    `correct` comes out false by the comparison with the reference and
    by nothing else."""
    root = _checkout(tmp_path)
    with open(os.path.join(root, "benchmark", "drivers", "serve_replica.py"), "a") as f:
        f.write(ALTERED_TOKEN)
    proc = _run(root, "--workload", "docqa_closed", "--seed", "4", "--rehearse")
    assert proc.returncode == 1
    line, errors = _last_lines(proc)
    assert line["correct"] is False and line["failed"] == 0
    verdicts = {k: v["ok"] for k, v in line["checks"].items()}
    assert verdicts == {
        "served_gap_max": False,
        **dict.fromkeys(["logits_rel_rms", "logits_rel_rms_row"] + OTHER_CHECKS, True),
    }
    # an altered token lies deviations under the reference's best
    assert line["checks"]["served_gap_max"]["value"] > 0.5
    # and the widest gap is read at the altered token, a request's last
    where = line["checks"]["served_gap_max"]["where"]
    token, served = re.search(
        r"the widest at token (\d+) of a request of \d+ \+ (\d+)$", where
    ).groups()
    assert int(token) == int(served) - 1
    assert errors[-1].startswith("[benchmark] failed: served_gap_max ")


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
