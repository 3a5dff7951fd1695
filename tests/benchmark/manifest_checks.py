"""Every check `tests/benchmark/` makes of a manifest, as functions of
a manifest and the root its files are found under, and a copy of the
repo's benchmark GROWN the way a later PR grows it: new files, and
entries at the END of the manifest's lists.

The test files run the checks on the repo's `BENCHMARK.json`, case by
case; `walk` runs all of them on any manifest, and one test walks the
grown copy through it: what "Adding a cell (no edit to any file here)"
in `benchmark/README.md` promises. No check holds a `workloads` list to
an exact value or an entry to a place in its list: `HELD` says what
each of today's per-layer entries moves and the cells it must still
list, and a list may grow."""

import json
import os
import re
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

CHAT, CLOSED = {"chat_loaded"}, {"docqa_closed", "doc_score_moe"}
TRAIN = {"pretrain_8k", "pretrain_8k_fsdp4"}
ITL, P95 = "itl_mean_ms", "itl_p95_ms"
TPUT, TOKENS = "serve_tokens_per_s", "train_tokens_per_s_chip"
#: Per-layer entry -> (moves, better, cells it lists today). An entry
#: keeps the first two and may list more cells; a new entry is not here.
HELD = {
    "gen_late_p99_ms": (ITL, "lower", CHAT),
    "ttft_p50_ms": (P95, "lower", CHAT),
    "ttft_p90_ms": (P95, "lower", CHAT),
    "itl_p99_ms": (P95, "lower", CHAT),
    "shed_share.itl": (ITL, "lower", CHAT),
    "shed_share.tput": (TPUT, "lower", CLOSED),
    "ingress_overhead_mean_ms.itl": (ITL, "lower", CHAT),
    "ingress_overhead_mean_ms.tput": (TPUT, "lower", CLOSED),
    "engine_tokens_per_step.itl": (ITL, "higher", CHAT),
    "engine_tokens_per_step.tput": (TPUT, "higher", CLOSED),
    "prefix_hit_token_share.itl": (ITL, "higher", CHAT),
    "prefix_hit_token_share.tput": (TPUT, "higher", CLOSED),
    "decode_step_mean_ms.itl": (ITL, "lower", CHAT),
    "decode_step_mean_ms.tput": (TPUT, "lower", CLOSED),
    "prefill_chunk_mean_ms.itl": (P95, "lower", CHAT),
    "prefill_chunk_mean_ms.tput": (TPUT, "lower", CLOSED),
    "data_wait_share": (TOKENS, "lower", TRAIN),
    "peak_hbm_gb.train": (TOKENS, "lower", TRAIN),
    "peak_hbm_gb.itl": (ITL, "lower", CHAT),
    "peak_hbm_gb.tput": (TPUT, "lower", CLOSED),
    "device_idle_share.train": (TOKENS, "lower", TRAIN),
    "device_idle_share.itl": (ITL, "lower", CHAT),
    "device_idle_share.tput": (TPUT, "lower", CLOSED),
    "collective_exposed_share": (TOKENS, "lower", {"pretrain_8k_fsdp4"}),
    "engine_host_share.itl": (ITL, "lower", CHAT),
    "engine_host_share.tput": (TPUT, "lower", CLOSED),
    "engine_admit_wait_mean_ms.itl": (P95, "lower", CHAT),
    "engine_admit_wait_mean_ms.tput": (TPUT, "lower", CLOSED),
    "flash_kernel_share": (TOKENS, "lower", TRAIN),
    "flash_roofline_share": (TOKENS, "higher", TRAIN),
    "kv_read_amplification.itl": (ITL, "lower", CHAT),
    "kv_read_amplification.tput": (TPUT, "lower", CLOSED),
    "moe_load_imbalance": (TPUT, "lower", {"doc_score_moe"}),
    "moe_experts_touched_share": (TPUT, "lower", {"doc_score_moe"}),
    "moe_kernel_share": (TPUT, "lower", {"doc_score_moe"}),
    "moe_roofline_share": (TPUT, "higher", {"doc_score_moe"}),
    "engine_ahead_share.itl": (ITL, "higher", CHAT),
    "engine_ahead_share.tput": (TPUT, "higher", CLOSED),
    "stream_items_per_fetch.itl": (ITL, "lower", CHAT),
    "stream_items_per_fetch.tput": (TPUT, "lower", CLOSED),
}


def names(manifest: dict, section: str) -> list:
    return [e["name"] for e in manifest[section]]


# -- the checks -------------------------------------------------------

def contract_keys(manifest, root=ROOT):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert len(json.dumps(manifest)) < 64 * 1024
    assert all(
        not w.startswith("/") and ".." not in w for w in manifest["command"]
    )


def run_budget(manifest, root=ROOT):
    s = manifest["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 180 + 1200 <= 43200


def four_chip_share(manifest, root=ROOT):
    cells = manifest["workloads"]
    four = [c for c in cells if c["chips"] == 4]
    assert all(c["chips"] in (1, 4) for c in cells)
    assert len(four) <= max(1, len(cells) // 4)


def section_is_legal(manifest, section, root=ROOT):
    entries = manifest[section]
    assert len(names(manifest, section)) == len(set(names(manifest, section)))
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer"):
            if key in e:
                assert 1 <= len(e[key]) <= 200
                assert "\n" not in e[key] and "\t" not in e[key]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
    if section == "end_to_end":
        assert "setup_s" in names(manifest, section)
        for e in entries:
            assert set(e) <= {"name", "unit", "better", "bound", "source", "workloads"}
            assert 0.01 <= e["bound"] <= 0.1
            assert e["source"] in ("host_clock", "device_trace")
    if section == "per_layer":
        for e in entries:
            assert set(e) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
            assert e["moves"] in names(manifest, "end_to_end")
    if section == "workloads":
        pairs = [(e["config"], e["traffic"]) for e in entries]
        assert len(pairs) == len(set(pairs))
        for e in entries:
            assert set(e) == {"name", "config", "traffic", "chips", "why"}
            assert NAME.match(e["traffic"]) and NAME.match(e["config"])
    if section == "configs":
        used = {c["config"] for c in manifest["workloads"]}
        files = [e["file"] for e in entries]
        assert len(files) == len(set(files))
        for e in entries:
            assert set(e) == {"name", "source", "file", "reduced", "why"}
            assert 1 <= len(e["source"]) <= 200
            assert e["name"] in used
            assert any(e["file"].startswith(p + "/") for p in manifest["paths"])
            assert all(NAME.match(k) for k in e["reduced"])


def cell_reports(manifest, cell, root=ROOT):
    e2e = [m["name"] for m in harness.metrics_of_cell(manifest, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = harness.metrics_of_cell(manifest, "per_layer", cell)
    assert per_layer
    # a per-layer metric is reported only where the metric it moves is
    assert all(m["moves"] in e2e for m in per_layer)


def cell_finds_its_files(manifest, cell, root=ROOT):
    entry = harness.find_cell(manifest, cell)
    config = harness.load_config(manifest, entry["config"], root)
    traffic = harness.load_traffic(entry["traffic"], root)
    generator = harness.load_module("traffic", traffic["kind"], root)
    driver = harness.load_module("drivers", generator.DRIVER, root)
    assert callable(generator.generate) and callable(driver.run)
    assert config["name"] == entry["config"]
    assert ("trainer" in config) == (generator.DRIVER == "train")
    assert ("engine" in config) == (generator.DRIVER == "serve")


def end_to_end_reader_exists(manifest, metric, root=ROOT):
    assert callable(harness.load_module("end_to_end", metric, root).reduce)


def layer_entry_agrees_with_its_reader(manifest, metric, root=ROOT):
    """The entry against its reader file, and against what it held:
    `moves` and `better` stay, the cells it listed are still listed,
    in any order and with any others beside them."""
    entry = next(m for m in manifest["per_layer"] if m["name"] == metric)
    module = harness.load_module(
        "layer_metrics", harness.reader_name(metric), root
    )
    assert callable(module.reduce)
    assert (module.LAYER, module.UNIT, module.SOURCE) == (
        entry["layer"], entry["unit"], entry["source"]
    )
    cells = set(entry["workloads"])
    assert len(cells) == len(entry["workloads"])
    assert cells <= set(names(manifest, "workloads"))
    if metric in HELD:
        moves, better, held = HELD[metric]
        assert (entry["moves"], entry["better"]) == (moves, better)
        assert cells >= held, held - cells
    # a tagged entry's cells report the metric it moves
    reporting = {
        c for c in cells if entry["moves"] in [
            m["name"]
            for m in harness.metrics_of_cell(manifest, "end_to_end", c)
        ]
    }
    assert reporting == cells, cells - reporting


def layer_names_are_in_perf_md(manifest, root=ROOT):
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    for layer in {m["layer"] for m in manifest["per_layer"]}:
        assert f"| {layer} |" in text, layer


def config_agrees_with_its_entry(manifest, name, root=ROOT):
    entry = next(c for c in manifest["configs"] if c["name"] == name)
    config = harness.load_config(manifest, name, root)
    assert config["name"] == name and config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    for key, cut in config["reduced"].items():
        assert cut["here"] == config[key] != cut["published"]
    assert config["assumed"] and config["deployment"]
    if "engine" in config:
        # the numbers a serve cell is held to, at its own size and at
        # the rehearsal's; the probe's rows: any length the engine can
        # hold with the positions each decodes, one of them past the
        # chunk, at both sizes
        from benchmark.drivers.serve_probe import DECODE_STEPS, HELD

        small = harness.apply_rehearsal(config)
        for sized in (config, small):
            held = [k for k in HELD if k in sized["tolerance"]]
            assert "served_gap_max" in held and "logits_rel_rms" in held
            assert all(sized["tolerance"][k] > 0 for k in held)
            engine = sized["engine"]
            assert all(
                0 < n <= engine["max_len"] - DECODE_STEPS
                for n in sized["probe_lengths"]
            )
            assert max(sized["probe_lengths"]) > engine["prefill_chunk"]
        assert config["tolerance"]["why"]


def config_resolves_to_a_reference(manifest, name, root=ROOT):
    from benchmark.reference import compare

    config = harness.load_config(manifest, name, root)
    module = compare.load(config.get("reference"), root)
    assert callable(module.forward)
    stem = config.get("reference", compare.DEFAULT)
    assert module.__file__ == os.path.join(
        root, "benchmark", "reference", f"{stem}.py"
    )
    if "engine" in config:
        # the plan of leaves it names (or `weights.shapes`) draws: legal
        # kinds, no leaf that is also a parent; nothing is computed
        import jax

        from benchmark.reference import weights

        small = harness.apply_rehearsal(config)
        tree = jax.eval_shape(
            lambda: weights.make(small["model"], small["dtype"], 0, module)
        )
        assert {"embed", "lm_head"} <= set(tree)


#: (check, the section whose names it takes one of, or None).
CHECKS = [
    (contract_keys, None), (run_budget, None), (four_chip_share, None),
    (layer_names_are_in_perf_md, None),
    (cell_reports, "workloads"), (cell_finds_its_files, "workloads"),
    (end_to_end_reader_exists, "end_to_end"),
    (layer_entry_agrees_with_its_reader, "per_layer"),
    (config_agrees_with_its_entry, "configs"),
    (config_resolves_to_a_reference, "configs"),
]


def walk(manifest, root=ROOT) -> int:
    """Every check on every item of a manifest -> checks made."""
    made = 0
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        section_is_legal(manifest, section, root)
        made += 1
    for check, section in CHECKS:
        for item in (names(manifest, section) if section else [None]):
            if item is None:
                check(manifest, root)
            else:
                check(manifest, item, root)
            made += 1
    return made


# -- a checkout, and the same one grown -------------------------------

def checkout(tmp_path) -> str:
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


def write_manifest(root: str, manifest: dict) -> None:
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)


#: The reference of a stub architecture that brings what a drawn row
#: brings: leaves `weights.shapes` does not know (one more stacked leaf
#: under `layers`, one under a parent of its own, one drawn around a
#: mean of its own), sized by a `model` key, and a `forward` that takes
#: `rows` and refuses a tree in which one of them is absent or
#: misshapen. Its mathematics is `llama_ref`'s, which the program
#: computes: the leaves are carried, not used. Each call appends
#: [positions fed, rows asked for] to the file at MARK.
STUB_REFERENCE = '''\
import json

from benchmark.reference import llama_ref, weights

MARK = {mark!r}


def own(model):
    d, layers, k = model["dim"], model["n_layers"], model["moe_top_k"]
    return {{
        "layers/w_index": ((layers, d, 4 * k), "matrix", d),
        "mtp/proj": ((d, d), "matrix", d),
        "layers/decay_log": ((layers, k), (-2.0, 0.5), 0),
    }}


def shapes(model):
    return {{**weights.shapes(model), **own(model)}}


def forward(params, tokens, model, rows=None):
    for path, (shape, _, _) in own(model).items():
        leaf = params
        for name in path.split("/"):
            leaf = leaf.get(name) if isinstance(leaf, dict) else None
        if leaf is None:
            raise ValueError(f"stub_ref: the tree has no leaf {{path}}")
        if tuple(leaf.shape) != shape:
            raise ValueError(
                f"stub_ref: leaf {{path}} is {{tuple(leaf.shape)}}, not {{shape}}"
            )
    with open(MARK, "a") as f:
        f.write(json.dumps([int(tokens.shape[0]), rows]) + "\\n")
    return llama_ref.forward(params, tokens, model, rows=rows)
'''
#: The key of `model` that sizes the stub's own leaves, and its value:
#: one the program's config class takes and a dense forward ignores
#: (a key the class lacks is the program change a `model_config` PR
#: makes, which a stub cannot).
STUB_MODEL_KEY = {"moe_top_k": 3}
#: A train cell's stub: the train driver takes the program's
#: `init_params` tree, so it names no leaves.
STUB_REFERENCE_TRAIN = '''\
import json

from benchmark.reference import llama_ref

MARK = {mark!r}


def forward(params, tokens, model, rows=None):
    with open(MARK, "a") as f:
        f.write(json.dumps([int(tokens.shape[0]), rows]) + "\\n")
    return llama_ref.forward(params, tokens, model, rows=rows)
'''
#: reader -> (layer, unit, source, the body of `reduce(run)`).
STUB_READERS = {
    "stub_requests": (
        "client", "requests", "host_clock",
        "    return len(run.get('requests') or []) or None\n",
    ),
    "stub_steps": (
        "engine", "steps", "program_counter",
        "    engine = run.get('engine') or {}\n"
        "    return (engine['after']['steps'] - engine['before'].get('steps', 0)\n"
        "            if engine else len(run.get('steps') or []) or None)\n",
    ),
}


def grow(root: str, base: str, cell: str, mark: str = os.devnull) -> dict:
    """What a `model_config` PR adds to the checkout at `root`, and
    nothing it may not: a reference module (a serve cell's names leaves
    of its own, `STUB_REFERENCE`), a configuration that names it
    (`base`'s sizes and the key that sizes those leaves), two readers,
    and at the END of the manifest's lists one configuration, one cell
    (`cell`'s traffic) and two per-layer entries, with the new cell's
    name at the end of every list `cell` is in. -> the grown manifest,
    written there."""
    bench = os.path.join(root, "benchmark")
    manifest = harness.load_manifest(root)
    config = dict(
        harness.load_config(manifest, base, root),
        name="stub-model", reference="stub_ref",
    )
    serve = "engine" in config
    if serve:
        config["model"] = dict(config["model"], **STUB_MODEL_KEY)
    with open(os.path.join(bench, "reference", "stub_ref.py"), "w") as f:
        f.write(
            (STUB_REFERENCE if serve else STUB_REFERENCE_TRAIN).format(mark=mark)
        )
    with open(os.path.join(bench, "configs", "stub-model.json"), "w") as f:
        json.dump(config, f)
    for reader, (layer, unit, source, body) in STUB_READERS.items():
        with open(os.path.join(bench, "layer_metrics", f"{reader}.py"), "w") as f:
            f.write(
                f"LAYER, UNIT, SOURCE = {layer!r}, {unit!r}, {source!r}\n"
                f"def reduce(run):\n{body}"
            )
    entry = next(c for c in manifest["configs"] if c["name"] == base)
    manifest["configs"].append(
        dict(entry, name="stub-model", file="benchmark/configs/stub-model.json")
    )
    held = harness.find_cell(manifest, cell)
    manifest["workloads"].append({
        "name": "stub_cell", "config": "stub-model",
        "traffic": held["traffic"], "chips": held["chips"], "why": "stub",
    })
    moves = [
        m["name"] for m in harness.metrics_of_cell(manifest, "end_to_end", cell)
        if m["name"] != "setup_s"
    ][0]
    for section in ("end_to_end", "per_layer"):
        for metric in manifest[section]:
            if cell in metric.get("workloads", ()):
                metric["workloads"].append("stub_cell")
    for reader, (layer, unit, source, _) in STUB_READERS.items():
        manifest["per_layer"].append({
            "name": reader, "unit": unit, "better": "higher",
            "source": source, "layer": layer, "moves": moves,
            "workloads": ["stub_cell"],
        })
    write_manifest(root, manifest)
    return manifest
