"""`trinity-mini-ep8` and `pretrain_8k_moe_swa`: the configuration's
file against the catalog's row and PR 55's cut, the program and the
reference's plan building the same tree at the issue's arithmetic
(shapes only), the window-operations count against a brute-force sum,
the two per-layer entries the cell brings on hand-made runs, and the
manifest grown by appends alone.

The cell's walk-through on the CPU is
`tests/test_trinity_cell_walkthrough.py`."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

NAME, CELL = "trinity-mini-ep8", "pretrain_8k_moe_swa"
FILE = os.path.join(ROOT, "benchmark", "configs", f"{NAME}.json")


MANIFEST = harness.load_manifest()

SLIDING, FULL = "sliding_attention", "full_attention"
#: config.json of arcee-ai/Trinity-Mini as the model-configs guide's
#: catalog has it (architectures.jsonl, line 8): every key.
PUBLISHED = dict(
    global_attn_every_n_layers=4, head_dim=128, hidden_act="silu",
    hidden_size=2048, intermediate_size=6144,
    layer_types=[SLIDING, SLIDING, SLIDING, FULL] * 8,
    load_balance_coeff=0.001, max_position_embeddings=131072,
    model_type="afmoe", moe_intermediate_size=1024, mup_enabled=True,
    n_group=1, num_attention_heads=32, num_dense_layers=2,
    num_expert_groups=1, num_experts=128, num_experts_per_tok=8,
    num_hidden_layers=32, num_key_value_heads=4, num_limited_groups=1,
    num_shared_experts=1, rms_norm_eps=1e-05, rope_scaling=None,
    rope_theta=10000, route_norm=True, route_scale=2.826,
    score_func="sigmoid", sliding_window=2048, tie_word_embeddings=False,
    topk_group=1, use_grouped_mm=True, vocab_size=200192,
)
#: never a width: the floors of the model-configs guide, section 4 (a
#: whole period and four expert layers behind the dense one, 8 routed
#: experts, an eighth of the vocabulary)
FLOORS = dict(
    num_hidden_layers=5, num_dense_layers=1, num_experts=8,
    vocab_size=200192 // 8,
)


def _config():
    return harness.load_config(MANIFEST, NAME)


def test_config_keeps_every_published_key_but_the_four_cuts():
    config = _config()
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["source"] == config["source"]
    assert entry["source"].endswith("arcee-ai/Trinity-Mini/blob/main/config.json")
    changed = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert changed == set(entry["reduced"]) == set(config["reduced"]) == set(FLOORS)
    for key, floor in FLOORS.items():
        cut = config["reduced"][key]
        assert cut["published"] == PUBLISHED[key] and cut["why"]
        assert cut["here"] == config[key] >= floor
    assert config["num_experts"] == 16 and config["vocab_size"] == 25024
    assert "EP8" in config["deployment"] and "512 picks" in config["deployment"]
    # the four terms the config has no key for, each with its source
    for point in (
        "attention_gate", "post_norms", "qk_norm", "no_rotary_on_full_layers"
    ):
        assert "modeling_afmoe" in config["assumed"][point]
    assert "load_balance_coeff" in config["assumed"]["left_out"]
    assert config["reference"] == "trinity_ref" and config["tolerance"]["why"]
    # the one key of the trainer that is not Mistral's, and why
    mistral = harness.load_config(MANIFEST, "mistral-7b-v0.3-l4")["trainer"]
    trainer = json.loads(json.dumps(config["trainer"]))
    assert trainer["optimizer"].pop("warmup_steps") == 2000
    mistral["optimizer"].pop("warmup_steps")
    assert trainer == mistral and "2,000" in config["trainer_why"]


def test_the_models_keys_are_the_published_widths_and_one_pattern_key():
    config = _config()
    model = config["model"]
    assert (
        model["dim"], model["n_heads"], model["custom_head_dim"],
        model["intermediate"], model["dense_intermediate"],
        model["moe_router_experts"], model["moe_top_k"],
        model["moe_route_scale"], model["moe_groups"],
        model["moe_top_groups"], model["norm_eps"],
    ) == tuple(PUBLISHED[k] for k in (
        "hidden_size", "num_attention_heads", "head_dim",
        "moe_intermediate_size", "intermediate_size", "num_experts",
        "num_experts_per_tok", "route_scale", "n_group", "topk_group",
        "rms_norm_eps",
    ))
    assert model["moe_shared_intermediate"] == (
        PUBLISHED["num_shared_experts"] * PUBLISHED["moe_intermediate_size"]
    )
    assert model["moe_router"] == "sigmoid_groups" and model["embed_scale"]
    assert model["attn_gate"] and model["post_norms"]
    assert model["qk_norm"] == "head" and model["moe_aux_weight"] == 0.0
    window = [PUBLISHED["sliding_window"], PUBLISHED["num_key_value_heads"],
              float(PUBLISHED["rope_theta"]), False]
    full = [0, PUBLISHED["num_key_value_heads"], 0, False]
    # ONE key holds the layers' kinds: a published leading dense layer
    # (both are sliding layers) and whole periods behind it
    depth = config["num_hidden_layers"]
    published = [1] + list(range(4, 4 + depth - 1))
    assert model["layer_kinds"] == [
        window if PUBLISHED["layer_types"][layer] == SLIDING else full
        for layer in published
    ]
    assert (depth - 1) % PUBLISHED["global_attn_every_n_layers"] == 0
    assert model["n_layers"] == depth and model["dense_layers"] == 1
    assert (model["moe_experts"], model["vocab_size"]) == (16, 25024)
    assert model["moe_first_expert"] == 0 and model["max_seq_len"] == 8192
    assert config["trainer"]["attention"] == "flash"
    small = harness.apply_rehearsal(config)["model"]
    kinds = small["layer_kinds"]
    assert {bool(k[0]) for k in kinds} == {True, False}  # both kinds
    assert small["dense_layers"] == 1 and small["moe_shared_intermediate"]
    assert (small["moe_experts"], small["moe_router_experts"]) == (2, 8)
    stream = harness.apply_rehearsal(harness.load_traffic("stream_8k"))
    assert max(k[0] for k in kinds) < stream["seq_len"]  # the window bites


def test_program_and_plan_build_one_tree_at_the_issues_arithmetic():
    """Shapes alone. Outside the experts a layer has 27.26 M (wq, wk,
    wv, wo and the gate), the shared expert 6.29 M, the router 0.26 M,
    a routed expert 6.29 M; a held expert layer 134.5 M, the dense
    layer 65.0 M, embedding and head 102.5 M at 25,024 rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import trinity_ref
    from ray_tpu.models.llama import LlamaConfig, init_params

    config = _config()
    cfg = LlamaConfig(**config["model"], dtype=jnp.dtype(config["dtype"]))
    tree = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    plan = trinity_ref.shapes(config["model"])
    flat = {
        "/".join(str(getattr(k, "key", k)) for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }
    assert flat == {path: tuple(shape) for path, (shape, _, _) in plan.items()}
    expert_layers = cfg.n_layers - 1
    windows = sum(1 for kind in cfg.layer_kinds if kind.window)

    def count(*paths):
        return sum(int(np.prod(flat[p])) for p in paths)

    attention = (
        count("layers/wq", "layers/wo", "layers/wg") / expert_layers
        + count("attn_window/wk", "attn_window/wv") / windows
    )
    assert round(attention / 1e6, 2) == 27.26
    assert flat["layers/wg"] == (expert_layers, 2048, 4096)
    shared = count("layers/shared_gate", "layers/shared_up", "layers/shared_down")
    assert round(shared / expert_layers / 1e6, 2) == 6.29
    assert round(count("layers/router") / expert_layers / 1e6, 2) == 0.26
    experts = count("layers/w_gate", "layers/w_up", "layers/w_down")
    assert round(experts / (expert_layers * 16) / 1e6, 2) == 6.29
    dense = count("dense_layers/w1", "dense_layers/w2", "dense_layers/w3")
    assert round((attention + dense) / 1e6, 1) == 65.0
    assert round(
        (attention + (shared + experts + count("layers/router")) / expert_layers)
        / 1e6, 1
    ) == 134.5
    assert round(count("embed", "lm_head") / 1e6, 1) == 102.5
    total = sum(int(np.prod(shape)) for shape in flat.values())
    assert cfg.num_params() == total
    assert round(total / 1e6, 1) == round(
        65.0 + expert_layers * 134.5 + 102.5, 1
    ) or abs(total / 1e6 - (65.0 + expert_layers * 134.5 + 102.5)) < 0.5


@pytest.mark.parametrize("seq_len, window", [
    (1, 0), (17, 0), (17, 1), (17, 5), (17, 17), (17, 40), (64, 16),
])
def test_seen_keys_is_the_brute_force_sum(seq_len, window):
    from benchmark.window_flops import seen_keys

    want = sum(
        min(p + 1, window) if window else p + 1 for p in range(seq_len)
    )
    assert seen_keys(seq_len, window) == want


def test_window_operations_count_a_layer_by_its_kind():
    from benchmark import flops, window_flops

    model = _config()["model"]
    per_pair = 2 * 2 * 32 * 128
    kinds = [bool(k[0]) for k in model["layer_kinds"]]
    want = per_pair * (
        kinds.count(True) * sum(min(p + 1, 2048) for p in range(8192))
        + kinds.count(False) * sum(p + 1 for p in range(8192))
    )
    assert window_flops.attention_flops_fwd(model, 8192) == want
    # a window layer's keys a query: 1,792 on average, a full layer's 4,096
    assert round(window_flops.seen_keys(8192, 2048) / 8192) == 1792
    assert round(window_flops.seen_keys(8192, 0) / 8192) == 4096
    # a model whose layers are all full: what `flops.py` counts
    mistral = harness.load_config(MANIFEST, "mistral-7b-v0.3-l4")["model"]
    assert window_flops.attention_flops_fwd(mistral, 8192) == pytest.approx(
        8192 * flops.attention_flops_per_token_fwd(mistral, 8192)
    )
    # and over this cell's layers that count is too high by the share
    # a kernel that skips its blocks would read past its roofline by
    plain = 8192 * flops.attention_flops_per_token_fwd(
        dict(model, n_kv_heads=4), 8192
    )
    assert 1.6 < plain / want < 1.9


def _traced_run(ops, busy=2.0):
    config, cell = _config(), harness.find_cell(MANIFEST, CELL)
    return {
        "cell": cell, "config": config,
        "traffic": harness.load_traffic(cell["traffic"]),
        "seq_len": 8192, "tokens_per_step": 8192,
        "device": {"kind": "TPU v5 lite"},
        "trace": {"device_ops": ops, "busy_s": busy},
    }


def test_the_two_new_readers_on_a_hand_made_trace():
    from benchmark import flops, window_flops

    run = _traced_run([
        ["fusion", 0.9], ["flash_bwd", 0.20], ["ragged-dot-none", 0.09],
        ["flash_fwd", 0.08], ["ragged-dot-metadata", 0.01],
    ])
    roofline = harness.load_module(
        "layer_metrics", "flash_window_roofline_share"
    ).reduce(run)
    peak = flops.peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    required = 4 * 3 * window_flops.attention_flops_fwd(
        run["config"]["model"], 8192
    )
    assert roofline == pytest.approx(100 * required / peak / 0.28)
    assert 0 < roofline < 100
    share = harness.load_module("layer_metrics", "moe_kernel_share").reduce(run)
    assert share == pytest.approx(100 * 0.10 / 2.0)


@pytest.mark.parametrize("run", [
    {}, {"trace": None}, {"trace": {"device_ops": [], "busy_s": 1.0}},
    {"trace": {"device_ops": [["fusion", 1.0]], "busy_s": 1.0}},
], ids=["no_trace", "none", "no_ops", "an_older_program"])
def test_the_new_reader_gives_nothing_where_there_is_nothing(run):
    run = dict(_traced_run([]), **run)
    reduce = harness.load_module(
        "layer_metrics", "flash_window_roofline_share"
    ).reduce
    assert reduce(run) is None


def test_the_cell_and_what_it_reports():
    cell = harness.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "stream_8k", 1
    )
    assert "512 picks" in cell["why"] and len(cell["why"]) <= 200
    reported = {
        m["name"] for section in ("end_to_end", "per_layer")
        for m in harness.metrics_of_cell(MANIFEST, section, CELL)
    }
    assert reported == {
        "train_tokens_per_s_chip", "setup_s", "data_wait_share",
        "peak_hbm_gb.train", "device_idle_share.train", "flash_kernel_share",
        "flash_window_roofline_share", "moe_kernel_share.train",
    }
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in ("flash_window_roofline_share", "moe_kernel_share.train"):
        assert by_name[name]["moves"] == "train_tokens_per_s_chip"
        assert CELL in by_name[name]["workloads"]
    # plain causal attention over every layer is not this cell's count
    assert CELL not in by_name["flash_roofline_share"]["workloads"]
    assert CELL not in by_name["collective_exposed_share"]["workloads"]


def test_every_check_of_a_manifest_holds_with_the_cell_admitted():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import manifest_checks as checks  # this directory

    assert checks.walk(MANIFEST) > 100
    assert sum(c["chips"] == 4 for c in MANIFEST["workloads"]) == 1


def test_admitting_the_cell_appends_entries_and_changes_none():
    """What `python3 -m benchmark.manifest_diff` says of the manifest
    with the cell against the one without: every list cut where the
    cell's first entry stands (what a later PR appended behind goes
    with it)."""
    from benchmark import manifest_diff

    mine = {
        NAME, CELL, "flash_window_roofline_share", "moe_kernel_share.train"
    }

    def before(entries, name=lambda e: e["name"]):
        names = [name(e) for e in entries]
        first = min((names.index(n) for n in mine if n in names), default=None)
        return entries[:first]

    old = json.loads(json.dumps(MANIFEST))
    for section in ("configs", "workloads", "per_layer"):
        old[section] = before(old[section])
    for section in ("end_to_end", "per_layer"):
        for metric in old[section]:
            if "workloads" in metric:
                metric["workloads"] = before(metric["workloads"], str)
    appended, problems = manifest_diff.diff(old, MANIFEST)
    assert not problems, problems
    assert any(CELL in line for line in appended)
