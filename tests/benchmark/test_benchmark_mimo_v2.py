"""`mimo-v2-flash-l7-ep16` and `agent_mixed_swa`: the configuration's
file against the published row and this PR's cut, the program and the
reference's plan building the same tree at the issue's arithmetic
(shapes only), the cell's traffic, the two readers this configuration
brings on hand-made runs, and the cell's walk-through on the CPU."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

MANIFEST = harness.load_manifest()
NAME, CELL = "mimo-v2-flash-l7-ep16", "agent_mixed_swa"
HERE = any(c["name"] == NAME for c in MANIFEST["configs"])
pytestmark = pytest.mark.skipif(not HERE, reason=f"no {NAME} in this manifest")

#: config.json of XiaomiMiMo/MiMo-V2-Flash (the model-configs guide's
#: catalog row): every key but the two per-layer lists, which follow.
PUBLISHED = dict(
    attention_value_scale=0.707, hidden_act="silu", hidden_size=4096,
    intermediate_size=16384, max_position_embeddings=262144,
    model_type="mimo_v2_flash", num_attention_heads=64, head_dim=192,
    num_hidden_layers=48, num_key_value_heads=4, layernorm_epsilon=1e-05,
    rope_theta=5000000, tie_word_embeddings=False, vocab_size=152576,
    partial_rotary_factor=0.334, sliding_window=128, swa_rope_theta=10000,
    attention_bias=False, v_head_dim=128, add_swa_attention_sink_bias=True,
    add_full_attention_sink_bias=False, sliding_window_size=128,
    attention_chunk_size=128, moe_intermediate_size=2048,
    n_routed_experts=256, n_shared_experts=None, num_experts_per_tok=8,
    norm_topk_prob=True, scoring_func="sigmoid", n_group=1, topk_group=1,
    topk_method="noaux_tc", routed_scaling_factor=None,
    swa_num_attention_heads=64, swa_num_key_value_heads=8, swa_head_dim=192,
    swa_v_head_dim=128,
)
CUT = dict(
    num_hidden_layers=7, n_routed_experts=16, vocab_size=19072,
    num_nextn_predict_layers=0,
)
#: never a width: the floors of the model-configs guide, section 4
FLOORS = dict(num_hidden_layers=5, n_routed_experts=8, vocab_size=152576 // 8)


def _config():
    return harness.load_config(MANIFEST, NAME)


def test_config_keeps_every_published_key_but_the_four_cuts():
    config = _config()
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["source"] == config["source"] and "MiMo-V2-Flash" in entry["source"]
    changed = {k for k, v in PUBLISHED.items() if config[k] != v}
    # (the row has no key for the multi-token-prediction layers its
    # `described_as` names: the file adds the family's and cuts it)
    assert changed | {"num_nextn_predict_layers"} == set(CUT)
    assert set(CUT) == set(entry["reduced"]) == set(config["reduced"])
    for key, here in CUT.items():
        assert config[key] == here == config["reduced"][key]["here"]
        assert config["reduced"][key]["published"] == PUBLISHED.get(key, 3)
        assert here >= FLOORS.get(key, 0)
    # the two lists whole, as published: 5 window layers to 1 full
    pattern = config["hybrid_layer_pattern"]
    assert len(pattern) == 48 == len(config["moe_layer_freq"])
    assert pattern[:7] == [0, 1, 1, 1, 1, 0, 1] and sum(pattern) == 39
    assert [i for i, kind in enumerate(pattern) if not kind] == [
        0, 5, 11, 17, 23, 29, 35, 41, 47
    ]
    assert config["moe_layer_freq"] == [0] + [1] * 47
    assert "EP16" in config["deployment"] and len(config["assumed"]) >= 8


def test_the_models_keys_are_the_published_widths_and_one_pattern_key():
    config = _config()
    model = config["model"]
    assert (
        model["dim"], model["n_heads"], model["custom_head_dim"],
        model["v_head_dim"], model["value_scale"], model["norm_eps"],
        model["intermediate"], model["dense_intermediate"],
        model["moe_router_experts"], model["moe_top_k"], model["moe_groups"],
        model["moe_top_groups"], model["max_seq_len"],
    ) == tuple(PUBLISHED[k] for k in (
        "hidden_size", "num_attention_heads", "head_dim", "v_head_dim",
        "attention_value_scale", "layernorm_epsilon", "moe_intermediate_size",
        "intermediate_size", "n_routed_experts", "num_experts_per_tok",
        "n_group", "topk_group", "max_position_embeddings",
    ))
    assert model["rotary_dim"] == int(192 * PUBLISHED["partial_rotary_factor"]) == 64
    assert model["moe_route_scale"] == 1.0  # routed_scaling_factor null
    full = [0, PUBLISHED["num_key_value_heads"], PUBLISHED["rope_theta"],
            PUBLISHED["add_full_attention_sink_bias"]]
    window = [PUBLISHED["sliding_window"], PUBLISHED["swa_num_key_value_heads"],
              PUBLISHED["swa_rope_theta"], PUBLISHED["add_swa_attention_sink_bias"]]
    # ONE key holds the layers' kinds, hybrid_layer_pattern's first seven
    assert model["layer_kinds"] == [
        window if kind else full for kind in config["hybrid_layer_pattern"][:7]
    ]
    assert model["dense_layers"] == config["moe_layer_freq"][:7].count(0) == 1
    assert (model["moe_experts"], model["vocab_size"], model["n_layers"]) == (
        CUT["n_routed_experts"], CUT["vocab_size"], CUT["num_hidden_layers"]
    )


def test_program_and_plan_build_one_tree_at_the_issues_arithmetic():
    """Shapes alone: 3,429.96 M parameters (the issue's 3,429.9 M and the
    norm weights, sinks and correction biases), 6.86 GB in bf16; the cache
    a token 2,560 bytes a full layer and 5,120 a window layer as
    counted, 3,072 and 6,144 in the whole lanes the chip keeps."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import mimo_v2_ref
    from ray_tpu.llm.kv_slots import PagedKVCache
    from ray_tpu.models.llama import LlamaConfig, init_params

    config = _config()
    cfg = LlamaConfig(**config["model"], dtype=jnp.dtype(config["dtype"]))
    tree = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    plan = mimo_v2_ref.shapes(config["model"])
    flat = {
        "/".join(str(getattr(k, "key", k)) for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }
    assert flat == {path: tuple(shape) for path, (shape, _, _) in plan.items()}
    assert plan["attn_window/sink"][:2] == ((5, 64), (0.0, mimo_v2_ref.SINK_DEVIATION))
    assert "attn_full/sink" not in plan

    def count(*paths):
        return sum(int(np.prod(flat[p])) for p in paths)

    def attention(kind, layers):  # one layer's wq, wk, wv, wo
        shared = count("layers/wq", "layers/wo") / 6
        return shared + count(f"attn_{kind}/wk", f"attn_{kind}/wv") / layers

    assert flat["dense_layers/wq"] == (1,) + flat["layers/wq"][1:]
    assert round(attention("full", 2) / 1e6, 2) == 89.13
    assert round(attention("window", 5) / 1e6, 2) == 94.37
    assert round(count("dense_layers/w1", "dense_layers/w2", "dense_layers/w3")
                 / 1e6, 2) == 201.33
    experts = count("layers/w_gate", "layers/w_up", "layers/w_down")
    assert round(experts / (6 * 16) / 1e6, 2) == 25.17
    assert round(count("layers/router") / 6e6, 2) == 1.05
    total = sum(int(np.prod(shape)) for shape in flat.values())
    assert total == 3_429_955_392 and cfg.num_params() == total
    assert round(total * 2 / 1e9, 2) == 6.86  # GB in bfloat16
    engine = config["engine"]
    bl = engine["kv_block_len"]
    cache = jax.eval_shape(lambda: PagedKVCache(
        cfg, engine["kv_blocks"], bl, engine["max_len"],
        engine["prefill_chunk"], engine["slots"],
    ).pool)
    assert {n: a.shape[0] for n, a in cache.items() if n[-1] in "kv"} == {
        "k": 2, "v": 2, "window_k": 5, "window_v": 5,
    }
    # kv heads of each kind; a 192-wide key kept in two whole lanes
    assert cache["k"].shape[2:] == (4, bl, 256) and cache["v"].shape[2:] == (4, bl, 128)
    assert cache["window_k"].shape[2:] == (8, bl, 256)
    assert cache["window_v"].shape[2:] == (8, bl, 128)
    assert cache["k"].shape[1] == engine["kv_blocks"] >= 22_000
    ring = -(-(128 - 1 + engine["prefill_chunk"]) // bl) + 1
    tails = engine["kv_blocks"] * bl // engine["prefill_chunk"] * 8
    assert cache["window_k"].shape[1] == engine["slots"] * ring + tails + 1
    assert cache["moe_counts"].shape == (6, 16) and cache["moe_routed"].shape == (6,)


def test_the_cell_and_its_traffic():
    from benchmark.traffic.lengths import quantile_lengths

    cell = harness.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "agent_mixed_closed", 1
    )
    assert sum(c["chips"] == 4 for c in MANIFEST["workloads"]) == 1
    traffic = harness.load_traffic(cell["traffic"])
    assert traffic["kind"] == "serve_closed_requires" and traffic["source"]
    assert traffic["requires"] == ["ray_tpu/llm/kv_window.py"]
    assert (
        traffic["clients"], traffic["group_docs"], traffic["questions_per_doc"],
        traffic["question_tokens"], traffic["warmup_requests"],
        traffic["warmup_new_tokens"], traffic["trace_seconds"],
    ) == (48, 16, 3, 64, 4, 8, 4)
    assert traffic["answer_tokens"] == {"dist": "uniform", "min": 256, "max": 768}
    lengths = quantile_lengths(traffic["document_tokens"], 16)
    assert lengths == [
        923, 1427, 1826, 2201, 2577, 2969, 3388, 3847, 4361, 4952, 5651,
        6510, 7623, 9189, 11757, 14336,
    ]
    config = _config()
    engine, window = config["engine"], config["sliding_window"]
    assert (engine["slots"], engine["max_len"], engine["kv_block_len"]) == (32, 16384, 16)
    assert 14336 + 64 + 768 <= engine["max_len"] and engine["prefix_cache"]
    # the probe: a row inside the window, one that decoding carries
    # across it, one past two chunks and a window
    inside, crossing, long_row = config["probe_lengths"]
    assert inside + 16 < window < crossing + 16 and crossing < window
    assert long_row > 2 * engine["prefill_chunk"] + window
    small_t, small = harness.apply_rehearsal(traffic), harness.apply_rehearsal(config)
    kinds = small["model"]["layer_kinds"]
    assert {bool(k[0]) for k in kinds} == {True, False}  # both kinds
    assert max(k[0] for k in kinds) < small_t["document_tokens"]["min"]
    assert (
        small_t["document_tokens"]["max"] + small_t["question_tokens"]
        + small_t["answer_tokens"]["max"] <= small["engine"]["max_len"]
    )
    reported = {
        m["name"] for section in ("end_to_end", "per_layer")
        for m in harness.metrics_of_cell(MANIFEST, section, CELL)
    }
    assert {
        "serve_tokens_per_s", "setup_s", "swa_key_share", "window_hit_kept_share",
        "moe_held_pick_share", "moe_load_imbalance", "moe_roofline_share",
        "moe_kernel_share", "moe_experts_touched_share",
        "prefix_hit_token_share.tput", "kv_read_amplification.tput",
        "prefill_padding_share.tput",
    } <= reported
    assert not {m for m in reported if m.startswith(("dsa_", "selected_attn"))}


def _engine_run(before, after):
    return {"engine": {"before": before, "after": after}}


def test_swa_key_share_is_what_the_window_layers_walked_of_the_whole_rows():
    reduce = harness.load_module("layer_metrics", "swa_key_share").reduce
    run = _engine_run(
        {"swa_keys_read": 1_000, "swa_keys_unwindowed": 50_000},
        {"swa_keys_read": 5_608, "swa_keys_unwindowed": 242_000},
    )
    assert reduce(run) == pytest.approx(100 * 4_608 / 192_000)  # 2.4 %
    same = _engine_run({}, {"swa_keys_read": 7, "swa_keys_unwindowed": 7})
    assert reduce(same) == 100.0  # the window is not used


def test_window_hit_kept_share_is_what_the_evicted_tails_cost():
    reduce = harness.load_module("layer_metrics", "window_hit_kept_share").reduce
    run = _engine_run(
        {"prefix_tokens_saved": 4_096, "prefix_tokens_full_hit": 4_096},
        {"prefix_tokens_saved": 4_096 + 30_720, "prefix_tokens_full_hit": 4_096 + 40_960},
    )
    assert reduce(run) == 75.0


@pytest.mark.parametrize("reader", ["swa_key_share", "window_hit_kept_share"])
@pytest.mark.parametrize("run", [
    {}, {"engine": None},
    _engine_run({}, {"kv_keys_read": 5, "prefix_tokens_saved": 3}),  # no window layers
    _engine_run(
        {"swa_keys_read": 4, "swa_keys_unwindowed": 9, "prefix_tokens_saved": 0,
         "prefix_tokens_full_hit": 0},
        {"swa_keys_read": 4, "swa_keys_unwindowed": 9, "prefix_tokens_saved": 0,
         "prefix_tokens_full_hit": 0},
    ),  # nothing moved in the window
], ids=["empty", "no_engine", "older_program", "idle_window"])
def test_the_new_readers_give_nothing_where_there_is_nothing(reader, run):
    assert harness.load_module("layer_metrics", reader).reduce(run) is None


def test_the_manifest_grew_by_appended_entries_alone():
    """What `python3 -m benchmark.manifest_diff` says of this manifest
    against the one before this PR: every list cut where this PR's
    first entry stands (what a later PR appended behind goes with it)."""
    from benchmark import manifest_diff

    mine = {NAME, CELL, "swa_key_share", "window_hit_kept_share"}

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
    assert len(old["workloads"]) == 6 and len(old["configs"]) == 5


def test_the_mix_refuses_a_checkout_without_the_window_pool(monkeypatch):
    """The parent commit under this benchmark has no
    `ray_tpu/llm/kv_window.py`: the cell ends before a cluster starts,
    exit code 1, where the warm-up would retry for 1,000 s."""
    from benchmark.traffic import serve_closed

    traffic = harness.apply_rehearsal(harness.load_traffic("agent_mixed_closed"))
    gated = harness.load_module("traffic", traffic["kind"])
    assert gated.warmup(traffic, 7, 512) == serve_closed.warmup(traffic, 7, 512)
    assert all(
        os.path.exists(os.path.join(ROOT, path)) for path in traffic["requires"]
    )
    monkeypatch.setattr(gated, "ROOT", os.path.join(ROOT, "benchmark"))
    with pytest.raises(harness.BenchmarkError, match="no ray_tpu/llm/kv_window.py"):
        gated.warmup(traffic, 7, 512)


@pytest.mark.timeout(900)
def test_the_cell_walks_through_on_the_cpu(tmp_path):
    """`run.py --workload agent_mixed_swa --rehearse --trace 1` on a
    copy of the checkout: HTTP -> proxy -> router -> replica -> engine
    over two page pools at the rehearsal's sizes (both kinds of layer,
    a window of 12 keys under documents of 40-160 tokens and answers
    longer than the window), float32, `correct` against the reference,
    prefix hits through the window pool's kept tails, and the cell's
    own readers among the names."""
    import subprocess

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import manifest_checks as checks  # this directory

    root = checks.checkout(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(
        JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_cpu_multi_thread_eigen=false",
        OMP_NUM_THREADS="1",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 47), "--rehearse",
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
        "swa_key_share", "window_hit_kept_share", "moe_held_pick_share",
        "moe_load_imbalance", "moe_experts_touched_share",
        "prefix_hit_token_share.tput", "kv_read_amplification.tput",
    } <= set(line["metric_names"])
    notes = json.loads(
        next(x for x in lines if x.startswith("[benchmark] notes "))[18:]
    )
    assert notes["probe"]["reference"].endswith("mimo_v2_ref")
    assert notes["engine_window"]["prefix_hit_token_share"] > 20.0
    assert notes["compiles_in_window"] == 0
