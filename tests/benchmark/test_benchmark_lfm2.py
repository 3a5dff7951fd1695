"""`lfm2-24b-a2b-ep8` and `chat_sessions_conv`: the configuration's
file against the published row and this PR's one cut, the program and
the reference's plan building the same tree at the issue's arithmetic
(shapes only), the cell's traffic, the two readers this configuration
brings on hand-made runs. (The cell's walk-through on the CPU is
`tests/test_lfm2_cell_walkthrough.py`: `test_benchmark_grown.py` runs
this whole directory again in one process inside 600 s, and a fourth
whole-cell rehearsal here would not fit beside the three it has.)"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

MANIFEST = harness.load_manifest()
NAME, CELL = "lfm2-24b-a2b-ep8", "chat_sessions_conv"
HERE = any(c["name"] == NAME for c in MANIFEST["configs"])
pytestmark = pytest.mark.skipif(not HERE, reason=f"no {NAME} in this manifest")

#: config.json of LiquidAI/LFM2-24B-A2B (the model-configs guide's
#: catalog row): every key but `layer_types`, which follows.
PUBLISHED = dict(
    conv_L_cache=3, conv_bias=False, hidden_size=2048, intermediate_size=11776,
    max_position_embeddings=128000, model_type="lfm2_moe",
    moe_intermediate_size=1536, norm_eps=1e-05, norm_topk_prob=True,
    num_attention_heads=32, num_dense_layers=2, num_experts=64,
    num_experts_per_tok=4, num_hidden_layers=40, num_key_value_heads=8,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    routed_scaling_factor=1, use_expert_bias=True, vocab_size=65536,
)
CUT = dict(num_experts=8)
#: never a width: the floor of the model-configs guide, section 4
FLOORS = dict(num_experts=8)
ATTENTION, CONV = [0, 8, 1000000, False], [0, 0, 0, False, 3]


def _config():
    return harness.load_config(MANIFEST, NAME)


def test_config_keeps_every_published_key_but_the_one_cut():
    config = _config()
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry["source"] == config["source"] and "LFM2-24B-A2B" in entry["source"]
    changed = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert changed == set(CUT) == set(entry["reduced"]) == set(config["reduced"])
    for key, here in CUT.items():
        assert config[key] == here == config["reduced"][key]["here"]
        assert config["reduced"][key]["published"] == PUBLISHED[key]
        assert here >= FLOORS[key]
    # the pattern whole, as published: conv, conv, attention, conv, ten times
    types = config["layer_types"]
    assert len(types) == 40 == config["num_hidden_layers"]
    assert types == ["conv", "conv", "full_attention", "conv"] * 10
    assert [i for i, kind in enumerate(types) if kind == "full_attention"] == list(
        range(2, 40, 4)
    )
    assert "EP8" in config["deployment"] and len(config["assumed"]) >= 10
    assert config["reference"] == "lfm2_moe_ref" and config["dtype"] == "bfloat16"


def test_the_models_keys_are_the_published_widths_and_one_pattern_key():
    config = _config()
    model = config["model"]
    assert (
        model["dim"], model["n_layers"], model["n_heads"], model["n_kv_heads"],
        model["vocab_size"], model["norm_eps"], model["intermediate"],
        model["dense_intermediate"], model["dense_layers"],
        model["moe_router_experts"], model["moe_top_k"], model["max_seq_len"],
        model["moe_route_scale"],
    ) == tuple(PUBLISHED[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "vocab_size", "norm_eps",
        "moe_intermediate_size", "intermediate_size", "num_dense_layers",
        "num_experts", "num_experts_per_tok", "max_position_embeddings",
        "routed_scaling_factor",
    ))
    assert model["rope_theta"] == PUBLISHED["rope_parameters"]["rope_theta"]
    assert (model["qk_norm"], model["moe_router"]) == ("head", "sigmoid_groups")
    assert (model["moe_groups"], model["moe_top_groups"]) == (1, 1)
    assert (model["moe_experts"], model["moe_first_expert"]) == (CUT["num_experts"], 0)
    # ONE key holds the layers' kinds and the taps, layer_types spelt out
    assert CONV[4] == PUBLISHED["conv_L_cache"] and ATTENTION[1] == model["n_kv_heads"]
    assert model["layer_kinds"] == [
        CONV if kind == "conv" else ATTENTION for kind in config["layer_types"]
    ]
    from ray_tpu.models.llama import LlamaConfig

    new = set(model) - set(LlamaConfig.__dataclass_fields__)
    assert not new  # every key is one `LlamaConfig` has


def test_program_and_plan_build_one_tree_at_the_issues_arithmetic():
    """Shapes alone: the issue's 3,761 M parameters (7.52 GB in bf16)
    leaf by leaf, and the head as a leaf of its own beside them (the
    benchmark's weights are drawn a leaf at a time: 134 M more, 7.79
    GB); the cache 20,480 bytes a token as laid out; a conv layer's
    state 8,192 bytes a row."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import lfm2_moe_ref
    from ray_tpu.llm.kv_slots import PagedKVCache
    from ray_tpu.models.llama import LlamaConfig, init_params

    config = _config()
    cfg = LlamaConfig(**config["model"], dtype=jnp.dtype(config["dtype"]))
    tree = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    plan = lfm2_moe_ref.shapes(config["model"])
    flat = {
        "/".join(str(getattr(k, "key", k)) for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }
    assert flat == {path: tuple(shape) for path, (shape, _, _) in plan.items()}
    assert plan["layers/router_bias"][1] == (0.0, lfm2_moe_ref.BIAS_DEVIATION)

    def count(*paths):
        return sum(int(np.prod(flat[p])) for p in paths)

    d = 2048
    # an attention layer: Wq, Wo in the stacks every layer has, Wk, Wv
    # and the two norms of 64 in its kind's
    attention = 2 * d * d + count(
        "attn_full/wk", "attn_full/wv", "attn_full/q_norm", "attn_full/k_norm"
    ) // 10
    assert attention == 10_485_888
    # a conv layer: W_in's thirds (one of them under `wq`), W_out, the taps
    conv = 2 * d * d + count(
        "attn_conv/wc", "attn_conv/wu", "attn_conv/taps"
    ) // 30
    assert conv == 16_783_360 and flat["attn_conv/taps"] == (30, 3, d)
    assert flat["layers/wq"] == (38, d, d) and flat["dense_layers/wq"] == (2, d, d)
    assert count("layers/wq", "layers/wo", "dense_layers/wq", "dense_layers/wo") == (
        40 * 2 * d * d
    )
    experts = count("layers/w_gate", "layers/w_up", "layers/w_down")
    assert experts // (38 * 8) == 9_437_184
    assert experts // 38 + count("layers/router", "layers/router_bias") // 38 == (
        8 * 9_437_184 + 131_136
    )
    assert round(count("dense_layers/w1", "dense_layers/w2", "dense_layers/w3")
                 / 2e6, 2) == 72.35
    total = sum(int(np.prod(shape)) for shape in flat.values())
    head = count("lm_head")
    assert head == count("embed") == 134_217_728
    assert total - head == 3_761_333_888  # the issue's 3,761 M, tied
    assert round((total - head) * 2 / 1e9, 2) == 7.52
    assert total == 3_895_551_616 == cfg.num_params()
    assert round(total * 2 / 1e9, 2) == 7.79  # GB in bfloat16, as served
    engine = config["engine"]
    bl = engine["kv_block_len"]
    cache = jax.eval_shape(lambda: PagedKVCache(
        cfg, engine["kv_blocks"], bl, engine["max_len"],
        engine["prefill_chunk"], engine["slots"],
    ).pool)
    # 10 attention layers, a head's key and value in ONE 128-wide entry
    assert cache["kv"].shape == (10, engine["kv_blocks"], 8, bl, 128)
    assert "k" not in cache and "v" not in cache
    assert cache["kv"].dtype.itemsize * 10 * 8 * 128 == 20_480  # bytes a token
    assert engine["kv_blocks"] * bl == 262_144
    assert round(cache["kv"].size * 2 / 1e9, 2) == 5.37
    # 30 conv layers: a slot a row, a snapshot a chunk of the pool, the null slot
    snapshots = engine["kv_blocks"] * bl // engine["prefill_chunk"]
    assert engine["slots"] + snapshots + 1 == 577  # held in whole tiles of 16
    assert cache["conv_state"].shape == (30, 2, 592, d)
    assert 30 * 2 * d * 2 == 245_760  # bytes a slot
    assert cache["moe_counts"].shape == (38, 8) and cache["moe_routed"].shape == (38,)
    for what in ("the training layout", "hf_convert"):
        with pytest.raises(NotImplementedError, match="serve path only"):
            cfg.require_plain_attention(what)


def test_the_cell_and_its_traffic():
    from benchmark.traffic.lengths import quantile_lengths

    cell = harness.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "chat_sessions_closed", 1
    )
    assert len(MANIFEST["workloads"]) >= 8
    assert sum(c["chips"] == 4 for c in MANIFEST["workloads"]) == 1
    traffic = harness.load_traffic(cell["traffic"])
    assert traffic["kind"] == "serve_closed_requires" and traffic["source"]
    assert traffic["requires"] == ["ray_tpu/llm/kv_state.py"]
    assert (
        traffic["clients"], traffic["group_docs"], traffic["questions_per_doc"],
        traffic["question_tokens"], traffic["warmup_requests"],
        traffic["warmup_new_tokens"], traffic["trace_seconds"],
    ) == (96, 16, 4, 48, 4, 8, 4)
    assert traffic["answer_tokens"] == {"dist": "uniform", "min": 128, "max": 384}
    assert traffic["document_tokens"] == {
        "dist": "lognormal", "median": 2048, "sigma": 0.7, "min": 256, "max": 7680,
    }
    lengths = quantile_lengths(traffic["document_tokens"], 16)
    assert len(lengths) == 16 and lengths == sorted(lengths)
    assert 256 <= lengths[0] < 1024 and lengths[7] < 2048 < lengths[8]
    assert 6000 < lengths[-1] <= 7680
    config = _config()
    engine = config["engine"]
    assert (engine["slots"], engine["max_len"], engine["kv_block_len"],
            engine["kv_blocks"]) == (64, 8192, 16, 16384)
    assert 7680 + 48 + 384 <= engine["max_len"] and engine["prefix_cache"]
    assert traffic["clients"] > engine["slots"]  # every slot in use
    # the probe: a row inside a chunk, one a few tokens past a whole
    # chunk (its first decoded positions read state two chunks wrote),
    # one of more than two chunks
    chunk = engine["prefill_chunk"]
    inside, past, long_row = config["probe_lengths"]
    assert inside < chunk < past < chunk + 16 and long_row > 2 * chunk
    small_t, small = harness.apply_rehearsal(traffic), harness.apply_rehearsal(config)
    kinds = small["model"]["layer_kinds"]
    assert {bool(k[4]) if len(k) > 4 else False for k in kinds} == {True, False}
    assert small["model"]["dense_layers"] == 2 and len(kinds) >= 2 + 2 * 4
    assert (small["model"]["moe_experts"], small["model"]["moe_router_experts"]) == (4, 16)
    assert small_t["document_tokens"]["min"] > small["engine"]["prefill_chunk"]
    assert (
        small_t["document_tokens"]["max"] + small_t["question_tokens"]
        + small_t["answer_tokens"]["max"] <= small["engine"]["max_len"]
    )
    reported = {
        m["name"] for section in ("end_to_end", "per_layer")
        for m in harness.metrics_of_cell(MANIFEST, section, CELL)
    }
    assert {
        "serve_tokens_per_s", "setup_s", "conv_hit_kept_share",
        "conv_state_cache_share", "moe_held_pick_share", "moe_load_imbalance",
        "moe_roofline_share", "moe_kernel_share", "moe_experts_touched_share",
        "prefix_hit_token_share.tput", "kv_read_amplification.tput",
        "prefill_padding_share.tput", "engine_tokens_per_step.tput",
    } <= reported
    assert not {
        m for m in reported
        if m.startswith(("dsa_", "selected_attn", "swa_", "window_"))
    }
    # and the two readers print for this cell and for no other
    for reader in ("conv_hit_kept_share", "conv_state_cache_share"):
        entry = next(m for m in MANIFEST["per_layer"] if m["name"] == reader)
        assert entry["workloads"] == [CELL] and entry["layer"] == "engine"
        assert entry["moves"] == "serve_tokens_per_s"


def test_the_expert_counts_are_right_at_8_of_64_top_4():
    """`moe_roofline_share` reads this configuration through
    `benchmark/moe_flops.py` as it reads the other three: a pick is one
    token through one HELD expert's three matrices."""
    from benchmark import moe_flops

    model = _config()["model"]
    assert moe_flops.pick_flops(model) == 3 * 2 * 2048 * 1536
    assert moe_flops.expert_bytes(model) == 9_437_184 * 2
    need = moe_flops.required(model, picks=1000, experts_touched=8 * 38)
    assert need == {
        "flops": 1000 * 18_874_368, "bytes": 8 * 38 * 18_874_368,
    }


def _engine_run(before, after):
    return {"engine": {"before": before, "after": after}}


def test_conv_hit_kept_share_is_what_the_evicted_snapshots_cost():
    reduce = harness.load_module("layer_metrics", "conv_hit_kept_share").reduce
    run = _engine_run(
        {"prefix_tokens_saved": 4_096, "prefix_tokens_full_hit": 4_096,
         "conv_hits_restored": 2},
        {"prefix_tokens_saved": 4_096 + 30_720,
         "prefix_tokens_full_hit": 4_096 + 40_960, "conv_hits_restored": 22},
    )
    assert reduce(run) == 75.0


def test_conv_state_cache_share_is_the_states_bytes_beside_the_pages():
    reduce = harness.load_module("layer_metrics", "conv_state_cache_share").reduce
    run = _engine_run(
        {"conv_state_bytes_in_use": 1, "kv_bytes_in_use": 1},  # not read
        {"conv_state_bytes_in_use": 245_760 * 400, "kv_bytes_in_use": 327_680 * 14_700},
    )
    assert reduce(run) == pytest.approx(
        100 * 98_304_000 / (98_304_000 + 4_816_896_000)
    )  # 2.0 %


@pytest.mark.parametrize("reader", ["conv_hit_kept_share", "conv_state_cache_share"])
@pytest.mark.parametrize("run", [
    {}, {"engine": None},
    _engine_run({}, {"kv_keys_read": 5, "prefix_tokens_saved": 3}),  # an older program
    _engine_run(
        {}, {"prefix_tokens_saved": 3, "prefix_tokens_full_hit": 4,
             "window_blocks_used": 2},
    ),  # a window pool's engine: the other hit counter, no states
    _engine_run(
        {"prefix_tokens_saved": 0, "prefix_tokens_full_hit": 0,
         "conv_hits_restored": 0, "conv_state_bytes_in_use": 0,
         "kv_bytes_in_use": 0},
        {"prefix_tokens_saved": 0, "prefix_tokens_full_hit": 0,
         "conv_hits_restored": 0, "conv_state_bytes_in_use": 0,
         "kv_bytes_in_use": 0},
    ),  # nothing moved in the window, nothing held
], ids=["empty", "no_engine", "older_program", "window_pool", "idle_window"])
def test_the_new_readers_give_nothing_where_there_is_nothing(reader, run):
    assert harness.load_module("layer_metrics", reader).reduce(run) is None


def test_the_manifest_grew_by_appended_entries_alone():
    """What `python3 -m benchmark.manifest_diff` says of this manifest
    against the one before this PR: every list cut where this PR's
    first entry stands (what a later PR appended behind goes with it)."""
    from benchmark import manifest_diff

    mine = {NAME, CELL, "conv_hit_kept_share", "conv_state_cache_share"}

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
    assert len(old["workloads"]) == 7 and len(old["configs"]) == 6


def test_the_mix_refuses_a_checkout_without_the_state_slots(monkeypatch):
    """The parent commit under this benchmark has no
    `ray_tpu/llm/kv_state.py`: the cell ends before a cluster starts,
    exit code 1, where the warm-up would retry for 1,000 s."""
    from benchmark.traffic import serve_closed

    traffic = harness.apply_rehearsal(harness.load_traffic("chat_sessions_closed"))
    gated = harness.load_module("traffic", traffic["kind"])
    assert gated.warmup(traffic, 7, 512) == serve_closed.warmup(traffic, 7, 512)
    assert all(
        os.path.exists(os.path.join(ROOT, path)) for path in traffic["requires"]
    )
    monkeypatch.setattr(gated, "ROOT", os.path.join(ROOT, "benchmark"))
    with pytest.raises(harness.BenchmarkError, match="no ray_tpu/llm/kv_state.py"):
        gated.warmup(traffic, 7, 512)
