"""`deepseek-v3.2-l5-ep16` and `longdoc_qa_dsa`: the configuration's
file against the published row and this PR's cut, the program building
it at its published widths (shapes only), the cell's traffic, and the
two readers this configuration brings, on hand-made runs."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

MANIFEST = harness.load_manifest()
NAME, CELL = "deepseek-v3.2-l5-ep16", "longdoc_qa_dsa"
HERE = any(c["name"] == NAME for c in MANIFEST["configs"])
pytestmark = pytest.mark.skipif(not HERE, reason=f"no {NAME} in this manifest")

#: config.json of deepseek-ai/DeepSeek-V3.2 (the model-configs guide's
#: catalog row): every number.
PUBLISHED = dict(
    first_k_dense_replace=3, hidden_size=7168, index_head_dim=128,
    index_n_heads=64, index_topk=2048, intermediate_size=18432,
    kv_lora_rank=512, max_position_embeddings=163840,
    moe_intermediate_size=2048, moe_layer_freq=1, n_group=8,
    n_routed_experts=256, n_shared_experts=1, num_attention_heads=128,
    num_experts_per_tok=8, num_hidden_layers=61, num_key_value_heads=128,
    num_nextn_predict_layers=1, q_lora_rank=1536, qk_nope_head_dim=128,
    qk_rope_head_dim=64, rms_norm_eps=1e-06, rope_theta=10000,
    routed_scaling_factor=2.5, topk_group=4, v_head_dim=128,
    vocab_size=129280, ep_size=1,
)
CUT = dict(
    num_hidden_layers=5, n_routed_experts=16, vocab_size=16160,
    num_nextn_predict_layers=0,
)
#: never a width: the floors of the model-configs guide, section 4
FLOORS = dict(num_hidden_layers=5, n_routed_experts=8, vocab_size=129280 // 8)


def _config():
    return harness.load_config(MANIFEST, NAME)


def test_config_keeps_every_published_number_but_the_four_cuts():
    config = _config()
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    changed = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert changed == set(CUT) == set(entry["reduced"]) == set(config["reduced"])
    for key, here in CUT.items():
        assert config[key] == here == config["reduced"][key]["here"]
        assert config["reduced"][key]["published"] == PUBLISHED[key]
        assert here >= FLOORS.get(key, 0)
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn",
    }
    assert config["scoring_func"] == "sigmoid" and config["norm_topk_prob"]
    assert config["topk_method"] == "noaux_tc"
    assert "EP16" in config["deployment"] and config["assumed"]


def test_the_models_keys_are_the_published_widths():
    config = _config()
    model, scaling = config["model"], config["rope_scaling"]
    assert (
        model["dim"], model["n_heads"], model["q_lora_rank"],
        model["kv_lora_rank"], model["qk_nope_head_dim"],
        model["qk_rope_head_dim"], model["v_head_dim"],
        model["index_n_heads"], model["index_head_dim"], model["index_topk"],
        model["intermediate"], model["dense_intermediate"],
        model["moe_shared_intermediate"], model["moe_router_experts"],
        model["moe_top_k"], model["moe_groups"], model["moe_top_groups"],
        model["moe_route_scale"], model["rope_theta"], model["norm_eps"],
    ) == tuple(PUBLISHED[k] for k in (
        "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "index_n_heads",
        "index_head_dim", "index_topk", "moe_intermediate_size",
        "intermediate_size", "moe_intermediate_size", "n_routed_experts",
        "num_experts_per_tok", "n_group", "topk_group",
        "routed_scaling_factor", "rope_theta", "rms_norm_eps",
    ))
    assert model["rope_scaling"] == [
        "yarn", scaling["factor"], scaling["beta_slow"], scaling["beta_fast"],
        scaling["original_max_position_embeddings"],
    ]
    # the cut: what this chip holds
    assert (model["n_layers"], model["dense_layers"]) == (5, 1)
    assert (model["moe_experts"], model["moe_first_expert"]) == (16, 0)
    assert model["vocab_size"] == 16160
    assert model["moe_router"] == "sigmoid_groups"


def test_the_program_builds_it_at_the_issues_arithmetic():
    """Shapes alone: 4,635 M parameters, 9.27 GB in bf16; the cache
    704 numbers a token a layer, kept in whole lanes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.generate import cache_leaves, init_block_pool
    from ray_tpu.models.llama import LlamaConfig, init_params

    config = _config()
    cfg = LlamaConfig(**config["model"], dtype=jnp.dtype(config["dtype"]))
    tree = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))

    def count(stack, *leaves):
        return sum(
            int(np.prod(tree[stack][leaf].shape[1:])) for leaf in leaves
        )

    attention = count("layers", "wq", "wq_b", "wkv_a", "wkv_b", "wo")
    indexer = count("layers", "wiq", "wik", "wiw")
    assert round(attention / 1e6, 1) == 187.1 and round(indexer / 1e6, 1) == 14.0
    assert round(count("dense_layers", "w1", "w2", "w3") / 1e6, 1) == 396.4
    experts = count("layers", "w_gate", "w_up", "w_down")
    shared = count("layers", "shared_gate", "shared_up", "shared_down")
    assert round(experts / 16e6, 1) == round(shared / 1e6, 1) == 44.0
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert 4635e6 <= total < 4636e6 and cfg.num_params() == total
    assert round(total * 2 / 1e9, 2) == 9.27  # GB in bfloat16
    engine = config["engine"]
    pool = jax.eval_shape(
        lambda: init_block_pool(cfg, engine["kv_blocks"], engine["kv_block_len"])
    )
    cache = cache_leaves(pool)
    assert {n: a.shape[-1] for n, a in cache.items()} == {
        "latent": 640, "index_k": 128,  # 576 and 128 numbers
    }
    tokens = engine["kv_blocks"] * engine["kv_block_len"]
    assert tokens >= 300_000  # a group of 16 documents beside 16 live rows
    assert sum(a.size * 2 for a in cache.values()) == tokens * 768 * 2 * 5
    assert pool["moe_counts"].shape == (4, 16) and pool["dsa_counts"].shape == (5, 2)


def test_the_cell_and_its_traffic():
    cell = harness.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "longdoc_qa_closed", 1
    )
    traffic = harness.load_traffic(cell["traffic"])
    # `serve_closed`'s requests behind a check of the checkout (below)
    assert traffic["kind"] == "serve_closed_requires" and traffic["source"]
    assert (
        traffic["clients"], traffic["group_docs"], traffic["questions_per_doc"],
        traffic["question_tokens"], traffic["warmup_requests"],
        traffic["warmup_new_tokens"], traffic["trace_seconds"],
    ) == (32, 16, 3, 64, 4, 8, 4)
    assert traffic["document_tokens"] == {"dist": "uniform", "min": 6144, "max": 12288}
    assert traffic["answer_tokens"] == {"dist": "uniform", "min": 64, "max": 128}
    config = _config()
    engine, topk = config["engine"], config["model"]["index_topk"]
    # every request fits a row, and its document is several times the
    # selection, at both sizes
    longest = 12288 + 64 + 128
    assert longest <= engine["max_len"] and 6144 >= 3 * topk
    small_t = harness.apply_rehearsal(traffic)
    small = harness.apply_rehearsal(config)
    assert small_t["document_tokens"]["min"] >= 4 * small["model"]["index_topk"]
    assert (
        small_t["document_tokens"]["max"] + small_t["question_tokens"]
        + small_t["answer_tokens"]["max"] <= small["engine"]["max_len"]
    )
    reported = {
        m["name"] for section in ("end_to_end", "per_layer")
        for m in harness.metrics_of_cell(MANIFEST, section, CELL)
    }
    assert {
        "serve_tokens_per_s", "setup_s", "dsa_selected_key_share",
        "moe_held_pick_share", "moe_load_imbalance", "moe_roofline_share",
        "selected_attn_kernel_share", "selected_attn_roofline_share",
        "prefix_hit_token_share.tput", "kv_read_amplification.tput",
    } <= reported


def _engine_run(before, after):
    return {"engine": {"before": before, "after": after}}


def test_selected_key_share_is_kept_over_visible_pairs():
    reduce = harness.load_module("layer_metrics", "dsa_selected_key_share").reduce
    run = _engine_run(
        {"dsa_keys_visible": 1000, "dsa_keys_selected": 900},
        {"dsa_keys_visible": 9000, "dsa_keys_selected": 3300},
    )
    assert reduce(run) == pytest.approx(30.0)
    # a prompt of n under a top-k of k keeps k (n - k / 2) of its
    # n^2 / 2 pairs; a step at n keys k of n
    n, k = 10000, 2048
    visible = n * (n + 1) // 2
    kept = sum(min(i, k) for i in range(1, n + 1))
    run = _engine_run({}, {"dsa_keys_visible": visible, "dsa_keys_selected": kept})
    assert reduce(run) == pytest.approx(100 * k * (n - k / 2) / (n * n / 2), rel=1e-3)


def test_held_pick_share_is_the_picks_that_met_a_held_expert():
    reduce = harness.load_module("layer_metrics", "moe_held_pick_share").reduce
    run = _engine_run(
        {"moe_picks_routed": 800, "moe_picks_prefill": 50, "moe_picks_decode": 0},
        {"moe_picks_routed": 16800, "moe_picks_prefill": 900, "moe_picks_decode": 150},
    )
    assert reduce(run) == pytest.approx(100 * 1000 / 16000)  # an even sixteenth


@pytest.mark.parametrize("reader", ["dsa_selected_key_share", "moe_held_pick_share"])
@pytest.mark.parametrize("run", [
    {}, {"engine": None},
    _engine_run({}, {"kv_keys_read": 5, "moe_picks_prefill": 7}),  # the parent's engine
    _engine_run(
        {"dsa_keys_visible": 4, "dsa_keys_selected": 2, "moe_picks_routed": 9},
        {"dsa_keys_visible": 4, "dsa_keys_selected": 2, "moe_picks_routed": 9},
    ),  # an idle window
], ids=["train", "no-engine", "other-engine", "idle"])
def test_the_new_readers_give_nothing_where_there_is_nothing(reader, run):
    assert harness.load_module("layer_metrics", reader).reduce(run) is None


def test_the_manifest_grew_by_appended_entries_alone():
    """What `python3 -m benchmark.manifest_diff` says of this manifest
    against the one before this PR: every list cut where this PR's
    first entry stands (what a later PR appended behind goes with it)."""
    from benchmark import manifest_diff

    mine = {
        NAME, CELL, "dsa_selected_key_share", "moe_held_pick_share",
        "selected_attn_kernel_share", "selected_attn_roofline_share",
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


# -- the chunk's attention kernel -------------------------------------

def test_pair_flops_against_a_hand_count():
    from benchmark import mla_flops

    model = _config()["model"]
    # a head: 192 score dims and 128 value dims, a multiply and an add
    # each; 128 heads
    assert mla_flops.pair_flops(model) == 128 * (2 * 192 + 2 * 128) == 81920
    assert mla_flops.required(model, 1000) == {"flops": 81_920_000}


def _trace(ops, busy=2.0, window=4.0):
    return {"device_ops": ops, "busy_s": busy, "window_s": window}


def test_kernel_share_sums_the_selected_attn_family_over_busy():
    reduce = harness.load_module("layer_metrics", "selected_attn_kernel_share").reduce
    run = {"trace": _trace([
        ["selected_attn", 0.6], ["fusion", 0.9], ["ragged-dot", 0.2],
    ])}
    assert reduce(run) == pytest.approx(30.0)


def test_roofline_share_is_required_over_taken_per_second():
    from benchmark.flops import peaks_for

    reduce = harness.load_module("layer_metrics", "selected_attn_roofline_share").reduce
    config = _config()
    kind = "TPU v5 lite"
    peak = peaks_for(kind)["bf16_flops_per_s"]
    # 48 s in which the chunks attended as many pairs as keep the chip
    # busy a tenth of the time at its peak; the kernel held it 30 %
    pairs = int(0.1 * 48 * peak / 81920)
    run = {
        "config": config, "device": {"kind": kind}, "window_s": 48.0,
        "engine": {
            "before": {"dsa_chunk_keys_selected": 5},
            "after": {"dsa_chunk_keys_selected": 5 + pairs},
        },
        "trace": _trace([["selected_attn", 1.2], ["fusion", 0.5]]),
    }
    assert reduce(run) == pytest.approx(100 * 0.1 / 0.3, rel=1e-6)


@pytest.mark.parametrize("reader", [
    "selected_attn_kernel_share", "selected_attn_roofline_share",
])
@pytest.mark.parametrize("run", [
    {}, {"trace": None},
    {"trace": _trace([["fusion", 1.0]])},  # the parent: no such kernel
    {"trace": _trace([["selected_attn", 0.5]], busy=0.0, window=0.0)},
], ids=["no-trace", "untraced", "no-kernel", "empty-window"])
def test_the_kernels_readers_give_nothing_where_there_is_nothing(reader, run):
    run = dict(run, config=_config(), device={"kind": "TPU v5 lite"}, window_s=48.0)
    assert harness.load_module("layer_metrics", reader).reduce(run) is None


# -- the cell end to end on the CPU -----------------------------------

@pytest.mark.timeout(900)
def test_the_cell_walks_through_on_the_cpu(tmp_path):
    """`run.py --workload longdoc_qa_dsa --rehearse --trace 1` on a
    copy of the checkout: HTTP -> proxy -> router -> replica -> engine
    over the latent cache at the rehearsal's sizes (documents 4 to 10
    times its `index_topk`), float32, `correct` against the reference,
    prefix hits, and the cell's own readers among the names."""
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
         "--workload", CELL, "--seed", str(2 ** 31 + 44), "--rehearse",
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
        "dsa_selected_key_share", "moe_held_pick_share", "moe_load_imbalance",
        "moe_experts_touched_share", "prefix_hit_token_share.tput",
        "kv_read_amplification.tput",
    } <= set(line["metric_names"])
    notes = json.loads(
        next(x for x in lines if x.startswith("[benchmark] notes "))[18:]
    )
    assert notes["probe"]["reference"].endswith("deepseek_v32_ref")
    assert notes["engine_window"]["prefix_hit_token_share"] > 20.0
    assert notes["compiles_in_window"] == 0


def test_the_mix_is_serve_closeds_and_refuses_a_checkout_without_the_program(monkeypatch):
    """`traffic/serve_closed_requires.py`: the same warm-up and the same
    window as `serve_closed` from the same seed; where a file the mix
    `requires` is not in the checkout (the parent commit under this
    benchmark) the warm-up, the first thing a serve run draws, refuses
    it: exit code 1 before a cluster starts, not 1,000 s of retries."""
    from benchmark.traffic import serve_closed

    traffic = harness.apply_rehearsal(harness.load_traffic("longdoc_qa_closed"))
    gated = harness.load_module("traffic", traffic["kind"])
    assert (gated.DRIVER, gated.LOOP) == (serve_closed.DRIVER, serve_closed.LOOP)
    assert gated.warmup(traffic, 7, 512) == serve_closed.warmup(traffic, 7, 512)
    ours = gated.generate(traffic, 7, 3.0, 512)
    theirs = serve_closed.generate(traffic, 7, 3.0, 512)
    assert ours["clients"] == theirs["clients"]
    assert [next(ours["requests"]) for _ in range(9)] == [
        next(theirs["requests"]) for _ in range(9)
    ]
    assert all(
        os.path.exists(os.path.join(ROOT, path)) for path in traffic["requires"]
    )
    with pytest.raises(harness.BenchmarkError, match="no ray_tpu/ops/gone.py"):
        gated.warmup(dict(traffic, requires=["ray_tpu/ops/gone.py"]), 7, 512)
