"""The yardstick's OLMoE entries (PR 26): the configuration against
the published config (the check `test_benchmark_yardstick.py` makes of
the dense ones, whose table this PR may not edit), a hand count for
`benchmark/moe_flops.py`, and each new per-layer reader on a hand-made
`run` and on the `run` a dense engine or the parent's program gives
(nothing, and no exception)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, moe_flops  # noqa: E402
from benchmark.reference import compare  # noqa: E402

MANIFEST = harness.load_manifest()
NAME, CELL = "olmoe-1b-7b-l8", "doc_score_moe"

#: config.json of allenai/OLMoE-1B-7B-0125-Instruct, as the catalog
#: beside the model-configs guide has it (architectures.jsonl).
PUBLISHED = dict(
    attention_bias=False, clip_qkv=None, hidden_act="silu", hidden_size=2048,
    intermediate_size=1024, max_position_embeddings=4096, model_type="olmoe",
    norm_topk_prob=False, num_attention_heads=16, num_experts=64,
    num_experts_per_tok=8, num_hidden_layers=16, num_key_value_heads=16,
    rms_norm_eps=1e-05, rope_scaling=None, rope_theta=10000,
    tie_word_embeddings=False, vocab_size=50304,
)


def test_config_keeps_every_published_key_and_cuts_depth_alone():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    config = harness.load_config(MANIFEST, NAME)
    assert config["source"] == entry["source"]
    changed = [k for k, v in PUBLISHED.items() if config[k] != v]
    assert changed == entry["reduced"] == ["num_hidden_layers"]
    assert config["reduced"] == {
        "num_hidden_layers": {"published": 16, "here": config["num_hidden_layers"]}
    }
    model = config["model"]
    assert (
        model["dim"], model["n_layers"], model["n_heads"], model["n_kv_heads"],
        model["intermediate"], model["vocab_size"], model["rope_theta"],
        model["norm_eps"], model["moe_experts"], model["moe_top_k"],
        model["max_seq_len"],
    ) == tuple(config[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "intermediate_size", "vocab_size",
        "rope_theta", "rms_norm_eps", "num_experts", "num_experts_per_tok",
        "max_position_embeddings",
    ))
    # norm_topk_prob false, q/k norm over the projection, head_dim derived
    assert model["moe_router"] == "softmax" and model["qk_norm"] == "proj"
    assert "custom_head_dim" not in model and "head_dim" in config["assumed"]
    assert config["assumed"] and config["deployment"]
    assert config["engine"]["max_len"] == config["max_position_embeddings"]
    rehearsal = harness.apply_rehearsal(config)["model"]
    assert rehearsal["moe_experts"] >= 8 and rehearsal["moe_top_k"] == 2


def test_the_program_builds_the_configuration_at_its_published_size():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig, flops_per_token, init_params

    config = harness.load_config(MANIFEST, NAME)
    cfg = LlamaConfig(**config["model"], dtype=jnp.dtype(config["dtype"]))
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    count = sum(x.size for x in jax.tree.leaves(shapes))
    layer = (
        4 * 2048 * 2048 + 2048 * 64 + 64 * 3 * 2048 * 1024 + 2 * 2048 + 2 * 2048
    )
    assert layer == 419_569_664  # the issue's 419.6 M a layer
    assert count == cfg.num_params() == 8 * layer + 2 * 50304 * 2048 + 2048
    assert shapes["layers"]["w_gate"].shape == (8, 64, 2048, 1024)
    assert shapes["layers"]["w_down"].shape == (8, 64, 1024, 2048)
    assert shapes["layers"]["q_norm"].shape == (8, 2048)
    # a token activates 8 of 64 experts: 6 x the active parameters
    active = count - 8 * 56 * 3 * 2048 * 1024
    assert flops_per_token(cfg, 1) == 6.0 * active + 12 * 8 * 2048 / 2


def test_the_configuration_resolves_to_its_own_reference():
    config = harness.load_config(MANIFEST, NAME)
    module = compare.load(config["reference"])
    assert module.__name__ == "benchmark.reference.olmoe_ref"
    assert callable(module.forward)


def test_the_cell_and_its_traffic():
    cell = harness.find_cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "doc_score_closed", 1
    )
    traffic = harness.load_traffic(cell["traffic"])
    docqa = harness.load_traffic("docqa_closed")
    assert traffic["kind"] == "serve_closed"
    # the dense cell's prompts, seen once, with a verdict for an answer
    for key in ("clients", "group_docs", "document_tokens", "question_tokens"):
        assert traffic[key] == docqa[key]
    assert traffic["questions_per_doc"] == 1
    assert traffic["answer_tokens"] == {"dist": "uniform", "min": 8, "max": 16}
    e2e = [m["name"] for m in harness.metrics_of_cell(MANIFEST, "end_to_end", CELL)]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    generator = harness.load_module("traffic", traffic["kind"])
    made = generator.generate(harness.apply_rehearsal(traffic), 5, 3.0, 512)
    first = [next(made["requests"]) for _ in range(8)]
    assert all(r["shared_tokens"] == 0 for r in first)  # nothing shared


def test_moe_flops_against_a_hand_count():
    model = {"dim": 8, "intermediate": 4, "moe_experts": 4}
    # gate, up, down: 3 matrices of 8 x 4, 2 operations a weight
    assert moe_flops.pick_flops(model) == 3 * 2 * 8 * 4 == 192
    assert moe_flops.expert_bytes(model) == 3 * 8 * 4 * 2 == 192
    assert moe_flops.expert_bytes(model, "float32") == 384
    assert moe_flops.required(model, picks=10, experts_touched=3) == {
        "flops": 1920, "bytes": 576
    }
    olmoe = harness.load_config(MANIFEST, NAME)["model"]
    assert moe_flops.pick_flops(olmoe) == 12_582_912  # 12.6 MFLOP a pick
    assert moe_flops.expert_bytes(olmoe) == 12_582_912  # 12.6 MB an expert
    # a chunk of 512: 0.41 TFLOP and 6.44 GB over the 8 layers
    chunk = moe_flops.required(olmoe, 512 * 8 * 8, 64 * 8)
    assert chunk["flops"] == pytest.approx(0.412e12, rel=0.01)
    assert chunk["bytes"] == pytest.approx(6.44e9, rel=0.01)


# -- the readers ------------------------------------------------------

def read(name, run):
    return harness.load_module("layer_metrics", name).reduce(run)


MODEL = {"dim": 2048, "intermediate": 1024, "moe_experts": 64, "moe_top_k": 8}
CONFIG = {"model": MODEL, "dtype": "bfloat16"}
BEFORE = {
    "steps": 10, "moe_picks_prefill": 1000, "moe_chunk_layers": 8,
    "moe_chunk_max_load": 100, "moe_chunk_experts": 500,
    "moe_picks_decode": 64, "moe_step_layers": 8,
    "moe_experts_touched": 60,
}
#: The window adds 10 chunks of 512 over 8 layers (picks 512 x 8 a
#: chunk-layer, the fullest expert 96 tokens where 64 is even, 60 of
#: the 64 experts touched) and 100 steps of 4 live rows over 8 layers
#: that touch 24 of 64 experts each.
AFTER = {
    "steps": 110,
    "moe_picks_prefill": 1000 + 10 * 8 * 4096, "moe_chunk_layers": 8 + 80,
    "moe_chunk_max_load": 100 + 80 * 96, "moe_chunk_experts": 500 + 80 * 60,
    "moe_picks_decode": 64 + 100 * 8 * 32, "moe_step_layers": 8 + 800,
    "moe_experts_touched": 60 + 800 * 24,
}


def serve_run(before, after, config=CONFIG, **more):
    return dict(
        {"engine": {"before": before, "after": after}, "config": config,
         "window_s": 10.0, "device": {"kind": "TPU v5 lite"}}, **more
    )


def test_load_imbalance_is_the_fullest_expert_over_the_even_share():
    assert read("moe_load_imbalance", serve_run(BEFORE, AFTER)) == pytest.approx(96 / 64)
    # counters that first show inside the window count from zero
    assert read("moe_load_imbalance", serve_run({"steps": 1}, AFTER)) == pytest.approx(
        (100 + 80 * 96) / ((1000 + 80 * 4096) / 64)
    )


def test_experts_touched_share_is_touched_over_all_experts_a_step_layer():
    assert read("moe_experts_touched_share", serve_run(BEFORE, AFTER)) == pytest.approx(
        100 * 24 / 64
    )


TRACE = {
    "busy_s": 3.0, "window_s": 4.0,
    "device_ops": [["fusion", 1.2], ["ragged-dot-none", 0.9],
                   ["ragged-dot-metadata", 0.1], ["copy", 0.3]],
}


def test_kernel_share_sums_the_ragged_dot_families_over_busy():
    run = serve_run(BEFORE, AFTER, trace=TRACE)
    assert read("moe_kernel_share", run) == pytest.approx(100 * 1.0 / 3.0)


def test_roofline_share_is_required_over_taken_per_second():
    run = serve_run(BEFORE, AFTER, trace=TRACE)
    picks = 10 * 8 * 4096 + 100 * 8 * 32
    touched = 60 * 80 + 800 * 24
    by_flops = picks * 12_582_912 / 197e12
    by_bytes = touched * 12_582_912 / 819e9
    assert by_bytes > by_flops  # chunks of 512 are bound by the weights' bytes
    required_per_s = by_bytes / 10.0
    taken_per_s = 1.0 / 4.0
    assert read("moe_roofline_share", run) == pytest.approx(
        100 * required_per_s / taken_per_s
    )
    # many tokens an expert: bound by the matrix unit instead
    heavy = dict(AFTER, moe_picks_prefill=AFTER["moe_picks_prefill"] * 100)
    assert read("moe_roofline_share", serve_run(BEFORE, heavy, trace=TRACE)) == (
        pytest.approx(100 * (
            (heavy["moe_picks_prefill"] - 1000 + 100 * 8 * 32)
            * 12_582_912 / 197e12 / 10.0
        ) / taken_per_s)
    )


DENSE = {"model": {"dim": 2048, "intermediate": 11008}, "dtype": "bfloat16"}


@pytest.mark.parametrize("reader", [
    "moe_load_imbalance", "moe_experts_touched_share", "moe_kernel_share",
    "moe_roofline_share",
])
@pytest.mark.parametrize("run", [
    {"engine": None, "config": CONFIG, "trace": None},
    # a dense engine: no expert counter, no ragged dot in the trace
    serve_run({"steps": 1}, {"steps": 9}, DENSE,
              trace={"busy_s": 1.0, "window_s": 2.0, "device_ops": [["fusion", 0.5]]}),
    # the parent's program under this configuration's name, untraced
    serve_run({"steps": 1}, {"steps": 9}, trace=None),
    # nothing happened in the window
    serve_run(AFTER, AFTER, trace={"busy_s": 0.0, "window_s": 0.0, "device_ops": []}),
], ids=["no-engine", "dense", "parent", "idle"])
def test_the_new_readers_give_nothing_where_there_is_nothing(reader, run):
    assert read(reader, run) is None
