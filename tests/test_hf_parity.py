"""Numerical parity of the flagship Llama against transformers'
reference implementation (torch CPU): same weights, same tokens, same
logits. This is the strongest correctness check the model stack has —
it pins RoPE convention, RMSNorm accumulation, SwiGLU gate order, GQA
repeat, attention masking, and every weight-layout transpose in
hf_convert.py at once."""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax  # noqa: E402

from decode_oracle import paged_greedy  # noqa: E402
from ray_tpu.models.hf_convert import config_from_hf, convert_hf_llama  # noqa: E402
from ray_tpu.models.llama import forward  # noqa: E402


def _tiny_hf_llama(n_heads=4, n_kv_heads=4, seed=0):
    from transformers import LlamaConfig as HFConfig
    from transformers import LlamaForCausalLM

    torch.manual_seed(seed)
    hf_cfg = HFConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=n_heads,
        num_key_value_heads=n_kv_heads,
        max_position_embeddings=64,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        attn_implementation="eager",
    )
    model = LlamaForCausalLM(hf_cfg)
    model.eval()
    return model


def _compare(model, tokens_np, atol=2e-4):
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens_np)).logits.numpy()
    cfg = config_from_hf(model.config)
    params = convert_hf_llama(model.state_dict(), cfg)
    ours = np.asarray(
        forward(params, jax.numpy.asarray(tokens_np), cfg)
    )
    diff = np.max(np.abs(ours - ref))
    assert diff < atol, f"logit mismatch: max abs diff {diff}"
    # Same argmax continuation everywhere (the check users feel).
    assert (ours.argmax(-1) == ref.argmax(-1)).all()


def test_logits_match_transformers_mha():
    model = _tiny_hf_llama(n_heads=4, n_kv_heads=4)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 128, (2, 33), dtype=np.int64)
    _compare(model, tokens)


def test_logits_match_transformers_gqa():
    """Grouped-query attention: kv heads < query heads exercises
    repeat_kv and the [d, kv_heads*hd] projection layout."""
    model = _tiny_hf_llama(n_heads=8, n_kv_heads=2, seed=1)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 128, (1, 48), dtype=np.int64)
    _compare(model, tokens)


def test_llama2_style_eps_respected():
    """rms_norm_eps=1e-5 (what Llama-2 ships) must map through —
    hardcoding 1e-6 converts real checkpoints into subtly different
    models."""
    from transformers import LlamaConfig as HFConfig
    from transformers import LlamaForCausalLM

    torch.manual_seed(3)
    hf_cfg = HFConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, max_position_embeddings=64,
        rms_norm_eps=1e-5, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    model = LlamaForCausalLM(hf_cfg)
    model.eval()
    cfg = config_from_hf(model.config)
    assert cfg.norm_eps == 1e-5
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, 128, (1, 24), dtype=np.int64)
    _compare(model, tokens)


def test_unsupported_checkpoint_features_fail_loudly():
    from transformers import LlamaConfig as HFConfig

    scaled = HFConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=2,
        rope_scaling={"rope_type": "yarn", "factor": 2.0},
    )
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        config_from_hf(scaled)

    class FakeConfig:
        model_type = "gpt_bigcode"
        rope_scaling = None

    with pytest.raises(NotImplementedError, match="model_type"):
        config_from_hf(FakeConfig())


def _tiny_hf_qwen2(n_heads=4, n_kv_heads=4, seed=0, tied=False):
    """Qwen2: same skeleton as Llama plus QKV projection biases — the
    second HF architecture (review r3 item 10), proving the converter
    isn't Llama-shape-hardcoded."""
    from transformers import Qwen2Config, Qwen2ForCausalLM

    torch.manual_seed(seed)
    hf_cfg = Qwen2Config(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=n_heads,
        num_key_value_heads=n_kv_heads,
        max_position_embeddings=64,
        rope_theta=10000.0,
        tie_word_embeddings=tied,
        use_sliding_window=False,
        attn_implementation="eager",
    )
    model = Qwen2ForCausalLM(hf_cfg)
    model.eval()
    return model


def test_qwen2_logits_match_transformers_mha():
    model = _tiny_hf_qwen2(n_heads=4, n_kv_heads=4, seed=7)
    cfg = config_from_hf(model.config)
    assert cfg.attn_bias  # qwen2 always carries QKV biases
    rng = np.random.default_rng(7)
    tokens = rng.integers(0, 128, (2, 33), dtype=np.int64)
    _compare(model, tokens)


def test_qwen2_logits_match_transformers_gqa_tied():
    """GQA + tied embeddings (how small Qwen2 checkpoints ship)."""
    model = _tiny_hf_qwen2(n_heads=8, n_kv_heads=2, seed=8, tied=True)
    rng = np.random.default_rng(8)
    tokens = rng.integers(0, 128, (1, 48), dtype=np.int64)
    _compare(model, tokens)


def test_qwen2_greedy_decode_matches_transformers_generate():
    """The paged serving forwards apply the biases too."""
    model = _tiny_hf_qwen2(n_heads=4, n_kv_heads=2, seed=9)
    rng = np.random.default_rng(9)
    prompt = rng.integers(1, 128, (2, 12), dtype=np.int64)
    with torch.no_grad():
        ref = model.generate(
            torch.from_numpy(prompt),
            max_new_tokens=10,
            do_sample=False,
            pad_token_id=0,
            eos_token_id=None,
        )[:, prompt.shape[1]:].numpy()
    cfg = config_from_hf(model.config)
    params = convert_hf_llama(model.state_dict(), cfg)
    ours = paged_greedy(params, cfg, prompt, 10)
    assert ours == ref.tolist()


def test_biased_llama_rejected_loudly():
    """Llama attention_bias=True biases ALL FOUR projections (incl.
    o_proj) — no slot here, so it must fail at config time, not
    convert into a numerically different model. (QKV-only biases are
    the supported biased layout — the Qwen2 tests above.)"""
    from transformers import LlamaConfig as HFConfig

    biased = HFConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=2, attention_bias=True,
        tie_word_embeddings=False,
    )
    with pytest.raises(NotImplementedError, match="attention_bias"):
        config_from_hf(biased)


def test_flash_attention_matches_hf_reference():
    """The Pallas-interpret flash path agrees with HF too (slightly
    looser: online-softmax accumulation order differs)."""
    import dataclasses

    model = _tiny_hf_llama(n_heads=4, n_kv_heads=4, seed=2)
    cfg = config_from_hf(model.config)
    cfg = dataclasses.replace(cfg, attention="flash")
    params = convert_hf_llama(model.state_dict(), cfg)
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, 128, (1, 32), dtype=np.int64)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).logits.numpy()
    ours = np.asarray(
        forward(params, jax.numpy.asarray(tokens), cfg)
    )
    assert np.max(np.abs(ours - ref)) < 2e-3


def test_greedy_decode_matches_transformers_generate():
    """Greedy decode through OUR paged prefill + decode-step programs
    produces the same continuation transformers.generate does — pins
    the pool's write indices, rotary offsets, and last-position logit
    selection of the serving path, not just the training forward."""
    model = _tiny_hf_llama(n_heads=4, n_kv_heads=4, seed=5)
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, 128, (2, 12), dtype=np.int64)
    with torch.no_grad():
        ref = model.generate(
            torch.from_numpy(prompt),
            max_new_tokens=10,
            do_sample=False,
            pad_token_id=0,
            # Ours runs the full budget (eos_token=-1 default); HF
            # must not stop early at its default eos_token_id=2, or a
            # lucky token-2 emission zero-pads only one side.
            eos_token_id=None,
        )[:, prompt.shape[1]:].numpy()
    cfg = config_from_hf(model.config)
    params = convert_hf_llama(model.state_dict(), cfg)
    ours = paged_greedy(params, cfg, prompt, 10)
    assert ours == ref.tolist()


def test_llama31_rope_scaling_parity():
    """Llama-3.1 'llama3' rope_scaling converts and matches HF's
    piecewise frequency scaling bit-for-bit at the logit level
    (review r4 weak #5: every Llama-3.1+ checkpoint used to be
    rejected by the NotImplementedError guard)."""
    from transformers import LlamaConfig as HFConfig
    from transformers import LlamaForCausalLM

    torch.manual_seed(5)
    hf_cfg = HFConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
        rope_theta=500000.0,
        rope_scaling={
            "rope_type": "llama3",
            "factor": 8.0,
            "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 32,
        },
        tie_word_embeddings=False,
        attn_implementation="eager",
    )
    model = LlamaForCausalLM(hf_cfg)
    model.eval()
    cfg = config_from_hf(model.config)
    assert cfg.rope_scaling == ("llama3", 8.0, 1.0, 4.0, 32)
    rng = np.random.default_rng(5)
    # Positions beyond original_max exercise the scaled-frequency band.
    tokens = rng.integers(0, 128, (1, 80), dtype=np.int64)
    _compare(model, tokens)


def test_linear_rope_scaling_parity():
    from transformers import LlamaConfig as HFConfig
    from transformers import LlamaForCausalLM

    torch.manual_seed(6)
    hf_cfg = HFConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, max_position_embeddings=128,
        rope_scaling={"rope_type": "linear", "factor": 4.0},
        tie_word_embeddings=False, attn_implementation="eager",
    )
    model = LlamaForCausalLM(hf_cfg)
    model.eval()
    rng = np.random.default_rng(6)
    tokens = rng.integers(0, 128, (1, 64), dtype=np.int64)
    _compare(model, tokens)


@pytest.mark.slow
def test_parity_at_depth_gqa_bf16():
    """Parity at realistic depth/width in bf16 (review r4 weak #5:
    tiny 2-layer configs never exercised the regime where 'subtly
    wrong logits' live): 24 layers, hidden 1024, GQA 16q/4kv heads,
    real Llama-3 rope theta, bf16 weights and activations on BOTH
    sides. Asserts bounded logit divergence (bf16 accumulation noise
    only) and token-identical greedy continuation at every position."""
    import jax.numpy as jnp

    from transformers import LlamaConfig as HFConfig
    from transformers import LlamaForCausalLM

    torch.manual_seed(7)
    hf_cfg = HFConfig(
        vocab_size=2048,
        hidden_size=1024,
        intermediate_size=2816,
        num_hidden_layers=24,
        num_attention_heads=16,
        num_key_value_heads=4,
        max_position_embeddings=256,
        rope_theta=500000.0,
        tie_word_embeddings=False,
        attn_implementation="eager",
    )
    model = LlamaForCausalLM(hf_cfg)
    model.eval()
    model = model.to(torch.bfloat16)
    rng = np.random.default_rng(7)
    tokens = rng.integers(0, 2048, (1, 96), dtype=np.int64)

    with torch.no_grad():
        ref = (
            model(torch.from_numpy(tokens))
            .logits.to(torch.float32)
            .numpy()
        )
    cfg = config_from_hf(model.config)
    cfg = type(cfg)(**{**cfg.__dict__, "dtype": jnp.bfloat16})
    params = convert_hf_llama(model.state_dict(), cfg)
    ours = np.asarray(
        forward(params, jax.numpy.asarray(tokens), cfg),
        dtype=np.float32,
    )
    diff = np.max(np.abs(ours - ref))
    # bf16 noise across 24 layers; measured headroom documented in the
    # assert so a regression is visible as a number, not just a fail.
    assert diff < 0.5, f"bf16 depth-parity drifted: max abs diff {diff}"
    # Greedy continuation: token-identical wherever the decision is
    # numerically decidable. Random-init logits sit near zero, so a
    # handful of positions have top-2 margins inside bf16 noise —
    # those flip on EITHER side's summation order (trained checkpoints
    # have wide margins); requiring them equal would test tie-breaking,
    # not correctness. Decidable = ref top-2 margin > 2x the measured
    # logit divergence.
    top2 = np.partition(ref, -2, axis=-1)
    margin = top2[..., -1] - top2[..., -2]
    decidable = margin > 2 * diff
    agree = ours.argmax(-1) == ref.argmax(-1)
    # Random-init logits cluster near zero, so only ~60% of positions
    # have decisive margins (trained checkpoints: nearly all).
    assert decidable.mean() > 0.4, (
        "test lost its power: almost every position is a near-tie"
    )
    assert agree[decidable].all(), (
        "greedy continuation diverged at decidable positions: "
        f"{(~agree & decidable).sum()} of {decidable.sum()}"
    )


def _tiny_hf_mistral(n_heads=4, n_kv_heads=2, seed=0,
                     sliding_window=None):
    """Mistral: third HF architecture — Llama skeleton, no biases,
    GQA by default; converts only with the sliding window disabled
    (how v0.3+ checkpoints ship)."""
    from transformers import MistralConfig, MistralForCausalLM

    torch.manual_seed(seed)
    hf_cfg = MistralConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=n_heads,
        num_key_value_heads=n_kv_heads,
        max_position_embeddings=64,
        rope_theta=10000.0,
        sliding_window=sliding_window,
        tie_word_embeddings=False,
        attn_implementation="eager",
    )
    model = MistralForCausalLM(hf_cfg)
    model.eval()
    return model


def test_mistral_logits_match_transformers_gqa():
    model = _tiny_hf_mistral(n_heads=4, n_kv_heads=2, seed=11)
    cfg = config_from_hf(model.config)
    assert not cfg.attn_bias  # mistral carries no projection biases
    rng = np.random.default_rng(11)
    tokens = rng.integers(0, 128, (2, 33), dtype=np.int64)
    _compare(model, tokens)


def test_mistral_active_sliding_window_rejected():
    """v0.1-style checkpoints (sliding_window=4096) must fail loudly:
    converting would silently drop the window and change long-context
    numerics."""
    model = _tiny_hf_mistral(sliding_window=32)
    with pytest.raises(NotImplementedError, match="sliding-window"):
        config_from_hf(model.config)


def _tiny_hf_gemma(n_heads=4, n_kv_heads=1, head_dim=32, seed=0):
    """Gemma: fourth HF architecture — GeGLU gate, (1+w) RMSNorm,
    sqrt(dim) embedding scale, head_dim decoupled from dim/n_heads,
    always-tied lm_head. The tiny config uses head_dim != dim/n_heads
    on purpose (Gemma-2B ships 8 heads x 256 on dim 2048)."""
    from transformers import GemmaConfig, GemmaForCausalLM

    torch.manual_seed(seed)
    hf_cfg = GemmaConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=n_heads,
        num_key_value_heads=n_kv_heads,
        head_dim=head_dim,
        max_position_embeddings=64,
        rope_theta=10000.0,
        hidden_activation="gelu_pytorch_tanh",
        attn_implementation="eager",
    )
    model = GemmaForCausalLM(hf_cfg)
    model.eval()
    return model


def test_gemma_logits_match_transformers():
    model = _tiny_hf_gemma(seed=13)
    cfg = config_from_hf(model.config)
    assert cfg.custom_head_dim == 32  # decoupled: 4 heads x 32 on dim 64
    assert cfg.act == "gelu_tanh" and cfg.norm_offset and cfg.embed_scale
    rng = np.random.default_rng(13)
    tokens = rng.integers(0, 128, (2, 33), dtype=np.int64)
    _compare(model, tokens, atol=5e-4)


def test_gemma_greedy_decode_matches_transformers_generate():
    """The KV-cache serving layer applies the Gemma conventions too
    (shared model_norm/model_glu/embed_tokens helpers)."""
    model = _tiny_hf_gemma(seed=14)
    rng = np.random.default_rng(14)
    prompt = rng.integers(1, 128, (2, 9), dtype=np.int64)
    with torch.no_grad():
        ref = model.generate(
            torch.from_numpy(prompt),
            max_new_tokens=10,
            do_sample=False,
            pad_token_id=0,
            eos_token_id=None,
        )[:, prompt.shape[1]:].numpy()
    cfg = config_from_hf(model.config)
    params = convert_hf_llama(model.state_dict(), cfg)
    ours = paged_greedy(params, cfg, prompt, 10)
    assert ours == ref.tolist()


def _tiny_hf_phi3(n_heads=4, n_kv_heads=2, seed=0):
    """Phi-3: fifth HF architecture — Llama skeleton with FUSED
    qkv_proj and gate_up_proj projections the converter must split."""
    from transformers import Phi3Config, Phi3ForCausalLM

    torch.manual_seed(seed)
    hf_cfg = Phi3Config(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=n_heads,
        num_key_value_heads=n_kv_heads,
        max_position_embeddings=64,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        pad_token_id=0,
        eos_token_id=1,
        bos_token_id=2,
        attn_implementation="eager",
    )
    model = Phi3ForCausalLM(hf_cfg)
    model.eval()
    return model


def test_phi3_logits_match_transformers():
    model = _tiny_hf_phi3(seed=17)
    cfg = config_from_hf(model.config)
    assert not cfg.attn_bias and cfg.act == "silu"
    rng = np.random.default_rng(17)
    tokens = rng.integers(0, 128, (2, 33), dtype=np.int64)
    _compare(model, tokens)


def test_phi3_greedy_decode_matches_transformers_generate():
    """The split fused projections feed the KV-cache serving path
    identically."""
    model = _tiny_hf_phi3(seed=18)
    rng = np.random.default_rng(18)
    prompt = rng.integers(3, 128, (2, 11), dtype=np.int64)
    with torch.no_grad():
        ref = model.generate(
            torch.from_numpy(prompt),
            max_new_tokens=10,
            do_sample=False,
            pad_token_id=0,
            eos_token_id=None,
        )[:, prompt.shape[1]:].numpy()
    cfg = config_from_hf(model.config)
    params = convert_hf_llama(model.state_dict(), cfg)
    ours = paged_greedy(params, cfg, prompt, 10)
    assert ours == ref.tolist()


def _tiny_hf_qwen3(n_heads=4, n_kv_heads=2, head_dim=16, seed=0):
    """Qwen3: sixth HF architecture — Llama skeleton plus per-head
    RMSNorm on q and k before RoPE (q_norm/k_norm), no biases, and a
    decoupled head_dim."""
    from transformers import Qwen3Config, Qwen3ForCausalLM

    torch.manual_seed(seed)
    hf_cfg = Qwen3Config(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=n_heads,
        num_key_value_heads=n_kv_heads,
        head_dim=head_dim,
        max_position_embeddings=64,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        attn_implementation="eager",
    )
    model = Qwen3ForCausalLM(hf_cfg)
    model.eval()
    return model


def test_qwen3_logits_match_transformers():
    # head_dim=32 with 4 heads on dim 64: genuinely decoupled
    # (4 x 32 != 64), like real Qwen3 checkpoints.
    model = _tiny_hf_qwen3(head_dim=32, seed=21)
    cfg = config_from_hf(model.config)
    assert cfg.qk_norm and not cfg.attn_bias
    assert cfg.custom_head_dim == 32
    rng = np.random.default_rng(21)
    tokens = rng.integers(0, 128, (2, 33), dtype=np.int64)
    _compare(model, tokens)


def test_qwen3_greedy_decode_matches_transformers_generate():
    """QK-norm applies identically on the KV-cache serving path
    (shared project_qkv)."""
    model = _tiny_hf_qwen3(seed=22)
    rng = np.random.default_rng(22)
    prompt = rng.integers(1, 128, (2, 9), dtype=np.int64)
    with torch.no_grad():
        ref = model.generate(
            torch.from_numpy(prompt),
            max_new_tokens=10,
            do_sample=False,
            pad_token_id=0,
            eos_token_id=None,
        )[:, prompt.shape[1]:].numpy()
    cfg = config_from_hf(model.config)
    params = convert_hf_llama(model.state_dict(), cfg)
    ours = paged_greedy(params, cfg, prompt, 10)
    assert ours == ref.tolist()


# ---------------------------------------------------------------------
# OLMoE (top-k of gated experts, projection-wide q/k norm)
# ---------------------------------------------------------------------

def _tiny_hf_olmoe(norm_topk_prob=False, seed=0):
    from transformers import OlmoeConfig, OlmoeForCausalLM

    torch.manual_seed(seed)
    hf_cfg = OlmoeConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=4,
        num_experts=8,
        num_experts_per_tok=2,
        norm_topk_prob=norm_topk_prob,
        max_position_embeddings=64,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        attn_implementation="eager",
    )
    model = OlmoeForCausalLM(hf_cfg)
    # RMSNorm weights are ones at init: a q/k norm over the wrong
    # axis, or left out of a path, must show.
    with torch.no_grad():
        for name, p in model.named_parameters():
            if name.endswith(("q_norm.weight", "k_norm.weight")):
                p.add_(0.3 * torch.randn_like(p))
    model.eval()
    return model


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_olmoe_logits_match_transformers(norm_topk_prob):
    """Three independent copies of OLMoE's equations agree in float32:
    transformers' `OlmoeForCausalLM`, the program, and the benchmark's
    plain reference. The reference against transformers is what
    catches a q/k norm in the wrong place or a renormalised gate in
    BOTH of this repo's copies."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.reference import olmoe_ref

    model = _tiny_hf_olmoe(norm_topk_prob, seed=31)
    cfg = config_from_hf(model.config)
    assert cfg.qk_norm == "proj" and cfg.moe_experts == 8
    assert cfg.moe_top_k == 2 and cfg.intermediate == 32
    assert cfg.moe_router == (
        "softmax_renorm" if norm_topk_prob else "softmax"
    )
    rng = np.random.default_rng(31)
    tokens = rng.integers(0, 128, (2, 33), dtype=np.int64)
    _compare(model, tokens)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).logits.numpy()
    params = convert_hf_llama(model.state_dict(), cfg)
    keys = dict(
        dim=cfg.dim, n_layers=cfg.n_layers, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, norm_eps=cfg.norm_eps,
        rope_theta=cfg.rope_theta, moe_top_k=cfg.moe_top_k,
        moe_router=cfg.moe_router,
    )
    for row in range(tokens.shape[0]):
        plain = np.asarray(olmoe_ref.forward(
            params, jax.numpy.asarray(tokens[row]), keys, q_block=16
        ))
        assert np.max(np.abs(plain - ref[row])) < 2e-4
