"""HuggingFace checkpoint conversion (Llama + Qwen2 + Qwen3 +
Mistral + Gemma + Phi-3 + OLMoE families).

The integration-parity role of the reference's framework adapters
(reference: python/ray/train/huggingface/ — Ray Train wraps HF
Trainer/accelerate; SURVEY §2.3 Train-integrations row): here the
integration is TPU-first — convert an HF `LlamaForCausalLM`,
`Qwen2ForCausalLM`, `Qwen3ForCausalLM`, `MistralForCausalLM`,
`GemmaForCausalLM` or `Phi3ForCausalLM` state dict into this
framework's stacked-scan parameter pytree and run it on the
JAX/Pallas stack. All six share a skeleton (RMSNorm, gated MLP,
rotate-half RoPE, GQA); Qwen2 adds QKV projection biases
(cfg.attn_bias); Mistral converts only with its sliding window
disabled (v0.3+ checkpoints — an active window would change
long-context numerics); Gemma-1 swaps in a GeGLU gate, (1+w)
RMSNorms, a sqrt(dim) embedding scale and a head_dim decoupled from
dim/n_heads (gemma-2's soft-capping stays loudly unsupported);
Phi-3 fuses qkv_proj and gate_up_proj, which the converter splits by
output-row ranges; Qwen3 adds per-head RMSNorm on q and k before
RoPE (cfg.qk_norm) with a decoupled head_dim. OLMoE
(`OlmoeForCausalLM`) swaps the dense MLP for top-k of gated experts
(per-expert gate/up/down_proj stacked on an expert axis -> w_gate /
w_up / w_down, `mlp.gate` -> router, gates renormalised or not from
norm_topk_prob) and norms q and k over the whole projection
(cfg.qk_norm "proj").
tests/test_hf_parity.py proves numerical parity of the full forward
(logits) against transformers' reference implementation for all six.

Weight-layout notes (torch Linear stores [out, in]; we store [in, out]
so activations right-multiply):
  q/k/v/o_proj.weight.T     -> wq/wk/wv/wo
  gate_proj.weight.T        -> w3   (our swiglu(x, gate) gates arg 2)
  up_proj.weight.T          -> w1
  down_proj.weight.T        -> w2
  embed_tokens.weight       -> embed           [vocab, dim]
  lm_head.weight.T          -> lm_head         [dim, vocab]
RoPE uses the same half-split (rotate_half) convention as HF; RMSNorm
eps maps from hf_config.rms_norm_eps (Llama-2 ships 1e-5);
rope_scaling types "llama3" (Llama-3.1+) and "linear" convert with
matching frequency scaling (ops/norms.py rope_frequencies).
Checkpoints carrying tensors with no slot here (o_proj biases,
yarn/dynamic rope variants) fail the conversion loudly instead of
converting into a numerically different model.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from .llama import LlamaConfig


def config_from_hf(hf_config) -> LlamaConfig:
    """Map a transformers LlamaConfig/Qwen2Config onto ours. Raises on
    HF features this model doesn't implement (silent drops would
    convert cleanly and generate subtly wrong logits)."""
    import jax.numpy as jnp

    if getattr(hf_config, "kv_lora_rank", None):
        LlamaConfig(
            kv_lora_rank=int(hf_config.kv_lora_rank)
        ).require_plain_attention("checkpoint conversion (hf_convert.py)")
    scaling = getattr(hf_config, "rope_scaling", None)
    rope_scaling = None
    if scaling:
        kind = scaling.get("rope_type", scaling.get("type"))
        if kind in (None, "default"):
            pass
        elif kind == "llama3":
            # Llama-3.1+ piecewise frequency scaling; numerics match
            # HF modeling_rope_utils._compute_llama3_parameters
            # (tests/test_hf_parity.py asserts logit parity).
            rope_scaling = (
                "llama3",
                float(scaling["factor"]),
                float(scaling.get("low_freq_factor", 1.0)),
                float(scaling.get("high_freq_factor", 4.0)),
                int(scaling["original_max_position_embeddings"]),
            )
        elif kind == "linear":
            rope_scaling = (
                "linear", float(scaling["factor"]), 1.0, 4.0, 0
            )
        else:
            raise NotImplementedError(
                f"rope_scaling type {kind!r} is not implemented "
                "(yarn/dynamic/longrope need their own numerics "
                "audit); converting anyway would mis-position every "
                "token"
            )
    model_type = getattr(hf_config, "model_type", "llama")
    if model_type not in (
        "llama", "qwen2", "mistral", "gemma", "phi3", "qwen3", "olmoe"
    ):
        raise NotImplementedError(
            f"model_type={model_type!r}: only the llama, qwen2, "
            "qwen3, mistral, gemma, phi3 and olmoe families convert; "
            "anything else would need its own numerics audit "
            "(gemma2's logit soft-capping and alternating sliding "
            "windows are NOT implemented — converting one would "
            "silently change its numerics)"
        )
    # Qwen2 gates SWA behind use_sliding_window (default False);
    # Mistral/Phi-3 enable it whenever sliding_window is set AND
    # smaller than the context (Phi-3.5 ships window >= context — a
    # no-op window that must not block conversion; Mistral v0.1's
    # 4096 < 32768 is active and must). An *active* window changes
    # long-context numerics this model doesn't implement.
    window = getattr(hf_config, "sliding_window", None)
    max_pos = getattr(hf_config, "max_position_embeddings", 4096)
    if getattr(hf_config, "use_sliding_window", False) or (
        model_type in ("mistral", "phi3")
        and window is not None
        and window < max_pos
    ):
        raise NotImplementedError(
            "active sliding-window attention is not implemented; "
            "converting would silently change long-context numerics"
        )
    if float(getattr(hf_config, "partial_rotary_factor", 1.0)) != 1.0:
        raise NotImplementedError(
            "partial_rotary_factor != 1.0 (Phi-4-style partial RoPE) "
            "is not implemented; converting would mis-position every "
            "token"
        )
    # Qwen2 carries QKV biases (and only those). Llama's rare
    # attention_bias=True variant ALSO biases o_proj — a layout this
    # model has no slot for, so it stays loudly unsupported. Scoped to
    # llama: a Qwen2 config.json carrying a (redundant)
    # attention_bias key must not trip a Llama-specific guard.
    if model_type == "llama" and getattr(
        hf_config, "attention_bias", False
    ):
        raise NotImplementedError(
            "llama attention_bias=True (biases on all four attention "
            "projections incl. o_proj) is unsupported; qwen2-style "
            "QKV-only biases are the supported biased layout"
        )
    # Gemma family: GeGLU gate, (1+w) norms, sqrt(dim) embedding
    # scale, head_dim decoupled from dim/n_heads, always-tied lm_head.
    act = "silu"
    if model_type == "gemma":
        # transformers' GemmaMLP reads ACT2FN[config.hidden_act]; the
        # separate hidden_activation field is stored but UNUSED by the
        # layer — parity means following hidden_act, and a checkpoint
        # where the two disagree is ambiguous (the 2024-era workaround
        # configs) and must fail loudly, not silently pick one.
        mapping = {"gelu_pytorch_tanh": "gelu_tanh", "gelu": "gelu_exact"}
        hidden_act = getattr(
            hf_config, "hidden_act", "gelu_pytorch_tanh"
        ) or "gelu_pytorch_tanh"
        legacy = getattr(hf_config, "hidden_activation", None)
        if hidden_act not in mapping:
            raise NotImplementedError(
                f"gemma hidden_act={hidden_act!r} unsupported"
            )
        if legacy is not None and legacy != hidden_act:
            raise NotImplementedError(
                f"gemma config carries conflicting activations "
                f"(hidden_act={hidden_act!r}, "
                f"hidden_activation={legacy!r}); converting would "
                "silently diverge from transformers, which uses "
                "hidden_act only"
            )
        act = mapping[hidden_act]
    moe = {}
    if model_type == "olmoe":
        if getattr(hf_config, "clip_qkv", None) is not None:
            raise NotImplementedError(
                "olmoe clip_qkv is not implemented (the published "
                "OLMoE-1B-7B configs carry null)"
            )
        if getattr(hf_config, "attention_bias", False):
            raise NotImplementedError(
                "olmoe attention_bias=True (o_proj bias too) has no "
                "slot here"
            )
        if getattr(hf_config, "hidden_act", "silu") != "silu":
            raise NotImplementedError(
                f"olmoe hidden_act={hf_config.hidden_act!r} unsupported"
            )
        moe = dict(
            moe_experts=hf_config.num_experts,
            moe_top_k=hf_config.num_experts_per_tok,
            moe_router=(
                "softmax_renorm" if hf_config.norm_topk_prob else "softmax"
            ),
        )
    head_dim = getattr(hf_config, "head_dim", 0) or 0
    if head_dim and head_dim * hf_config.num_attention_heads == (
        hf_config.hidden_size
    ):
        head_dim = 0  # derived — keep the config canonical
    return LlamaConfig(
        attn_bias=model_type == "qwen2",
        qk_norm={"qwen3": "head", "olmoe": "proj"}.get(model_type, False),
        **moe,
        custom_head_dim=head_dim,
        act=act,
        norm_offset=model_type == "gemma",
        embed_scale=model_type == "gemma",
        norm_eps=getattr(hf_config, "rms_norm_eps", 1e-6),
        vocab_size=hf_config.vocab_size,
        dim=hf_config.hidden_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=getattr(
            hf_config, "num_key_value_heads",
            hf_config.num_attention_heads,
        ),
        intermediate=hf_config.intermediate_size,
        rope_theta=getattr(hf_config, "rope_theta", 10000.0),
        rope_scaling=rope_scaling,
        max_seq_len=getattr(
            hf_config, "max_position_embeddings", 4096
        ),
        dtype=jnp.float32,
        attention="reference",
        remat=False,
    )


def _np(tensor) -> np.ndarray:
    # .float() first: torch bf16 tensors don't expose .numpy().
    return np.asarray(
        tensor.detach().cpu().float().numpy(), dtype=np.float32
    )


def convert_hf_llama(state_dict: Dict[str, Any], cfg: LlamaConfig):
    """HF LlamaForCausalLM / Qwen2ForCausalLM state dict -> our params
    pytree (layers stacked on axis 0 for lax.scan)."""
    import jax.numpy as jnp

    cfg.require_plain_attention("checkpoint conversion (hf_convert.py)")
    if cfg.layer_kinds:
        # (the training forward runs these since PR 55; a checkpoint's
        # names for the stacks a kind are not written down here yet)
        raise NotImplementedError(
            "checkpoint conversion (hf_convert.py) has no layers of more "
            "than one kind (layer_kinds): it lays every layer under "
            "`layers/*`, and such a model's lie in stacks a kind"
        )
    L = cfg.n_layers
    consumed = set()

    def layer_key(i: int, name: str) -> str:
        return f"model.layers.{i}.{name}"

    def stack(name: str, transpose: bool = True):
        mats = []
        for i in range(L):
            key = layer_key(i, name)
            consumed.add(key)
            w = _np(state_dict[key])
            mats.append(w.T if transpose else w)
        return jnp.asarray(np.stack(mats), dtype=cfg.dtype)

    def split_fused(name: str, boundaries):
        """Split a FUSED projection (Phi-3 qkv_proj / gate_up_proj)
        along its OUTPUT axis at `boundaries`, via the same stack()
        loader ([L, in, out] after transpose). The boundaries must
        cover the matrix exactly — silently dropped rows would
        convert into a numerically wrong model with every shape
        self-consistent."""
        whole = stack(name)
        if whole.shape[-1] != boundaries[-1]:
            raise ValueError(
                f"{name}: fused width {whole.shape[-1]} != expected "
                f"{boundaries[-1]} from the config's head/intermediate "
                "geometry — refusing to convert a partial split"
            )
        out, lo = [], 0
        for hi in boundaries:
            out.append(whole[..., lo:hi])
            lo = hi
        return out

    hd = cfg.head_dim
    fused = layer_key(0, "self_attn.qkv_proj.weight") in state_dict
    if fused:  # Phi-3 layout
        q_rows = cfg.n_heads * hd
        kv_rows = cfg.n_kv_heads * hd
        wq, wk, wv = split_fused(
            "self_attn.qkv_proj.weight",
            [q_rows, q_rows + kv_rows, q_rows + 2 * kv_rows],
        )
        # gate_up_proj fuses [gate; up]; our forward computes
        # glu(h @ w1, h @ w3) with the gate in w3.
        w3, w1 = split_fused(
            "mlp.gate_up_proj.weight",
            [cfg.intermediate, 2 * cfg.intermediate],
        )
        layers = {"wq": wq, "wk": wk, "wv": wv, "w3": w3, "w1": w1}
    else:
        layers = {
            "wq": stack("self_attn.q_proj.weight"),
            "wk": stack("self_attn.k_proj.weight"),
            "wv": stack("self_attn.v_proj.weight"),
        }
        if cfg.moe_experts:  # OLMoE: [L, E, in, out] per projection
            def experts(name: str):
                return jnp.stack([
                    stack(f"mlp.experts.{e}.{name}.weight")
                    for e in range(cfg.moe_experts)
                ], axis=1)

            layers.update({
                "router": stack("mlp.gate.weight"),
                "w_gate": experts("gate_proj"),
                "w_up": experts("up_proj"),
                "w_down": experts("down_proj"),
            })
        else:
            layers.update({
                # Our swiglu(x, gate) gates its SECOND argument; the
                # forward computes swiglu(h @ w1, h @ w3), so
                # gate_proj lands in w3.
                "w3": stack("mlp.gate_proj.weight"),
                "w1": stack("mlp.up_proj.weight"),
            })
    if not cfg.moe_experts:
        layers["w2"] = stack("mlp.down_proj.weight")
    layers.update({
        "wo": stack("self_attn.o_proj.weight"),
        "attn_norm": stack("input_layernorm.weight", transpose=False),
        "mlp_norm": stack(
            "post_attention_layernorm.weight", transpose=False
        ),
    })
    if cfg.attn_bias:  # Qwen2-family QKV biases (1-D: no transpose)
        layers.update({
            "bq": stack("self_attn.q_proj.bias", transpose=False),
            "bk": stack("self_attn.k_proj.bias", transpose=False),
            "bv": stack("self_attn.v_proj.bias", transpose=False),
        })
    if cfg.qk_norm:  # Qwen3 per-head / OLMoE projection-wide weights
        layers.update({
            "q_norm": stack("self_attn.q_norm.weight", transpose=False),
            "k_norm": stack("self_attn.k_norm.weight", transpose=False),
        })
    embed = _np(state_dict["model.embed_tokens.weight"])
    consumed.add("model.embed_tokens.weight")
    if "lm_head.weight" in state_dict:
        lm_head = _np(state_dict["lm_head.weight"]).T
        consumed.add("lm_head.weight")
    else:  # tied embeddings
        lm_head = embed.T
    consumed.add("model.norm.weight")
    # Every weight must be accounted for: a checkpoint with tensors we
    # don't map (attention/MLP biases, adapters) would otherwise
    # convert silently into a numerically different model.
    leftover = [
        k for k in state_dict
        if k not in consumed
        and not k.endswith("rotary_emb.inv_freq")  # derived buffer
    ]
    if leftover:
        raise ValueError(
            f"unconverted checkpoint tensors {leftover[:8]}"
            f"{'...' if len(leftover) > 8 else ''} — this model has no "
            "slot for them (e.g. attention_bias=True is unsupported)"
        )
    return {
        "embed": jnp.asarray(embed, dtype=cfg.dtype),
        "layers": layers,
        "final_norm": jnp.asarray(
            _np(state_dict["model.norm.weight"]), dtype=cfg.dtype
        ),
        "lm_head": jnp.asarray(lm_head, dtype=cfg.dtype),
    }


def load_hf_llama(model) -> Tuple[Dict[str, Any], LlamaConfig]:
    """From a live transformers LlamaForCausalLM/Qwen2ForCausalLM (or
    a local path loadable by AutoModelForCausalLM — this hermetic
    environment has no model hub access, so paths must be local)."""
    if isinstance(model, str):
        from transformers import AutoModelForCausalLM

        model = AutoModelForCausalLM.from_pretrained(model)
    cfg = config_from_hf(model.config)
    params = convert_hf_llama(model.state_dict(), cfg)
    return params, cfg
