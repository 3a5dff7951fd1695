"""Llama-family decoder — the flagship model (BASELINE.md north star:
Llama-2-7B pretraining).

TPU-first design choices:
  * pure-functional params pytree (no module system) so pjit/GSPMD see
    plain arrays with logical-axis annotations (parallel/sharding.py);
  * layers stacked on a leading axis and iterated with `lax.scan` —
    one layer trace instead of n_layers, keeping XLA compile time flat;
  * `jax.checkpoint` around each layer (rematerialization) so HBM
    holds one layer's activations during backward;
  * attention via the Pallas flash kernel (ops/attention.py), ring
    attention (ops/ring_attention.py) when the sequence is sharded
    over `sp`;
  * bfloat16 params/activations, f32 logits for the softmax-xent.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..ops.attention import (
    flash_attention,
    flash_attention_sharded,
    mha_reference,
    repeat_kv,
)
from ..ops.moe import moe_ffn_dropless, moe_ffn_ep, route_grouped_sigmoid
from ..ops.norms import apply_rotary, rms_norm, rotary_embedding, swiglu
from ..ops.ring_attention import ring_attention
from ..parallel.sharding import Annotated, annotate


class AttnKind(NamedTuple):
    """What one KIND of attention layer has of its own in a model that
    mixes kinds (`LlamaConfig.layer_kinds`): how many keys a query
    sees, itself the last of them (0: every key up to itself), its kv
    heads, its rotary base, and whether each head has a learned SINK
    logit, which joins the softmax's denominator and carries no
    value. A kind with `conv` taps is no attention at all: its mixer
    is a gated short convolution over positions (LFM2's `conv` layers,
    `conv_L_cache` taps), it has no keys, and what it keeps of a row
    is the `conv - 1` columns before the next position: a STATE that
    belongs to the row, not pages (llm/kv_state.py)."""

    window: int = 0
    kv_heads: int = 0
    rope_theta: float = 10000.0
    sink: bool = False
    conv: int = 0

    @property
    def cache(self) -> str:
        """Which of a row's caches this kind's layers keep."""
        if self.conv:
            return "conv"
        return "window" if self.window else "full"


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    intermediate: int = 11008
    rope_theta: float = 10000.0
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    attention: str = "flash"  # flash | reference | ring
    remat: bool = True
    # Rematerialization policy: "full" recomputes the whole layer in
    # backward (min HBM, ~33% extra FLOPs); "dots" saves matmul
    # outputs and recomputes only cheap elementwise ops (the standard
    # TPU LLM trade — near-"none" speed at a fraction of the memory);
    # "dots_flash" additionally saves the flash-attention kernel's
    # (out, lse) residuals (ops/attention.py checkpoint names) so the
    # backward never re-runs the forward flash kernel — ~36 MB/layer
    # of HBM at the 410M bench shape buys back ~2.5% of step time;
    # ignored when remat=False.
    remat_policy: str = "full"  # full | dots | dots_flash
    # ---- mixture of experts ----
    #: >0 turns every FFN into a top-k-routed MoE with this many
    #: gated experts of width `intermediate`, activation `act` (0 =
    #: dense GLU). Every expert on one device runs dropless
    #: (ops/moe.py); experts shard over the `ep` mesh axis when an
    #: ep_axis is passed (shard_map) — SURVEY §2.4 EP row.
    moe_experts: int = 0
    moe_top_k: int = 2
    #: What the top-k gates are: "softmax_renorm" (the k largest
    #: softmax probabilities, rescaled to sum to one: Mixtral, Switch)
    #: or "softmax" (left as they are: OLMoE, `norm_topk_prob: false`).
    moe_router: str = "softmax_renorm"
    #: Buffer size of the expert-parallel exchange only (picks past
    #: it drop); the single-device path has no capacity.
    moe_capacity_factor: float = 2.0
    #: Weight of the Switch/GShard load-balancing auxiliary loss.
    moe_aux_weight: float = 0.01
    #: RMSNorm epsilon (HF rms_norm_eps; Llama-2 ships 1e-5).
    norm_eps: float = 1e-6
    #: Attention QKV projection biases (Qwen2-family; Llama has none).
    attn_bias: bool = False
    #: RoPE frequency scaling: None, or the tuple
    #: (kind, factor, low_freq_factor, high_freq_factor, original_max)
    #: — kind "linear" (position interpolation) or "llama3"
    #: (Llama-3.1 piecewise; see ops/norms.py rope_frequencies).
    #: A tuple (not a dict) so the frozen config stays hashable for
    #: jit static args.
    rope_scaling: Any = None
    # ---- Gemma-family knobs ----
    #: Per-head dimension when it is NOT dim//n_heads (Gemma-2B:
    #: dim 2048, 8 heads, head_dim 256). 0 = derived.
    custom_head_dim: int = 0
    #: GLU gate activation: "silu" (Llama/Qwen/Mistral SwiGLU),
    #: "gelu_tanh" (Gemma GeGLU, torch tanh approximation) or
    #: "gelu_exact" (erf — what transformers uses when a config says
    #: plain "gelu").
    act: str = "silu"
    #: RMSNorm scales by (1 + w) instead of w (Gemma stores w around
    #: zero; applying it Llama-style silently zeroes activations).
    norm_offset: bool = False
    #: Multiply embedding output by sqrt(dim) (Gemma normalizer).
    embed_scale: bool = False
    #: RMSNorm on q and k before RoPE: False (none), "head" (over
    #: each head's head_dim, one weight of head_dim shared by the
    #: heads: Qwen3; True means this) or "proj" (over the whole
    #: projection before the split into heads: OLMoE).
    qk_norm: Any = False
    # ---- DeepSeek-family keys (the serve forwards only) ----
    #: Latent attention (MLA): >0 is the width of the compressed
    #: key-value latent, which with `qk_rope_head_dim` shared rotary
    #: key dims is ALL the cache holds of a token (models/generate.py).
    #: The heads are then `qk_nope_head_dim + qk_rope_head_dim` wide
    #: for scores and `v_head_dim` for values, both expanded from the
    #: latent, and q is projected through a latent of `q_lora_rank`.
    #: Rotary acts on the rope dims alone (`rope_scaling` "yarn").
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    #: Learned sparse attention (DSA) over the latent cache: >0 is how
    #: many keys a query attends, the largest by an indexer's score
    #: (`index_n_heads` heads of `index_head_dim`, whose keys the
    #: cache holds beside the latent).
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    #: With "sigmoid_groups" (`moe_router`): the router's outputs
    #: where they are more than the `moe_experts` HELD here (one
    #: rank's share of an expert-parallel layer: experts
    #: `moe_first_expert` and after), its groups, the groups a token
    #: may choose from, and the factor on the normalised gates.
    moe_router_experts: int = 0
    moe_first_expert: int = 0
    moe_groups: int = 1
    moe_top_groups: int = 1
    moe_route_scale: float = 1.0
    #: Width of the shared expert every token passes beside its
    #: routed ones (0: none).
    moe_shared_intermediate: int = 0
    #: Leading layers, of `n_layers`, whose FFN is a dense GLU of
    #: width `dense_intermediate` in a model whose others are expert
    #: layers: a stack of their own, `dense_layers/*`.
    dense_layers: int = 0
    dense_intermediate: int = 0
    # ---- layers of more than one kind ----
    #: The model's layers in order, an `AttnKind` each (from a JSON
    #: file `[window, kv_heads, rope_theta, sink]`, with the taps as a
    #: fifth entry for a conv layer: `[0, 0, 0, false, 3]`), where they
    #: are not all alike: window and full attention mixed (MiMo-V2's
    #: `hybrid_layer_pattern`, with each kind's own `num_key_value_heads`,
    #: `rope_theta` and sink), or gated short convolutions among
    #: attention layers (LFM2's `layer_types`). At most one kind with a
    #: window, one without and one of convolutions: each has its cache,
    #: a row's table and the work list in a paged forward
    #: (models/generate.py, llm/kv_window.py, llm/kv_state.py), and
    #: what a kind changes of the mixer leaves a stack of its own
    #: (`kinds_layer_shapes`).
    #: `n_kv_heads` and `rope_theta` are then not read.
    layer_kinds: tuple = ()
    #: The leading dims of a head that rotary turns (0: all of it).
    rotary_dim: int = 0
    #: A factor on the values (MiMo-V2's `attention_value_scale`).
    value_scale: float = 1.0
    # ---- Trinity-family keys (`layer_kinds` models; the training
    # forward only: the paged forwards refuse them by name) ----
    #: A sigmoid gate on the attention output, before `wo`:
    #: `o * sigmoid(a Wg)` of the block's normed input `a` (leaf `wg`
    #: [dim, heads x head_dim] beside `wq`).
    attn_gate: bool = False
    #: An RMSNorm on each half's OUTPUT before it joins the residual
    #: (leaves `attn_post_norm`, `mlp_post_norm`), beside the norms on
    #: the halves' inputs.
    post_norms: bool = False

    def __post_init__(self):
        if self.qk_norm is True:
            object.__setattr__(self, "qk_norm", "head")
        if self.qk_norm not in (False, "head", "proj"):
            raise ValueError(f"unknown qk_norm {self.qk_norm!r}")
        if self.moe_router not in (
            "softmax", "softmax_renorm", "sigmoid_groups"
        ):
            raise ValueError(f"unknown moe_router {self.moe_router!r}")
        if isinstance(self.rope_scaling, list):  # from a JSON file
            object.__setattr__(
                self, "rope_scaling", tuple(self.rope_scaling)
            )
        if self.index_topk and not self.kv_lora_rank:
            raise ValueError("index_topk selects keys of a latent cache")
        if self.dense_layers and not self.moe_experts:
            raise ValueError("dense_layers lead a model of expert layers")
        if self.layer_kinds:
            kinds = tuple(AttnKind(*kind) for kind in self.layer_kinds)
            object.__setattr__(self, "layer_kinds", kinds)
            if len(kinds) != self.n_layers:
                raise ValueError(
                    f"layer_kinds names {len(kinds)} layers of {self.n_layers}"
                )
            if len({k.cache for k in set(kinds)}) != len(set(kinds)):
                raise ValueError(
                    "layer_kinds: at most one kind with a window, one "
                    "without and one of convolutions (each has one cache)"
                )
            if self.kv_lora_rank:
                raise ValueError("layer_kinds are kinds of plain attention")
            if self.qk_norm == "proj":
                raise ValueError(
                    "layer_kinds: a q/k norm is a head's (qk_norm='head')"
                )
        elif self.attn_gate or self.post_norms:
            raise ValueError(
                "attn_gate and post_norms are a layer_kinds model's"
            )

    def attn_kinds(self) -> Dict[str, tuple]:
        """{cache: (its `AttnKind`, the layers of that kind)} of a
        model with `layer_kinds`, in the order the kinds first occur."""
        out: Dict[str, tuple] = {}
        for layer, kind in enumerate(self.layer_kinds):
            out.setdefault(kind.cache, (kind, []))[1].append(layer)
        return out

    def require_plain_attention(self, what: str) -> None:
        """The one refusal of a configuration by the code that has no
        equations for it (training, conversion from a checkpoint): it
        would otherwise run other mathematics. Latent attention, and
        of a model with `layer_kinds` a kind with a sink, a kind of
        gated short convolutions, and values narrower than the keys."""
        if self.kv_lora_rank:
            raise NotImplementedError(
                f"{what} has no latent attention (kv_lora_rank="
                f"{self.kv_lora_rank}): a DeepSeek-family configuration "
                "runs on the serve path only (models/generate.py)"
            )
        for kind in set(self.layer_kinds):
            if kind.sink or kind.conv:
                raise NotImplementedError(
                    f"{what} has window and full attention mixed "
                    "(layer_kinds), and of those neither a kind with a "
                    "sink logit nor one of gated short convolutions "
                    f"(conv taps): {tuple(kind)} runs on the serve path "
                    "only (models/generate.py)"
                )
        if self.layer_kinds and self.v_head_dim not in (0, self.head_dim):
            raise NotImplementedError(
                f"{what} has no values narrower than the keys in a "
                f"model with layer_kinds (v_head_dim={self.v_head_dim}): "
                "the serve path only (models/generate.py)"
            )

    @property
    def head_dim(self) -> int:
        return self.custom_head_dim or self.dim // self.n_heads

    def num_params(self) -> int:
        if self.kv_lora_rank or self.layer_kinds:
            shapes = jax.eval_shape(
                lambda: init_params(jax.random.PRNGKey(0), self)
            )
            return sum(
                math.prod(leaf.shape) for leaf in jax.tree.leaves(shapes)
            )
        embed = self.vocab_size * self.dim
        if self.moe_experts:
            ffn = self.dim * self.moe_experts + (
                3 * self.moe_experts * self.dim * self.intermediate
            )  # router + per-expert gate/up/down
        else:
            ffn = 3 * self.dim * self.intermediate  # w1, w2, w3
        per_layer = (
            self.dim * self.n_heads * self.head_dim  # wq
            + 2 * self.dim * self.n_kv_heads * self.head_dim  # wk, wv
            + self.n_heads * self.head_dim * self.dim  # wo
            + ffn
            + 2 * self.dim  # norms
        )
        if self.attn_bias:
            per_layer += (
                self.n_heads + 2 * self.n_kv_heads
            ) * self.head_dim
        per_layer += sum(self.qk_norm_widths())
        return embed * 2 + self.n_layers * per_layer + self.dim

    def qk_norm_widths(self) -> tuple:
        """Lengths of the q and k norm weights: () with no q/k norm."""
        if self.qk_norm == "proj":
            return (
                self.n_heads * self.head_dim,
                self.n_kv_heads * self.head_dim,
            )
        return (self.head_dim, self.head_dim) if self.qk_norm else ()

    # ---- presets ----
    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
            intermediate=128, max_seq_len=128, dtype=jnp.float32, **kw
        )

    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        """reference parity target: Llama-2-7B (BASELINE.json configs)."""
        return LlamaConfig(**kw)

    @staticmethod
    def gemma_2b(**kw) -> "LlamaConfig":
        """Gemma-1 2B geometry: GeGLU, (1+w) norms, sqrt(dim) embed
        scale, head_dim decoupled from dim/n_heads."""
        return LlamaConfig(
            vocab_size=256000, dim=2048, n_layers=18, n_heads=8,
            n_kv_heads=1, intermediate=16384, custom_head_dim=256,
            act="gelu_tanh", norm_offset=True, embed_scale=True,
            **kw
        )

    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256, dim=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, intermediate=14336, rope_theta=500000.0,
            max_seq_len=8192, **kw
        )

    @staticmethod
    def bench_410m(**kw) -> "LlamaConfig":
        """GPT-medium-scale config for single-chip benchmarking.

        TPU-shaped: head_dim=128 (8 heads) fills the 128-wide MXU
        lanes and halves the softmax VPU work per attention FLOP vs
        the GPT-medium-standard 16x64 split — same param count, same
        flagship (Llama-7B-class) head geometry."""
        return LlamaConfig(
            vocab_size=32000, dim=1024, n_layers=24, n_heads=8,
            n_kv_heads=8, intermediate=2816, max_seq_len=2048, **kw
        )


def model_norm(cfg: LlamaConfig, x, weight):
    """RMSNorm with the family's scale convention — shared by the
    training layer and the KV-cache serving layer so the two can't
    diverge (Gemma scales by 1+w; Llama-family by w)."""
    return rms_norm(
        x, weight, eps=cfg.norm_eps, offset=1.0 if cfg.norm_offset else 0.0
    )


def model_glu(cfg: LlamaConfig, x, gate):
    """GLU with the family's gate activation: act(gate) * x."""
    if cfg.act == "silu":
        return swiglu(x, gate)
    if cfg.act == "gelu_tanh":
        return jax.nn.gelu(gate, approximate=True) * x
    if cfg.act == "gelu_exact":
        return jax.nn.gelu(gate, approximate=False) * x
    raise ValueError(f"unknown activation {cfg.act!r}")


def embed_tokens(cfg: LlamaConfig, params, tokens):
    """Embedding lookup (+ Gemma's sqrt(dim) normalizer, applied in
    the embedding dtype to match transformers' rounding)."""
    x = params["embed"][tokens].astype(cfg.dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.dim), cfg.dtype)
    return x


def init_params(key: jax.Array, cfg: LlamaConfig) -> Dict[str, Any]:
    """Random initialization, layers stacked on axis 0."""
    k_embed, k_layers, k_out = jax.random.split(key, 3)
    dt = cfg.dtype
    hd = cfg.head_dim

    def norm_init(key, fan_in, shape):
        return (
            jax.random.normal(key, shape, jnp.float32)
            * (1.0 / math.sqrt(fan_in))
        ).astype(dt)

    if cfg.kv_lora_rank or cfg.layer_kinds:
        return {
            "embed": norm_init(k_embed, cfg.dim, (cfg.vocab_size, cfg.dim)),
            **_init_stacks(k_layers, cfg, norm_init),
            "final_norm": jnp.ones((cfg.dim,), dt),
            "lm_head": norm_init(k_out, cfg.dim, (cfg.dim, cfg.vocab_size)),
        }
    keys = jax.random.split(k_layers, 8)
    L = cfg.n_layers
    layers = {
        "wq": norm_init(keys[0], cfg.dim, (L, cfg.dim, cfg.n_heads * hd)),
        "wk": norm_init(keys[1], cfg.dim, (L, cfg.dim, cfg.n_kv_heads * hd)),
        "wv": norm_init(keys[2], cfg.dim, (L, cfg.dim, cfg.n_kv_heads * hd)),
        "wo": norm_init(keys[3], cfg.n_heads * hd, (L, cfg.n_heads * hd, cfg.dim)),
        "attn_norm": jnp.ones((L, cfg.dim), dt),
        "mlp_norm": jnp.ones((L, cfg.dim), dt),
    }
    if cfg.attn_bias:
        layers.update({
            "bq": jnp.zeros((L, cfg.n_heads * hd), dt),
            "bk": jnp.zeros((L, cfg.n_kv_heads * hd), dt),
            "bv": jnp.zeros((L, cfg.n_kv_heads * hd), dt),
        })
    if cfg.qk_norm:
        q_width, k_width = cfg.qk_norm_widths()
        layers.update({
            "q_norm": jnp.ones((L, q_width), dt),
            "k_norm": jnp.ones((L, k_width), dt),
        })
    if cfg.moe_experts:
        E = cfg.moe_experts
        layers.update({
            "router": norm_init(keys[4], cfg.dim, (L, cfg.dim, E)),
            "w_gate": norm_init(
                keys[5], cfg.dim, (L, E, cfg.dim, cfg.intermediate)
            ),
            "w_up": norm_init(
                keys[7], cfg.dim, (L, E, cfg.dim, cfg.intermediate)
            ),
            "w_down": norm_init(
                keys[6], cfg.intermediate,
                (L, E, cfg.intermediate, cfg.dim),
            ),
        })
    else:
        layers.update({
            "w1": norm_init(keys[4], cfg.dim, (L, cfg.dim, cfg.intermediate)),
            "w3": norm_init(keys[5], cfg.dim, (L, cfg.dim, cfg.intermediate)),
            "w2": norm_init(keys[6], cfg.intermediate, (L, cfg.intermediate, cfg.dim)),
        })
    return {
        "embed": norm_init(k_embed, cfg.dim, (cfg.vocab_size, cfg.dim)),
        "layers": layers,
        "final_norm": jnp.ones((cfg.dim,), dt),
        "lm_head": norm_init(k_out, cfg.dim, (cfg.dim, cfg.vocab_size)),
    }


def latent_layer_shapes(cfg: LlamaConfig, L: int, experts: bool) -> Dict:
    """{leaf: (shape, fan in; 0 for a norm weight or a bias)} of a
    stack of `L` latent-attention layers, expert layers or dense.

    Attention: `wq` [d, q_lora_rank] and `q_norm` (the query's latent
    and its RMSNorm), `wq_b` -> heads x (nope + rope); `wkv_a`
    [d, kv_lora_rank + rope] (the latent and the one rotary key of all
    heads) and `kv_norm` over the latent; `wkv_b` [kv_lora_rank,
    heads x (nope + v)], a head's key part then its value part; `wo`.
    Indexer: `wiq` [q_lora_rank, index heads x index dim], `wik`
    [d, index dim] with LayerNorm `ik_norm`, `ik_bias`, `wiw`
    [d, index heads]. FFN: dense `w1` `w3` `w2`, or `router`
    [d, outputs], `router_bias` [outputs], the held experts' `w_gate`
    `w_up` `w_down` and the shared expert's `shared_gate` `shared_up`
    `shared_down`."""
    d, H = cfg.dim, cfg.n_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    out = {
        "attn_norm": ((L, d), 0), "mlp_norm": ((L, d), 0),
        "wq": ((L, d, qr), d), "q_norm": ((L, qr), 0),
        "wq_b": ((L, qr, H * (nope + rope)), qr),
        "wkv_a": ((L, d, kvr + rope), d), "kv_norm": ((L, kvr), 0),
        "wkv_b": ((L, kvr, H * (nope + vd)), kvr),
        "wo": ((L, H * vd, d), H * vd),
    }
    if cfg.index_topk:
        ih, idim = cfg.index_n_heads, cfg.index_head_dim
        out.update({
            "wiq": ((L, qr, ih * idim), qr), "wik": ((L, d, idim), d),
            "ik_norm": ((L, idim), 0), "ik_bias": ((L, idim), 0),
            "wiw": ((L, d, ih), d),
        })
    if not experts:
        f = cfg.dense_intermediate
        out.update({
            "w1": ((L, d, f), d), "w3": ((L, d, f), d), "w2": ((L, f, d), f),
        })
        return out
    E, f = cfg.moe_experts, cfg.intermediate
    outputs = cfg.moe_router_experts or E
    out.update({
        "router": ((L, d, outputs), d), "router_bias": ((L, outputs), 0),
        "w_gate": ((L, E, d, f), d), "w_up": ((L, E, d, f), d),
        "w_down": ((L, E, f, d), f),
    })
    fs = cfg.moe_shared_intermediate
    if fs:
        out.update({
            "shared_gate": ((L, d, fs), d), "shared_up": ((L, d, fs), d),
            "shared_down": ((L, fs, d), fs),
        })
    return out


def kinds_layer_shapes(cfg: LlamaConfig) -> Dict[str, Dict]:
    """{stack: {leaf: (shape, fan in; 0 for a norm weight, a bias or a
    sink)}} of a model with `layer_kinds`. `dense_layers/*` (the
    leading dense layers) and `layers/*` (the others), stacked as a
    latent model's: the attention leaves whose shape no kind changes
    (`attn_norm`, `wq` -> heads x head_dim, `wo`) beside the layer's
    FFN (`mlp_norm`; `w1` `w3` `w2` of `dense_intermediate`, or
    `router` [d, outputs], `router_bias` and the held experts' `w_gate`
    `w_up` `w_down`, with `moe_shared_intermediate` the shared expert's
    `shared_gate` `shared_up` `shared_down`), with `attn_gate` the
    gate's `wg` [d, heads x head_dim] and with `post_norms` the norms
    on the halves' outputs, `attn_post_norm` `mlp_post_norm`. What a
    kind changes is stacked by kind,
    `attn_full/*` and `attn_window/*` over that kind's layers in order:
    `wk` -> the kind's kv heads x head_dim, `wv` -> kv heads x
    `v_head_dim`, where the kind has one `sink` [heads], and with
    `qk_norm` the two norms over a head, `q_norm` `k_norm`.

    A kind with `conv` taps (a gated short convolution, no attention)
    keeps the names: its input projection `[B | C | u] = n W_in` lies
    as its three `dim x dim` thirds, the `B` third under `layers/wq`
    (so every layer of a stack has a first input projection there,
    whatever its kind), `W_out` under `layers/wo`, the operator norm
    under `layers/attn_norm`; `attn_conv/*` holds what the kind has of
    its own: the `C` and `u` thirds `wc` `wu` and the `taps` [conv,
    dim] (tap `conv - 1` meets the current position). Such a model's
    heads x head_dim and heads x `v_head_dim` are both `dim`."""
    d, H, hd = cfg.dim, cfg.n_heads, cfg.head_dim
    vd = cfg.v_head_dim or hd
    E, f = cfg.moe_experts, cfg.intermediate
    outputs = cfg.moe_router_experts or E
    out: Dict[str, Dict] = {}
    for name, L, ffn in (
        ("dense_layers", cfg.dense_layers, lambda L, f: {
            "w1": ((L, d, f), d), "w3": ((L, d, f), d), "w2": ((L, f, d), f),
        }),
        ("layers", cfg.n_layers - cfg.dense_layers, lambda L, f: {
            "router": ((L, d, outputs), d), "router_bias": ((L, outputs), 0),
            "w_gate": ((L, E, d, f), d), "w_up": ((L, E, d, f), d),
            "w_down": ((L, E, f, d), f),
        }),
    ):
        if L:
            out[name] = {
                "attn_norm": ((L, d), 0), "wq": ((L, d, H * hd), d),
                "wo": ((L, H * vd, d), H * vd), "mlp_norm": ((L, d), 0),
                **ffn(L, cfg.dense_intermediate if name == "dense_layers" else f),
            }
            # (what later families brought stands behind: a leaf's key
            # is its place in its stack, `_init_stacks`)
            fs = cfg.moe_shared_intermediate
            if fs and name == "layers":
                out[name].update({
                    "shared_gate": ((L, d, fs), d),
                    "shared_up": ((L, d, fs), d),
                    "shared_down": ((L, fs, d), fs),
                })
            if cfg.attn_gate:
                out[name]["wg"] = ((L, d, H * vd), d)
            if cfg.post_norms:
                out[name].update({
                    "attn_post_norm": ((L, d), 0),
                    "mlp_post_norm": ((L, d), 0),
                })
    for kind, layers in cfg.attn_kinds().values():
        L, kv = len(layers), kind.kv_heads
        if kind.conv:
            if H * hd != d or H * vd != d:
                raise ValueError(
                    "a conv layer's thirds are dim x dim: heads x head_dim "
                    f"is {H * hd}, dim {d}"
                )
            out["attn_conv"] = {
                "wc": ((L, d, d), d), "wu": ((L, d, d), d),
                "taps": ((L, kind.conv, d), kind.conv),
            }
            continue
        out[f"attn_{kind.cache}"] = {
            "wk": ((L, d, kv * hd), d), "wv": ((L, d, kv * vd), d),
            **({"sink": ((L, H), 0)} if kind.sink else {}),
            **({"q_norm": ((L, hd), 0), "k_norm": ((L, hd), 0)}
               if cfg.qk_norm else {}),
        }
    return out


def _init_stacks(key, cfg: LlamaConfig, norm_init) -> Dict:
    """The stacks of a model whose layers are not all alike. Latent
    attention: `dense_layers` (where it has leading dense layers) and
    `layers`; `layer_kinds`: `kinds_layer_shapes`."""
    if cfg.layer_kinds:
        plans = kinds_layer_shapes(cfg)
    else:
        plans = {
            name: latent_layer_shapes(cfg, L, experts)
            for name, L, experts in (
                ("dense_layers", cfg.dense_layers, False),
                ("layers", cfg.n_layers - cfg.dense_layers, True),
            ) if L
        }
    out: Dict[str, Any] = {}
    for number, (name, plan) in enumerate(plans.items()):
        # (the key of a latent model's leaf is what it has been)
        base = 64 * (name == "layers") + 64 * number * name.startswith("attn_")
        stack = {}
        for i, (leaf, (shape, fan_in)) in enumerate(plan.items()):
            if fan_in:
                stack[leaf] = norm_init(
                    jax.random.fold_in(key, i + base), fan_in, shape
                )
            elif leaf.endswith("bias") or leaf == "sink":
                stack[leaf] = jnp.zeros(shape, cfg.dtype)
            else:
                stack[leaf] = jnp.ones(shape, cfg.dtype)
        out[name] = stack
    return out


#: Logical axes of every leaf a layer stack may hold, behind its
#: leading `layers` axis (`param_annotations`).
_LEAF_AXES = {
    "wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
    "wv": ("embed", "kv_heads"), "wo": ("heads", "embed"),
    "wg": ("embed", "heads"),
    "bq": ("heads",), "bk": ("kv_heads",), "bv": ("kv_heads",),
    "router": ("embed", None),
    "w_gate": ("expert", "embed", "mlp"), "w_up": ("expert", "embed", "mlp"),
    "w_down": ("expert", "mlp", "embed"),
    "w1": ("embed", "mlp"), "w3": ("embed", "mlp"), "w2": ("mlp", "embed"),
    "shared_gate": ("embed", "mlp"), "shared_up": ("embed", "mlp"),
    "shared_down": ("mlp", "embed"),
}


def param_annotations(cfg: LlamaConfig) -> Dict[str, Any]:
    """Logical-axis annotations matching init_params' tree: GSPMD maps
    these through PARAM_RULES (fsdp shards embed dims, tp shards
    heads/mlp/vocab)."""
    cfg.require_plain_attention("the training layout (models/llama.py)")
    top = {
        "embed": annotate("vocab", "embed"),
        "final_norm": annotate(None),
        "lm_head": annotate("embed", "vocab"),
    }
    if cfg.layer_kinds:
        # The tree `kinds_layer_shapes` plans: a norm weight or a bias
        # (no fan in) is whole on every device.
        return {
            **top,
            **{
                stack: {
                    leaf: annotate(
                        "layers", *_LEAF_AXES.get(leaf, (None,) * (len(shape) - 1))
                    )
                    for leaf, (shape, _) in plan.items()
                }
                for stack, plan in kinds_layer_shapes(cfg).items()
            },
        }
    leaves = ["wq", "wk", "wv", "wo", "attn_norm", "mlp_norm"]
    if cfg.attn_bias:
        leaves += ["bq", "bk", "bv"]
    if cfg.qk_norm:
        leaves += ["q_norm", "k_norm"]
    leaves += (
        ["router", "w_gate", "w_up", "w_down"] if cfg.moe_experts
        else ["w1", "w3", "w2"]
    )
    return {
        **top,
        "layers": {
            leaf: annotate("layers", *_LEAF_AXES.get(leaf, (None,)))
            for leaf in leaves
        },
    }


def project_qkv(cfg: LlamaConfig, h, layer, kind: Optional[AttnKind] = None):
    """Shared QKV projection (+ Qwen2-family biases) and head split —
    the training layer and the KV-cache serving layer must use the
    SAME projection or their logits silently diverge.
    h: [b, t, dim] -> each of q/k/v: [b, heads, t, head_dim]. For a
    layer of `kind` (`layer_kinds`): that kind's kv heads, and values
    `v_head_dim` wide, times `value_scale`."""
    b, t, _ = h.shape
    hd = cfg.head_dim
    q, k, v = h @ layer["wq"], h @ layer["wk"], h @ layer["wv"]
    if kind is not None:
        def heads(x, n):
            return x.reshape(b, t, n, -1).transpose(0, 2, 1, 3)

        if cfg.value_scale != 1.0:
            v = v * jnp.asarray(cfg.value_scale, v.dtype)
        q, k = heads(q, cfg.n_heads), heads(k, kind.kv_heads)
        if cfg.qk_norm:  # a head's, BEFORE RoPE (as below)
            q = rms_norm(q, layer["q_norm"], eps=cfg.norm_eps)
            k = rms_norm(k, layer["k_norm"], eps=cfg.norm_eps)
        return q, k, heads(v, kind.kv_heads)
    if cfg.attn_bias:
        q, k, v = q + layer["bq"], k + layer["bk"], v + layer["bv"]
    if cfg.qk_norm == "proj":
        # OLMoE: one RMSNorm over the whole projection, heads not yet
        # split, BEFORE RoPE (transformers' OlmoeAttention).
        q = rms_norm(q, layer["q_norm"], eps=cfg.norm_eps)
        k = rms_norm(k, layer["k_norm"], eps=cfg.norm_eps)
    q = q.reshape(b, t, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, t, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, t, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    if cfg.qk_norm == "head":
        # Qwen3: per-head RMSNorm over head_dim, BEFORE RoPE (callers
        # apply rope to whatever this returns, matching transformers'
        # q_norm/k_norm placement).
        q = rms_norm(q, layer["q_norm"], eps=cfg.norm_eps)
        k = rms_norm(k, layer["k_norm"], eps=cfg.norm_eps)
    return q, k, v


@jax.named_scope("layer/attention")
def _attention(cfg: LlamaConfig, q, k, v, sp_axis: Optional[str],
               mesh=None, kind: Optional[AttnKind] = None):
    """Causal attention of one layer; of a layer of `kind`
    (`layer_kinds`) over that kind's kv heads and inside its window."""
    kv_heads, window = (
        (cfg.n_kv_heads, 0) if kind is None else (kind.kv_heads, kind.window)
    )
    k = repeat_kv(k, cfg.n_heads // kv_heads)
    v = repeat_kv(v, cfg.n_heads // kv_heads)
    if cfg.attention == "ring" and sp_axis is not None:
        if window:
            raise NotImplementedError(
                "ring attention has no window: a model with window "
                "layers trains with attention='flash' or 'reference'"
            )
        return ring_attention(q, k, v, sp_axis, causal=True)
    if cfg.attention == "flash":
        if mesh is not None and mesh.size > 1:
            return flash_attention_sharded(
                q, k, v, mesh, causal=True, window=window
            )
        return flash_attention(q, k, v, causal=True, window=window)
    return mha_reference(q, k, v, causal=True, window=window)


def _kind_attention(cfg: LlamaConfig, h, layer, kind: AttnKind, rotary,
                    sp_axis=None, mesh=None):
    """The attention of a layer of `kind` on its normed input h
    [b, t, dim] -> [b, heads, t, head_dim]: `layer` holds `wq` and the
    kind's own leaves; `rotary` is the kind's (cos, sin), None for a
    kind that turns nothing (`rope_theta` 0)."""
    with jax.named_scope("layer/attn_qkv"):
        q, k, v = project_qkv(cfg, h, layer, kind)
        if rotary is not None:
            q = apply_rotary(q, *rotary)
            k = apply_rotary(k, *rotary)
    return _attention(cfg, q, k, v, sp_axis, mesh, kind)


def _kind_layer(cfg: LlamaConfig, x, layer, attend, ep_axis=None):
    """One decoder block of a model with `layer_kinds`, a dense layer
    or an expert layer alike (`_mlp` tells them apart by the leaves).
    `attend(h)` is the layer's attention by its kind
    (`_kind_attention`). -> (x, aux) as `_layer`."""
    b, t, _ = x.shape
    with jax.named_scope("layer/attn_qkv"):
        h = model_norm(cfg, x, layer["attn_norm"])
    attn = attend(h).transpose(0, 2, 1, 3).reshape(b, t, -1)
    if cfg.attn_gate:
        with jax.named_scope("layer/attn_gate"):
            attn = attn * jax.nn.sigmoid(h @ layer["wg"])
    with jax.named_scope("layer/attn_out"):
        x = _residual(cfg, x, attn @ layer["wo"], layer, "attn_post_norm")
    with jax.named_scope("layer/mlp"):
        x, aux, _ = _mlp(cfg, x, layer, ep_axis)
    return x, aux


def _residual(cfg: LlamaConfig, x, out, layer, post_norm: str):
    """x + out, or where the layer norms a half's OUTPUT
    (`post_norms`) x + norm(out)."""
    if post_norm in layer:
        with jax.named_scope("layer/post_norm"):
            out = model_norm(cfg, out, layer[post_norm])
    return x + out


def _layer(cfg: LlamaConfig, x, layer, cos, sin, sp_axis=None,
           ep_axis=None, mesh=None):
    """One decoder block. x: [batch, seq, dim]. Returns (x, aux) where
    aux is the MoE load-balancing loss (0 for dense layers)."""
    b, t, _ = x.shape
    hd = cfg.head_dim
    with jax.named_scope("layer/attn_qkv"):
        h = model_norm(cfg, x, layer["attn_norm"])
        q, k, v = project_qkv(cfg, h, layer)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    attn = _attention(cfg, q, k, v, sp_axis, mesh)
    with jax.named_scope("layer/attn_out"):
        attn = attn.transpose(0, 2, 1, 3).reshape(
            b, t, cfg.n_heads * hd
        )
        x = x + attn @ layer["wo"]
    with jax.named_scope("layer/mlp"):
        x, aux, _ = _mlp(cfg, x, layer, ep_axis)
    return x, aux


#: The experts' matrices of a MoE layer, stacked `[L, E, ., .]`.
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _mlp(cfg: LlamaConfig, x, layer, ep_axis=None, live=None,
         layer_idx=None):
    """The second half of every block — training and the paged
    forwards call this one: norm, dense GLU or
    MoE, residual. x: [b, t, dim] -> (x, aux, counts): the MoE
    load-balancing loss (0 for a dense layer) and the picks each
    expert got, [E] int32 (None for a dense layer). `live` [b] marks
    the rows a serve step computes for real; with `layer_idx` the
    `EXPERT_LEAVES` of `layer` are the whole stacks and that is the
    layer to use (ops/moe.py `moe_ffn_dropless` has both)."""
    b, t, _ = x.shape
    h = model_norm(cfg, x, layer["mlp_norm"])
    if not cfg.moe_experts or "router" not in layer:
        # (a leading dense layer of an expert model has no router)
        out = model_glu(cfg, h @ layer["w1"], h @ layer["w3"]) @ layer["w2"]
        x = _residual(cfg, x, out, layer, "mlp_post_norm")
        return x, jnp.zeros((), jnp.float32), None
    moe = dict(
        k=cfg.moe_top_k,
        renormalise=cfg.moe_router == "softmax_renorm",
        glu=partial(model_glu, cfg),
    )
    flat = h.reshape(b * t, -1)
    if cfg.moe_router == "sigmoid_groups":
        with jax.named_scope("moe/route"):
            moe.update(
                routed=route_grouped_sigmoid(
                    flat, layer["router"], layer["router_bias"],
                    cfg.moe_top_k, cfg.moe_groups, cfg.moe_top_groups,
                    cfg.moe_route_scale,
                ),
                first_expert=cfg.moe_first_expert,
                routed_over=layer["router"].shape[-1],
            )
    if ep_axis is not None:
        out, aux = moe_ffn_ep(
            layer, flat, axis_name=ep_axis,
            capacity_factor=cfg.moe_capacity_factor, **moe,
        )
        counts = None
    else:
        out, aux, counts = moe_ffn_dropless(
            layer, flat,
            live=None if live is None else jnp.repeat(live, t),
            layer=layer_idx, **moe,
        )
    if "shared_gate" in layer:
        with jax.named_scope("moe/shared"):
            out = out + model_glu(
                cfg, flat @ layer["shared_up"], flat @ layer["shared_gate"]
            ) @ layer["shared_down"]
    x = _residual(cfg, x, out.reshape(b, t, -1), layer, "mlp_post_norm")
    return x, aux, counts


def forward_and_aux(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    positions: Optional[jax.Array] = None,
    sp_axis: Optional[str] = None,
    ep_axis: Optional[str] = None,
    mesh=None,
) -> tuple:
    """Token ids [batch, seq] → (logits [batch, seq, vocab] f32,
    aux: summed MoE load-balancing loss, 0 for dense models).

    With sequence parallelism, `tokens` is the local seq shard and
    `positions` carries its global positions. `mesh` is the mesh of
    the GSPMD jit this is traced under (what `make_train_step` was
    given): with more than one device the flash kernel must run per
    shard (ops.attention.flash_attention_sharded; without it JAX
    refuses to lower the kernel). Leave it None inside a `shard_map`,
    where the call is already per shard.
    """
    cfg.require_plain_attention("the training forward (models/llama.py)")
    b, t = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    with jax.named_scope("embed"):
        x = embed_tokens(cfg, params, tokens)
    if cfg.layer_kinds:
        x, aux = _kinds_layers(
            params, x, cfg, positions, sp_axis, ep_axis, mesh
        )
    else:
        cos, sin = rotary_embedding(
            positions, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
        )

        def body(x, layer):
            return _layer(cfg, x, layer, cos, sin, sp_axis, ep_axis, mesh)

        x, aux = jax.lax.scan(_remat(cfg, body), x, params["layers"])
    with jax.named_scope("final_norm"):
        x = model_norm(cfg, x, params["final_norm"])
    with jax.named_scope("lm_head"):
        logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, jnp.sum(aux)


def _remat(cfg: LlamaConfig, body):
    """`body` under the configuration's rematerialization policy."""
    if not cfg.remat:
        return body
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    if cfg.remat_policy == "dots_flash":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(
                    "flash_out", "flash_lse"
                ),
            ),
        )
    return jax.checkpoint(body)


def _kinds_layers(params, x, cfg: LlamaConfig, positions, sp_axis,
                  ep_axis, mesh):
    """The layers of a model with `layer_kinds` on x [b, t, dim] ->
    (x, the summed auxiliary loss).

    The leading dense stack, then the expert stack, each ONE scan over
    its layers, as the all-alike path's: the stack's leaves
    (`dense_layers/*`, `layers/*`) are the scan's `xs`, so a layer's
    weights reach its block as the scan hands them out and their
    gradients come back stacked, and the body is `_kind_layer` under
    the remat policy. What a layer's KIND changes is its attention
    alone, and that alone is chosen inside the body: a `lax.switch`
    over the kinds the stack holds around `_kind_attention`, each
    branch with its kind's window, kv heads, rotary tables (none for a
    kind that turns nothing) and its own leaves, read from the kind's
    small stacks (`attn_window/*`, `attn_full/*`: `wk`, `wv`, the q/k
    norms) at the layer's index into its kind. One trace of the block
    and one of each kind's attention, whatever the depth and the
    pattern.

    Why not as `generate._paged_forward` walks this tree, a scan over
    whole PERIODS whose body slices the period's layers out of its
    `xs`, and why the switch is no wider than the attention: by the
    compiler's own count of the step's memory at Trinity-Mini's nine
    layers (`benchmark/compile_rehearsal.py`, a described v5e, PR 55)
    the periods' scan takes 20.3 GB, a switch around the whole block
    19.1 GB and this loop 15.6 GB (the count runs some 5 GB over what
    the chip then reads, 10.9 GB for this loop, so the first two would
    not have fitted 16). A slice is a value the body computes, so
    `jax.checkpoint` keeps it and the scan stacks it; and the backward
    pass of a `switch` keeps the residuals of EVERY branch, zeros for
    the ones not taken: around a whole expert layer that is the
    layer's rows twice."""
    kinds = cfg.attn_kinds()
    names = list(kinds)
    of_kind = {name: params[f"attn_{name}"] for name in names}
    rotary = {
        name: rotary_embedding(
            positions, cfg.rotary_dim or cfg.head_dim, kind.rope_theta,
            cfg.rope_scaling,
        ) if kind.rope_theta else None
        for name, (kind, _) in kinds.items()
    }
    #: layer -> (its kind's place in `names`, its index into that kind)
    where = {
        layer: (which, at)
        for which, (_, layers) in enumerate(kinds.values())
        for at, layer in enumerate(layers)
    }

    def attention_of(name):
        def attend(h, wq, of_kind, at):
            own = {
                leaf: jax.lax.dynamic_index_in_dim(w, at, keepdims=False)
                for leaf, w in of_kind[name].items()
            }
            return _kind_attention(
                cfg, h, {"wq": wq, **own}, kinds[name][0], rotary[name],
                sp_axis, mesh,
            )

        return attend

    auxs = []
    first = 0
    for stack in (params.get("dense_layers"), params.get("layers")):
        if stack is None:
            continue
        depth = stack["attn_norm"].shape[0]
        which, at = zip(*(where[first + i] for i in range(depth)))
        present = sorted(set(which))
        branches = [attention_of(names[k]) for k in present]

        @partial(_remat, cfg)
        def block(x, layer, of_kind, which, at, branches=branches):
            def attend(h):
                if len(branches) == 1:
                    return branches[0](h, layer["wq"], of_kind, at)
                return jax.lax.switch(
                    which, branches, h, layer["wq"], of_kind, at
                )

            return _kind_layer(cfg, x, layer, attend, ep_axis)

        def body(x, xs, block=block):
            return block(x, xs[0], of_kind, *xs[1:])

        x, aux = jax.lax.scan(body, x, (
            stack,
            jnp.asarray([present.index(k) for k in which], jnp.int32),
            jnp.asarray(at, jnp.int32),
        ))
        auxs.append(jnp.sum(aux))
        first += depth
    return x, sum(auxs)


def forward(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    positions: Optional[jax.Array] = None,
    sp_axis: Optional[str] = None,
    ep_axis: Optional[str] = None,
    mesh=None,
) -> jax.Array:
    """Token ids [batch, seq] → logits [batch, seq, vocab] (f32)."""
    return forward_and_aux(
        params, tokens, cfg, positions=positions, sp_axis=sp_axis,
        ep_axis=ep_axis, mesh=mesh,
    )[0]


def masked_xent(logits: jax.Array, targets: jax.Array) -> tuple:
    """Masked next-token cross-entropy pieces: (sum_nll, token_count).
    `targets` < 0 are masked out. Returned unreduced so data-parallel
    callers can psum both before dividing."""
    mask = (targets >= 0).astype(jnp.float32)
    safe_targets = jnp.maximum(targets, 0)
    # logsumexp-minus-gather rather than log_softmax-then-gather:
    # identical value, but it never materializes the full [*, vocab]
    # log-probability tensor (2 GiB of f32 HBM traffic per direction
    # at bench shapes).
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(
        logits, safe_targets[..., None], axis=-1
    )[..., 0]
    return jnp.sum((lse - tgt) * mask), jnp.sum(mask)


def loss_fn(
    params: Dict[str, Any],
    tokens: jax.Array,
    targets: jax.Array,
    cfg: LlamaConfig,
    *,
    positions: Optional[jax.Array] = None,
    sp_axis: Optional[str] = None,
    ep_axis: Optional[str] = None,
    mesh=None,
) -> jax.Array:
    """Mean next-token cross-entropy (+ weighted MoE aux loss).
    `targets` < 0 are masked out."""
    logits, aux = forward_and_aux(
        params, tokens, cfg, positions=positions, sp_axis=sp_axis,
        ep_axis=ep_axis, mesh=mesh,
    )
    with jax.named_scope("loss"):
        nll_sum, count = masked_xent(logits, targets)
        xent = nll_sum / jnp.maximum(count, 1.0)
    return xent + cfg.moe_aux_weight * aux


def flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """Training FLOPs/token (fwd+bwd), standard 6N + attention term.
    N is `num_params()`, embedding lookup included; the benchmark
    counts required operations without it (benchmark/flops.py), so
    measured MFU comes from there. For MoE, N counts only the
    parameters a token activates (top-k experts, not all E)."""
    n = cfg.num_params()
    if cfg.moe_experts:
        inactive = (cfg.moe_experts - cfg.moe_top_k) * 3 * (
            cfg.dim * cfg.intermediate
        )
        n -= cfg.n_layers * max(inactive, 0)
    # QK^T + AV over n_heads*head_dim total attention width — equal to
    # dim for Llama-family, decoupled for Gemma-style geometries.
    attn_width = cfg.n_heads * cfg.head_dim
    attn = 12 * cfg.n_layers * attn_width * seq_len
    return 6.0 * n + attn / 2  # causal factor 1/2 on the attn term
