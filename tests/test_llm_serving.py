"""End-to-end LLM serving: the paged decode path behind a Serve
deployment — the framework's pieces composed the way a user would
(reference story: vLLM-on-Ray; here the in-tree engine serves)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decode_oracle import greedy_uncached

MODEL = dict(
    vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
    intermediate=128, max_seq_len=64, attention="reference",
)
ENGINE = dict(slots=4, max_len=32, prefill_chunk=8, max_new_tokens=6)


def tokens_of(chunks):
    return [int(t) for t in b"".join(chunks).split()]


def test_llm_deployment_with_batching(rt_session):
    """A user's own deployment around an `InferenceEngine`: six calls
    in flight share four slots, and every one gets its completion."""
    import ray_tpu.serve as serve

    @serve.deployment(max_ongoing_requests=8)
    class LlamaService:
        def __init__(self):
            from ray_tpu.llm import EngineConfig, InferenceEngine
            from ray_tpu.models.llama import LlamaConfig, init_params

            cfg = LlamaConfig(dtype=jnp.float32, **MODEL)
            self.engine = InferenceEngine(
                init_params(jax.random.PRNGKey(0), cfg), cfg,
                EngineConfig(**ENGINE), family="tiny",
            )

        def complete(self, prompt):
            return list(self.engine.submit(prompt))

        def requests_done(self):
            return self.engine.stats()["requests_done"]

    try:
        handle = serve.run(
            LlamaService.bind(), name="llm", route_prefix=None
        )
        prompts = [[1 + i, 7, 12, 5] for i in range(6)]
        responses = [handle.complete.remote(p) for p in prompts]
        results = [r.result(timeout=120) for r in responses]
        assert len(results) == 6
        for tokens in results:
            assert len(tokens) == 6
            assert all(0 <= t < 128 for t in tokens)
        # Determinism: same prompt, same greedy completion.
        again = handle.complete.remote(prompts[0]).result(timeout=120)
        assert again == results[0]
        assert handle.requests_done.remote().result(timeout=60) == 7
    finally:
        serve.shutdown()


def test_llm_token_streaming(rt_session):
    """Token streaming through `build_llm_app`: the consumer receives
    each token as its own chunk while decoding is still running
    (reference story: streaming chat completions), and the greedy
    stream equals the uncached forward's continuation."""
    import ray_tpu.serve as serve
    from ray_tpu.llm import build_llm_app
    from ray_tpu.models.llama import LlamaConfig, init_params

    family = {
        "kind": "init", "seed": 0,
        "config": dict(MODEL, dtype="float32"),
    }
    try:
        handle = serve.run(
            build_llm_app({"tiny": family}, engine=ENGINE),
            name="llm-stream", route_prefix=None,
        )
        stream = handle.options(stream=True).remote(
            {"prompt": [1, 7, 12, 5], "max_new_tokens": 6}
        )
        chunks = list(stream)
    finally:
        serve.shutdown()
    assert len(chunks) == 6  # one chunk a token
    tokens = tokens_of(chunks)
    cfg = LlamaConfig(dtype=jnp.float32, **MODEL)
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert tokens == greedy_uncached(params, cfg, [1, 7, 12, 5], 6)


def test_serve_converted_hf_checkpoint(rt_session, tmp_path):
    """The full user story: an HF Llama checkpoint converts, deploys
    behind Serve on the engine, and the served greedy tokens are
    IDENTICAL to transformers.generate on the same weights."""
    rt = rt_session
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers import LlamaConfig as HFConfig
    from transformers import LlamaForCausalLM

    import ray_tpu.serve as serve
    from ray_tpu.llm import build_llm_app

    torch.manual_seed(9)
    hf = LlamaForCausalLM(HFConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, max_position_embeddings=64,
        tie_word_embeddings=False, attn_implementation="eager",
    ))
    hf.eval()
    ckpt = str(tmp_path / "tiny_llama")
    hf.save_pretrained(ckpt)

    prompt = np.random.default_rng(9).integers(
        1, 128, (1, 10), dtype=np.int64
    )
    with torch.no_grad():
        expected = hf.generate(
            torch.from_numpy(prompt), max_new_tokens=6,
            do_sample=False, pad_token_id=0, eos_token_id=None,
        )[:, prompt.shape[1]:].numpy().tolist()

    try:
        handle = serve.run(
            build_llm_app(
                {"hf": {"kind": "hf", "path": ckpt}}, engine=ENGINE
            ),
            name="hf-llm", route_prefix=None,
        )
        served = tokens_of(
            handle.options(stream=True).remote(
                {"prompt": prompt[0].tolist(), "max_new_tokens": 6}
            )
        )
        assert [served] == expected
    finally:
        serve.shutdown()
