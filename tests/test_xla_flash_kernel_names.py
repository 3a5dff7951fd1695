"""The flash kernels at the benchmark's training widths, compiled for a
described TPU v5e (no chip attached; nothing runs): the device trace
names a kernel by its HLO instruction, so the names the benchmark's
`flash_kernel_share` sums by are pinned here, with the `named_scope`
the model puts around attention.

With them, because this is the one file that may describe a topology,
the serve forwards compiled for the same described chip: the paged
decode step and prefill chunk hold ONE block pool (ISSUE 24), and a
step's attention is one `paged_attn` kernel a layer body at the
benchmark's own sizes (ISSUE 54).

The topology is described inside a module-scoped fixture of this file
and nowhere else (on-chip-measurement guide, section 2): only one
process may load the TPU's library, and only a test that has started
may try."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import llama

#: `benchmark/configs/mistral-7b-v0.3-l4.json`: 32 heads / 8 KV heads
#: x 128, one 8,192-token sequence a chip.
HEADS, KV_HEADS, HEAD_DIM, SEQ = 32, 8, 128, 8192


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _attention_kernel_calls(one_chip, kv_heads, kind=None):
    """{instruction name: op_name} of the Mosaic calls in the compiled
    gradient of the model's attention block, of a layer of `kind`
    (`layer_kinds`) where one is given."""
    from jax.experimental.compilation_cache import compilation_cache

    cfg = llama.LlamaConfig(
        vocab_size=32768, dim=HEADS * HEAD_DIM, n_layers=1,
        n_heads=HEADS, n_kv_heads=kv_heads, intermediate=14336,
        max_seq_len=SEQ, dtype=jnp.bfloat16, attention="flash",
    )

    def loss(q, k, v):
        out = llama._attention(cfg, q, k, v, None, kind=kind)
        return out.astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct(
        (1, HEADS, SEQ, HEAD_DIM), jnp.bfloat16, sharding=one_chip
    )
    kv = jax.ShapeDtypeStruct(
        (1, kv_heads, SEQ, HEAD_DIM), jnp.bfloat16, sharding=one_chip
    )
    # `flash_attention` asks jax.default_backend(), which is the CPU
    # here, and would take its reference branch: steered in the test,
    # not through an option of the program. A compile for a described
    # chip cannot be read back from the persistent cache, so it is
    # kept out of it.
    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(  # rt: noqa[RT301] — compiled once, for a described chip, by a module-scoped fixture; nothing is called
            jax.grad(loss, argnums=(0, 1, 2))
        ).lower(q, kv, kv).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
        patch.undo()
    calls = {}
    for line in text.splitlines():
        if "tpu_custom_call" not in line or " = " not in line:
            continue
        name = line.strip().split(" = ")[0].removeprefix("ROOT ")
        op_name = re.search(r'op_name="([^"]*)"', line)
        calls[name.lstrip("%")] = op_name.group(1) if op_name else ""
    return calls


@pytest.fixture(scope="module")
def kernel_calls(one_chip):
    """The plain causal kernels at `mistral-7b-v0.3-l4`'s widths."""
    return _attention_kernel_calls(one_chip, KV_HEADS)


@pytest.fixture(scope="module")
def windowed_kernel_calls(one_chip):
    """The kernels under a window at `trinity-mini-ep8`'s widths: 32
    heads / 4 kv heads x 128, 8,192 tokens under a window of 2,048."""
    return _attention_kernel_calls(
        one_chip, 4, llama.AttnKind(window=2048, kv_heads=4)
    )


def test_two_mosaic_kernels_and_nothing_unnamed(kernel_calls):
    families = sorted(re.sub(r"[.\d]+$", "", n) for n in kernel_calls)
    assert families == ["flash_bwd", "flash_fwd"], kernel_calls


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd"])
def test_kernel_is_named_and_scoped(kernel_calls, kernel):
    (name, op_name), = [
        kv for kv in kernel_calls.items() if kv[0].startswith(kernel)
    ]
    assert re.fullmatch(rf"{kernel}(\.\d+)?", name)
    assert "layer/attention" in op_name
    assert f"/{kernel}/" in op_name


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd"])
def test_the_windowed_call_keeps_the_kernels_names(
    windowed_kernel_calls, kernel
):
    """A window is an argument of the SAME two Pallas calls (ISSUE 55):
    Mosaic compiles them at the benchmark's widths (the clamped index
    maps, the second inequality of the mask), and the device trace
    finds them by the names `flash_kernel_share` sums by."""
    families = sorted(
        re.sub(r"[.\d]+$", "", n) for n in windowed_kernel_calls
    )
    assert families == ["flash_bwd", "flash_fwd"], windowed_kernel_calls
    (name, op_name), = [
        kv for kv in windowed_kernel_calls.items() if kv[0].startswith(kernel)
    ]
    assert re.fullmatch(rf"{kernel}(\.\d+)?", name)
    assert "layer/attention" in op_name and f"/{kernel}/" in op_name


@pytest.mark.parametrize("program", ["paged_decode_step", "paged_prefill"])
def test_paged_programs_update_the_pool_in_place(one_chip, program):
    """With the pool donated, a compiled serve forward's temporaries
    are smaller than ONE of the pool's two arrays: no second pool, no
    re-laid copy of it around the layers."""
    from jax.experimental.compilation_cache import compilation_cache

    from ray_tpu.models import generate

    # The benchmark's pool geometry (16 slots x 4,096 keys in blocks of
    # 16, 2 kv heads x 128) under a shallow model: each array is 201 MB,
    # more than the chip's fast memory could hide a copy of.
    cfg = llama.LlamaConfig(
        vocab_size=512, dim=HEAD_DIM * 8, n_layers=6, n_heads=8, n_kv_heads=2,
        intermediate=512, max_seq_len=4096, dtype=jnp.bfloat16,
    )
    slots, block, chunk = 16, 16, 128
    width = cfg.max_seq_len // block

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda s: spec(s.shape, s.dtype),
        jax.eval_shape(
            lambda k: llama.init_params(k, cfg), jax.random.PRNGKey(0)
        ),
    )
    pool_shape = (
        cfg.n_layers, slots * width + 1, cfg.n_kv_heads, block, cfg.head_dim
    )
    pool = {
        "k": spec(pool_shape, cfg.dtype), "v": spec(pool_shape, cfg.dtype)
    }
    # (the step's attention kernel asks jax.default_backend() whether
    # to run interpreted: steered here, as the other compiles are)
    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "default_backend", lambda: "tpu")
    if program == "paged_decode_step":
        lowered = jax.jit(
            generate._paged_decode_step_impl,
            static_argnames=("temperature", "top_k", "cfg"),
            donate_argnums=(2, 4),
        ).lower(
            params, cfg, pool, spec((slots, width), jnp.int32),
            spec((slots, cfg.vocab_size), jnp.float32),
            spec((slots,), jnp.int32), spec((slots,), jnp.bool_),
            spec((2,), jnp.uint32), temperature=0.0, top_k=0,
        )
    else:
        lowered = jax.jit(
            generate._paged_prefill_impl, static_argnames=("cfg",),
            donate_argnums=(3,),
        ).lower(
            params, cfg, spec((1, chunk), jnp.int32), pool,
            spec((1, width), jnp.int32), spec((), jnp.int32),
            spec((), jnp.int32),
        )
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        memory = lowered.compile().memory_analysis()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
        patch.undo()
    one_array = 2 * int(jnp.prod(jnp.asarray(pool_shape)))
    assert memory.alias_size_in_bytes >= 2 * one_array  # donated, reused
    assert memory.temp_size_in_bytes < one_array, memory


#: configuration -> the `paged_attn` calls its step's program holds: one
#: a body of attention layers over a pool the kernel reads in place
#: (`generate.step_reads_in_place`). LFM2's 38 expert layers are nine
#: whole periods of four, scanned as one body, and an attention and a
#: conv layer behind them; a latent pool's step has no such kernel.
PAGED_ATTN_CALLS = {
    "lfm2-24b-a2b-ep8": 2, "olmoe-1b-7b-l8": 1, "qwen2.5-3b": 1,
    "deepseek-v3.2-l5-ep16": 0,
}


@pytest.mark.parametrize("config", sorted(PAGED_ATTN_CALLS))
def test_the_steps_attention_is_one_kernel_a_body_that_reads_the_pool_in_place(
    one_chip, config
):
    """`paged_engine_step` at a benchmark configuration's own sizes,
    compiled for the described chip (ISSUE 54): a step's attention is
    one Mosaic call named `paged_attn` a layer body (what
    `breakdown.device_ops` prints it by), the donated pool is updated
    in place around it (the kernel reads the pool the layer's write
    returned: no copy of a leaf), and nothing has the shape of the
    walk's page gather, `[pairs, tile_blocks, kv_heads, block_len,
    lanes]`, which the step's program copied a trip."""
    from jax.experimental.compilation_cache import compilation_cache

    from benchmark import compile_rehearsal, harness
    from ray_tpu.models import generate

    settings = harness.load_config(harness.load_manifest(), config)
    cfg, a = compile_rehearsal.serve_arguments(settings, one_chip)

    def step(params, pool, last_logits, state, key):
        return generate._paged_engine_step_impl(
            params, cfg, pool, last_logits, state, key, 0.0, 0
        )

    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
            *(a[n] for n in ("params", "pool", "last_logits", "state", "key"))
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
        patch.undo()
    text = compiled.as_text()
    calls = [
        line.strip().split(" = ")[0].removeprefix("ROOT ").lstrip("%")
        for line in text.splitlines()
        if "tpu_custom_call" in line and " = " in line
    ]
    kernels = [c for c in calls if re.fullmatch(r"paged_attn(\.\d+)?", c)]
    assert len(kernels) == PAGED_ATTN_CALLS[config], calls
    in_place = generate.step_reads_in_place(cfg, a["pool"])
    assert any(in_place.values()) == bool(kernels), in_place
    leaves = generate.cache_leaves(a["pool"])
    pool_bytes = sum(
        leaf.dtype.itemsize * int(np.prod(leaf.shape))
        for leaf in leaves.values()
    )
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes  # donated, reused
    smallest = min(
        leaf.dtype.itemsize * int(np.prod(leaf.shape))
        for leaf in leaves.values()
    )
    assert memory.temp_size_in_bytes < smallest, memory
    slots, block = settings["engine"]["slots"], settings["engine"]["kv_block_len"]
    for name, leaf in leaves.items():
        if leaf.ndim != 5 or not kernels:
            continue
        _, _, kv_heads, _, lanes = leaf.shape
        gathered = rf"\[{slots},\d+,{kv_heads},{block},{lanes}\]"
        assert not re.search(gathered, text), (name, gathered)


def test_expert_matmuls_are_named_ragged_dot_and_scoped(one_chip):
    """The dropless expert layer at OLMoE's widths, compiled for the
    described chip: XLA turns `lax.ragged_dot` into a grouped-matmul
    kernel of its own whose instructions are named `ragged-dot-*`
    (what `benchmark/layer_metrics/moe_kernel_share.py` sums by), one
    per projection, and one `ragged-dot-metadata` that lays out the
    groups for all three. XLA renames the kernels' `op_name` too, so
    the instruction name is what finds them; the `moe/route`,
    `moe/experts` (the gather of the sorted rows, the activation) and
    `moe/combine` scopes are on the operations around them. Nothing
    runs every expert on every token: the compiler's own count of the
    program's operations is the picks', not 8 times that."""
    from jax.experimental.compilation_cache import compilation_cache

    from ray_tpu.ops.moe import moe_ffn_dropless

    experts, dim, width, tokens, k = 64, 2048, 1024, 512, 8

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {
        "router": spec((dim, experts)),
        "w_gate": spec((experts, dim, width)),
        "w_up": spec((experts, dim, width)),
        "w_down": spec((experts, width, dim)),
    }
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(
            lambda p, x: moe_ffn_dropless(p, x, k=k, renormalise=False)
        ).lower(params, spec((tokens, dim))).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    kernels = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and " = " in line
    ]
    names = [
        line.strip().split(" = ")[0].removeprefix("ROOT ").lstrip("%")
        for line in kernels
    ]
    assert names and all(n.startswith("ragged-dot") for n in names), names
    matmuls = [
        line for line, n in zip(kernels, names) if "metadata" not in n
    ]
    assert len(matmuls) == 3  # gate, up, down
    assert len(names) == 4  # and one layout of the groups for all three
    for scope in ("moe/route", "moe/experts", "moe/combine"):
        assert scope in text, scope
    # 512 tokens x 8 picks x 3 matrices x 2 x 2048 x 1024, not 8 times
    # that: the compiler's own count of the program's operations.
    flops = compiled.cost_analysis()["flops"]
    assert flops < 1.5 * tokens * k * 3 * 2 * dim * width, flops


def _build_latent_program(
    one_chip, cfg, program, n_blocks, block, width, chunk, slots=16
):
    """-> (compiled, the pool's specs): `paged_decode_step` over `slots`
    rows or `paged_prefill` of one `chunk`, of a latent-attention
    configuration with a pool of `n_blocks` blocks of `block` and
    tables `width` wide, compiled for the described chip with the
    compile cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    from ray_tpu.models import generate

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda s: spec(s.shape, s.dtype),
        jax.eval_shape(
            lambda k: llama.init_params(k, cfg), jax.random.PRNGKey(0)
        ),
    )
    pool = jax.tree.map(
        lambda s: spec(s.shape, s.dtype),
        jax.eval_shape(
            lambda: generate.init_block_pool(cfg, n_blocks, block)
        ),
    )
    # the kernels ask jax.default_backend() whether to run interpreted:
    # steered here, as the flash kernels' compile above is
    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        if program == "paged_decode_step":
            lowered = jax.jit(
                generate._paged_decode_step_impl,
                static_argnames=("temperature", "top_k", "cfg"),
                donate_argnums=(2, 4),
            ).lower(
                params, cfg, pool, spec((slots, width), jnp.int32),
                spec((slots, cfg.vocab_size), jnp.float32),
                spec((slots,), jnp.int32), spec((slots,), jnp.bool_),
                spec((2,), jnp.uint32), temperature=0.0, top_k=0,
            )
        else:
            lowered = jax.jit(
                generate._paged_prefill_impl, static_argnames=("cfg",),
                donate_argnums=(3,),
            ).lower(
                params, cfg, spec((1, chunk), jnp.int32), pool,
                spec((1, width), jnp.int32), spec((), jnp.int32),
                spec((), jnp.int32),
            )
        return lowered.compile(), pool
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
        patch.undo()


@pytest.mark.parametrize("program", ["paged_decode_step", "paged_prefill"])
def test_latent_programs_are_scoped_and_update_the_pool_in_place(
    one_chip, program
):
    """The serve forwards of a latent-attention configuration with an
    indexer (DeepSeek-V3.2's mechanisms at a shallow, narrow size, the
    benchmark's block length and cache widths), compiled for the
    described chip: every stage stands under the scope the trace is
    read by (`mla/*`, `dsa/*`, `moe/shared` beside `moe/route`,
    `moe/experts`, `moe/combine`), the kernels are the experts'
    `ragged-dot` and, in a chunk alone, `selected_attn` (what
    `benchmark/layer_metrics/selected_attn_kernel_share.py` sums by;
    a step gathers its selected entries in plain XLA) with
    `latent_expand`, which writes the keys and values it reads
    (ISSUE 45: no fill, no copy a tile), and the donated
    pool is updated in place. The last is not a given: a latent entry of 576
    numbers, kept 576 wide, made the TPU lay the pool out with its
    blocks innermost and re-lay all of it around every step (a copy
    of 1.9 GB at the benchmark's size), so the entry is declared in
    whole lanes (`generate._lanes`)."""
    cfg = llama.LlamaConfig(
        vocab_size=512, dim=1024, n_layers=3, n_heads=16, n_kv_heads=16,
        intermediate=256, max_seq_len=4096, dtype=jnp.bfloat16,
        rope_scaling=("yarn", 40, 1, 32, 4096),
        kv_lora_rank=512, q_lora_rank=256, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        index_topk=512, index_n_heads=8, index_head_dim=128,
        moe_experts=4, moe_top_k=4, moe_router="sigmoid_groups",
        moe_router_experts=16, moe_groups=4, moe_top_groups=2,
        moe_route_scale=2.5, moe_shared_intermediate=256,
        dense_layers=1, dense_intermediate=512,
    )
    slots, block, chunk = 16, 16, 256
    width = cfg.max_seq_len // block
    compiled, pool = _build_latent_program(
        one_chip, cfg, program, slots * width + 1, block, width, chunk,
        slots,
    )
    assert pool["latent"].shape[-1] == 640 and pool["index_k"].shape[-1] == 128
    text = compiled.as_text()
    for scope in (
        "mla/q", "mla/kv_latent", "dsa/index", "dsa/select", "mla/attend",
        "mla/out", "moe/shared", "moe/route", "moe/experts", "moe/combine",
    ):
        assert scope in text, scope
    kernels = {
        re.sub(r"[.\d]+$", "", line.strip().split(" = ")[0]
               .removeprefix("ROOT ").lstrip("%"))
        for line in text.splitlines()
        if "tpu_custom_call" in line and " = " in line
    }
    families = {n for n in kernels if not n.startswith("ragged-dot")}
    assert any(n.startswith("ragged-dot") for n in kernels), kernels
    assert families == (
        set() if program == "paged_decode_step"
        else {"selected_attn", "latent_expand"}
    ), kernels
    memory = compiled.memory_analysis()
    latent = pool["latent"]
    one_leaf = 2 * int(jnp.prod(jnp.asarray(latent.shape)))
    assert memory.alias_size_in_bytes >= one_leaf  # donated, reused
    assert memory.temp_size_in_bytes < one_leaf, memory
    if program == "paged_prefill":
        assert not _fills_and_copies_of_expanded_buffers(
            text, cfg.n_heads * 128
        )
        # what the program held before ISSUE 45, at these sizes
        assert memory.temp_size_in_bytes <= 4709376, memory


def _fills_and_copies_of_expanded_buffers(text: str, columns: int):
    """The `broadcast` and `dynamic-update-slice` instructions of a
    compiled chunk whose result is a whole buffer of expanded keys or
    values, `bf16[rows, keys of a whole table, heads x lanes]`: what
    `_expand_latent` cost before its kernel wrote each tile once
    (ISSUE 45: a fill of 554 MB and a copy a tile, twice a layer)."""
    found = []
    for line in text.splitlines():
        made = re.match(
            r"\s*(?:ROOT )?(%\S+) = bf16\[\d+,(\d+),(\d+)\]\S* "
            r"(broadcast|dynamic-update-slice)\(", line,
        )
        if made and int(made.group(3)) == columns and int(made.group(2)) >= 4096:
            found.append(made.group(1))
    return found


def test_latent_chunk_at_the_benchmarks_sizes_writes_its_expansion_once(
    one_chip
):
    """`paged_prefill` of `deepseek-v3.2-l5-ep16` as the cell runs it
    (chunk 512, a table of 1,024 blocks of 16, the pool's 20,480
    blocks), compiled for the described chip: the expansion is the
    `latent_expand` kernel, one call a stack of layers, no buffer of
    16,896 keys x 16,384 columns is filled or copied into, and the
    program's temporaries are no more than they were with the fills
    (1,279,400,960 bytes)."""
    import json
    import pathlib

    config = json.loads((
        pathlib.Path(__file__).parent.parent
        / "benchmark/configs/deepseek-v3.2-l5-ep16.json"
    ).read_text())
    engine, model = config["engine"], dict(config["model"])
    model["rope_scaling"] = tuple(model["rope_scaling"])
    cfg = llama.LlamaConfig(**model, dtype=jnp.dtype(config["dtype"]))
    block = engine["kv_block_len"]
    compiled, _ = _build_latent_program(
        one_chip, cfg, "paged_prefill", engine["kv_blocks"], block,
        engine["max_len"] // block, engine["prefill_chunk"],
    )
    text = compiled.as_text()
    calls = [
        line.strip().split(" = ")[0].removeprefix("ROOT ").lstrip("%")
        for line in text.splitlines()
        if "tpu_custom_call" in line and " = " in line
    ]
    expansions = [n for n in calls if n.startswith("latent_expand")]
    assert len(expansions) == 2, calls  # the dense stack's and the experts'
    assert f"bf16[1,16896,{cfg.n_heads * 128}]" in text  # the buffers exist
    assert not _fills_and_copies_of_expanded_buffers(text, cfg.n_heads * 128)
    assert compiled.memory_analysis().temp_size_in_bytes <= 1279400960


@pytest.mark.parametrize("queries", [2048, 512], ids=["chunk", "quarter"])
def test_selected_attention_compiles_at_the_benchmarks_widths(one_chip, queries):
    """The chunk's attention kernel at `deepseek-v3.2-l5-ep16`'s sizes
    (128 heads, 128 + 64 key dims in whole lanes, 128 value dims, a
    table of 18,432 keys, tiles of 512), compiled by Mosaic for the
    described chip: one `selected_attn` call whose temporaries are a
    block's, not the scores'."""
    from jax.experimental.compilation_cache import compilation_cache

    from ray_tpu.ops import selected_attention as sa

    heads, keys, lanes = 128, 18432, 128

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(
            lambda *a: sa.selected_attention(*a, scale=0.135)
        ).lower(
            spec((1, heads, queries, lanes)), spec((1, heads, queries, lanes)),
            spec((1, keys, heads * lanes)), spec((1, keys, lanes)),
            spec((1, keys, heads * lanes)), spec((1, queries, keys), jnp.int8),
            spec((1,), jnp.int32), spec((1,), jnp.int32),
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
        patch.undo()
    calls = [
        line.strip().split(" = ")[0].removeprefix("ROOT ").lstrip("%")
        for line in compiled.as_text().splitlines()
        if "tpu_custom_call" in line and " = " in line
    ]
    assert len(calls) == 1 and re.fullmatch(r"selected_attn(\.\d+)?", calls[0])
    # the two scaled copies of q; [heads, queries, keys] float32
    # scores would be 19 GB at a chunk
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * (
        heads * queries * lanes * 2
    )
