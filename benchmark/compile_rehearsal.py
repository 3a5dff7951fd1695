"""Rehearsal 3 of the on-chip-measurement guide: compile a cell's main
programs at real size for a *described* v5e (no chip attached) and
print `memory_analysis()`. Nothing runs, so this says whether a
program fits and which collectives the compiler put in, never a time.

    JAX_PLATFORMS=cpu python -m benchmark.compile_rehearsal <cell> [key=value ...]

`key=value` overrides a trainer or engine key for this compile only
(e.g. remat_policy=dots), which is how the remat policy in a config
file was chosen.
"""

from __future__ import annotations

import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _memory(compiled) -> dict:
    m = compiled.memory_analysis()
    gb = 1e9
    return {
        "argument_gb": m.argument_size_in_bytes / gb,
        "output_gb": m.output_size_in_bytes / gb,
        "alias_gb": m.alias_size_in_bytes / gb,
        "temp_gb": m.temp_size_in_bytes / gb,
        "total_gb": (
            m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes
        ) / gb,
    }


def _collectives(text: str) -> dict:
    """Collective instructions (sync or `-start`) and Mosaic kernels in
    a compiled program's text."""
    names = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
    counts = {
        n: len(re.findall(rf"= \S+ {n}(-start)?\(", text)) for n in names
    }
    counts["tpu_custom_call"] = text.count("tpu_custom_call")
    return counts


def train(config: dict, traffic: dict, chips: int, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models.llama import (
        LlamaConfig, init_params, loss_fn, param_annotations,
    )
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.train_step import default_optimizer, make_train_step

    trainer = config["trainer"]
    cfg = LlamaConfig(
        **config["model"], dtype=jnp.dtype(config["dtype"]),
        attention=trainer["attention"], remat_policy=trainer["remat_policy"],
    )
    mesh = MeshSpec(**trainer["mesh"]).build(list(topo.devices)[:chips])
    # `flash_attention` asks jax.default_backend(), which is the CPU
    # here, and would take its reference branch: steer it in this
    # script (the guide: not through an option of the program).
    jax.default_backend = lambda: "tpu"
    optimizer = default_optimizer(**trainer["optimizer"])
    init_fn, step_fn = make_train_step(
        lambda p, t, y: loss_fn(p, t, y, cfg, mesh=mesh),
        optimizer, mesh, param_annotations(cfg),
    )
    from ray_tpu.parallel.sharding import PARAM_RULES, tree_shardings
    from ray_tpu.train.train_step import TrainState, infer_opt_shardings

    shardings = tree_shardings(mesh, param_annotations(cfg), PARAM_RULES)
    repl = NamedSharding(mesh, P())
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings,
    )
    opt_sh = infer_opt_shardings(optimizer, shapes, shardings, repl)
    opt = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(optimizer.init, shapes), opt_sh,
    )
    state = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
        params=params, opt_state=opt,
    )
    batch = traffic["sequences_per_chip"] * chips
    data_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)
    tokens = jax.ShapeDtypeStruct(
        (batch, traffic["seq_len"]), jnp.int32,
        sharding=NamedSharding(mesh, P(data_axes or None, None)),
    )
    compiled = step_fn.wrapped.lower(state, tokens, tokens).compile()
    return {
        "program": "train.step", "memory": _memory(compiled),
        "ops": _collectives(compiled.as_text()),
    }


def decode(config: dict, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import generate

    from ray_tpu.llm.kv_slots import default_block_len
    from ray_tpu.models.llama import LlamaConfig, init_params

    one = SingleDeviceSharding(topo.devices[0])
    engine = config["engine"]
    cfg = LlamaConfig(**config["model"], dtype=jnp.dtype(config["dtype"]))

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree.map(
        lambda s: spec(s.shape, s.dtype),
        jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0)),
    )
    # (block length, table width, blocks in the pool) as
    # `InferenceEngine` derives them from its config
    block = engine["kv_block_len"] or default_block_len(engine["prefill_chunk"])
    width = engine["max_len"] // block
    n_blocks = engine["kv_blocks"] or engine["slots"] * width + 1
    pool_shape = (cfg.n_layers, n_blocks, cfg.n_kv_heads, block, cfg.head_dim)
    pool = {"k": spec(pool_shape, cfg.dtype), "v": spec(pool_shape, cfg.dtype)}
    slots, chunk = engine["slots"], engine["prefill_chunk"]
    out = []
    step = jax.jit(
        generate._paged_decode_step_impl,
        static_argnames=("temperature", "top_k", "cfg"),
        donate_argnums=(2, 4),
    )
    compiled = step.lower(
        params, cfg, pool, spec((slots, width), jnp.int32),
        spec((slots, cfg.vocab_size), jnp.float32),
        spec((slots,), jnp.int32), spec((slots,), jnp.bool_),
        spec((2,), jnp.uint32), temperature=0.0, top_k=0,
    ).compile()
    out.append({"program": "paged_decode_step", "memory": _memory(compiled)})
    prefill = jax.jit(
        generate._paged_prefill_impl, static_argnames=("cfg",),
        donate_argnums=(3,),
    )
    compiled = prefill.lower(
        params, cfg, spec((1, chunk), jnp.int32), pool,
        spec((1, width), jnp.int32), spec((), jnp.int32), spec((), jnp.int32),
    ).compile()
    out.append({"program": "paged_prefill", "memory": _memory(compiled)})
    return out


def main() -> None:
    from jax.experimental import topologies

    from benchmark import harness

    manifest = harness.load_manifest()
    cell = harness.find_cell(manifest, sys.argv[1])
    config = harness.load_config(manifest, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    for pair in sys.argv[2:]:
        key, value = pair.split("=", 1)
        group = "trainer" if "trainer" in config else "engine"
        try:
            value = json.loads(value)
        except ValueError:
            pass
        config[group][key] = value
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    if "trainer" in config:
        result = train(config, traffic, cell["chips"], topo)
    else:
        result = decode(config, topo)
    print(json.dumps({"cell": cell["name"], "overrides": sys.argv[2:],
                      "result": result}))


if __name__ == "__main__":
    main()
