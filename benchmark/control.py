"""The control of `correct`: what the comparison reads when the plain
reference stands in the program's place one precision below the one
the configuration states. Not part of a run; run it once on the chip
at a configuration's own size, and again when a limit is in question.

    python3 benchmark/control.py --config <name> [--seeds 1,2,3] [--rehearse]

Every configuration here states bfloat16, so the step below is 8 bits,
the one that would tempt a later PR (a decode step is bound by the
bytes of weights it reads): every matmul weight is rounded to int8,
symmetric, one scale per output channel, and read back in the weights'
own type. Activations, norms, biases and the embedding lookup stay as
they are, so this is the mildest 8-bit path: one that also rounds
activations reads more. A limit holds where the smallest control
reading is at least three times the largest sound reading of the
program and the limit lies between them (PERF.md section 2 has both).

A train configuration's control (this command) reads one seeded
sequence of the cell's length, all positions and the last: the
relative RMS distance of the control's logits from the reference's
own, the number the train check compares. A serve configuration's
control reads what the run's own comparison reads, over the run's own
sample of served requests, so it is a flag of the run:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --control

(`drivers/serve_probe.py`: at each position of the same prompts and
served tokens, the gap of the token this control puts first; the
notes' `probe.control`, whose `correct` has to be false.)
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def int8_matrix(w):
    """One [in, out] matrix rounded to int8 per output channel and
    read back in its own dtype."""
    import jax.numpy as jnp

    x = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=0, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (jnp.clip(jnp.round(x / scale), -127, 127) * scale).astype(w.dtype)


def int8_weights(params: dict) -> dict:
    """The parameter tree with every matmul weight through
    `int8_matrix`: every leaf under a parent (`layers`, and whatever
    other stacks or groups a configuration's plan has, however deep)
    whose last two axes are a matrix behind one or more leading axes
    (`[layers, in, out]`, an expert layer's `[layers, experts, in,
    out]`, the router `[layers, d, E]`), one matrix at a time, so the
    float32 copy is one matrix's and each expert gets scales of its
    own; and `lm_head`. A matrix that stands alone under a parent is
    rounded where its plan stacks it (`[1, in, out]`). The old leaves
    are donated: two trees of a model that fills half a chip do not fit
    beside each other."""
    from functools import partial

    import jax

    def mapped(w):
        rounded = int8_matrix
        for _ in range(w.ndim - 2):
            rounded = partial(jax.lax.map, rounded)
        return rounded(w)

    stacked = jax.jit(mapped, donate_argnums=0)
    out = {
        name: jax.tree.map(lambda w: stacked(w) if w.ndim >= 3 else w, group)
        if isinstance(group, dict) else group
        for name, group in params.items()
    }
    out["lm_head"] = stacked(params["lm_head"])
    return out


def control_errors(config: dict, seeds: list, seq_len: int) -> list:
    """Per seed: the relative RMS error of the int8 control's logits
    against the reference's, over a seeded sequence of `seq_len`
    tokens, all positions and the last one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig, init_params

    from benchmark.reference import compare

    reference = compare.load(config.get("reference"))
    model = config["model"]
    cfg = LlamaConfig(**model, dtype=jnp.dtype(config["dtype"]))
    rows = []
    for seed in seeds:
        params = jax.jit(lambda k: init_params(k, cfg))(
            jax.random.PRNGKey(seed)
        )
        tokens = jnp.asarray(
            np.random.default_rng([seed, 0xC0DE]).integers(
                0, model["vocab_size"], size=seq_len
            ), jnp.int32,
        )
        want = np.asarray(reference.forward(params, tokens, model))
        got = np.asarray(reference.forward(int8_weights(params), tokens, model))
        rows.append({
            "seed": seed, "tokens": seq_len,
            "all_positions": compare.relative_rms_error(got, want),
            "last_position": compare.relative_rms_error(got[-1], want[-1]),
            "limit": config["tolerance"]["logits_rel_rms"],
        })
        del params, want, got
    return rows


def main() -> int:
    from benchmark import harness

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--seeds", default="1,2,3")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()
    manifest = harness.load_manifest()
    config = harness.load_config(manifest, args.config)
    traffic = next(
        harness.load_traffic(c["traffic"]) for c in manifest["workloads"]
        if c["config"] == args.config
    )
    if args.rehearse:
        config = harness.apply_rehearsal(config)
        traffic = harness.apply_rehearsal(traffic)
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    device = harness.describe(jax.devices())
    if not args.rehearse and device["platform"] != "tpu":
        print(f"no TPU: JAX reports {device}", file=sys.stderr)
        return 1
    seeds = [int(x) for x in args.seeds.split(",")]
    if "engine" in config:
        print(
            "a serve configuration's control is read by the run itself: "
            "benchmark/run.py --workload <cell> --control", file=sys.stderr,
        )
        return 2
    for row in control_errors(config, seeds, int(traffic["seq_len"])):
        print(json.dumps(dict(row, config=args.config, device=device)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
