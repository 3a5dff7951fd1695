"""Correctness of a serve cell, decided once the window has closed, the
replica's peak memory has been read and the cluster is shut down, in a
short process that holds the chip the replica gave back (`serve.py`),
so that neither it nor the reference's pass is part of `setup_s`.

The weights are the benchmark's, made here again from the seed
(`reference/weights.py`, as the replica was handed them): neither side
of the comparison takes its weights from the other; which leaves the
tree has is the reference module's to say (`shapes`). Two comparisons
with the plain reference, which runs once over each sequence, one at a
time, padded at its end to a multiple of a quarter of `max_len` (it is
causal: what pads the end moves no position before it), and is asked
for the logits of the rows that are compared and no other
(`forward(..., rows=(start, stop))`): a served request's served
positions, a probe row's every position.

1. THE WINDOW'S OWN SERVED TOKENS. `serve.py` draws from the seed a
   sample of the requests the window FINISHED (`sample`: the longest and
   `SAMPLE_REQUESTS - 1` others). At every served token: how far its
   logit in the reference lies under the reference's best at that
   position, in units of the position's logit deviation (`gaps`). A
   greedy token that the program got right to rounding is the best or
   lies a rounding under it; a token from a wrong page, a dropped chunk,
   another request or an altered stream lies deviations under.
   `served_gap_max`, the widest gap of the sample, is held to the
   configuration's `tolerance`; the mean and the share of tokens that
   are the reference's best are printed beside it. This covers whatever
   served those tokens (HTTP, router, admission, prefix cache, every
   chunk of every prompt, the engine's step at the window's batch, the
   stream out) but, being a handful of near-ties among some hundreds of
   tokens, it does not tell bf16 from int8. That is the second's part.

2. THE PROGRAMS THE WINDOW DROVE, AT ITS SHAPES, LOGIT BY LOGIT. Every
   slot of the engine is filled (`probe_lengths` gives the prompts, dealt
   to the slots in turn): each row is prefilled as `llm/engine.py`
   prefills a prompt, by `paged_prefill` chunk after chunk at `offset` 0,
   `chunk`, 2 x `chunk` ... with `valid_len = offset + chunk` and the
   row's own block table, then `DECODE_STEPS` calls of
   `paged_engine_step`, the engine's own step program with its state on
   the device, ALL slots alive, each row fed its own greedy token. Pool,
   tables and state have the engine's shapes, so both are the programs
   the replica compiled. Compared: every prompt position of the first
   row of each length as one relative RMS error, every decoded position
   of all slots as another; the larger, `logits_rel_rms`, is held to the
   `tolerance`, and so is `logits_rel_rms_row`, the worst single row's
   (its prefill or its decoded positions alone), where the `tolerance`
   names it.

    python -m benchmark.drivers.serve_probe <spec.json>

prints one JSON line: device, the numbers, per-row and per-request
rows, `correct`. With `"control": true` in the spec (`run.py --control`)
the line also holds what the same comparisons read of the control in
the program's place (`benchmark/control.py`'s int8 weights under the
same reference, over the same sequences; for the served tokens, the
gap of the token the control puts first), with `control.correct`
decided by the same limits, and `altered_gap_min`, the smallest gap
that one served token altered by one reads over the sample's requests.
"""

from __future__ import annotations

import json
import random
import sys

#: Requests of a window that are compared.
SAMPLE_REQUESTS = 8
#: Positions every slot decodes in the second comparison.
DECODE_STEPS = 16
#: The numbers a configuration's `tolerance` may hold.
HELD = ("served_gap_max", "logits_rel_rms", "logits_rel_rms_row")


def sample(records: list, seed: int, k: int = SAMPLE_REQUESTS) -> list:
    """Of the requests a window finished, the longest and `k - 1`
    others drawn from the seed -> [{"prompt", "tokens"}]."""
    finished = [r for r in records if r.get("ok") and r.get("tokens")]
    if not finished:
        return []
    longest = max(finished, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in finished if r is not longest]
    picked = random.Random(int(seed)).sample(rest, min(k - 1, len(rest)))
    return [
        {"prompt": list(r["prompt"]), "tokens": list(r["tokens"])}
        for r in [longest] + picked
    ]


def gaps(logits, tokens):
    """logits [m, vocab] float32 of the reference at the positions that
    produced `tokens` [m] -> per position, (best logit - the token's
    logit) / the position's logit deviation, on the host."""
    import jax
    import jax.numpy as jnp

    tokens = jnp.asarray(tokens, jnp.int32)
    picked = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    gap = (jnp.max(logits, axis=-1) - picked) / jnp.std(logits, axis=-1)
    return jax.device_get(gap)


def reference_logits(
    reference, params, tokens, model: dict, bucket: int, rows=None,
):
    """The reference's logits over `tokens` [t], every position's
    [t, vocab] or those of `rows` = (start, stop) alone, run at the
    next multiple of `bucket` so that a handful of shapes serve every
    run."""
    import jax.numpy as jnp
    import numpy as np

    fed = np.zeros(-(-len(tokens) // bucket) * bucket, np.int32)
    fed[:len(tokens)] = tokens
    return reference.forward(
        params, jnp.asarray(fed), model, rows=tuple(rows or (0, len(tokens)))
    )


def served_logits(forward, request: dict):
    """The logits [m, vocab] at the positions that produce a request's
    m served tokens (the last prompt position and all served tokens but
    the last), in one pass of `forward(tokens, rows)` over prompt +
    served that is asked for those m rows and no other: a request may
    be as long as `max_len`."""
    n = len(request["prompt"])
    fed = list(request["prompt"]) + list(request["tokens"][:-1])
    return forward(fed, (n - 1, len(fed)))


def served_summary(rows: list) -> dict:
    """Per-request gaps -> `served_gap_max` and what is printed beside."""
    import numpy as np

    every = np.concatenate([r["gaps"] for r in rows])
    worst = max(range(len(rows)), key=lambda i: float(rows[i]["gaps"].max()))
    return {
        "requests": len(rows), "tokens": int(every.size),
        "served_gap_max": float(every.max()),
        "served_gap_mean": float(every.mean()),
        "best_share": float(np.mean(every == 0.0)),
        "worst_request": worst,
        "worst_token": int(rows[worst]["gaps"].argmax()),
        "requests_rows": [
            {
                "n_prompt": r["n_prompt"], "n_out": int(r["gaps"].size),
                "gap_max": float(r["gaps"].max()),
                "gap_mean": float(r["gaps"].mean()),
            } for r in rows
        ],
    }


# -- the programs at the engine's shapes ------------------------------

def probe_prompts(spec: dict) -> list:
    """One prompt per entry of `probe_lengths`, drawn from the seed."""
    import numpy as np

    rng = np.random.default_rng([spec["seed"], 0x9E0B])
    vocab = spec["model"]["vocab_size"]
    return [rng.integers(1, vocab, size=n) for n in spec["probe_lengths"]]


def chunk_offsets(n: int, chunk: int) -> list:
    """(start of the tokens, `offset` passed) of each chunk of an
    `n`-token prompt, as the engine walks it."""
    return [(s, s) for s in range(0, -(-n // chunk) * chunk, chunk)]


def forwards(spec: dict, params, prompts: list) -> tuple:
    """The timed path's two programs with every slot in use: slot r
    holds prompt r mod len(prompts). -> (prefill logits [n, vocab] of
    the first row of each prompt, decode logits [steps, slots, vocab],
    decoded tokens [steps, slots])."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm.kv_slots import default_block_len
    from ray_tpu.models.generate import (
        init_block_pool, paged_engine_step, paged_prefill,
    )
    from ray_tpu.models.llama import LlamaConfig

    engine = spec["engine"]
    cfg = LlamaConfig(**spec["model"], dtype=jnp.dtype(spec["dtype"]))
    # block length, table width and blocks in the pool as
    # `InferenceEngine` derives them from its config
    block = engine["kv_block_len"] or default_block_len(engine["prefill_chunk"])
    width = engine["max_len"] // block
    n_blocks = engine["kv_blocks"] or engine["slots"] * width + 1
    chunk, slots = engine["prefill_chunk"], engine["slots"]
    if max(map(len, prompts)) + DECODE_STEPS > engine["max_len"]:
        raise ValueError("a probe row and its decoded tokens pass max_len")
    pool = init_block_pool(cfg, n_blocks, block)
    tables = np.zeros((slots, width), np.int32)
    positions = np.zeros(slots, np.int32)
    last_logits = jnp.zeros((slots, cfg.vocab_size), jnp.float32)
    prefill_logits = []
    next_block = 1
    for row in range(slots):
        prompt = prompts[row % len(prompts)]
        n = len(prompt)
        need = -(-(n + DECODE_STEPS) // block)
        if next_block + need > n_blocks:
            raise ValueError("the probe's rows do not fit the pool")
        tables[row, :need] = np.arange(next_block, next_block + need)
        next_block += need
        table = jnp.asarray(tables[row:row + 1])
        padded = np.zeros((1, -(-n // chunk) * chunk), np.int32)
        padded[0, :n] = prompt
        kept = []
        for start, offset in chunk_offsets(n, chunk):
            logits, pool = paged_prefill(
                params, cfg, jnp.asarray(padded[:, start:start + chunk]),
                pool, table, np.int32(offset), np.int32(offset + chunk),
            )
            kept.append(logits[0, :min(chunk, n - start)])
            del logits
        last_logits = last_logits.at[row].set(kept[-1][-1])
        if row < len(prompts):
            prefill_logits.append(
                jnp.concatenate(kept) if len(kept) > 1 else kept[0]
            )
        del kept
        positions[row] = n
    state = {
        "tables": jnp.asarray(tables), "positions": jnp.asarray(positions),
        "alive": jnp.ones(slots, bool), "eos": jnp.full(slots, -1, jnp.int32),
        "budget": jnp.full(slots, DECODE_STEPS + 1, jnp.int32),
        "step": jnp.zeros((), jnp.int32),
    }
    decode_logits, tokens = [], []
    for _ in range(DECODE_STEPS):
        fetch, pool, last_logits, state = paged_engine_step(
            params, cfg, pool, last_logits, state, jax.random.PRNGKey(0),
            temperature=0.0, top_k=0,
        )
        tokens.append(np.asarray(fetch["token"]))
        decode_logits.append(last_logits + 0)  # the next step donates it
    del pool, last_logits
    return prefill_logits, jnp.stack(decode_logits), np.stack(tokens)


def compare_rows(lengths: list, sequences: list, got, want) -> dict:
    """Pooled and per-row errors of the second comparison. Slot r holds
    `sequences[r]`, a prompt of `lengths[r mod len(lengths)]` tokens
    and the tokens it decoded; `got(r)` is one side's (prefill logits
    [n, vocab] or None past the first row of each prompt, decode
    logits [steps, vocab]); `want(tokens)` the reference's logits over
    a whole sequence, in which position n + j is what decode step j
    returned. Rows that hold the same sequence share one pass, and one
    sequence's reference logits live at once."""
    from benchmark.reference.compare import pooled, squared_sums

    groups: dict = {}
    for row, seq in enumerate(sequences):
        groups.setdefault(tuple(int(t) for t in seq), []).append(row)
    rows = [None] * len(sequences)
    for seq, members in groups.items():
        ref = want(list(seq))
        for row in members:
            n = lengths[row % len(lengths)]
            got_prefill, got_decode = got(row)
            steps = list(zip(*squared_sums(got_decode, ref[n:], axis=-1)))
            rows[row] = {
                "tokens": n, "decode": pooled(steps), "_decode": steps,
                "decode_steps": [pooled([s]) for s in steps],
            }
            if got_prefill is not None:
                first = squared_sums(got_prefill, ref[:n])
                rows[row].update(prefill=pooled([first]), _prefill=first)
        del ref
    out = {
        "prefill": pooled([r["_prefill"] for r in rows if "_prefill" in r]),
        "decode": pooled([s for r in rows for s in r["_decode"]]),
    }
    out["logits_rel_rms"] = max(out["prefill"], out["decode"])
    by_row = [
        (r[part], part, i) for i, r in enumerate(rows)
        for part in ("prefill", "decode") if part in r
    ]
    out["logits_rel_rms_row"], part, row = max(by_row)
    out["worst_row"] = f"{part}, slot {row} of {rows[row]['tokens']} tokens"
    out["rows"] = [
        {k: v for k, v in r.items() if not k.startswith("_")} for r in rows
    ]
    return out


def verdict(out: dict, limits: dict) -> bool:
    import math

    return all(
        math.isfinite(out[name]) and out[name] <= limit
        for name, limit in limits.items()
    )


def probe(spec: dict, device: dict) -> dict:
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import compare, weights

    model, seed = spec["model"], spec["seed"]
    requests = spec["served"]
    if not requests:
        raise ValueError("no served request to compare")
    bucket = max(spec["engine"]["max_len"] // 4, 1)
    limits = {k: spec["tolerance"][k] for k in HELD if k in spec["tolerance"]}
    reference = compare.load(spec.get("reference"))
    control = bool(spec.get("control"))
    params = weights.make(model, spec["dtype"], seed, reference)

    def logits_of(weights_, tokens, rows=None):
        return reference_logits(
            reference, weights_, tokens, model, bucket, rows
        )

    # 2 first: the pool and the program's logits leave the chip before
    # the served requests' longer passes.
    prompts = probe_prompts(spec)
    lengths = [len(p) for p in prompts]
    got_prefill, got_decode, tokens = forwards(spec, params, prompts)
    sequences = [
        np.concatenate([prompts[r % len(prompts)], tokens[:, r]])
        for r in range(tokens.shape[1])
    ]
    kept = {}  # the reference's logits of the rows, for the control

    def want(seq):
        logits = logits_of(params, seq)
        if control:
            kept[tuple(seq)] = logits
        return logits

    out = {
        "device": device, "seed": seed, "reference": reference.__name__,
        "leaves": len(jax.tree.leaves(params)),
        "limits": limits, **compare_rows(
            lengths, sequences,
            lambda r: (
                got_prefill[r] if r < len(prompts) else None, got_decode[:, r]
            ), want,
        ),
    }
    del got_prefill, got_decode

    low_out, low_tokens = None, None
    if control:
        # The control in the program's place, over the same sequences:
        # its logits of the rows against the reference's, and the token
        # it puts first at every served position.
        from benchmark.control import int8_weights

        low = int8_weights(params)

        def control_row(row):
            n = lengths[row % len(lengths)]
            logits = logits_of(low, list(sequences[row]))
            return (logits[:n] if row < len(prompts) else None), logits[n:]

        low_out = compare_rows(
            lengths, sequences, control_row, lambda seq: kept[tuple(seq)]
        )
        kept.clear()
        low_tokens = [
            np.asarray(jnp.argmax(
                served_logits(partial(logits_of, low), r), axis=-1
            )) for r in requests
        ]
        del low
        params = weights.make(model, spec["dtype"], seed, reference)

    rng, vocab = random.Random(int(seed)), model["vocab_size"]
    rows, low_rows, altered = [], [], []
    for i, request in enumerate(requests):
        logits = served_logits(partial(logits_of, params), request)
        n = len(request["prompt"])
        rows.append({"n_prompt": n, "gaps": gaps(logits, request["tokens"])})
        if control:
            low_rows.append({"n_prompt": n, "gaps": gaps(logits, low_tokens[i])})
            # one served token altered by one, at a place drawn from the
            # seed: the mildest fault the served comparison is for
            j = rng.randrange(len(request["tokens"]))
            token = (request["tokens"][j] + 1) % vocab
            altered.append(float(gaps(logits[j][None], [token])[0]))
        del logits
    out.update(served_summary(rows))
    out["correct"] = verdict(out, limits)
    if control:
        low_out.update(served_summary(low_rows))
        low_out["correct"] = verdict(low_out, limits)
        out["control"] = low_out
        out["altered_gap_min"] = min(altered)
    return out


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    # The compile cache is where `run.py` put it
    # (`JAX_COMPILATION_CACHE_DIR`, inherited).
    import jax

    from benchmark.harness import BenchmarkError, describe

    device = describe(jax.devices())
    if not spec["rehearse"] and device["platform"] != "tpu":
        print(f"no TPU: JAX reports {device}", file=sys.stderr)
        return 1
    if device["count"] < spec["chips"]:
        print(f"needs {spec['chips']} chip(s): {device}", file=sys.stderr)
        return 1
    try:
        out = probe(spec, device)
    except Exception as e:
        # The chip was there and the comparison could not be made. What
        # another try cannot mend (the reference or the plan refusing a
        # leaf or a module, a row that does not fit the chip) ends the
        # run on this line with no second try (exit code 2); anything
        # else exits 1 as an uncaught error would, and is tried again.
        import traceback

        traceback.print_exc()
        print(f"probe failed: {type(e).__name__}: {e}", file=sys.stderr)
        final = isinstance(e, (ValueError, KeyError, TypeError, BenchmarkError))
        return 2 if final or "RESOURCE_EXHAUSTED" in str(e) else 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
