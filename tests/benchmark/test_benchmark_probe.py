"""What decides `correct` in a serve cell, at sizes a test run holds:
the gap of a served token under the reference's best, the sample of a
window's finished requests, the benchmark's own weights, the int8
control (which has to fail the limits), a token altered where it is
produced, and the lines a run ends on."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import control, harness  # noqa: E402
from benchmark.drivers import serve_probe  # noqa: E402
from benchmark.reference import compare, weights  # noqa: E402

MANIFEST = harness.load_manifest()
SERVE_CONFIGS = [
    c["name"] for c in MANIFEST["configs"]
    if "engine" in harness.load_config(MANIFEST, c["name"])
]


# -- the number compared ----------------------------------------------

def test_gaps_of_known_logits():
    logits = np.array([
        [0.0, 1.0, 3.0, -4.0],   # deviation sqrt(6.5)
        [2.0, 2.0, -2.0, -2.0],  # deviation 2
        [0.0, 0.0, 0.0, 8.0],
    ], np.float32)
    got = serve_probe.gaps(logits, [2, 3, 3])
    assert got == pytest.approx([0.0, 2.0, 0.0])  # the best, 4 under it, the best
    got = serve_probe.gaps(logits, [1, 1, 0])
    assert got == pytest.approx([2.0 / 6.5 ** 0.5, 0.0, 8.0 / 12.0 ** 0.5])
    rows = [
        {"n_prompt": 5, "gaps": np.array([0.0, 0.5, 0.0])},
        {"n_prompt": 9, "gaps": np.array([0.1])},
    ]
    out = serve_probe.served_summary(rows)
    assert (out["requests"], out["tokens"]) == (2, 4)
    assert out["served_gap_max"] == 0.5 and out["served_gap_mean"] == pytest.approx(0.15)
    assert (out["worst_request"], out["worst_token"]) == (0, 1)
    assert out["best_share"] == 0.5
    # the verdict: every held number at or under its limit, and a number
    assert serve_probe.verdict(out, {"served_gap_max": 0.5})
    assert not serve_probe.verdict(out, {"served_gap_max": 0.49})
    assert not serve_probe.verdict(dict(out, logits_rel_rms=0.03), {
        "served_gap_max": 0.5, "logits_rel_rms": 0.029,
    })
    assert not serve_probe.verdict(dict(out, served_gap_max=float("nan")), {"served_gap_max": 9.0})


def test_pooled_error_of_a_known_pair_of_arrays():
    want = np.array([[3.0, 4.0], [0.0, 5.0], [6.0, 8.0]], np.float32)
    got = want + np.array([[0.3, 0.4], [0.0, 0.0], [0.0, 0.0]], np.float32)
    one = compare.squared_sums(got, want)  # all positions as one: sqrt(0.25 / 150)
    assert one == pytest.approx((0.25, 150.0))
    assert compare.pooled([one]) == pytest.approx(compare.relative_rms_error(got, want))
    diff, ref = compare.squared_sums(got, want, axis=-1)
    rows = list(zip(diff, ref))
    # row by row, then pooled: the same number, not the mean of the rows'
    assert compare.pooled(rows) == pytest.approx(compare.pooled([one]))
    assert [compare.pooled([r]) for r in rows] == pytest.approx([0.1, 0.0, 0.0])


def test_compare_rows_pools_the_slots_and_names_the_worst_row():
    rng = np.random.default_rng(0)
    lengths, steps, vocab = [5, 3], 4, 7
    # four slots: slots 2 and 3 hold the prompts of 0 and 1 again; slot 3
    # decoded other tokens than slot 1, so it is a sequence of its own
    prompts = [rng.integers(1, 9, size=n) for n in lengths]
    tails = [rng.integers(1, 9, size=steps) for _ in range(3)]
    sequences = [
        np.concatenate([prompts[0], tails[0]]), np.concatenate([prompts[1], tails[1]]),
        np.concatenate([prompts[0], tails[0]]), np.concatenate([prompts[1], tails[2]]),
    ]
    refs, asked = {}, []

    def want(seq):
        asked.append(tuple(seq))
        return refs.setdefault(tuple(seq), rng.normal(size=(len(seq), vocab)).astype(np.float32))

    for seq in sequences:
        want([int(t) for t in seq])
    asked.clear()
    noise = [0.01, 0.01, 0.01, 0.2]  # slot 3's decode is off

    def got(row):
        n = lengths[row % 2]
        ref = refs[tuple(int(t) for t in sequences[row])]
        return (ref[:n] * 1.01 if row < 2 else None), ref[n:] * (1 + noise[row])

    out = serve_probe.compare_rows(lengths, sequences, got, want)
    assert len(asked) == 3  # one pass a distinct sequence
    assert out["prefill"] == pytest.approx(0.01, rel=1e-3)
    assert out["logits_rel_rms"] == out["decode"] > out["prefill"]
    assert out["logits_rel_rms_row"] == pytest.approx(0.2, rel=1e-3)
    assert out["worst_row"] == "decode, slot 3 of 3 tokens"
    assert [r["tokens"] for r in out["rows"]] == [5, 3, 5, 3]
    assert ["prefill" in r for r in out["rows"]] == [True, True, False, False]
    assert all(len(r["decode_steps"]) == steps for r in out["rows"])
    energy = [float(np.sum(refs[tuple(int(t) for t in s)][lengths[i % 2]:] ** 2))
              for i, s in enumerate(sequences)]
    assert out["decode"] == pytest.approx((
        sum(n ** 2 * e for n, e in zip(noise, energy)) / sum(energy)
    ) ** 0.5, rel=1e-3)


def test_the_sample_holds_the_longest_finished_request_and_is_drawn_from_the_seed():
    records = [
        {"ok": i % 5 != 0, "cut": i % 5 == 0, "prompt": list(range(10 + i)),
         "tokens": [1] * (i % 3)} for i in range(40)
    ]
    finished = [r for r in records if r["ok"] and r["tokens"]]
    a = serve_probe.sample(records, 7)
    assert len(a) == serve_probe.SAMPLE_REQUESTS
    assert a == serve_probe.sample(records, 7) != serve_probe.sample(records, 8)
    longest = max(finished, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    for seed in (7, 8, 2 ** 31 + 5):
        got = serve_probe.sample(records, seed)
        assert got[0] == {"prompt": longest["prompt"], "tokens": longest["tokens"]}
        assert all(
            any(g["prompt"] == r["prompt"] for r in finished) for g in got
        )  # never a cut one, a failed one or one with no token
        assert len({len(g["prompt"]) for g in got}) == len(got)  # none twice
    assert len(serve_probe.sample(finished[:3], 1)) == 3
    assert serve_probe.sample([r for r in records if not r["ok"]], 1) == []


# -- the weights are the benchmark's ----------------------------------

@pytest.mark.parametrize("name", SERVE_CONFIGS)
def test_the_benchmarks_weights_have_the_tree_the_program_takes_and_are_not_its_draw(name):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig, init_params

    config = harness.apply_rehearsal(harness.load_config(MANIFEST, name))
    model = config["model"]
    reference = compare.load(config.get("reference"))
    made = weights.make(model, "bfloat16", 2 ** 31 + 7, reference)
    cfg = LlamaConfig(**model, dtype=jnp.bfloat16)
    theirs = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))

    def flat(tree, of=lambda v: np.asarray(v, np.float32)):
        return {
            jax.tree_util.keystr(p): of(v)
            for p, v in jax.tree_util.tree_leaves_with_path(tree)
        }

    # every leaf the program's tree has, at its shape and in its type;
    # where the reference names the leaves itself its plan may hold more
    # (the layers that read them are the same PR's change to the program)
    kinds = flat(made, lambda v: (v.shape, v.dtype))
    assert flat(theirs, lambda v: (v.shape, v.dtype)).items() <= kinds.items()
    if not hasattr(reference, "shapes"):
        assert jax.tree.structure(made) == jax.tree.structure(theirs)
    again = weights.make(model, "bfloat16", 2 ** 31 + 7, reference)
    other = weights.make(model, "bfloat16", 2 ** 31 + 8, reference)
    drawn = flat(init_params(jax.random.PRNGKey(2 ** 31 + 7), cfg))
    made, again, other = map(flat, (made, again, other))
    for key, leaf in made.items():
        assert np.array_equal(leaf, again[key]), key      # the seed's
        assert not np.array_equal(leaf, other[key]), key  # and no other's
        assert key not in drawn or not np.array_equal(leaf, drawn[key]), key  # nor the program's
        assert leaf.std() > 0.01, key  # no all-ones norm, no zero bias
    assert made["['layers']['attn_norm']"].mean() == pytest.approx(1.0, abs=0.05)
    fan_in = model["dim"]
    assert made["['layers']['wq']"].std() == pytest.approx(fan_in ** -0.5, rel=0.1)


# -- the control ------------------------------------------------------

def test_the_control_rounds_expert_leaves_an_expert_at_a_time():
    import jax
    import jax.numpy as jnp

    key = jax.random.split(jax.random.PRNGKey(0), 4)
    experts = jax.random.normal(key[0], (2, 3, 16, 8), jnp.float32)
    # one expert ten times the others: a scale of its own, or its
    # neighbours lose their digits
    experts = experts.at[0, 1].multiply(10.0)
    params = {
        "embed": jax.random.normal(key[1], (11, 16)),
        "final_norm": jnp.ones(16),
        "lm_head": jax.random.normal(key[2], (16, 11)),
        "layers": {
            "w_gate": experts,
            "router": jax.random.normal(key[3], (2, 16, 3)),
            "wq": jax.random.normal(key[3], (2, 16, 16)),
            "attn_norm": jnp.ones((2, 16)),
        },
    }
    kept = jax.tree.map(np.asarray, params)
    low = control.int8_weights(jax.tree.map(jnp.copy, params))
    for name in ("embed", "final_norm"):
        assert np.array_equal(low[name], kept[name])
    assert np.array_equal(low["layers"]["attn_norm"], kept["layers"]["attn_norm"])
    for name in ("w_gate", "router", "wq", "lm_head"):
        got = np.asarray(low["layers"].get(name, low.get(name)))
        was = kept["layers"].get(name, kept.get(name))
        assert got.shape == was.shape and not np.array_equal(got, was)
        # every [in, out] matrix on an int8 grid of its OWN: one scale
        # per output channel of each layer's, each expert's matrix
        for m, q in zip(was.reshape(-1, *was.shape[-2:]), got.reshape(-1, *was.shape[-2:])):
            scale = np.abs(m).max(axis=0) / 127.0
            levels = q / scale
            assert np.allclose(levels, np.round(levels), atol=1e-3)
            assert np.abs(levels).max() == pytest.approx(127.0, abs=1e-3)
            assert np.all(np.abs(q - m) <= 0.5 * scale + 1e-5)  # rounded, not cut


# -- sound, the control, and the timed path broken ---------------------

def _greedy(reference, params, model, prompt, steps, pad):
    import jax.numpy as jnp

    seq = list(prompt)
    for _ in range(steps):
        fed = np.zeros(pad, np.int32)
        fed[:len(seq)] = seq
        seq.append(int(jnp.argmax(
            reference.forward(params, jnp.asarray(fed), model)[len(seq) - 1]
        )))
    return seq[len(prompt):]


def test_the_probe_walks_a_prompt_as_the_engine_does():
    assert serve_probe.chunk_offsets(70, 32) == [(0, 0), (32, 32), (64, 64)]
    assert serve_probe.chunk_offsets(64, 32) == [(0, 0), (32, 32)]
    assert serve_probe.chunk_offsets(13, 32) == [(0, 0)]


@pytest.mark.timeout(400)
@pytest.mark.parametrize("name", SERVE_CONFIGS)
def test_the_control_and_each_fault_come_out_not_correct(name, monkeypatch):
    """At the rehearsal's size and limits (float32): the program's
    forwards with every slot alive, a row of three chunks among them,
    and the reference's own greedy tokens as what was served, are
    correct; the int8 control over the same sequences is NOT, by every
    held number; nor is a second chunk fed at offset 0 (caught by the
    logits), nor one served token altered (caught by its gap)."""
    import jax

    config = harness.apply_rehearsal(harness.load_config(MANIFEST, name))
    model, seed, engine = config["model"], 11, config["engine"]
    assert max(config["probe_lengths"]) > 2 * engine["prefill_chunk"]  # three chunks
    reference = compare.load(config.get("reference"))
    params = weights.make(model, config["dtype"], seed, reference)
    rng = np.random.default_rng(seed)
    served = []
    for n in (150, 33, 70, 120):
        prompt = rng.integers(1, model["vocab_size"], size=n).tolist()
        served.append({
            "prompt": prompt,
            "tokens": _greedy(reference, params, model, prompt, 24, 192),
        })
    spec = {
        "model": model, "dtype": config["dtype"], "seed": seed,
        "reference": config.get("reference"), "engine": engine,
        "tolerance": config["tolerance"], "served": served,
        "probe_lengths": config["probe_lengths"], "control": True,
    }
    device = harness.describe(jax.devices())
    out = serve_probe.probe(spec, device)
    limits = out["limits"]
    assert limits == {
        k: config["tolerance"][k] for k in serve_probe.HELD if k in config["tolerance"]
    } and len(limits) >= 2
    assert out["correct"] and out["tokens"] == 4 * 24
    assert out["served_gap_max"] == 0.0 and out["best_share"] == 1.0
    assert out["logits_rel_rms"] == max(out["prefill"], out["decode"]) <= limits["logits_rel_rms"]
    assert out["logits_rel_rms"] <= out["logits_rel_rms_row"] <= limits["logits_rel_rms"]
    # every slot alive, prompts dealt in turn, 16 positions each
    assert [r["tokens"] for r in out["rows"]] == [
        config["probe_lengths"][r % 3] for r in range(engine["slots"])
    ]
    assert all(len(r["decode_steps"]) == serve_probe.DECODE_STEPS for r in out["rows"])
    assert ["prefill" in r for r in out["rows"]] == [True] * 3 + [False] * (engine["slots"] - 3)
    # the control, by the same comparisons against the same limits
    low = out["control"]
    assert low["correct"] is False
    for held in limits:
        assert low[held] > 3 * limits[held], held
    assert low["tokens"] == out["tokens"] and low["best_share"] < 1.0
    # one served token altered by one lies deviations under the best
    assert out["altered_gap_min"] > 100 * limits["served_gap_max"]
    served[2]["tokens"][5] = (served[2]["tokens"][5] + 1) % model["vocab_size"]
    spec["control"] = False
    altered = serve_probe.probe(spec, device)
    assert altered["correct"] is False
    assert (altered["worst_request"], altered["worst_token"]) == (2, 5)
    assert altered["served_gap_max"] > 100 * limits["served_gap_max"]
    assert altered["logits_rel_rms"] == out["logits_rel_rms"]  # the programs are sound
    served[2]["tokens"][5] = (served[2]["tokens"][5] - 1) % model["vocab_size"]
    # the second chunk fed at offset 0: its keys land on the first
    # chunk's pages and its queries see the wrong positions
    walk = serve_probe.chunk_offsets
    monkeypatch.setattr(serve_probe, "chunk_offsets", lambda n, chunk: [
        (start, 0 if i == 1 else offset) for i, (start, offset) in enumerate(walk(n, chunk))
    ])
    broken = serve_probe.probe(spec, device)
    assert broken["correct"] is False and broken["served_gap_max"] == 0.0
    assert broken["logits_rel_rms"] > 100 * limits["logits_rel_rms"]
    assert broken["logits_rel_rms_row"] >= broken["logits_rel_rms"]
    assert broken["worst_row"].endswith(f"of {max(config['probe_lengths'])} tokens")
    for a, b in zip(out["rows"], broken["rows"]):
        if a["tokens"] <= engine["prefill_chunk"]:  # rows inside one chunk are what they were
            assert b["prefill" if "prefill" in a else "decode"] == pytest.approx(
                a["prefill" if "prefill" in a else "decode"]
            )
    with pytest.raises(ValueError, match="no served request"):
        serve_probe.probe(dict(spec, served=[]), {})


def test_a_row_that_cannot_decode_inside_max_len_is_refused():
    config = harness.apply_rehearsal(harness.load_config(MANIFEST, "qwen2.5-3b"))
    spec = {
        "model": config["model"], "dtype": config["dtype"], "seed": 1,
        "engine": config["engine"],
        "probe_lengths": [config["engine"]["max_len"] - 3],
    }
    with pytest.raises(ValueError, match="max_len"):
        serve_probe.forwards(spec, None, serve_probe.probe_prompts(spec))


# -- the lines a run ends on ------------------------------------------

def test_checks_say_what_failed_and_come_last_in_the_result_line():
    checks = [
        harness.check("served_gap_max", 0.0412, 0.03, where="384 served tokens of 8 requests"),
        harness.check("compiles_in_window", 0, 0),
        harness.check("replay_equal", True, 1, at_least=True),
        harness.check("finite", False, 1, at_least=True),
        harness.check("failed", 3, 0, first_status=503),
    ]
    assert [c["ok"] for c in checks] == [False, True, True, False, False]
    lines = harness.check_lines(checks)
    assert lines[0] == (
        "[benchmark] check: served_gap_max 0.0412 against 0.03 (where 384 served tokens of 8 requests): FAILED"
    )
    assert lines[1].endswith("compiles_in_window 0 against 0: ok")
    assert lines[-3:] == [
        "[benchmark] failed: served_gap_max 0.0412 against 0.03 (where 384 served tokens of 8 requests)",
        "[benchmark] failed: finite 0 against 1",
        "[benchmark] failed: failed 3 against 0 (first_status 503)",
    ]
    run = {"correct": False, "attempted": 9, "failed": 3, "checks": checks,
           "device": {"platform": "tpu"}, "breakdown": {"device_ops": []}}
    line = json.loads(harness.result_line(run, {"setup_s": {"value": 1.0, "unit": "s"}}))
    assert list(line)[-1] == "checks" and list(line)[:5] == [
        "correct", "attempted", "failed", "metrics", "device",
    ]
    assert line["checks"]["served_gap_max"] == {
        "value": 0.0412, "limit": 0.03, "ok": False, "where": "384 served tokens of 8 requests",
    }
    assert line["checks"]["failed"]["first_status"] == 503
    # a NaN reading fails its check
    assert not harness.check("logits_rel_rms", float("nan"), 0.025)["ok"]


@pytest.mark.parametrize("error, code", [
    (ValueError("stub_ref: the tree has no leaf layers/w_index"), 2),
    (KeyError("layers"), 2),
    (TypeError("forward() got an unexpected keyword argument 'rows'"), 2),
    (harness.BenchmarkError("reference 'old_ref': forward(...) has no `rows`"), 2),
    (RuntimeError("RESOURCE_EXHAUSTED: Error allocating device buffer"), 2),
    (RuntimeError("INTERNAL: the chip dropped out"), 1),
    (OSError("Device or resource busy"), 1),
], ids=lambda x: type(x).__name__ if isinstance(x, Exception) else None)
def test_the_probes_child_says_whether_another_try_can_mend_it(
    error, code, tmp_path, monkeypatch, capsys,
):
    """Exit code 2 ends the run with no second try (`serve.run_probe`):
    only what a try cannot mend gets it, the reference or the plan
    refusing and a row that does not fit; the rest exits 1 as an
    uncaught error does, and is tried again."""
    import json
    import sys

    path = tmp_path / "served.json"
    path.write_text(json.dumps({"rehearse": True, "chips": 1}))

    def raises(spec, device):
        raise error

    monkeypatch.setattr(serve_probe, "probe", raises)
    monkeypatch.setattr(sys, "argv", ["serve_probe", str(path)])
    assert serve_probe.main() == code
    said = capsys.readouterr()
    assert said.out == ""
    assert said.err.strip().splitlines()[-1].startswith(
        f"probe failed: {type(error).__name__}: "
    )
