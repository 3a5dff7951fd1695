"""The benchmark's own weights and the rows a reference is asked for:
a configuration's reference module names its leaves (`weights.make`
draws the plan it gives, under any parents, and draws the leaves the
four configurations have today bit for bit as the parent commit drew
them), the name reaches the replica, and a reference computes logits
only at the rows that are compared."""

import os
import sys
import types
import zlib

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.drivers import serve_probe  # noqa: E402
from benchmark.reference import compare, weights  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import manifest_checks as checks  # noqa: E402  (this directory)

MANIFEST = harness.load_manifest()
SEED = 2 ** 31 + 39
#: crc32 of every leaf's bytes as commit 4c2e926 (PR 36, the parent of
#: PR 39) drew it at the rehearsal's size from SEED, on the CPU.
PARENT = {
    'qwen2.5-3b': {
        'bfloat16': {
            'embed': 1636136254, 'final_norm': 3970353833, 'layers/attn_norm': 2741361443,
            'layers/bk': 1003491782, 'layers/bq': 2941211410, 'layers/bv': 1841470527,
            'layers/mlp_norm': 1894616827, 'layers/w1': 533667477, 'layers/w2': 1067738392,
            'layers/w3': 402687976, 'layers/wk': 3974568525, 'layers/wo': 1225357353,
            'layers/wq': 2484738219, 'layers/wv': 3703908726, 'lm_head': 4208129973,
        },
        'float32': {
            'embed': 3610696362, 'final_norm': 2004500662, 'layers/attn_norm': 315003819,
            'layers/bk': 3695324713, 'layers/bq': 287675717, 'layers/bv': 1968797887,
            'layers/mlp_norm': 1307670320, 'layers/w1': 1607289012, 'layers/w2': 3604188936,
            'layers/w3': 2735730249, 'layers/wk': 2518702465, 'layers/wo': 214165946,
            'layers/wq': 2722677132, 'layers/wv': 3160378038, 'lm_head': 374717332,
        },
    },
    'mistral-7b-v0.3-l4': {
        'bfloat16': {
            'embed': 1636136254, 'final_norm': 3970353833, 'layers/attn_norm': 2741361443,
            'layers/mlp_norm': 1894616827, 'layers/w1': 533667477, 'layers/w2': 1067738392,
            'layers/w3': 402687976, 'layers/wk': 3974568525, 'layers/wo': 1225357353,
            'layers/wq': 2484738219, 'layers/wv': 3703908726, 'lm_head': 4208129973,
        },
        'float32': {
            'embed': 3610696362, 'final_norm': 2004500662, 'layers/attn_norm': 315003819,
            'layers/mlp_norm': 1307670320, 'layers/w1': 1607289012, 'layers/w2': 3604188936,
            'layers/w3': 2735730249, 'layers/wk': 2518702465, 'layers/wo': 214165946,
            'layers/wq': 2722677132, 'layers/wv': 3160378038, 'lm_head': 374717332,
        },
    },
    'mistral-7b-v0.3-l8': {
        'bfloat16': {
            'embed': 1636136254, 'final_norm': 3970353833, 'layers/attn_norm': 2741361443,
            'layers/mlp_norm': 1894616827, 'layers/w1': 533667477, 'layers/w2': 1067738392,
            'layers/w3': 402687976, 'layers/wk': 3974568525, 'layers/wo': 1225357353,
            'layers/wq': 2484738219, 'layers/wv': 3703908726, 'lm_head': 4208129973,
        },
        'float32': {
            'embed': 3610696362, 'final_norm': 2004500662, 'layers/attn_norm': 315003819,
            'layers/mlp_norm': 1307670320, 'layers/w1': 1607289012, 'layers/w2': 3604188936,
            'layers/w3': 2735730249, 'layers/wk': 2518702465, 'layers/wo': 214165946,
            'layers/wq': 2722677132, 'layers/wv': 3160378038, 'lm_head': 374717332,
        },
    },
    'olmoe-1b-7b-l8': {
        'bfloat16': {
            'embed': 1636136254, 'final_norm': 3970353833, 'layers/attn_norm': 2741361443,
            'layers/k_norm': 867505179, 'layers/mlp_norm': 1894616827, 'layers/q_norm': 2282164719,
            'layers/router': 2428045563, 'layers/w_down': 4289847176, 'layers/w_gate': 3665530369,
            'layers/w_up': 504867708, 'layers/wk': 3452400475, 'layers/wo': 1225357353,
            'layers/wq': 2484738219, 'layers/wv': 437381242, 'lm_head': 4208129973,
        },
        'float32': {
            'embed': 3610696362, 'final_norm': 2004500662, 'layers/attn_norm': 315003819,
            'layers/k_norm': 1895931344, 'layers/mlp_norm': 1307670320, 'layers/q_norm': 3167441019,
            'layers/router': 1470323236, 'layers/w_down': 2754691529, 'layers/w_gate': 3198947890,
            'layers/w_up': 2368514565, 'layers/wk': 338861529, 'layers/wo': 214165946,
            'layers/wq': 2722677132, 'layers/wv': 2041222602, 'lm_head': 374717332,
        },
    },
}


def _small(name: str) -> dict:
    return harness.apply_rehearsal(harness.load_config(MANIFEST, name))


def _flat(tree) -> dict:
    import jax

    return {
        "/".join(k.key for k in path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


# -- the leaves of today are drawn as they were -----------------------

@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("name", sorted(PARENT))
def test_every_leaf_of_todays_trees_is_drawn_bit_for_bit_as_the_parent_drew_it(name, dtype):
    # the trees the parent had: a configuration added since is not
    # here and is nobody's to pin (`PARENT` never grows)
    assert name in checks.names(MANIFEST, "configs")
    config = _small(name)
    module = compare.load(config.get("reference"))
    assert not hasattr(module, "shapes")  # `llama_ref`, `olmoe_ref` define none
    made = _flat(weights.make(config["model"], dtype, SEED, module))
    assert {k: zlib.crc32(v.tobytes()) for k, v in made.items()} == PARENT[name][dtype]
    assert all(str(v.dtype) == dtype for v in made.values())
    assert sorted(made) == sorted(weights.shapes(config["model"]))


# -- a plan with leaves of its own ------------------------------------

def _plan(model):
    d, layers = model["dim"], model["n_layers"]
    plan = weights.shapes(model)
    del plan["layers/bv"]  # a plan may leave a leaf out
    plan["layers/indexer/wq"] = ((layers, d, 24), "matrix", d)
    plan["layers/indexer/k_norm"] = ((layers, 24), "norm", 0)
    plan["dense_layers/w1"] = ((1, d, 3 * d), "matrix", d)
    plan["mtp/gate/bias"] = ((4096,), "bias", 0)
    plan["layers/decay_log"] = ([layers, 2048], (-2.0, 0.5), 0)
    return plan


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_a_plan_adds_leaves_under_any_parent_and_moves_no_other(dtype):
    model = _small("qwen2.5-3b")["model"]
    reference = types.SimpleNamespace(shapes=_plan)
    old = _flat(weights.make(model, dtype, SEED))
    new = _flat(weights.make(model, dtype, SEED, reference))
    assert set(new) - set(old) == {
        "layers/indexer/wq", "layers/indexer/k_norm", "dense_layers/w1",
        "mtp/gate/bias", "layers/decay_log",
    }
    assert set(old) - set(new) == {"layers/bv"}
    for path in set(old) & set(new):
        assert np.array_equal(old[path], new[path]), path  # drawn unchanged
    for path, (shape, _, _) in _plan(model).items():
        assert new[path].shape == tuple(shape) and str(new[path].dtype) == dtype
    wide = {k: v.astype(np.float32) for k, v in new.items()}
    d = model["dim"]
    assert wide["layers/indexer/wq"].std() == pytest.approx(d ** -0.5, rel=0.05)
    assert wide["dense_layers/w1"].std() == pytest.approx(d ** -0.5, rel=0.05)
    assert wide["layers/indexer/k_norm"].mean() == pytest.approx(1.0, abs=0.05)
    assert wide["mtp/gate/bias"].std() == pytest.approx(0.05, rel=0.05)
    assert abs(wide["mtp/gate/bias"].mean()) < 0.005
    assert wide["layers/decay_log"].mean() == pytest.approx(-2.0, abs=0.03)
    assert wide["layers/decay_log"].std() == pytest.approx(0.5, rel=0.05)
    # the seed's tree, and no other's
    again = _flat(weights.make(model, dtype, SEED, reference))
    other = _flat(weights.make(model, dtype, SEED + 1, reference))
    for path, leaf in new.items():
        assert np.array_equal(leaf, again[path]), path
        assert not np.array_equal(leaf, other[path]), path
    # two new leaves are not one draw under two names
    assert not np.array_equal(
        new["layers/indexer/k_norm"], new["layers/attn_norm"][:, :24]
    )


@pytest.mark.parametrize("plan, said", [
    ({"w": ((4,), "gate", 0)}, "kind 'gate'"),
    ({"w": ((4,), (0.0, 1.0, 2.0), 0)}, r"kind \(0.0, 1.0, 2.0\)"),
    ({"a/b": ((4,), "norm", 0), "a/b/c": ((4,), "norm", 0)}, "'a/b/c' is both"),
    ({"a/b/c/d": ((4,), "norm", 0), "a/b": ((4,), "norm", 0)}, "'a/b' is both"),
], ids=["kind", "triple", "leaf-then-parent", "parent-then-leaf"])
def test_a_plan_that_is_not_one_is_refused(plan, said):
    with pytest.raises(ValueError, match=said):
        weights.make({}, "float32", 1, types.SimpleNamespace(shapes=lambda m: plan))


# -- the name reaches the replica -------------------------------------

def test_deploy_hands_the_replica_the_configurations_reference(monkeypatch):
    import ray_tpu.serve as serve
    from ray_tpu.util.accelerators import tpu

    from benchmark.drivers import serve as driver

    bound = []
    monkeypatch.setattr(serve, "run", lambda app, **kw: bound.append(app))
    monkeypatch.setattr(serve, "start", lambda **kw: 0)
    monkeypatch.setattr(tpu, "cluster_tpu_chips", lambda: 0)
    for name in ("olmoe-1b-7b-l8", "qwen2.5-3b"):
        config = _small(name)
        driver.deploy(config, 7)
        (families,) = bound.pop().args
        assert families == {name: {
            "kind": "benchmark", "seed": 7, "reference": config.get("reference"),
            "config": dict(config["model"], dtype=config["dtype"]),
        }}
    assert families["qwen2.5-3b"]["reference"] is None  # `llama_ref` by default


def test_the_replica_builds_the_tree_the_grown_stub_names(tmp_path, monkeypatch):
    from benchmark.drivers import serve_replica

    root = checks.checkout(tmp_path)
    grown = checks.grow(root, "qwen2.5-3b", "docqa_closed")
    config = harness.apply_rehearsal(harness.load_config(grown, "stub-model", root))
    load = compare.load
    monkeypatch.setattr(compare, "load", lambda name=None: load(name, root))
    spec = {
        "kind": "benchmark", "seed": 3, "reference": config["reference"],
        "config": dict(config["model"], dtype=config["dtype"]),
    }
    params, cfg = serve_replica.build_model(spec)
    made = _flat(params)
    model = config["model"]
    k = checks.STUB_MODEL_KEY["moe_top_k"]
    assert model["moe_top_k"] == cfg.moe_top_k == k
    assert made["layers/w_index"].shape == (model["n_layers"], model["dim"], 4 * k)
    assert made["mtp/proj"].shape == (model["dim"], model["dim"])
    assert made["layers/decay_log"].shape == (model["n_layers"], k)
    assert made["layers/decay_log"].mean() == pytest.approx(-2.0, abs=0.5)
    assert serve_replica.LOAD_S["leaves"] == len(made) == len(weights.shapes(model)) + 3
    # without the name it is `weights.shapes`' tree, leaf for leaf the same draw
    plain, _ = serve_replica.build_model(dict(spec, reference=None))
    plain = _flat(plain)
    assert sorted(plain) == sorted(weights.shapes(model))
    assert all(np.array_equal(plain[k], made[k]) for k in plain)


# -- logits only where they are compared ------------------------------

@pytest.mark.parametrize("block", [256, 32], ids=["one-block", "blocks-of-32"])
@pytest.mark.parametrize("name", ["qwen2.5-3b", "olmoe-1b-7b-l8"])
def test_the_rows_of_a_reference_are_the_rows_of_its_every_position(name, block, monkeypatch):
    import jax.numpy as jnp

    from benchmark.reference import llama_ref

    # the head runs over whole blocks of rows: with blocks shorter than
    # the sequence the rows asked for start inside one, or at its end
    monkeypatch.setattr(llama_ref, "HEAD_BLOCK", block)
    config = _small(name)
    model = config["model"]
    reference = compare.load(config.get("reference"))
    params = weights.make(model, "float32", 5, reference)
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(1, model["vocab_size"], size=96)
    )
    whole = np.asarray(reference.forward(params, tokens, model))
    assert whole.shape == (96, model["vocab_size"])
    for a, b in [(0, 96), (40, 41), (17, 80), (95, 96), (60, 92), (1, 33)]:
        part = np.asarray(reference.forward(params, tokens, model, rows=(a, b)))
        assert part.shape == (b - a, model["vocab_size"])
        assert np.abs(part - whole[a:b]).max() <= 1e-6 * np.abs(whole).max()


def _counting(vocab: int):
    """A reference whose logit of token v at position p is known, and
    which writes down what it was asked for."""
    asked = []

    def forward(params, tokens, model, rows=None):
        asked.append((len(tokens), rows))
        t = np.arange(len(tokens), dtype=np.float32)[:, None]
        out = t * 1000.0 + np.arange(vocab, dtype=np.float32)[None, :]
        return out if rows is None else out[rows[0]:rows[1]]

    return types.SimpleNamespace(forward=forward), asked


def test_a_served_request_is_asked_for_the_rows_compared_and_no_other():
    reference, asked = _counting(11)
    request = {"prompt": list(range(1, 71)), "tokens": [3, 1, 4, 1, 5]}

    def forward(tokens, rows=None):
        return serve_probe.reference_logits(reference, None, tokens, {}, 64, rows)

    got = np.asarray(serve_probe.served_logits(forward, request))
    # positions 69 .. 73 produce the five served tokens; fed 74 tokens, run at 128
    assert got.shape == (5, 11)
    assert got[:, 0].tolist() == [69000.0, 70000.0, 71000.0, 72000.0, 73000.0]
    assert asked == [(128, (69, 74))]
    # a probe row takes every position it holds, and none of the padding
    asked.clear()
    every = np.asarray(forward(list(range(1, 71))))
    assert every.shape == (70, 11) and every[-1, 0] == 69000.0
    assert asked == [(128, (0, 70))]


def test_a_reference_whose_forward_takes_no_rows_is_refused_by_name(tmp_path):
    """`rows` is part of the contract: every position's logits of a
    long request do not fit, so a module without it is not sliced, it
    is refused where it is loaded."""
    root = checks.checkout(tmp_path)
    with open(os.path.join(root, "benchmark", "reference", "old_ref.py"), "w") as f:
        f.write("def forward(params, tokens, model):\n    return None\n")
    with pytest.raises(harness.BenchmarkError, match="'old_ref'.*has no `rows`"):
        compare.load("old_ref", root)
    for name in (None, "llama_ref", "olmoe_ref"):
        assert callable(compare.load(name, root).forward)
