"""Accelerator hot-path analysis tests (`ray_tpu devtools accel`,
devtools/accel.py rules RT301-RT306) and the static<->runtime bridge
into the compile watch (`compile_watch.load_inventory`/`static_hint`).

Every rule has a seeded-bug fixture (must fire) and a corrected twin
(must stay quiet); the repo analyzes itself clean — package and
tests — so every jit wrap site is either registered with
`compile_watch.instrument` or carries an explicit, reviewed
`# rt: noqa[RT3xx]`. Also here: the noqa-hygiene contract shared by
all four passes (RT090/RT190/RT290/RT390 — a suppression naming a
nonexistent rule, or one that never fires on its line, is itself a
finding), regression tests for the convictions this pass produced
(generate/rl/train registration, the engine mixed-generation host-sync
fix), the program-inventory JSON shape, and the doctor correlation: a
live recompile storm's problem record carries a `static_hint` naming
the static RT302 site.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from ray_tpu.devtools.accel import (
    RULES,
    accel_paths,
    accel_sources,
    build_inventory,
    build_inventory_sources,
    main as accel_main,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "ray_tpu")
TESTS = os.path.dirname(os.path.abspath(__file__))


def fired(source: str, path: str = "mod.py"):
    return {
        f.rule
        for f in accel_sources([(path, textwrap.dedent(source))])
    }


# ---------------------------------------------------------------------------
# one seeded-bug fixture + one corrected twin per rule
# ---------------------------------------------------------------------------

CASES = [
    (
        "RT301",
        # jit wrapper minted inside the loop: every iteration re-traces.
        """
        import jax

        def run_epoch(params, batches):
            out = []
            for batch in batches:
                step = jax.jit(lambda p, b: (p * b).sum())
                out.append(step(params, batch))
            return out
        """,
        True,
    ),
    (
        "RT301",
        # corrected twin: module-level wrap, loop reuses the cache.
        """
        import jax

        _step = jax.jit(lambda p, b: (p * b).sum())

        def run_epoch(params, batches):
            return [_step(params, batch) for batch in batches]
        """,
        False,
    ),
    (
        "RT302",
        # len() reaches a static position: one compile per batch size.
        """
        import jax

        _tail = jax.jit(lambda x, n: x[:n], static_argnums=(1,))

        def run(rows, batch):
            for x in rows:
                _tail(x, len(batch))
        """,
        True,
    ),
    (
        "RT302",
        # corrected twin: the bound is a hashable config constant.
        """
        import jax

        _tail = jax.jit(lambda x, n: x[:n], static_argnums=(1,))

        MAX_ROWS = 128

        def run(rows):
            for x in rows:
                _tail(x, MAX_ROWS)
        """,
        False,
    ),
    (
        "RT303",
        # float() on a device value inside the jit-stepped hot loop:
        # one blocking D2H round trip per iteration.
        """
        import jax

        _step = jax.jit(lambda x: (x * 2).sum())

        def train(batches):
            total = 0.0
            for batch in batches:
                loss = _step(batch)
                total += float(loss)
            return total
        """,
        True,
    ),
    (
        "RT303",
        # corrected twin: accumulate on device, sync once after.
        """
        import jax

        _step = jax.jit(lambda x: (x * 2).sum())

        def train(batches):
            total = None
            for batch in batches:
                loss = _step(batch)
                total = loss if total is None else total + loss
            return float(total)
        """,
        False,
    ),
    (
        "RT304",
        # state is donated to the update, then read again.
        """
        import jax

        _update = jax.jit(lambda s, g: s - g, donate_argnums=(0,))

        def apply(state, grads):
            new_state = _update(state, grads)
            drift = new_state - state
            return new_state, drift
        """,
        True,
    ),
    (
        "RT304",
        # corrected twin: the donated name is rebound, never re-read.
        """
        import jax

        _update = jax.jit(lambda s, g: s - g, donate_argnums=(0,))

        def apply(state, grads):
            state = _update(state, grads)
            return state
        """,
        False,
    ),
    (
        "RT305",
        # clock read right after an async dispatch: measures dispatch,
        # not the computation.
        """
        import time
        import jax

        _step = jax.jit(lambda x: (x * 2).sum())

        def bench(batch):
            t0 = time.perf_counter()
            out = _step(batch)
            elapsed = time.perf_counter() - t0
            return elapsed, out
        """,
        True,
    ),
    (
        "RT305",
        # corrected twin: block_until_ready fences before the clock.
        """
        import time
        import jax

        _step = jax.jit(lambda x: (x * 2).sum())

        def bench(batch):
            t0 = time.perf_counter()
            out = _step(batch)
            jax.block_until_ready(out)
            elapsed = time.perf_counter() - t0
            return elapsed, out
        """,
        False,
    ),
    (
        "RT306",
        # jit invisible to the compile watch: its compiles land in the
        # "(unregistered)" ledger where no storm can be attributed.
        """
        import jax

        _step = jax.jit(lambda x: x + 1)
        """,
        True,
    ),
    (
        "RT306",
        # corrected twin: registered by name.
        """
        import jax

        from ray_tpu._private import compile_watch

        _step = compile_watch.instrument(
            "mod.step", jax.jit(lambda x: x + 1)
        )
        """,
        False,
    ),
]


@pytest.mark.parametrize(
    "rule,source,expect",
    CASES,
    ids=[
        f"{rule}-{'seeded' if expect else 'corrected'}"
        for rule, _, expect in CASES
    ],
)
def test_rule_fixtures(rule, source, expect):
    rules = fired(source)
    if expect:
        assert rule in rules, f"{rule} did not fire:\n{source}"
    else:
        assert rule not in rules, f"{rule} fired on the corrected twin"


def test_test_files_exempt_from_hot_path_rules():
    """RT303/RT305/RT306 are about production hot loops; test files
    sync and time deliberately, so only the universal rules
    (RT301/RT302/RT304) apply there."""
    sync_in_loop = """
        import jax

        _step = jax.jit(lambda x: (x * 2).sum())

        def train(batches):
            total = 0.0
            for batch in batches:
                total += float(_step(batch))
            return total
    """
    assert "RT303" in fired(sync_in_loop, path="pkg/mod.py")
    assert fired(sync_in_loop, path="tests/test_mod.py") == set()
    # ...but a donation bug in a test is still a bug.
    donate = """
        import jax

        _up = jax.jit(lambda s: s * 2, donate_argnums=(0,))

        def helper(state):
            out = _up(state)
            return out + state
    """
    assert "RT304" in fired(donate, path="tests/test_mod.py")


# ---------------------------------------------------------------------------
# shared suppression contract + noqa hygiene (all four passes)
# ---------------------------------------------------------------------------

SEEDED_306 = """
    import jax

    _step = jax.jit(lambda x: x + 1)
"""


def test_noqa_suppresses_on_the_flagged_line():
    src = textwrap.dedent(
        """
        import jax

        _step = jax.jit(lambda x: x + 1)  # rt: noqa[RT306] — probe
        """
    )
    assert "RT306" not in {
        f.rule for f in accel_sources([("mod.py", src)])
    }


def test_noqa_must_name_the_rule():
    src = textwrap.dedent(
        """
        import jax

        _step = jax.jit(lambda x: x + 1)  # rt: noqa[RT301]
        """
    )
    rules = {f.rule for f in accel_sources([("mod.py", src)])}
    # The finding survives a suppression naming a different rule...
    assert "RT306" in rules
    # ...and the useless suppression is itself reported (RT301 never
    # fires on that line).
    assert "RT390" in rules


def test_bare_noqa_suppresses_everything_quietly():
    src = textwrap.dedent(
        """
        import jax

        _step = jax.jit(lambda x: x + 1)  # rt: noqa
        """
    )
    assert {f.rule for f in accel_sources([("mod.py", src)])} == set()


def test_hygiene_catches_unknown_rule_id():
    src = textwrap.dedent(
        """
        import jax

        _step = jax.jit(lambda x: x + 1)  # rt: noqa[RT306,RT399]
        """
    )
    findings = accel_sources([("mod.py", src)])
    assert {f.rule for f in findings} == {"RT390"}
    assert any("RT399" in f.message for f in findings)


def test_hygiene_is_not_suppressible():
    src = textwrap.dedent(
        """
        import jax

        _step = jax.jit(lambda x: x + 1)  # rt: noqa[RT301,RT390]
        """
    )
    rules = {f.rule for f in accel_sources([("mod.py", src)])}
    assert "RT390" in rules


def test_hygiene_ignores_string_literals():
    """Only real comments are audited — analysis-test fixtures hold
    noqa text in string literals and must not trip the hygiene."""
    src = '''
SRC = """
x = 1  # rt: noqa[RT301]
"""
'''
    assert {f.rule for f in accel_sources([("mod.py", src)])} == set()


def test_hygiene_in_sibling_passes():
    """Satellite: the same audit runs in lint (RT090), check (RT190)
    and race (RT290) — one contract across all four passes."""
    from ray_tpu.devtools.check import check_sources
    from ray_tpu.devtools.concurrency import race_sources
    from ray_tpu.devtools.lint import lint_source

    stale = "x = 1  # rt: noqa[RT004]\n"
    assert "RT090" in {f.rule for f in lint_source(stale, "mod.py")}
    stale_check = "x = 1  # rt: noqa[RT102]\n"
    assert "RT190" in {
        f.rule for f in check_sources([("mod.py", stale_check)])
    }
    stale_race = "x = 1  # rt: noqa[RT203]\n"
    assert "RT290" in {
        f.rule for f in race_sources([("mod.py", stale_race)])
    }
    # Cross-family ownership: a stale RT2xx suppression is the race
    # pass's to report, not lint's or accel's.
    assert "RT090" not in {
        f.rule for f in lint_source(stale_race, "mod.py")
    }
    assert "RT390" not in {
        f.rule for f in accel_sources([("mod.py", stale_race)])
    }


# ---------------------------------------------------------------------------
# CLI contract: exit codes, --json, --rules, --list-rules, --inventory
# ---------------------------------------------------------------------------


def test_main_exit_codes_and_json(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(SEEDED_306))
    assert accel_main([str(bad), "--json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out[0]["rule"] == "RT306"
    assert out[0]["path"] == str(bad)

    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    assert accel_main([str(clean)]) == 0
    assert accel_main([str(tmp_path / "missing.py")]) == 2
    assert accel_main([str(bad), "--rules", "RT999"]) == 2


def test_list_rules(capsys):
    assert accel_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in RULES:
        assert rule_id in out
    assert "RT390" in out


def test_rules_filter(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(SEEDED_306))
    assert accel_main([str(bad), "--rules", "RT301"]) == 0
    assert accel_main([str(bad), "--rules", "RT306"]) == 1


def test_parse_error_is_rt000(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    findings = accel_paths([str(bad)])
    assert [f.rule for f in findings] == ["RT000"]


# ---------------------------------------------------------------------------
# the program inventory (the doctor bridge's static half)
# ---------------------------------------------------------------------------


def test_inventory_shape_and_hazard_attachment():
    src = textwrap.dedent(
        """
        import jax

        from ray_tpu._private import compile_watch

        _tail = compile_watch.instrument(
            "mod.tail",
            jax.jit(lambda x, n: x[:n], static_argnums=(1,)),
        )
        _anon = jax.jit(lambda x: x + 1)

        def run(rows, batch):
            for x in rows:
                _tail(x, len(batch))
        """
    )
    inv = build_inventory_sources([("mod.py", src)])
    assert inv["version"] == 1
    by_name = {p["program"]: p for p in inv["programs"] if p["program"]}
    tail = by_name["mod.tail"]
    assert tail["registered"] is True
    assert tail["name_kind"] == "literal"
    assert tail["static_argnums"] == [1]
    assert tail["hazards"], "RT302 hazard missing from inventory"
    hazard = tail["hazards"][0]
    assert hazard["rule"] == "RT302"
    assert hazard["path"] == "mod.py"
    assert "len(" in hazard["message"]
    # The anonymous jit lands in the unregistered worklist.
    assert len(inv["unregistered"]) == 1


def test_cli_inventory_mode(tmp_path, capsys):
    mod = tmp_path / "mod.py"
    mod.write_text(textwrap.dedent(SEEDED_306))
    assert accel_main([str(mod), "--inventory"]) == 0
    inv = json.loads(capsys.readouterr().out)
    assert inv["version"] == 1
    assert len(inv["programs"]) == 1


def test_static_hint_resolves_literal_and_pattern(tmp_path, monkeypatch):
    from ray_tpu._private import compile_watch as cw

    inv = {
        "version": 1,
        "programs": [
            {
                "program": "train.step",
                "name_kind": "literal",
                "path": "pkg/train.py",
                "line": 10,
                "registered": True,
                "hazards": [
                    {
                        "rule": "RT302",
                        "path": "pkg/loop.py",
                        "line": 44,
                        "message": "run: static argument 1 derives "
                        "from len(...)",
                    }
                ],
            },
            {
                "program": "engine.run[*]",
                "name_kind": "pattern",
                "path": "pkg/engine.py",
                "line": 77,
                "registered": True,
                "hazards": [],
            },
        ],
        "unregistered": [],
    }
    path = tmp_path / "inventory.json"
    path.write_text(json.dumps(inv))
    monkeypatch.setenv("RT_accel_inventory", str(path))
    try:
        cw.load_inventory(refresh=True)
        hint = cw.static_hint("train.step")
        assert "pkg/loop.py:44" in hint
        assert "RT302" in hint
        # f-string program names were inventoried as fnmatch patterns.
        hint2 = cw.static_hint("engine.run[gen3]")
        assert "pkg/engine.py:77" in hint2
        assert cw.static_hint("nope") is None
    finally:
        monkeypatch.delenv("RT_accel_inventory")
        cw.load_inventory(refresh=True)


def test_package_inventory_has_no_unregistered_programs():
    """Satellite: every jit wrap site in the shipped package is
    registered with compile_watch.instrument — the static proof that
    "(unregistered)" compile counts stay zero."""
    inv = build_inventory([PKG])
    assert inv["unregistered"] == []
    names = {p["program"] for p in inv["programs"] if p["program"]}
    # The convictions fixed in this PR, by name.
    for prog in (
        "generate.paged_prefill",
        "generate.paged_decode_step",
        "rl.sample_actions",
        "rl.dqn.td_update",
        "rl.ppo.minibatch_update",
        "rl.policy_program",
        "train.init_params",
        "train.pipeline.init_params",
    ):
        assert prog in names, f"{prog} missing from inventory"


# ---------------------------------------------------------------------------
# the repo holds itself to the rules
# ---------------------------------------------------------------------------


def test_repo_analyzes_clean():
    findings = accel_paths([PKG, TESTS])
    assert findings == [], "\n".join(f.render() for f in findings)


def test_devtools_all_includes_accel(tmp_path):
    from ray_tpu.devtools import all_main

    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(SEEDED_306))
    out_path = tmp_path / "out.json"
    with open(out_path, "w") as fh:
        rc = all_main([str(bad), "--json"], out=fh)
    assert rc == 1
    rules = {f["rule"] for f in json.loads(out_path.read_text())}
    assert "RT306" in rules


# ---------------------------------------------------------------------------
# regression tests for the convictions this pass produced
# ---------------------------------------------------------------------------


def test_generate_wraps_registered_and_callable():
    """The generate.py jits register by name and still work; the
    module-level forwarders pickle by reference."""
    import pickle

    from decode_oracle import paged_greedy
    from ray_tpu._private import compile_watch
    from ray_tpu._private.compile_watch import WatchedFunction
    from ray_tpu.models import generate as g
    from ray_tpu.models.llama import LlamaConfig, init_params

    assert isinstance(g._patch_step_slot_jit, WatchedFunction)
    assert g._patch_step_slot_jit.name == "generate.patch_step_slot"
    # Importable call sites pickle the NAME, not the wrapper.
    assert pickle.loads(pickle.dumps(g.paged_decode_step)) is not None

    cfg = LlamaConfig.tiny()
    import jax

    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = paged_greedy(params, cfg, [[3] * 4, [3] * 4], 3)
    assert [len(row) for row in tokens] == [3, 3]
    for name in ("generate.paged_prefill", "generate.paged_decode_step"):
        assert compile_watch.snapshot()[name]["compiles"] >= 1, name


def test_engine_mixed_generation_merge_stays_on_device():
    """The mixed-generation decode window used to np.asarray each
    group's tokens inside the loop (RT303); it now merges on device
    and syncs once. Static regression: the engine analyzes clean."""
    path = os.path.join(PKG, "llm", "engine.py")
    findings = [
        f
        for f in accel_paths([path])
        if f.rule == "RT303"
    ]
    assert findings == [], "\n".join(f.render() for f in findings)


def test_rl_and_train_programs_compile_under_their_names():
    """Run a registered rl program and assert the compile lands in
    the NAMED ledger. (Eager ops — jnp.asarray, PRNG setup — still
    compile anonymously on first touch; the zero-anonymous bar is a
    steady-state property and bench --smoke enforces it there.)"""
    import jax
    import numpy as np

    from ray_tpu._private import compile_watch as cw
    from ray_tpu.rl.models import init_policy_params, sample_actions

    params = init_policy_params(jax.random.PRNGKey(0), 4, 2)
    key = jax.random.PRNGKey(1)
    sample_actions(params, np.zeros((3, 4), np.float32), key)
    # Steady state: a second call with the same shapes must not
    # compile again — named or anonymous.
    snap0 = cw.snapshot()
    sample_actions(params, np.zeros((3, 4), np.float32), key)
    snap1 = cw.snapshot()
    assert snap1["rl.sample_actions"]["compiles"] >= 1
    assert (
        snap1["rl.sample_actions"]["compiles"]
        == snap0["rl.sample_actions"]["compiles"]
    )
    unreg0 = snap0.get("(unregistered)", {}).get("compiles", 0)
    unreg1 = snap1.get("(unregistered)", {}).get("compiles", 0)
    assert unreg1 == unreg0, "steady-state call compiled anonymously"


def test_stale_noqa_hygiene_keeps_repo_clean():
    """The audit that removed daemon/worker's stale suppressions is a
    live gate: the whole tree carries zero stale/unknown noqas."""
    from ray_tpu.devtools import (
        check_paths,
        lint_paths,
        race_paths,
    )

    hygiene = {"RT090", "RT190", "RT290", "RT390"}
    findings = [
        f
        for f in (
            lint_paths([PKG])
            + check_paths([PKG, TESTS])
            + race_paths([PKG, TESTS])
            + accel_paths([PKG, TESTS])
        )
        if f.rule in hygiene
    ]
    assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# doctor correlation: live storm -> static site (the bridge, end to end)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_storm_problem_carries_static_hint_two_nodes(tmp_path):
    """A 2-node cluster, a worker-side drifting jit registered under a
    name the static inventory knows: `ray_tpu doctor --json` must
    report the recompile storm WITH a `static_hint` naming the RT302
    source site — the bridge from runtime symptom to static fix."""
    inventory = {
        "version": 1,
        "programs": [
            {
                "program": "test.storm_step",
                "name_kind": "literal",
                "path": "ray_tpu/models/generate.py",
                "line": 241,
                "registered": True,
                "hazards": [
                    {
                        "rule": "RT302",
                        "path": "pkg/train_loop.py",
                        "line": 88,
                        "message": "train_loop: static argument 1 "
                        "derives from len(...)",
                    }
                ],
            }
        ],
        "unregistered": [],
    }
    inv_path = tmp_path / "inventory.json"
    inv_path.write_text(json.dumps(inventory))
    os.environ["RT_accel_inventory"] = str(inv_path)
    try:
        from ray_tpu.cluster_utils import Cluster

        import ray_tpu as rt

        c = Cluster(initialize_head=True, head_resources={"CPU": 2.0})
        c.add_node(num_cpus=2, resources={"remote_node": 4.0})
        c.wait_for_nodes(2)
        rt.init(address=c.address)
        try:

            @rt.remote
            def drifting(n):
                import jax
                import jax.numpy as jnp
                import numpy as np

                from ray_tpu._private import compile_watch as cw
                from ray_tpu.util import metrics

                fn = cw.instrument(
                    "test.storm_step",
                    jax.jit(lambda x: (x * 2 + 1).sum()),  # rt: noqa[RT301] — fixture exists to provoke recompiles
                )
                for i in range(2, n + 2):
                    fn(jnp.asarray(np.zeros((4, i), np.float32)))
                metrics.flush()
                return n

            assert rt.get(
                drifting.options(
                    resources={"remote_node": 1.0}
                ).remote(12),
                timeout=120,
            ) == 12

            env = dict(os.environ)
            env["PYTHONPATH"] = (
                REPO + os.pathsep + env.get("PYTHONPATH", "")
            )
            env.pop("RT_ADDRESS", None)
            out = subprocess.run(
                [
                    sys.executable,
                    "-m",
                    "ray_tpu",
                    "doctor",
                    "--json",
                    "--address",
                    c.address,
                    "--no-stacks",
                ],
                capture_output=True,
                text=True,
                timeout=120,
                env=env,
            )
            assert out.returncode == 1, out.stdout + out.stderr
            verdict = json.loads(out.stdout)
            storms = [
                p
                for p in verdict["problems"]
                if p["kind"] == "recompile_storm"
            ]
            assert storms, verdict["problems"]
            storm = storms[0]
            assert storm["program"] == "test.storm_step"
            # The bridge: the live symptom names the static fix site.
            assert "pkg/train_loop.py:88" in storm["static_hint"]
            assert "RT302" in storm["static_hint"]
        finally:
            rt.shutdown()
            c.shutdown()
    finally:
        os.environ.pop("RT_accel_inventory", None)
        from ray_tpu._private import compile_watch as cw

        cw.load_inventory(refresh=True)
