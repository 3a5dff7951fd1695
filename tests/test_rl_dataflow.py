"""Decoupled RL dataflow tests (ISSUE 13): rollout-queue gates,
versioned weight sync, the engine's policy batch path, drainless
weight pushes (token-exact in-flight streams), and chaos — a killed
env runner never stalls the queue, a dead engine fails fast with
EngineDead, never a hang."""

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


# ---------------------------------------------------------------------
# rollout queue gates (pure bookkeeping, no cluster)
# ---------------------------------------------------------------------

def test_rollout_queue_capacity_backpressure():
    from ray_tpu.rl.rollout_queue import RolloutQueue

    q = RolloutQueue(capacity=2, max_weight_lag=4)
    meta = {"weight_version": 0, "env_steps": 8}
    assert q.put({"ref": ["a"]}, meta) == "ok"
    assert q.put({"ref": ["b"]}, meta) == "ok"
    assert q.put({"ref": ["c"]}, meta) == "full"  # learner behind
    assert q.depth() == 2
    got = q.get_batch(8)
    assert [f["item"]["ref"][0] for f in got] == ["a", "b"]  # FIFO
    assert q.put({"ref": ["c"]}, meta) == "ok"
    stats = q.stats()
    assert stats["rejected_full"] == 1
    assert stats["puts"] == 3
    assert stats["env_steps_in"] == 24


def test_rollout_queue_weight_lag_gates():
    """Both staleness gates: a put too far behind the learner version
    is refused ("throttle"), and a fragment that AGED while queued is
    dropped at get — stale data never trains."""
    from ray_tpu.rl.rollout_queue import RolloutQueue

    q = RolloutQueue(capacity=8, max_weight_lag=1)
    assert q.put({"ref": ["v0"]}, {"weight_version": 0}) == "ok"
    q.set_learner_version(2)
    # 2 - 0 > 1: the queued fragment is now stale; a NEW v0 put is
    # throttled at the door.
    assert q.put({"ref": ["v0b"]}, {"weight_version": 0}) == "throttle"
    assert q.put({"ref": ["v2"]}, {"weight_version": 2}) == "ok"
    got = q.get_batch(8)
    assert [f["item"]["ref"][0] for f in got] == ["v2"]
    stats = q.stats()
    assert stats["dropped_stale"] == 1
    assert stats["rejected_stale"] == 1
    # Learner version is monotonic: a late lower set is a no-op.
    assert q.set_learner_version(1) == 2


def test_weight_store_versioning():
    from ray_tpu.rl.weight_sync import WeightStore

    store = WeightStore()
    assert store.latest_version() == 0
    assert store.get() == (0, None)
    assert store.publish(["ref1"], 1) == 1
    assert store.publish(["stale"], 1) == 1  # late retry ignored
    assert store.publish(["ref2"], 3) == 3
    version, item = store.get()
    assert (version, item) == (3, ["ref2"])
    assert store.stats()["publishes"] == 2


# ---------------------------------------------------------------------
# engine policy path (in-process, no cluster)
# ---------------------------------------------------------------------

def _policy_engine(params, obs_size=4, **cfg_kw):
    from ray_tpu.llm.engine import EngineConfig, InferenceEngine
    from ray_tpu.rl.dataflow import PolicyProgram

    return InferenceEngine(
        params,
        None,
        EngineConfig(**cfg_kw),
        family="rl-test",
        program=PolicyProgram(obs_size),
    )


@pytest.fixture(scope="module")
def policy_params():
    from ray_tpu.rl.models import init_policy_params

    return init_policy_params(jax.random.PRNGKey(0), 4, 2)


def test_policy_requests_batch_into_one_forward(policy_params):
    """Ragged concurrent submits coalesce: N threads' rows come back
    row-exact (each ticket gets ITS slice) and the engine serves them
    in far fewer program steps than requests."""
    eng = _policy_engine(policy_params)
    try:
        results = {}

        def worker(i, rows):
            obs = np.full((rows, 4), float(i), np.float32)
            ticket = eng.submit_policy(obs)
            results[i] = (rows, ticket.result(timeout=30))

        threads = [
            threading.Thread(target=worker, args=(i, 1 + i % 3))
            for i in range(12)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 12
        for i, (rows, out) in results.items():
            assert out["actions"].shape == (rows,)
            assert out["logp"].shape == (rows,)
            assert out["values"].shape == (rows,)
            assert np.isfinite(out["logp"]).all()
        stats = eng.stats()
        assert stats["policy_rows_served"] == sum(
            1 + i % 3 for i in range(12)
        )
        assert stats["policy_steps"] < 12  # batching happened
    finally:
        eng.close()


def test_policy_reply_matches_local_program(policy_params):
    """Engine-served and runner-local inference run the SAME batch
    program: identical params + obs + key -> identical outputs (the
    two dataflow modes differ only in where the forward runs)."""
    from ray_tpu.rl.dataflow import PolicyProgram

    eng = _policy_engine(policy_params)
    try:
        obs = np.linspace(-1, 1, 8, dtype=np.float32).reshape(2, 4)
        ticket = eng.submit_policy(obs)
        out = ticket.result(timeout=30)
        assert ticket.version == 0
        # Deterministic heads must agree exactly; the sampled head
        # depends on the engine's key schedule, so compare the
        # deterministic ones.
        program = PolicyProgram(4)
        ref = program.run(
            policy_params, obs, jax.random.PRNGKey(0)
        )
        np.testing.assert_array_equal(
            out["greedy"], np.asarray(ref["greedy"])
        )
        np.testing.assert_allclose(
            out["values"], np.asarray(ref["values"]), rtol=1e-6
        )
    finally:
        eng.close()


def test_engine_death_fails_policy_requests_fast(policy_params):
    """Chaos: pending policy tickets get EngineDead when the loop
    dies — within seconds, never a hang — and later submits latch
    rejected."""
    from ray_tpu.llm.engine import EngineDead

    eng = _policy_engine(policy_params)

    # Break the program so the NEXT batch kills the loop.
    def boom(params, inputs, key):
        raise RuntimeError("injected program failure")

    eng._program.run = boom
    ticket = eng.submit_policy(np.zeros((2, 4), np.float32))
    t0 = time.monotonic()
    with pytest.raises((EngineDead, RuntimeError)):
        ticket.result(timeout=30)
    assert time.monotonic() - t0 < 10  # fast, not a timeout crawl
    deadline = time.monotonic() + 10
    while not eng.stats()["dead"] and time.monotonic() < deadline:
        time.sleep(0.01)
    with pytest.raises(EngineDead):
        eng.submit_policy(np.zeros((1, 4), np.float32))


def test_policy_path_serves_through_weight_pushes(policy_params):
    """Drainless sync on the policy path: continuous submits from a
    side thread while weights are pushed repeatedly — every ticket
    succeeds (zero errors attributable to the pushes) and observed
    versions are monotonic."""
    from ray_tpu.rl.models import init_policy_params

    eng = _policy_engine(policy_params)
    try:
        errors = []
        versions = []
        stop = threading.Event()

        def submitter():
            while not stop.is_set():
                try:
                    ticket = eng.submit_policy(
                        np.zeros((2, 4), np.float32)
                    )
                    ticket.result(timeout=30)
                    versions.append(ticket.version)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
                    return

        thread = threading.Thread(target=submitter)
        thread.start()
        for v in range(1, 4):
            eng.update_weights(
                init_policy_params(jax.random.PRNGKey(v), 4, 2),
                version=v,
            )
            # Wait until a ticket is actually SERVED at >= v before
            # the next push (the first batch may still be jitting),
            # so every generation demonstrably served traffic.
            deadline = time.monotonic() + 30
            while (
                (not versions or versions[-1] < v)
                and not errors
                and time.monotonic() < deadline
            ):
                time.sleep(0.005)
        stop.set()
        thread.join(timeout=30)
        assert not errors, errors
        assert versions, "no policy requests served"
        assert versions == sorted(versions)  # monotonic
        assert versions[-1] >= 1  # pushes actually took effect
    finally:
        eng.close()


# ---------------------------------------------------------------------
# drainless weight sync on the LLM path (acceptance criterion)
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_llm():
    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(
        vocab_size=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        intermediate=128, max_seq_len=128, dtype=jnp.float32,
        attention="reference",
    )
    old = init_params(jax.random.PRNGKey(0), cfg)
    new = init_params(jax.random.PRNGKey(99), cfg)
    return cfg, old, new


def test_weight_push_mid_decode_token_exact(tiny_llm):
    """THE drainless-sync acceptance test: a weight push lands while
    a stream decodes. The engine serves continuously (no shed, no
    error, no drain): the in-flight stream finishes TOKEN-EXACT on
    the old weights, a stream admitted after the push is token-exact
    on the new weights, both decode CONCURRENTLY through the mixed-
    generation window, and the old generation is dropped once its
    last request retires."""
    from decode_oracle import greedy_uncached
    from ray_tpu.llm import EngineConfig, InferenceEngine

    cfg, p_old, p_new = tiny_llm
    eng = InferenceEngine(
        p_old, cfg,
        EngineConfig(slots=2, max_len=48, prefill_chunk=8,
                     max_new_tokens=16),
        family="drainless",
    )
    try:
        rng = np.random.default_rng(3)
        prompt = rng.integers(1, 128, size=6).tolist()
        stream_old = eng.submit(prompt, max_new_tokens=16)
        it = iter(stream_old)
        out_old = [next(it), next(it)]  # provably mid-decode
        assert eng.update_weights(p_new) == 1
        stream_new = eng.submit(prompt, max_new_tokens=16)
        out_old.extend(it)  # finishes while stream_new decodes
        out_new = list(stream_new)
        assert stream_old.finish_reason == "length"  # no error/shed
        assert stream_new.finish_reason == "length"

        def ref(params):
            return greedy_uncached(params, cfg, prompt, 16)

        assert out_old == ref(p_old)  # token-exact on OLD weights
        assert out_new == ref(p_new)  # next admission on NEW weights
        stats = eng.stats()
        assert stats["weight_version"] == 1
        assert stats["weight_gens"] == 1  # old generation dropped
        assert stats["requests_done"] == 2
    finally:
        eng.close()


def test_weight_push_rejects_stale_version(tiny_llm):
    from ray_tpu.llm import EngineConfig, InferenceEngine

    cfg, p_old, p_new = tiny_llm
    eng = InferenceEngine(
        p_old, cfg, EngineConfig(slots=1, max_len=48, prefill_chunk=8),
        family="ver",
    )
    try:
        assert eng.update_weights(p_new, version=5) == 5
        with pytest.raises(ValueError):
            eng.update_weights(p_old, version=5)
    finally:
        eng.close()


# ---------------------------------------------------------------------
# live dataflow chaos (cluster)
# ---------------------------------------------------------------------

def _small_flow(policy, **kw):
    from ray_tpu.rl import PPOConfig

    knobs = dict(queue_capacity=8, max_weight_lag=4)
    knobs.update(kw)
    return (
        PPOConfig()
        .environment("CartPole-v1")
        .env_runners(
            num_env_runners=2,
            num_envs_per_env_runner=4,
            rollout_fragment_length=8,
        )
        .dataflow(policy=policy, **knobs)
        .debugging(seed=0)
        .build()
    )


def test_runner_kill_mid_rollout_queue_keeps_flowing(rt_session):
    """Chaos: rt.kill of an env runner mid-rollout costs its
    fragment(s), never the flow — updates keep landing, the slot is
    respawned + re-synced, and the fleet is back to full strength."""
    import ray_tpu as rt

    algo = _small_flow("local")
    try:
        algo.train()
        before = algo.flow.stats()["fragments_by_runner"].get(0, 0)
        rt.kill(algo.flow.runner_handle(0))
        for _ in range(3):  # flows THROUGH the death + restore
            result = algo.train()
        stats = algo.flow.stats()
        assert stats["runner_failures"] >= 1
        assert stats["fragments_dropped"] >= 1
        assert result["weight_version"] == 4  # every update landed
        # Restored-slot proof: slot 0's RESPAWNED actor produces
        # fragments again. (Not a ping: runner mailboxes legitimately
        # queue deep behind in-flight sample calls, so liveness is
        # shown by output, bounded by a few more updates.)
        deadline = time.monotonic() + 60
        while (
            algo.flow.stats()["fragments_by_runner"].get(0, 0)
            <= before
            and time.monotonic() < deadline
        ):
            algo.train()
        assert (
            algo.flow.stats()["fragments_by_runner"].get(0, 0)
            > before
        ), algo.flow.stats()
    finally:
        algo.stop()


def test_engine_actor_death_fails_fast(rt_session):
    """Chaos: the policy engine's step loop dying must surface as
    EngineDead at the driver within the call timeout — pending act()
    callers error fast, the learner loop never hangs."""
    import ray_tpu as rt
    from ray_tpu.llm.engine import EngineDead

    algo = _small_flow("engine")
    try:
        algo.train()
        rt.get(algo.flow._engine.die.remote(), timeout=30)
        t0 = time.monotonic()
        with pytest.raises(EngineDead):
            algo.train()
        assert time.monotonic() - t0 < 90  # fast, never a hang
    finally:
        algo.stop()


def test_queue_backpressure_throttles_runners_live(rt_session):
    """With a 1-deep queue and no learner consuming, runner puts hit
    the capacity gate ('full' waits) and depth never exceeds the
    bound — the backpressure contract, live."""
    algo = _small_flow("local")
    try:
        flow = algo.flow
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            flow._pump()
            time.sleep(0.05)
            stats = flow.queue_stats()
            if stats["rejected_full"] > 0:
                break
        stats = flow.queue_stats()
        assert stats["rejected_full"] > 0
        assert stats["depth"] <= stats["capacity"]
        algo.train()  # the learner drains it and training proceeds
    finally:
        algo.stop()


def test_decoupled_ppo_engine_mode_trains(rt_session):
    """Engine-served policy inference end to end: a few iterations
    train, versions advance, the engine batches rows from both
    runners, and weight pushes land drainlessly (no failed
    requests)."""
    algo = _small_flow("engine")
    try:
        for _ in range(2):
            result = algo.train()
        assert np.isfinite(result["episode_return_mean"])
        assert result["weight_version"] == 2
        engine_stats = algo.flow.engine_stats()
        assert engine_stats["policy_rows_served"] > 0
        assert engine_stats["weight_version"] == 2
        assert not engine_stats["dead"]
        stats = algo.flow.stats()
        assert stats["fragments_ok"] >= 2
        assert stats["runner_failures"] == 0
    finally:
        algo.stop()


def test_sync_interval_beyond_lag_bound_never_deadlocks(rt_session):
    """Regression (review finding): with
    sync_interval_updates > max_weight_lag + 1 the queue's staleness
    gates must compare against the last PUBLISHED version — the
    freshest weights a runner can fetch — not the learner's private
    update count, or every put throttles against weights that don't
    exist yet and the flow deadlocks."""
    algo = _small_flow(
        "local", max_weight_lag=1, sync_interval_updates=5
    )
    try:
        for _ in range(3):  # crosses non-publish updates
            result = algo.train()
        assert result["weight_version"] == 3
        stats = algo.flow.queue_stats()
        # Runners were never mass-throttled into a stall.
        assert stats["gets"] > 0
    finally:
        algo.stop()


def test_decoupled_dqn_trains(rt_session):
    from ray_tpu.rl import DQNConfig

    cfg = DQNConfig().environment("CartPole-v1").debugging(seed=0)
    cfg.rollout_length = 8
    cfg.num_envs = 4
    cfg.learning_starts = 32
    cfg.num_updates_per_iteration = 4
    algo = cfg.dataflow(
        policy="local", num_env_runners=2, queue_capacity=8
    ).build()
    try:
        r1 = algo.train()
        r2 = algo.train()
        assert r2["num_updates"] > r1["num_updates"] or (
            r2["num_updates"] >= 4
        )
        assert r2["epsilon"] < 1.0
        assert np.isfinite(r2["td_loss"])
    finally:
        algo.stop()


def test_decoupled_ppo_save_restore(rt_session, tmp_path):
    algo = _small_flow("local")
    try:
        algo.train()
        path = algo.save(str(tmp_path / "ckpt"))
    finally:
        algo.stop()
    algo2 = _small_flow("local")
    try:
        algo2.restore(path)
        assert algo2.iteration == 1
        result = algo2.train()
        assert result["training_iteration"] == 2
    finally:
        algo2.stop()


@pytest.mark.slow
def test_decoupled_ppo_learns_cartpole(rt_session):
    """Learning regression: the decoupled dataflow must not trade
    correctness for overlap — near-on-policy settings (lag bound 2,
    shallow queue) clear the same CartPole bar as synchronous PPO."""
    from ray_tpu.rl import PPOConfig

    algo = (
        PPOConfig()
        .environment("CartPole-v1")
        .dataflow(policy="local", queue_capacity=4, max_weight_lag=2)
        .debugging(seed=0)
        .build()
    )
    try:
        best = 0.0
        for _ in range(30):
            result = algo.train()
            best = max(best, result["episode_return_mean"])
            if best >= 100.0:
                break
        assert best >= 100.0, f"decoupled PPO plateaued at {best}"
    finally:
        algo.stop()
