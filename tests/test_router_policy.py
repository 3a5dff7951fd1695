"""Router policy + load-accounting tests (ISSUE 11): least-
outstanding-tokens beats round-robin under a skewed mix, the
outstanding-token estimate is released on every stream exit path
(the phantom-load regression: abandon/cancel and engine/replica
death must not leave ghost load pinned on a replica), and SLO
admission sheds when every candidate is over threshold.

These drive the DeploymentHandle's accounting surface directly — no
cluster — so the invariants run in milliseconds."""

import pytest

import ray_tpu.serve.router as router
from ray_tpu.serve.router import (
    DEFAULT_TOKEN_ESTIMATE,
    DeploymentHandle,
    DeploymentOverloaded,
    DeploymentResponseGenerator,
    estimate_request_tokens,
    pick_least_outstanding,
)


@pytest.fixture(autouse=True)
def _fresh_config_cache():
    """The router caches Config.from_env() process-wide (hot path);
    tests that monkeypatch RT_serve_* need a fresh read, and must not
    leak their config into later tests in the same process."""
    router._reset_config_cache()
    yield
    router._reset_config_cache()


# ---------------------------------------------------------------------
# token estimation
# ---------------------------------------------------------------------

def test_estimate_from_llm_payload():
    payload = {"prompt": list(range(40)), "max_new_tokens": 16}
    assert estimate_request_tokens((payload,), {}) == 56


def test_estimate_from_request_body():
    class FakeRequest:
        def json(self):
            return {"prompt": [1, 2, 3], "max_new_tokens": 7}

    assert estimate_request_tokens((FakeRequest(),), {}) == 10


def test_estimate_prompt_without_budget_adds_default():
    payload = {"prompt": [1, 2, 3]}
    assert (
        estimate_request_tokens((payload,), {})
        == 3 + DEFAULT_TOKEN_ESTIMATE
    )


def test_estimate_falls_back_for_opaque_payloads():
    assert estimate_request_tokens((), {}) == DEFAULT_TOKEN_ESTIMATE
    assert (
        estimate_request_tokens(("not a dict",), {})
        == DEFAULT_TOKEN_ESTIMATE
    )
    assert (
        estimate_request_tokens(({"x": 1},), {})
        == DEFAULT_TOKEN_ESTIMATE
    )


# ---------------------------------------------------------------------
# policy
# ---------------------------------------------------------------------

def test_pick_least_outstanding_prefers_min_load():
    replicas = [{"id": "a"}, {"id": "b"}, {"id": "c"}]
    outstanding = {"a": 500, "b": 20, "c": 100}
    assert pick_least_outstanding(replicas, outstanding)["id"] == "b"
    # Missing entries count as zero load.
    outstanding = {"a": 1, "c": 1}
    assert pick_least_outstanding(replicas, outstanding)["id"] == "b"


def test_pick_least_outstanding_breaks_ties_across_replicas():
    replicas = [{"id": "a"}, {"id": "b"}]
    seen = {
        pick_least_outstanding(replicas, {})["id"] for _ in range(200)
    }
    assert seen == {"a", "b"}  # idle replicas share cold traffic


def test_least_tokens_beats_round_robin_under_skewed_mix():
    """ISSUE 11 satellite: a skewed mix (long completions interleaved
    with short chats) round-robined across 2 replicas piles every
    long request onto one of them; least-outstanding-tokens balances
    assigned WORK, so the busiest replica ends up with far less of
    it (lower makespan = lower queueing delay at equal throughput)."""
    heavy, light = 200, 10
    costs = [heavy, light] * 20

    round_robin = [0, 0]
    for i, cost in enumerate(costs):
        round_robin[i % 2] += cost

    replicas = [{"id": "r0"}, {"id": "r1"}]
    least = {"r0": 0, "r1": 0}
    for cost in costs:
        pick = pick_least_outstanding(replicas, least)
        least[pick["id"]] += cost

    assert max(round_robin) == 20 * heavy  # all longs on one replica
    assert max(least.values()) < 0.6 * max(round_robin)


# ---------------------------------------------------------------------
# phantom-load regression: every exit path releases the estimate
# ---------------------------------------------------------------------

def _handle():
    return DeploymentHandle("app", "dep")


def _no_stream():
    """What the runtime hands the router for a stream, with nothing
    behind it: no cluster, so close() has nobody to tell."""
    from ray_tpu import ObjectRefGenerator
    from ray_tpu._private.ids import TaskID

    return ObjectRefGenerator(TaskID.from_random(), count=0)


def test_stream_chunks_decay_outstanding_tokens():
    handle = _handle()
    handle._ongoing_sent("r1", 10)
    gen = DeploymentResponseGenerator(
        _no_stream(), handle, "r1", tokens=10
    )
    # Simulate 4 delivered chunks' worth of decay.
    for _ in range(4):
        gen._tokens_left -= 1
        handle._tokens_done("r1", 1)
    assert handle._outstanding_tokens["r1"] == 6
    gen.close()  # releases the remainder exactly once
    assert handle._outstanding_tokens.get("r1", 0) == 0
    gen.close()  # idempotent
    assert handle._outstanding_tokens.get("r1", 0) == 0


def test_abandoned_stream_releases_full_estimate():
    """The PR 10 cancel path frees the engine's KV slot mid-decode;
    the router-side outstanding-token estimate must follow (ISSUE 11
    phantom-load fix), or the replica looks loaded forever."""
    handle = _handle()
    handle._ongoing_sent("r1", 464)
    gen = DeploymentResponseGenerator(
        _no_stream(), handle, "r1", tokens=464
    )
    gen.close()  # client disconnected before any chunk
    assert handle._outstanding_tokens.get("r1", 0) == 0
    assert handle._ongoing.get("r1") == 0


def test_membership_prune_clears_dead_replica_load():
    """Engine/replica death: the controller pushes a membership
    without the dead id; its accounting entries must vanish so the
    replacement replica doesn't inherit phantom load."""
    handle = _handle()
    handle._ongoing_sent("dead", 500)
    handle._ongoing_sent("live", 30)
    handle._state["replicas"] = [{"id": "live"}]
    with handle._lock:
        handle._prune_gone_locked()
    assert "dead" not in handle._outstanding_tokens
    assert "dead" not in handle._ongoing
    assert handle._outstanding_tokens["live"] == 30


def test_response_result_releases_tokens_once():
    handle = _handle()
    handle._ongoing_sent("r1", 64)
    from ray_tpu.serve.router import DeploymentResponse

    response = DeploymentResponse(lambda timeout: "ok", handle)
    response._replica_id = "r1"
    response._tokens = 64
    assert response.result() == "ok"
    assert handle._outstanding_tokens.get("r1", 0) == 0
    assert response.result() == "ok"  # second resolve: no double free
    assert handle._outstanding_tokens.get("r1", 0) == 0


def test_dropped_response_releases_estimate_on_gc():
    """Review-caught leak: a non-streaming response fired and DROPPED
    (never .result()-ed) must not pin its token estimate on the
    replica forever — a handful of dropped requests would otherwise
    push the least-loaded replica over the SLO threshold and 503
    everything after."""
    from ray_tpu.serve.router import DeploymentResponse

    handle = _handle()
    handle._ongoing_sent("r1", 500)
    response = DeploymentResponse(lambda timeout: "ok", handle)
    response._replica_id = "r1"
    response._tokens = 500
    del response  # GC without result()
    assert handle._outstanding_tokens.get("r1", 0) == 0
    assert handle._ongoing.get("r1") == 0


# ---------------------------------------------------------------------
# SLO admission
# ---------------------------------------------------------------------

def test_slo_admission_sheds_over_threshold(monkeypatch):
    monkeypatch.setenv("RT_serve_slo_queue_threshold_tokens", "100")
    handle = _handle()
    handle._ongoing_sent("r1", 150)
    with pytest.raises(DeploymentOverloaded):
        handle._slo_admit({"id": "r1"}, 10)


def test_slo_admission_admits_under_threshold(monkeypatch):
    monkeypatch.setenv("RT_serve_slo_queue_threshold_tokens", "100")
    handle = _handle()
    handle._ongoing_sent("r1", 99)
    handle._slo_admit({"id": "r1"}, 10)  # no raise


def test_slo_admission_kill_switch(monkeypatch):
    monkeypatch.setenv("RT_serve_slo_queue_threshold_tokens", "100")
    monkeypatch.setenv("RT_serve_slo_admission_enabled", "0")
    handle = _handle()
    handle._ongoing_sent("r1", 10_000)
    handle._slo_admit({"id": "r1"}, 10)  # disabled: no raise
