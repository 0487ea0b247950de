"""Autoscaled verifier service pool (system/verifier_pool.py): fleet
membership under names.verifier_servers with keepalive TTL eviction,
per-attempt deadlines with retry-to-a-DIFFERENT-server, the per-backend
circuit breaker on a fake clock (including probe priority over healthy
backends), degradation to the in-process verifier registry, the typed
shape-mismatch error, and the supervisor's verifier lane scaling on
synthetic SLO violations."""

import time

import pytest

from areal_tpu.base import faults as faults_mod
from areal_tpu.base import metrics, name_resolve, names
from areal_tpu.interfaces import reward_service
from areal_tpu.system.fleet import CircuitBreaker, SupervisorLane
from areal_tpu.system.verifier_pool import (
    VerifierPool,
    VerifierWorker,
    list_verifiers,
    verifier_discovery,
)

MATH_OK = {
    "task": "math",
    "text": r"the answer is \boxed{7}",
    "payload": {"solutions": [r"\boxed{7}"]},
}
MATH_BAD = {
    "task": "math",
    "text": r"\boxed{3}",
    "payload": {"solutions": [r"\boxed{7}"]},
}

# Nothing listens here; connections are refused immediately, so a
# "dead backend" attempt fails fast without eating the test budget.
DEAD_URL = "http://127.0.0.1:1"


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture
def worker():
    w = VerifierWorker()
    yield w
    w.close()


def _announce(sid, url="http://h:1", ttl=None):
    kw = {"keepalive_ttl": ttl} if ttl is not None else {}
    name_resolve.add(
        names.verifier_server("e", "t", sid), url, replace=True, **kw
    )


class TestMembership:
    def test_discovery_lists_announced_verifiers(self):
        _announce("a", "http://h:1")
        _announce("b", "http://h:2")
        discover = verifier_discovery("e", "t")
        assert discover() == {"a": "http://h:1", "b": "http://h:2"}
        assert list_verifiers("e", "t") == ["a", "b"]
        name_resolve.delete(names.verifier_server("e", "t", "a"))
        assert list_verifiers("e", "t") == ["b"]

    def test_ttl_expiry_evicts_dead_worker(self):
        _announce("dying", ttl=0.05)
        pool = VerifierPool(
            discovery=verifier_discovery("e", "t"), refresh_s=0.0
        )
        assert "dying" in pool.servers()
        time.sleep(0.15)
        assert "dying" not in pool.servers()
        # The breaker outlives the eviction: a rejoin on the same sid is
        # re-admitted through its existing breaker, not as a stranger.
        assert "dying" in pool.breakers

    def test_late_join_picks_up_within_one_refresh(self):
        _announce("a")
        pool = VerifierPool(
            discovery=verifier_discovery("e", "t"), refresh_s=0.0
        )
        assert set(pool.servers()) == {"a"}
        _announce("b")  # joins after the pool was built
        assert set(pool.servers()) == {"a", "b"}
        assert isinstance(pool.breakers["b"], CircuitBreaker)

    def test_worker_announce_heartbeat_and_deregister(self, worker):
        sid = worker.announce("e", "t", ttl=0.3)
        assert sid == f"v{worker.port}"
        # The heartbeat thread outlives the TTL window.
        time.sleep(0.6)
        assert sid in list_verifiers("e", "t")
        worker.close()
        assert sid not in list_verifiers("e", "t")

    def test_needs_discovery_or_servers(self):
        with pytest.raises(ValueError):
            VerifierPool()


class TestPooledGrading:
    def test_round_trip_through_one_worker(self, worker):
        # One bounded attempt (the default is three of 60 s): a worker
        # that cannot answer fails `graded_local == 0` below in 20 s.
        pool = VerifierPool(
            servers={"w": worker.url}, attempt_timeout_s=20.0, max_attempts=1
        )
        assert pool.verify_batch([MATH_OK, MATH_BAD]) == [True, False]
        assert pool.graded_pooled == 2 and pool.graded_local == 0
        assert worker.graded == 2

    def test_attempt_deadline_cuts_off_slow_backend(self):
        w = VerifierWorker(
            faults=faults_mod.FaultInjector.parse("slow@ms=500&point=grade")
        )
        try:
            pool = VerifierPool(
                servers={"slow": w.url},
                attempt_timeout_s=0.1,
                max_attempts=2,
                backoff_s=0.0,
            )
            t0 = time.monotonic()
            assert pool.verify_batch([MATH_OK]) == [True]
            # Deadline fired and the pool degraded rather than waiting
            # out the 500ms grade.
            assert time.monotonic() - t0 < 0.45
            assert pool.graded_local == 1 and pool.graded_pooled == 0
        finally:
            w.close()

    def test_retry_lands_on_a_different_server(self, worker):
        bad = VerifierWorker(
            faults=faults_mod.FaultInjector.parse("error@point=grade")
        )
        try:
            # Sorted tie-break dispatches to "a" (the erroring backend)
            # first; the retry must land on "z" and succeed.
            pool = VerifierPool(
                servers={"a": bad.url, "z": worker.url},
                max_attempts=3,
                backoff_s=0.0,
                breaker_threshold=5,
            )
            assert pool.verify_batch([MATH_OK, MATH_BAD]) == [True, False]
            assert pool.redispatches >= 1
            assert pool.graded_pooled == 2 and pool.graded_local == 0
            assert worker.graded == 2 and bad.graded == 0
            # One failure is below threshold: "a" stays dispatchable.
            assert pool.breakers["a"].state == CircuitBreaker.CLOSED
        finally:
            bad.close()

    def test_shape_mismatch_is_typed_and_counted(self, worker):
        def _expose_shape_errors():
            from areal_tpu.apps.metrics_report import parse_prometheus_text

            samples, _ = parse_prometheus_text(
                metrics.default_registry().expose()
            )
            return sum(
                v
                for name, labels, v in samples
                if name == "areal_reward_remote_errors_total"
                and labels.get("reason") == "shape"
            )

        worker.grade_batch = lambda items: [True] * (len(items) + 1)
        with pytest.raises(reward_service.VerifierShapeError) as ei:
            reward_service.post_verify(worker.url, [MATH_OK], 5.0)
        assert reward_service._error_reason(ei.value) == "shape"

        before = _expose_shape_errors()
        pool = VerifierPool(
            servers={"w": worker.url}, max_attempts=1, backoff_s=0.0
        )
        # Typed, retryable, counted — and the pool still answers.
        assert pool.verify_batch([MATH_OK]) == [True]
        assert pool.graded_local == 1
        assert _expose_shape_errors() == before + 1


class TestBreakerLifecycle:
    """Breaker semantics on a fake clock: no sleeps, no wall time."""

    def _pool(self, urls, clk, **kw):
        kw.setdefault("attempt_timeout_s", 0.5)
        kw.setdefault("max_attempts", 1)
        kw.setdefault("backoff_s", 0.0)
        kw.setdefault("breaker_threshold", 1)
        kw.setdefault("breaker_cooldown_s", 5.0)
        return VerifierPool(
            discovery=lambda: dict(urls), refresh_s=0.0, clock=clk, **kw
        )

    def test_open_breaker_blocks_until_probe_recloses(self, worker):
        urls = {"a": DEAD_URL}
        clk = _Clock()
        pool = self._pool(urls, clk)
        assert pool.verify_batch([MATH_OK]) == [True]  # local fallback
        br = pool.breakers["a"]
        assert br.state == CircuitBreaker.OPEN and br.opens == 1
        # Inside the cooldown the open breaker blocks dispatch entirely.
        assert pool.verify_batch([MATH_OK]) == [True]
        assert pool.graded_local == 2 and br.opens == 1
        # The backend heals; past cooldown the NEXT batch is the probe.
        urls["a"] = worker.url
        clk.t = 5.0
        assert pool.verify_batch([MATH_OK]) == [True]
        assert br.state == CircuitBreaker.CLOSED and br.closes == 1
        assert pool.graded_pooled == 1

    def test_failed_probe_reopens_with_fresh_cooldown(self):
        urls = {"a": DEAD_URL}
        clk = _Clock()
        pool = self._pool(urls, clk)
        pool.verify_batch([MATH_OK])
        clk.t = 5.0
        # Probe rides the batch, fails against the still-dead backend,
        # and re-opens with a fresh cooldown.
        assert pool.verify_batch([MATH_OK]) == [True]
        br = pool.breakers["a"]
        assert br.state == CircuitBreaker.OPEN and br.opens == 2
        clk.t = 9.0
        assert not br.probe_due()
        clk.t = 10.0
        assert br.probe_due()

    def test_probe_takes_priority_over_healthy_backends(self, worker):
        # Regression: with a healthy backend always available, the
        # healed backend's open breaker must still get probed — the
        # probe outranks least-loaded selection.
        urls = {"a": DEAD_URL, "z": worker.url}
        clk = _Clock()
        pool = self._pool(urls, clk, max_attempts=2)
        assert pool.verify_batch([MATH_OK]) == [True]  # a fails -> z
        br = pool.breakers["a"]
        assert br.state == CircuitBreaker.OPEN
        assert pool.redispatches == 1 and pool.graded_pooled == 1
        urls["a"] = worker.url
        clk.t = 5.0
        assert pool.verify_batch([MATH_OK]) == [True]
        assert br.state == CircuitBreaker.CLOSED and br.closes == 1


class TestDegradation:
    def test_empty_fleet_degrades_to_local_registry(self):
        pool = VerifierPool(servers={})
        assert pool.verify_batch([MATH_OK, MATH_BAD]) == [True, False]
        assert pool.graded_local == 2 and pool.graded_pooled == 0

    def test_recovery_clears_degraded_flag(self, worker):
        urls = {}
        pool = VerifierPool(discovery=lambda: dict(urls), refresh_s=0.0)
        pool.verify_batch([MATH_OK])
        assert pool._degraded
        urls["w"] = worker.url
        assert pool.verify_batch([MATH_OK]) == [True]
        assert not pool._degraded and pool.graded_pooled == 1

    def test_local_fallback_disabled_raises(self):
        pool = VerifierPool(servers={}, local_fallback=False)
        with pytest.raises(RuntimeError):
            pool.verify_batch([MATH_OK])
        dead = VerifierPool(
            servers={"a": DEAD_URL},
            local_fallback=False,
            max_attempts=1,
            attempt_timeout_s=0.5,
            backoff_s=0.0,
        )
        with pytest.raises(reward_service._RETRYABLE):
            dead.verify_batch([MATH_OK])


class TestVerifierLane:
    """The supervisor's verifier lane on synthetic SLO violations —
    injectable list/spawn/drain, no processes."""

    def _lane(self, live, clk, **kw):
        from areal_tpu.apps.metrics_report import parse_slo_rule

        kw.setdefault(
            "rules", [parse_slo_rule("crit: grade_latency_p99 <= 5")]
        )
        return SupervisorLane(
            name="verifier",
            list_servers=lambda: list(live),
            spawn=lambda: live.append(f"v{len(live)}"),
            drain=lambda sid: live.remove(sid),
            clock=clk,
            **kw,
        )

    def test_crit_latency_violation_spawns(self):
        live = ["v0"]
        lane = self._lane(live, _Clock(), max_servers=4)
        d = lane.evaluate([{"grade_latency_p99": 9.0}])
        assert d.action == "spawn" and "grade_latency_p99" in d.reason
        lane.apply(d)
        assert live == ["v0", "v1"] and lane.epoch == 1

    def test_spawn_respects_max_servers_and_cooldown(self):
        clk = _Clock()
        hot = [{"grade_latency_p99": 9.0}]
        lane = self._lane(["v0", "v1"], clk, max_servers=2)
        d = lane.evaluate(hot)
        assert d.action == "hold" and "max_servers" in d.reason
        live = ["v0"]
        lane2 = self._lane(live, clk, max_servers=8, action_cooldown_s=30.0)
        lane2.step(hot)
        assert live == ["v0", "v1"]
        assert lane2.evaluate(hot).action == "hold"
        clk.t = 31.0
        assert lane2.evaluate(hot).action == "spawn"

    def test_refill_after_ttl_eviction_bypasses_cooldown(self):
        clk = _Clock()
        live = ["v0", "v1"]
        lane = self._lane(
            live, clk, min_servers=2, action_cooldown_s=1000.0
        )
        lane.step([{"grade_latency_p99": 9.0}])  # spawn; cooldown starts
        assert len(live) == 3
        live.clear()
        live.append("v0")  # two workers crash; TTL evicted them
        d = lane.evaluate([{"grade_latency_p99": 0.0}])
        assert d.action == "spawn" and "refill" in d.reason
        lane.apply(d)
        assert len(live) == 2

    def test_sustained_idle_drains_but_not_below_min(self):
        clk = _Clock()
        live = ["v0", "v1"]
        lane = self._lane(
            live, clk, min_servers=1, idle_rounds=2, action_cooldown_s=0.0
        )
        idle = [{"grade_latency_p99": 0.1, "verifier_queue_depth": 0.0}]
        assert lane.step(idle).action == "hold"
        d = lane.step(idle)
        assert d.action == "drain" and d.victim == "v1"
        assert live == ["v0"]
        for _ in range(5):
            assert lane.step(idle).action == "hold"  # never below min

    def test_traffic_resets_the_idle_streak(self):
        clk = _Clock()
        lane = self._lane(
            ["v0", "v1"], clk, min_servers=1, idle_rounds=2,
            action_cooldown_s=0.0,
        )
        idle = {"grade_latency_p99": 0.1, "verifier_queue_depth": 0.0}
        busy = {"grade_latency_p99": 0.1, "verifier_queue_depth": 7.0}
        assert lane.step([idle]).action == "hold"
        assert lane.step([busy]).action == "hold"  # streak reset
        assert lane.step([idle]).action == "hold"
        assert lane.step([idle]).action == "drain"
