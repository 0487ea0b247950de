"""Elastic rollout fleet: fault-spec parsing and injection
(base/faults.py), the per-server circuit breaker, the SLO-driven fleet
supervisor with epoch persistence, discovery over the names.gen_servers
subtree, and the arealint metrics-names gate over the new fleet code."""

import os
import threading
import time

import pytest

from areal_tpu.base import name_resolve, names, recover
from areal_tpu.base.faults import (
    FaultError,
    FaultInjector,
    FaultSpec,
    parse_faults,
)
from areal_tpu.system.fleet import (
    CircuitBreaker,
    FleetSupervisor,
    fleet_discovery,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestFaultSpecParsing:
    def test_grammar_round_trip(self):
        specs = parse_faults("kill@t=5s, hang@p=0.1 slow@ms=500&p=0.5")
        assert [s.kind for s in specs] == ["kill", "hang", "slow"]
        assert specs[0].arm_after_s == 5.0
        assert specs[1].prob == 0.1
        assert specs[2].latency_s == 0.5 and specs[2].prob == 0.5

    def test_duration_units(self):
        assert parse_faults("kill@t=500ms")[0].arm_after_s == 0.5
        assert parse_faults("kill@t=2.5")[0].arm_after_s == 2.5

    def test_point_filter(self):
        (s,) = parse_faults("error@point=health")
        assert s.matches("health", 0.0)
        assert not s.matches("generate", 0.0)

    def test_arm_delay_gates_matching(self):
        s = FaultSpec(kind="error", arm_after_s=10.0)
        assert not s.matches("generate", 9.9)
        assert s.matches("generate", 10.0)

    @pytest.mark.parametrize(
        "bad",
        ["explode", "kill@t", "error@p=2", "slow@bogus=1", "", "   "],
    )
    def test_bad_specs_fail_loudly(self, bad):
        with pytest.raises(ValueError):
            parse_faults(bad)


class TestFaultInjector:
    def test_error_fires_and_counts(self):
        fired = []
        inj = FaultInjector.parse("error", on_fire=fired.append)
        with pytest.raises(FaultError):
            inj.fire("generate")
        assert inj.fired["error"] == 1 and fired == ["error"]

    def test_slow_sleeps(self):
        inj = FaultInjector.parse("slow@ms=30")
        t0 = time.monotonic()
        inj.fire("generate")  # returns normally after the added latency
        assert time.monotonic() - t0 >= 0.025
        assert inj.fired["slow"] == 1

    def test_probability_is_seeded_and_deterministic(self):
        def run(seed):
            inj = FaultInjector.parse("error@p=0.5", seed=seed)
            hits = []
            for _ in range(32):
                try:
                    inj.fire("x")
                    hits.append(0)
                except FaultError:
                    hits.append(1)
            return hits

        assert run(7) == run(7)
        assert 0 < sum(run(7)) < 32

    def test_hang_blocks_until_release(self):
        inj = FaultInjector.parse("hang")
        errs = []

        def worker():
            try:
                inj.fire("generate")
            except FaultError as e:
                errs.append(e)

        t = threading.Thread(target=worker)
        t.start()
        time.sleep(0.05)
        assert t.is_alive()  # still blocked, like a hung server
        inj.release()
        t.join(timeout=5)
        assert not t.is_alive() and len(errs) == 1

    def test_kill_never_fires_inline(self):
        inj = FaultInjector.parse("kill@t=0s")
        inj.fire("generate")  # no exception: the HOST polls kill_due
        assert inj.kill_due()
        assert inj.fired["kill"] == 1
        inj.kill_due()
        assert inj.fired["kill"] == 1  # recorded once

    def test_from_env_gate(self):
        assert FaultInjector.from_env(environ={}) is None
        inj = FaultInjector.from_env(environ={"AREAL_FAULTS": "error"})
        assert inj is not None and inj.specs[0].kind == "error"


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestCircuitBreaker:
    def test_opens_after_threshold_consecutive_failures(self):
        clk = _Clock()
        br = CircuitBreaker(threshold=3, cooldown_s=5.0, clock=clk)
        br.record_failure()
        br.record_failure()
        assert br.state == CircuitBreaker.CLOSED and br.allow_dispatch()
        br.record_success()  # resets the consecutive count
        br.record_failure()
        br.record_failure()
        assert br.state == CircuitBreaker.CLOSED
        br.record_failure()
        assert br.state == CircuitBreaker.OPEN and not br.allow_dispatch()
        assert br.opens == 1

    def test_half_open_probe_closes_on_success(self):
        clk = _Clock()
        br = CircuitBreaker(threshold=1, cooldown_s=5.0, clock=clk)
        br.record_failure()
        assert br.state == CircuitBreaker.OPEN
        assert not br.probe_due()
        clk.t = 5.0
        assert br.probe_due()
        br.begin_probe()
        assert br.state == CircuitBreaker.HALF_OPEN
        assert not br.allow_dispatch()  # only the probe goes through
        br.record_success()
        assert br.state == CircuitBreaker.CLOSED and br.closes == 1

    def test_half_open_failure_reopens_with_fresh_cooldown(self):
        clk = _Clock()
        br = CircuitBreaker(threshold=1, cooldown_s=5.0, clock=clk)
        br.record_failure()
        clk.t = 5.0
        br.begin_probe()
        br.record_failure()
        assert br.state == CircuitBreaker.OPEN and br.opens == 2
        clk.t = 9.0  # 4s into the FRESH cooldown
        assert not br.probe_due()
        clk.t = 10.0
        assert br.probe_due()

    def test_transition_callback_fires(self):
        seen = []
        br = CircuitBreaker(threshold=1, cooldown_s=0.0, clock=_Clock(),
                            on_transition=seen.append)
        br.record_failure()
        br.begin_probe()
        br.record_success()
        assert seen == [
            CircuitBreaker.OPEN,
            CircuitBreaker.HALF_OPEN,
            CircuitBreaker.CLOSED,
        ]


class TestFleetDiscovery:
    def test_lists_announced_servers(self):
        name_resolve.add(
            names.gen_server("e", "t", "s1"), "http://h:1", replace=True
        )
        name_resolve.add(
            names.gen_server("e", "t", "s2"), "zmq://h:2", replace=True
        )
        discover = fleet_discovery("e", "t")
        assert discover() == {"s1": "http://h:1", "s2": "zmq://h:2"}
        name_resolve.delete(names.gen_server("e", "t", "s1"))
        assert discover() == {"s2": "zmq://h:2"}

    def test_keepalive_expiry_drops_dead_servers(self):
        name_resolve.add(
            names.gen_server("e", "t", "dying"), "http://h:1",
            keepalive_ttl=0.05, replace=True,
        )
        discover = fleet_discovery("e", "t")
        assert "dying" in discover()
        time.sleep(0.15)
        assert "dying" not in discover()


def _announce(sid):
    name_resolve.add(
        names.gen_server("e", "t", sid), f"http://h/{sid}", replace=True
    )


class TestFleetSupervisor:
    def _sup(self, **kw):
        from areal_tpu.apps.metrics_report import parse_slo_rule

        kw.setdefault(
            "rules", [parse_slo_rule("crit: staleness_p99 <= 4")]
        )
        kw.setdefault("clock", _Clock())
        return FleetSupervisor(
            "e", "t", spawn=kw.pop("spawn", None),
            drain=kw.pop("drain", None), **kw,
        )

    def test_crit_capacity_violation_spawns(self):
        spawned = []
        _announce("s1")
        sup = self._sup(spawn=lambda: spawned.append("x"), max_servers=2)
        d = sup.evaluate({"staleness_p99": 9.0, "goodput": 100.0})
        assert d.action == "spawn"
        sup.apply(d)
        assert spawned == ["x"] and sup.membership_epoch == 1

    def test_spawn_respects_max_servers_and_cooldown(self):
        clk = _Clock()
        _announce("s1")
        _announce("s2")
        sup = self._sup(max_servers=2, clock=clk)
        d = sup.evaluate({"staleness_p99": 9.0})
        assert d.action == "hold" and "max_servers" in d.reason
        # Below max but cooling down after an action:
        sup2 = self._sup(spawn=lambda: None, max_servers=8,
                         action_cooldown_s=30.0, clock=clk)
        sup2.apply(sup2.evaluate({"staleness_p99": 9.0}))
        d = sup2.evaluate({"staleness_p99": 9.0})
        assert d.action == "hold" and "cooling down" in d.reason
        clk.t = 31.0
        assert sup2.evaluate({"staleness_p99": 9.0}).action == "spawn"

    def test_sustained_idle_drains_but_not_below_min(self):
        drained = []
        _announce("s1")
        _announce("s2")
        idle = {"staleness_p99": 0.0, "goodput": 0.0, "idle_frac": 1.0,
                "in_flight": 0.0}
        sup = self._sup(
            drain=drained.append, min_servers=1, idle_rounds=3,
        )
        assert sup.evaluate(dict(idle)).action == "hold"
        assert sup.evaluate(dict(idle)).action == "hold"
        d = sup.evaluate(dict(idle))
        assert d.action == "drain" and d.victim == "s2"
        sup.apply(d)
        assert drained == ["s2"]
        # A busy scrape resets the idle streak.
        sup2 = self._sup(min_servers=1, idle_rounds=2)
        sup2.evaluate(dict(idle))
        sup2.evaluate({"staleness_p99": 0.0, "goodput": 50.0,
                       "idle_frac": 0.1, "in_flight": 4.0})
        assert sup2.evaluate(dict(idle)).action == "hold"
        # At min_servers, sustained idle still holds.
        name_resolve.delete(names.gen_server("e", "t", "s2"))
        sup3 = self._sup(min_servers=1, idle_rounds=1)
        assert sup3.evaluate(dict(idle)).action == "hold"

    def test_membership_epoch_persists_through_recover_info(self, tmp_path):
        _announce("s1")
        root = str(tmp_path)
        sup = self._sup(
            spawn=lambda: None, recover_root=root, max_servers=4,
        )
        sup.apply(sup.evaluate({"staleness_p99": 9.0}))
        assert sup.membership_epoch == 1
        info = recover.load(root)
        assert info.fleet_state["membership_epoch"] == 1
        assert info.fleet_state["servers"] == ["s1"]
        # A restarted supervisor resumes the epoch counter.
        sup2 = self._sup(recover_root=root)
        assert sup2.membership_epoch == 1

    def test_persist_merges_with_existing_recover_info(self, tmp_path):
        root = str(tmp_path)
        recover.dump(
            recover.RecoverInfo(rollout_state={"cursor": 7}), root
        )
        _announce("s1")
        sup = self._sup(spawn=lambda: None, recover_root=root)
        sup.apply(sup.evaluate({"staleness_p99": 9.0}))
        info = recover.load(root)
        # The master's fields survive the supervisor's write.
        assert info.rollout_state == {"cursor": 7}
        assert info.fleet_state["membership_epoch"] == 1


class TestRecoverFleetState:
    def test_fleet_state_round_trip(self, tmp_path):
        info = recover.RecoverInfo(
            replay_watermarks={"version": 5},
            rollout_state={"cursor": 40, "membership_epoch": 3},
            fleet_state={"membership_epoch": 3, "servers": ["s1", "s2"]},
        )
        recover.dump(info, str(tmp_path))
        back = recover.load(str(tmp_path))
        assert back.fleet_state == {
            "membership_epoch": 3, "servers": ["s1", "s2"],
        }
        assert back.rollout_state["membership_epoch"] == 3
        assert back.replay_watermarks == {"version": 5}

    def test_old_pickle_without_fleet_state_backfills(self, tmp_path):
        import pickle

        info = recover.RecoverInfo()
        del info.__dict__["fleet_state"]
        with open(tmp_path / recover.RECOVER_FILE, "wb") as f:
            pickle.dump(info, f)
        back = recover.load(str(tmp_path))
        assert back.fleet_state == {}


class TestFleetMetricNames:
    def test_new_metric_registrations_pass_metrics_names_rule(self):
        """The elastic-fleet code registers new series
        (areal_rollout_redispatch_total, areal_rollout_breaker_*,
        areal_rollout_servers, areal_gen_faults_total); the arealint
        metrics-names rule must stay green over every file that touches
        the metrics registry in this PR."""
        from areal_tpu.analysis import Severity, analyze_paths
        from areal_tpu.analysis.rules import get_rules

        paths = [
            os.path.join(REPO, "areal_tpu", "system", "rollout.py"),
            os.path.join(REPO, "areal_tpu", "system", "fleet.py"),
            os.path.join(REPO, "areal_tpu", "system", "gen_server.py"),
            os.path.join(REPO, "areal_tpu", "base", "faults.py"),
        ]
        findings = analyze_paths(
            paths, rules=get_rules(["metrics-names"]), relative_to=REPO
        )
        errs = [f for f in findings if f.severity == Severity.ERROR]
        assert not errs, "\n".join(f.render() for f in errs)
