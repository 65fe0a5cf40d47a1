"""Cooperative preemption + the provable scheduler (docs/scheduling.md).

Four layers, innermost out:

- the pure :class:`PreemptionPolicy` (cluster/policy.py): shrink-first
  partial reclaim, minimum-runtime protection, per-queue eviction budgets;
- the discrete-event simulator (cluster/sim.py): invariant suites over
  >= 1000 seeded synthetic arrivals per mix, driving the SAME policy class
  the live pool runs (a parity guard greps for re-divergence);
- the live ``PoolService`` drain machinery: two-phase checkpoint-then-yield
  eviction, shrink notices over the ``poll_exited`` piggyback, deadline
  escalation, drain cancellation, and the journal's waiting-age persistence;
- the headline E2E: a prod arrival drains a running dev gang, which
  urgent-checkpoints through the real ``CheckpointManager`` and yields
  inside the deadline — with a kill-path control run proving the drain
  strictly reduced ``restart_rework`` — and an elastic victim sheds a
  worker via shrink instead of dying whole.
"""

import json
import os
import sys
import threading
import time

import pytest

from tony_tpu import constants
from tony_tpu.cluster import policy as pol
from tony_tpu.cluster import sim as simmod
from tony_tpu.cluster.events import Event, EventType
from tony_tpu.cluster.policy import AppView, PreemptionPolicy
from tony_tpu.cluster.pool import PoolService
from tony_tpu.cluster.sim import GB, PoolSimulator, SimJob, run_mix
from tony_tpu.config import keys
from tony_tpu.cluster.session import JobStatus
from tony_tpu.obs import goodput as obs_goodput
from tony_tpu.obs import metrics as obs_metrics

from tests.test_pool import (
    FAST,
    FIXTURES,
    SECRET,
    register_cpu_node,
    spawn_agent,
)
from tests.test_pool_queue import submit_async

pytestmark = pytest.mark.sched

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def counter_value(name: str, **labels) -> float:
    """Current value of one (labeled) counter child in the process registry."""
    for m in obs_metrics.REGISTRY.snapshot():
        if m.get("name") != name:
            continue
        for s in m.get("samples", []):
            if all(s.get("labels", {}).get(k) == v for k, v in labels.items()):
                return float(s.get("value", 0.0))
    return 0.0


# ---------------------------------------------------------------------------
# Pure policy units
# ---------------------------------------------------------------------------
class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def _totals(mem_gb=8):
    return (mem_gb * GB, 256, 0)


def make_apps(*specs):
    return [AppView(**s) for s in specs]


class TestPolicyGuards:
    def test_min_runtime_protects_fresh_admittee_from_reclaim(self):
        clock = FakeClock()
        p = PreemptionPolicy({"a": 0.5, "b": 0.5}, preemption=True,
                             min_runtime_ms=5000, clock=clock)
        borrower = AppView("b1", "b", demand=(8 * GB, 1, 0), held=(8 * GB, 1, 0),
                           admitted=True, admitted_at=clock.t - 1.0)
        head = AppView("a1", "a", demand=(2 * GB, 1, 0), wait_since=clock.t - 60)
        d = p.schedule([borrower, head], _totals())
        assert d.empty()  # borrower admitted 1s ago: protected
        clock.t += 10.0
        d = p.schedule([borrower, head], _totals())
        assert d.admit == ["a1"] and [e.app_id for e in d.evict] == ["b1"]

    def test_min_runtime_protects_from_priority_preemption(self):
        clock = FakeClock()
        p = PreemptionPolicy({"q": 1.0}, preemption=True,
                             min_runtime_ms=5000, clock=clock)
        low = AppView("low", "q", priority=0, demand=(8 * GB, 1, 0),
                      held=(8 * GB, 1, 0), admitted=True, admitted_at=clock.t)
        high = AppView("high", "q", priority=9, demand=(8 * GB, 1, 0))
        assert p.schedule([low, high], _totals()).empty()
        clock.t += 6.0
        d = p.schedule([low, high], _totals())
        assert d.admit == ["high"] and [e.app_id for e in d.evict] == ["low"]

    def test_eviction_budget_caps_a_queue_and_refills(self):
        clock = FakeClock()
        p = PreemptionPolicy({"a": 0.5, "b": 0.5}, preemption=True,
                             eviction_budget=1, budget_window_ms=10_000,
                             clock=clock)

        def world():
            return [
                AppView("b1", "b", demand=(4 * GB, 1, 0), held=(4 * GB, 1, 0),
                        admitted=True, seq=0),
                AppView("b2", "b", demand=(4 * GB, 1, 0), held=(4 * GB, 1, 0),
                        admitted=True, seq=1),
                AppView("a1", "a", demand=(2 * GB, 1, 0), seq=2,
                        wait_since=clock.t - 60),
            ]

        d = p.schedule(world(), _totals())
        assert len(d.evict) == 1 and d.admit == ["a1"]  # 1 disruption: allowed
        # the SAME aggressor queue is out of budget now
        d2 = p.schedule(world(), _totals())
        assert d2.empty()
        clock.t += 11.0  # window rolls: budget refills
        d3 = p.schedule(world(), _totals())
        assert len(d3.evict) == 1 and d3.admit == ["a1"]

    def test_grace_defers_reclaim(self):
        clock = FakeClock()
        p = PreemptionPolicy({"a": 0.5, "b": 0.5}, preemption=True,
                             grace_ms=2000, clock=clock)
        borrower = AppView("b1", "b", demand=(8 * GB, 1, 0), held=(8 * GB, 1, 0),
                           admitted=True)
        head = AppView("a1", "a", demand=(2 * GB, 1, 0), wait_since=clock.t - 0.5)
        assert p.schedule([borrower, head], _totals()).empty()
        clock.t += 2.0
        assert not p.schedule([borrower, head], _totals()).empty()


class TestPolicyShrink:
    def world(self, clock, slack=7):
        borrower = AppView(
            "dev1", "dev", demand=(8 * GB, 8, 0), held=(8 * GB, 8, 0),
            admitted=True, elastic_unit=(GB, 1, 0), elastic_slack=slack)
        head = AppView("prod1", "prod", demand=(2 * GB, 1, 0),
                       wait_since=clock.t - 60)
        return [borrower, head]

    def test_shrink_preferred_over_whole_eviction(self):
        clock = FakeClock()
        p = PreemptionPolicy({"prod": 0.6, "dev": 0.4}, preemption=True, clock=clock)
        apps = self.world(clock)
        d = p.schedule(apps, _totals())
        assert d.admit == ["prod1"] and not d.evict
        assert [(s.app_id, s.workers) for s in d.shrink] == [("dev1", 2)]
        dev = apps[0]
        # the view reflects the applied shrink: demand reduced, settled flag
        assert dev.demand[0] == 6 * GB and dev.shrink_pending and dev.elastic_slack == 5

    def test_shrink_never_digs_victim_below_its_share(self):
        """The head needs 6 GB; dev's excess over share is only ~4.8 GB —
        shedding stops at dev's share, and the pure-evict fallback evicts
        whole instead (the app only ran by borrowing)."""
        clock = FakeClock()
        p = PreemptionPolicy({"prod": 0.6, "dev": 0.4}, preemption=True, clock=clock)
        apps = [
            AppView("dev1", "dev", demand=(8 * GB, 8, 0), held=(8 * GB, 8, 0),
                    admitted=True, elastic_unit=(GB, 1, 0), elastic_slack=7),
            AppView("prod1", "prod", demand=(4 * GB, 1, 0), wait_since=clock.t - 60),
        ]
        d = p.schedule(apps, _totals())
        assert d.admit == ["prod1"]
        if d.shrink:
            # shrink alone must not have pushed dev below its 3.2 GB share
            shed = sum(s.workers for s in d.shrink)
            assert 8 * GB - shed * GB >= 0.4 * 8 * GB
        else:
            assert [e.app_id for e in d.evict] == ["dev1"]

    def test_whole_eviction_when_slack_insufficient(self):
        clock = FakeClock()
        p = PreemptionPolicy({"prod": 0.6, "dev": 0.4}, preemption=True, clock=clock)
        apps = self.world(clock, slack=1)  # can shed 1 GB; head needs 2 GB
        d = p.schedule(apps, _totals())
        assert d.admit == ["prod1"]
        assert [e.app_id for e in d.evict] == ["dev1"] and not d.shrink

    def test_shrink_pending_app_is_not_revictimized(self):
        clock = FakeClock()
        p = PreemptionPolicy({"prod": 0.6, "dev": 0.4}, preemption=True, clock=clock)
        apps = self.world(clock)
        apps[0].shrink_pending = True
        d = p.schedule(apps, _totals())
        assert d.empty()  # in-flight shrink: wait for it, no piling on


# ---------------------------------------------------------------------------
# Simulator invariant suites (the tier-1 proof: >= 1000 arrivals per seed)
# ---------------------------------------------------------------------------
class TestSimulatorInvariants:
    @pytest.mark.parametrize("mix,seed", [
        ("batch", 0), ("bursty", 1), ("elastic", 2), ("priority", 3),
    ])
    def test_invariants_over_1000_arrivals(self, mix, seed):
        report = run_mix(mix, 1000, seed=seed)
        assert report.ok(), report.violations[:5]
        assert report.completed == report.jobs == 1000

    def test_budgeted_run_holds_budget_invariant(self):
        report = run_mix("priority", 1000, seed=5, eviction_budget=2,
                         budget_window_ms=30_000)
        assert report.ok(), report.violations[:5]

    def test_deterministic_per_seed(self):
        a = run_mix("bursty", 300, seed=9)
        b = run_mix("bursty", 300, seed=9)
        assert a.to_dict() == b.to_dict()

    def test_shrink_fires_in_a_crafted_pressure_scenario(self):
        """An elastic dev borrower holding the whole pool sheds workers for
        a prod arrival instead of dying whole."""
        queues = {"prod": 0.5, "dev": 0.5}
        sim = PoolSimulator(queues, (8 * GB, 256, 0), preemption=True,
                            grace_ms=0, drain_ms=5000, min_runtime_ms=0)
        jobs = [
            SimJob("dev-big", "dev", arrival_s=0.0, work_s=300.0,
                   demand=(8 * GB, 8, 0), elastic_unit=(GB, 1, 0),
                   elastic_slack=7, checkpoint_every_s=30.0),
            SimJob("prod-late", "prod", arrival_s=10.0, work_s=30.0,
                   demand=(2 * GB, 1, 0)),
        ]
        report = sim.run(jobs)
        assert report.ok(), report.violations
        assert report.shrinks >= 1 and report.evictions == 0

    def test_invariant_checker_catches_a_broken_policy(self, monkeypatch):
        """Prove the checker checks: a policy that admits everyone blindly
        must trip the no-oversubscription invariant."""
        def admit_everyone(self, world, totals):
            # schedule_world is the sim's entry point (the persistent-index
            # path); a blind admit must still trip the checker
            d = pol.Decision()
            for a in world.views.values():
                if not a.admitted:
                    a.admitted = True
                    d.admit.append(a.app_id)
            return d

        monkeypatch.setattr(PreemptionPolicy, "schedule_world", admit_everyone)
        report = run_mix("batch", 50, seed=0)
        assert any("oversubscription" in v for v in report.violations)

    def test_sim_cli_reports_and_exits_zero(self, capsys):
        from tony_tpu.cli.sim import main as sim_main

        rc = sim_main(["--mix", "batch", "--jobs", "200", "--seed", "4"])
        out = capsys.readouterr().out
        assert rc == 0 and "invariants: OK" in out
        rc = sim_main(["--queues", "prod=0.9,dev=0.9"])
        assert rc == 2  # oversubscribed guarantees rejected


# ---------------------------------------------------------------------------
# Live ↔ policy parity: the pool must IMPORT the policy, not re-implement it
# ---------------------------------------------------------------------------
class TestPolicyParity:
    def test_pool_and_sim_share_the_policy_class(self):
        svc = PoolService(secret=SECRET)
        try:
            sim = PoolSimulator({"default": 1.0}, (GB, 8, 0))
            assert type(svc._policy) is PreemptionPolicy
            assert type(sim.policy) is PreemptionPolicy
            assert simmod.PreemptionPolicy is pol.PreemptionPolicy
        finally:
            svc.stop()

    def test_no_scheduling_algorithm_left_in_pool_py(self):
        """Grep guard against re-divergence (same pattern as the
        artifact-index parity test): the admission/preemption ALGORITHM must
        live only in policy.py — pool.py applies decisions."""
        src = open(os.path.join(REPO_ROOT, "tony_tpu", "cluster", "pool.py")).read()
        for forbidden in (
            "def _preempt_for_locked",
            "def _reclaim_across_queues_locked",
            "blocked_heads",
            "over_share",
            "freed_primary",
            # r14 indexed-pass internals: the pool feeds the WorldIndex
            # deltas and applies decisions — it must never grow its own
            # head-selection, victim-walk, or eligibility logic
            "waiting_in",
            "others_waiting",
            "victims_iter",
            "deficit_dims",
            "slack_left",
            "note_admitted",
            "note_evicted",
        ):
            assert forbidden not in src, (
                f"{forbidden!r} found in pool.py — the scheduling algorithm "
                "belongs in cluster/policy.py (shared with tony sim)")
        assert "from tony_tpu.cluster.policy import" in src
        sim_src = open(os.path.join(REPO_ROOT, "tony_tpu", "cluster", "sim.py")).read()
        assert "PreemptionPolicy" in sim_src


# ---------------------------------------------------------------------------
# Live pool drain machinery (direct PoolService, no RPC)
# ---------------------------------------------------------------------------
def make_pool(**kw):
    return PoolService(heartbeat_interval_ms=100, max_missed_heartbeats=3,
                       secret=SECRET, **kw)


class TestPoolDrain:
    def test_two_phase_eviction_defers_kills_and_notifies(self):
        svc = make_pool(preemption=True, preemption_drain_ms=60_000)
        register_cpu_node(svc, "n0")  # 4 GB
        svc.register_app("victim", memory_bytes=3 * GB, vcores=1)
        got = svc.allocate("victim", "worker", 0, 3 * GB, 1, 0)
        svc.register_app("agg", priority=5, memory_bytes=3 * GB, vcores=1)
        # demoted, but NOT killed: the drain window is open
        st = svc.pool_status()
        assert [w["app_id"] for w in st["queues"]["default"]["waiting"]] == ["victim"]
        assert st["queues"]["default"]["waiting"][0]["draining"] is True
        assert st["drains_active"] == 1
        assert not svc._nodes["n0"].pending_kills
        # the notice rides the victim's poll
        resp = svc.poll_exited("victim", with_preempt=True)
        notice = resp["preempt"]
        assert notice["mode"] == "drain" and 0 < notice["deadline_ms"] <= 60_000
        # a cooperative yield (release) resolves the drain as mode=drain
        before = counter_value("tony_pool_preemptions_total", mode="drain")
        svc.release("victim", got["id"])
        assert counter_value("tony_pool_preemptions_total", mode="drain") == before + 1
        assert svc.pool_status()["drains_active"] == 0
        assert svc.poll_exited("victim", with_preempt=True)["preempt"] is None
        svc.stop()

    def test_drain_ms_zero_keeps_the_classic_kill_path(self):
        svc = make_pool(preemption=True)  # drain-ms 0
        register_cpu_node(svc, "n0")
        before = counter_value("tony_pool_preemptions_total", mode="kill")
        svc.register_app("victim", memory_bytes=3 * GB, vcores=1)
        got = svc.allocate("victim", "worker", 0, 3 * GB, 1, 0)
        svc.register_app("agg", priority=5, memory_bytes=3 * GB, vcores=1)
        assert got["id"] in svc._nodes["n0"].pending_kills  # immediate
        assert counter_value("tony_pool_preemptions_total", mode="kill") == before + 1
        svc.stop()

    def test_deadline_escalates_to_kill(self):
        svc = make_pool(preemption=True, preemption_drain_ms=150)
        register_cpu_node(svc, "n0")
        svc.register_app("victim", memory_bytes=3 * GB, vcores=1)
        got = svc.allocate("victim", "worker", 0, 3 * GB, 1, 0)
        svc.register_app("agg", priority=5, memory_bytes=3 * GB, vcores=1)
        assert not svc._nodes["n0"].pending_kills
        before = counter_value("tony_pool_preemptions_total", mode="kill")
        time.sleep(0.25)
        with svc._lock:
            svc._escalate_drains_locked()  # what the liveness loop runs
        assert got["id"] in svc._nodes["n0"].pending_kills
        assert counter_value("tony_pool_preemptions_total", mode="kill") == before + 1
        # the kill still reports as a preemption to the victim's poll
        svc.node_heartbeat("n0", exited={got["id"]: 137})
        assert svc.poll_exited("victim") == {got["id"]: constants.EXIT_PREEMPTED}
        svc.stop()

    def test_drain_cancelled_when_victim_readmitted(self):
        svc = make_pool(preemption=True, preemption_drain_ms=60_000)
        register_cpu_node(svc, "n0")
        svc.register_app("victim", memory_bytes=3 * GB, vcores=1)
        got = svc.allocate("victim", "worker", 0, 3 * GB, 1, 0)
        svc.register_app("agg", priority=5, memory_bytes=3 * GB, vcores=1)
        assert svc.pool_status()["drains_active"] == 1
        req_id = svc.poll_exited("victim", with_preempt=True)["preempt"]["req_id"]
        # the aggressor leaves before the victim yields → victim re-admits,
        # drain cancelled, nothing ever killed
        svc.release_all("agg")
        st = svc.pool_status()
        assert [a["app_id"] for a in st["queues"]["default"]["admitted"]] == ["victim"]
        assert st["drains_active"] == 0
        assert svc.poll_exited("victim", with_preempt=True)["preempt"] == {
            "cancelled": req_id}
        assert not svc._nodes["n0"].pending_kills
        assert got["id"] in svc._containers  # still running
        svc.stop()

    def test_shrink_notice_and_resolution(self):
        svc = make_pool(preemption=True, preemption_drain_ms=60_000,
                        queues={"prod": 0.5, "dev": 0.5})
        register_cpu_node(svc, "n0")  # 4 GB → 2 GB shares
        svc.register_app("dev1", queue="dev", memory_bytes=4 * GB, vcores=2,
                         elastic_unit=[2 * GB, 1, 0], elastic_slack=1)
        a = svc.allocate("dev1", "worker", 0, 2 * GB, 1, 0)
        svc.allocate("dev1", "worker", 1, 2 * GB, 1, 0)
        svc.register_app("prod1", queue="prod", memory_bytes=2 * GB, vcores=1)
        st = svc.pool_status()
        # partial reclaim: dev1 stays ADMITTED (draining), prod1 admitted too
        assert [x["app_id"] for x in st["queues"]["dev"]["admitted"]] == ["dev1"]
        assert st["queues"]["dev"]["admitted"][0]["draining"] is True
        assert [x["app_id"] for x in st["queues"]["prod"]["admitted"]] == ["prod1"]
        notice = svc.poll_exited("dev1", with_preempt=True)["preempt"]
        assert notice["mode"] == "shrink" and notice["shrink_workers"] == 1
        # the AM sheds: releases both containers (rebuild at size 1)
        before = counter_value("tony_pool_preemptions_total", mode="shrink")
        svc.release("dev1", a["id"])
        assert counter_value("tony_pool_preemptions_total", mode="shrink") == before + 1
        assert svc.pool_status()["drains_active"] == 0
        svc.stop()

    def test_shrink_escalates_to_whole_eviction(self):
        svc = make_pool(preemption=True, preemption_drain_ms=100,
                        queues={"prod": 0.5, "dev": 0.5})
        register_cpu_node(svc, "n0")
        svc.register_app("dev1", queue="dev", memory_bytes=4 * GB, vcores=2,
                         elastic_unit=[2 * GB, 1, 0], elastic_slack=1)
        c0 = svc.allocate("dev1", "worker", 0, 2 * GB, 1, 0)
        c1 = svc.allocate("dev1", "worker", 1, 2 * GB, 1, 0)
        svc.register_app("prod1", queue="prod", memory_bytes=2 * GB, vcores=1)
        assert svc.poll_exited("dev1", with_preempt=True)["preempt"]["mode"] == "shrink"
        # shrink deadlines floor at 10s (the shed is a rebuild); force-expire
        # instead of sleeping the test through it
        with svc._lock:
            svc._drains["dev1"]["deadline"] = 0.0
            svc._escalate_drains_locked()
        st = svc.pool_status()
        assert [w["app_id"] for w in st["queues"]["dev"]["waiting"]] == ["dev1"]
        kills = set(svc._nodes["n0"].pending_kills)
        assert {c0["id"], c1["id"]} <= kills
        svc.stop()

    def test_pool_status_share_utilization_fields(self):
        svc = make_pool(queues={"prod": 0.75, "dev": 0.25})
        register_cpu_node(svc, "n0")  # 4 GB
        svc.register_app("p1", queue="prod", memory_bytes=3 * GB, vcores=1)
        svc.allocate("p1", "worker", 0, 3 * GB, 1, 0)
        st = svc.pool_status()
        assert st["primary_dimension"] == "memory_bytes"
        q = st["queues"]["prod"]
        assert q["share_capacity"] == int(0.75 * 4 * GB)
        assert q["used"] == 3 * GB
        svc.stop()

    def test_waiting_age_survives_pool_restart(self, tmp_path):
        """Satellite: journal replay must not reset wait_since — a pool
        restart used to silently restart every waiter's reclaim grace."""
        journal = str(tmp_path / "pool.jsonl")
        svc = make_pool(journal_path=journal)
        register_cpu_node(svc, "n0")
        svc.register_app("busy", memory_bytes=3 * GB, vcores=1)
        svc.allocate("busy", "worker", 0, 3 * GB, 1, 0)
        svc.register_app("waiter", memory_bytes=3 * GB, vcores=1)
        svc.allocate("waiter", "worker", 0, 3 * GB, 1, 0)  # queued
        time.sleep(0.4)
        age_before = svc.pool_status()["queues"]["default"]["waiting"][0]["waiting_s"]
        assert age_before >= 0.4
        svc.stop()
        svc2 = make_pool(journal_path=journal)
        register_cpu_node(svc2, "n0")
        waiting = svc2.pool_status()["queues"]["default"]["waiting"]
        assert [w["app_id"] for w in waiting] == ["waiter"]
        # the age carried across the restart (>= what it was, not reset to 0)
        assert waiting[0]["waiting_s"] >= age_before
        svc2.stop()

    def test_drain_deadline_survives_pool_restart(self, tmp_path):
        journal = str(tmp_path / "pool.jsonl")
        svc = make_pool(preemption=True, preemption_drain_ms=60_000,
                        journal_path=journal)
        register_cpu_node(svc, "n0")
        svc.register_app("victim", memory_bytes=3 * GB, vcores=1)
        svc.allocate("victim", "worker", 0, 3 * GB, 1, 0)
        svc.register_app("agg", priority=5, memory_bytes=3 * GB, vcores=1)
        req = svc.poll_exited("victim", with_preempt=True)["preempt"]["req_id"]
        svc.stop()
        svc2 = make_pool(preemption=True, preemption_drain_ms=60_000,
                         journal_path=journal)
        assert svc2.pool_status()["drains_active"] == 1
        notice = svc2.poll_exited("victim", with_preempt=True)["preempt"]
        assert notice["req_id"] == req and notice["deadline_ms"] <= 60_000
        svc2.stop()


# ---------------------------------------------------------------------------
# Goodput: the drain window is classified, not lumped into `other`
# ---------------------------------------------------------------------------
def ev(t, ms, **payload):
    return Event(EventType(t), payload, ms)


class TestGoodputDrainPhase:
    def test_drain_window_classified(self):
        events = [
            ev("APPLICATION_INITED", 0),
            ev("TASK_REGISTERED", 100, task="w:0"),
            ev("GANG_COMPLETE", 200),
            ev("PREEMPTION_REQUESTED", 1000, req_id="p1", mode="drain"),
            ev("PREEMPTION_YIELDED", 2500, req_id="p1", cooperative=True),
            ev("HEARTBEAT_LOST", 2500, reason="gang restart: preempted"),
            ev("TASK_REGISTERED", 2600, task="w:0"),
            ev("GANG_COMPLETE", 2700),
            ev("TASK_FINISHED", 5000, task="w:0", exit_code=0),
            ev("APPLICATION_FINISHED", 5100, status="SUCCEEDED"),
        ]
        led = obs_goodput.build_ledger("a", events)
        assert led.phases_ms.get("preempt_drain", 0) == 1500
        assert sum(led.phases_ms.values()) == led.wall_ms  # exact partition

    def test_escalated_window_ends_at_escalation(self):
        events = [
            ev("APPLICATION_INITED", 0),
            ev("GANG_COMPLETE", 100),
            ev("PREEMPTION_REQUESTED", 1000, req_id="p1", mode="drain"),
            ev("PREEMPTION_ESCALATED", 4000, req_id="p1"),
            ev("HEARTBEAT_LOST", 4100, reason="gang restart: preempted"),
            ev("GANG_COMPLETE", 4200),
            ev("TASK_FINISHED", 6000, task="w:0", exit_code=0),
            ev("APPLICATION_FINISHED", 6100, status="SUCCEEDED"),
        ]
        led = obs_goodput.build_ledger("a", events)
        assert led.phases_ms.get("preempt_drain", 0) == 3000
        assert sum(led.phases_ms.values()) == led.wall_ms

    def test_cancelled_window_closes_at_cancellation(self):
        """A pool-cancelled drain must not classify the rest of the run as
        preempt_drain: PREEMPTION_CANCELLED terminates the window."""
        events = [
            ev("APPLICATION_INITED", 0),
            ev("GANG_COMPLETE", 100),
            ev("PREEMPTION_REQUESTED", 1000, req_id="p1", mode="drain"),
            ev("PREEMPTION_CANCELLED", 1800, req_id="p1"),
            ev("TASK_FINISHED", 60_000, task="w:0", exit_code=0),
            ev("APPLICATION_FINISHED", 60_100, status="SUCCEEDED"),
        ]
        led = obs_goodput.build_ledger("a", events)
        assert led.phases_ms.get("preempt_drain", 0) == 800
        assert led.phases_ms.get("productive", 0) > 50_000
        assert sum(led.phases_ms.values()) == led.wall_ms

    def test_no_drain_events_no_phase(self):
        events = [
            ev("APPLICATION_INITED", 0),
            ev("GANG_COMPLETE", 100),
            ev("TASK_FINISHED", 2000, task="w:0", exit_code=0),
            ev("APPLICATION_FINISHED", 2100, status="SUCCEEDED"),
        ]
        led = obs_goodput.build_ledger("a", events)
        assert led.phases_ms.get("preempt_drain", 0) == 0


class TestDrainSurfaces:
    def test_trace_summary_prints_drain_episodes(self):
        from tony_tpu.cli.trace import summarize

        spans = [
            {"name": "am.run", "identity": "am", "trace_id": "t",
             "start_ms": 0, "end_ms": 10_000},
            {"name": "am.preempt_drain", "identity": "am", "trace_id": "t",
             "start_ms": 2000, "end_ms": 3500,
             "attrs": {"mode": "drain", "cooperative": True}},
        ]
        out = summarize(spans)
        assert "preemption drains" in out and "1 episode(s)" in out
        assert "drain" in out

    def test_portal_share_bar_renders_over_guarantee_in_red(self):
        from tony_tpu.portal.server import _share_bar

        under = _share_bar({"share_capacity": 4 * GB, "used": 2 * GB})
        assert "50%" in under and "#e33" not in under
        over = _share_bar({"share_capacity": 2 * GB, "used": 4 * GB})
        assert "200%" in over and "#e33" in over
        assert _share_bar({"share_capacity": 0, "used": 0}) == "—"


# ---------------------------------------------------------------------------
# Courier + urgent-save signal over real files
# ---------------------------------------------------------------------------
class TestDrainRelay:
    def test_urgent_signal_roundtrip(self, tmp_path, monkeypatch):
        metrics = str(tmp_path / "m.json")
        monkeypatch.setenv("TONY_TRAIN_METRICS_FILE", metrics)
        monkeypatch.setenv("TONY_PROFILE_POLL_MS", "50")
        from tony_tpu.train.checkpoint import UrgentSaveSignal

        sig = UrgentSaveSignal()
        assert sig.poll() is None  # idle: nothing to do
        with open(metrics + ".drain", "w") as f:
            json.dump({"req_id": "r1"}, f)
        time.sleep(0.06)
        assert sig.poll() == "r1"
        time.sleep(0.06)
        assert sig.poll() is None  # dedup: handled once
        sig.acknowledge("r1", 7)
        done = json.load(open(metrics + ".drain.done"))
        assert done == {"req_id": "r1", "step": 7}

    def test_courier_writes_control_and_reports_done_once(self, tmp_path):
        from tony_tpu.obs.introspect import DrainCourier

        metrics = str(tmp_path / "m.json")
        reports = []
        courier = DrainCourier(lambda **kw: reports.append(kw))
        courier.handle({"req_id": "r9"}, metrics)
        ctl = json.load(open(metrics + ".drain"))
        assert ctl == {"req_id": "r9"}
        assert reports == []  # no done file yet
        with open(metrics + ".drain.done", "w") as f:
            json.dump({"req_id": "r9", "step": 12}, f)
        courier.handle(None, metrics)
        courier.handle({"req_id": "r9"}, metrics)  # redelivery: idempotent
        assert reports == [{"req_id": "r9", "step": 12}]

    def test_courier_retries_report_on_rpc_failure(self, tmp_path):
        from tony_tpu.obs.introspect import DrainCourier

        metrics = str(tmp_path / "m.json")
        calls = []

        def flaky(**kw):
            calls.append(kw)
            if len(calls) == 1:
                raise OSError("am unreachable")

        courier = DrainCourier(flaky)
        courier.handle({"req_id": "r2"}, metrics)
        with open(metrics + ".drain.done", "w") as f:
            json.dump({"req_id": "r2", "step": 3}, f)
        with pytest.raises(OSError):
            courier.handle(None, metrics)
        courier.handle(None, metrics)  # retried on the next beat
        assert len(calls) == 2


# ---------------------------------------------------------------------------
# Headline E2E: drain beats kill; shrink beats whole-gang eviction
# ---------------------------------------------------------------------------
def fixture_cmd(name, *args):
    return " ".join([sys.executable, os.path.join(FIXTURES, name), *map(str, args)])


PREEMPT_CONF = {
    keys.TASK_METRICS_INTERVAL_MS: "200",    # dense METRICS_SNAPSHOTs: the
    keys.PROFILE_POLL_INTERVAL_MS: "100",    # rework derivation reads them
    keys.GOODPUT_INTERVAL_MS: "60000",       # keep the tick out of the way
}


def wait_for(cond, what, timeout=120):
    """`cond` polled until it holds. The deadline is sized for the driver's six busy
    workers: a submitted job's child takes 10 s to its third step alone and, by
    the builders' runs, more than the 45 s this gave it under them (the one way
    `test_drain_checkpoints_then_yields_and_beats_the_kill_path` has failed)."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.05)
    pytest.fail(f"timed out waiting for {what}")


def read_step(path, default=-1):
    try:
        with open(path) as f:
            return int(json.load(f)["step"])
    except (OSError, ValueError, KeyError):
        return default


def finished_events(tmp_tony_root, app_id):
    from tony_tpu.cluster import history

    return history.read_events(os.path.join(str(tmp_tony_root), "history"), app_id)


def run_preemption_scenario(tmp_tony_root, tmp_path, drain_ms):
    """Two queues under pool pressure: a dev victim gang borrows the pool, a
    prod arrival reclaims it. Returns (victim_events, resume_step, verdicts)."""
    svc = PoolService(
        heartbeat_interval_ms=100, max_missed_heartbeats=4, secret=SECRET,
        preemption=True, preemption_drain_ms=drain_ms,
        queues={"prod": 0.5, "dev": 0.5},
    )
    svc.start()
    agent = spawn_agent(svc.address, "solo", str(tmp_path))
    try:
        wait_for(lambda: any(n.alive for n in svc._nodes.values()),
                 "agent registration", 15)
        shared = tmp_path / f"shared-{drain_ms}"
        h1, t1, r1 = submit_async(tmp_tony_root, {
            **FAST, **PREEMPT_CONF,
            keys.TPU_POOL_SPEC: "rm:%s:%d" % svc.address,
            keys.TPU_POOL_SECRET: SECRET,
            keys.APPLICATION_QUEUE: "dev",
            "tony.worker.instances": "1", "tony.worker.memory": "3g",
            keys.EXECUTES: fixture_cmd("preempt_train.py", shared, 12, 150),
        })
        # victim running and past step 3 before the aggressor arrives
        wait_for(lambda: read_step(shared / "step-r0.json") >= 3,
                 "victim to make progress")
        quick = tmp_path / f"prod-{drain_ms}.py"
        quick.write_text("import time; time.sleep(1)\n")
        h2, t2, r2 = submit_async(tmp_tony_root, {
            **FAST,
            keys.TPU_POOL_SPEC: "rm:%s:%d" % svc.address,
            keys.TPU_POOL_SECRET: SECRET,
            keys.APPLICATION_QUEUE: "prod",
            "tony.worker.instances": "1", "tony.worker.memory": "2g",
            keys.EXECUTES: f"{sys.executable} {quick}",
        })
        t2.join(timeout=90)
        t1.join(timeout=90)
        assert r2.get("final") == JobStatus.SUCCEEDED, h2.final_status()
        assert r1.get("final") == JobStatus.SUCCEEDED, h1.final_status()
        events = finished_events(tmp_tony_root, h1.app_id)
        resume = read_step(shared / "resume-1.json")
        return events, resume, h1.app_id
    finally:
        if agent.poll() is None:
            agent.terminate()
        try:
            agent.wait(timeout=5)
        except Exception:
            agent.kill()
        svc.stop()


@pytest.mark.e2e
class TestPreemptionE2E:
    def test_drain_checkpoints_then_yields_and_beats_the_kill_path(
        self, tmp_tony_root, tmp_path
    ):
        """The headline: with a generous drain window the victim
        urgent-checkpoints through the real CheckpointManager and yields —
        it resumes from that checkpoint and its measured restart_rework is
        strictly smaller than the kill-path control run's."""
        drain_before = counter_value("tony_pool_preemptions_total", mode="drain")
        events_d, resume_d, app_d = run_preemption_scenario(
            tmp_tony_root, tmp_path, drain_ms=15_000)
        # cooperative: the victim checkpointed BEFORE dying and resumed there
        types = [e.type.value for e in events_d]
        assert "PREEMPTION_REQUESTED" in types and "PREEMPTION_YIELDED" in types
        assert "PREEMPTION_ESCALATED" not in types
        yielded = next(e for e in events_d if e.type.value == "PREEMPTION_YIELDED")
        assert yielded.payload.get("cooperative") is True
        saved = yielded.payload.get("saved_steps") or {}
        assert resume_d > 0 and saved.get("worker:0") == resume_d
        assert counter_value(
            "tony_pool_preemptions_total", mode="drain") == drain_before + 1

        # control run: drain-ms 0 → classic kill, resume from nothing
        events_k, resume_k, app_k = run_preemption_scenario(
            tmp_tony_root, tmp_path, drain_ms=0)
        assert resume_k == 0
        assert "PREEMPTION_REQUESTED" not in [e.type.value for e in events_k]

        led_d = obs_goodput.build_ledger(app_d, events_d)
        led_k = obs_goodput.build_ledger(app_k, events_k)
        # the drain window is classified (not `other`) and the cooperative
        # run's rework is strictly below the kill run's
        assert led_d.phases_ms.get("preempt_drain", 0) > 0
        rework_d = led_d.phases_ms.get("restart_rework", 0)
        rework_k = led_k.phases_ms.get("restart_rework", 0)
        assert rework_k > rework_d, (rework_k, rework_d)
        # exact partition still holds with the new phase in play
        assert sum(led_d.phases_ms.values()) == led_d.wall_ms
        assert sum(led_k.phases_ms.values()) == led_k.wall_ms

    @pytest.mark.slow
    def test_elastic_victim_sheds_workers_instead_of_dying(
        self, tmp_tony_root, tmp_path
    ):
        """Partial reclaim: a 2-worker elastic dev gang sheds one worker
        (divisor rebuild, resumed from the urgent checkpoint) for a prod
        arrival — no whole-gang eviction, no re-queue."""
        svc = PoolService(
            heartbeat_interval_ms=100, max_missed_heartbeats=4, secret=SECRET,
            preemption=True, preemption_drain_ms=15_000,
            queues={"prod": 0.5, "dev": 0.5},
        )
        svc.start()
        agent = spawn_agent(svc.address, "solo", str(tmp_path))
        shrink_before = counter_value("tony_pool_preemptions_total", mode="shrink")
        try:
            wait_for(lambda: any(n.alive for n in svc._nodes.values()),
                     "agent registration", 15)
            shared = tmp_path / "shared-shrink"
            h1, t1, r1 = submit_async(tmp_tony_root, {
                **FAST, **PREEMPT_CONF,
                keys.TPU_POOL_SPEC: "rm:%s:%d" % svc.address,
                keys.TPU_POOL_SECRET: SECRET,
                keys.APPLICATION_QUEUE: "dev",
                "tony.worker.instances": "2", "tony.worker.memory": "2g",
                keys.ELASTIC_MIN_WORKERS: "1",
                keys.ELASTIC_SHRINK_ON_PREEMPT: "true",
                keys.EXECUTES: fixture_cmd("preempt_train.py", shared, 12, 150),
            })
            wait_for(lambda: read_step(shared / "step-r0.json") >= 3,
                     "victim to make progress")
            quick = tmp_path / "prod-shrink.py"
            quick.write_text("import time; time.sleep(1)\n")
            h2, t2, r2 = submit_async(tmp_tony_root, {
                **FAST,
                keys.TPU_POOL_SPEC: "rm:%s:%d" % svc.address,
                keys.TPU_POOL_SECRET: SECRET,
                keys.APPLICATION_QUEUE: "prod",
                "tony.worker.instances": "1", "tony.worker.memory": "2g",
                keys.EXECUTES: f"{sys.executable} {quick}",
            })
            t2.join(timeout=90)
            t1.join(timeout=90)
            assert r2.get("final") == JobStatus.SUCCEEDED, h2.final_status()
            assert r1.get("final") == JobStatus.SUCCEEDED, h1.final_status()
            events = finished_events(tmp_tony_root, h1.app_id)
            types = [e.type.value for e in events]
            req = next(e for e in events if e.type.value == "PREEMPTION_REQUESTED")
            assert req.payload.get("mode") == "shrink"
            assert req.payload.get("resize") == {"worker": 1}
            assert "PREEMPTION_YIELDED" in types
            assert "PREEMPTION_ESCALATED" not in types
            resized = [
                e for e in events
                if e.type.value == "GANG_RESIZED" and not e.payload.get("rejected")
            ]
            assert resized and resized[-1].payload["trigger"] == "preempt"
            assert resized[-1].payload["instances"].get("worker") == 1
            # resumed from the urgent checkpoint at the smaller world size
            assert read_step(shared / "resume-1.json") > 0
            assert counter_value(
                "tony_pool_preemptions_total", mode="shrink") == shrink_before + 1
        finally:
            if agent.poll() is None:
                agent.terminate()
            try:
                agent.wait(timeout=5)
            except Exception:
                agent.kill()
            svc.stop()


# ---------------------------------------------------------------------------
# Slow soak: pool-pressure scenario through `tony chaos --expect-preempt-drain`
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.e2e
class TestPoolPressureSoak:
    def test_chaos_expect_preempt_drain_under_pool_pressure(
        self, tmp_tony_root, tmp_path, monkeypatch, capsys
    ):
        """`tony chaos` drives the victim under a benign rpc-noise schedule
        while a prod job reclaims the pool: the run must show a cooperative
        drain (victim checkpointed before dying, nothing escalated)."""
        from tony_tpu.cli.chaos import main as chaos_main

        svc = PoolService(
            heartbeat_interval_ms=100, max_missed_heartbeats=4, secret=SECRET,
            preemption=True, preemption_drain_ms=20_000,
            queues={"prod": 0.5, "dev": 0.5},
        )
        svc.start()
        agent = spawn_agent(svc.address, "solo", str(tmp_path))
        try:
            wait_for(lambda: any(n.alive for n in svc._nodes.values()),
                     "agent registration", 15)
            shared = tmp_path / "soak-shared"

            def aggressor():
                wait_for(lambda: read_step(shared / "step-r0.json") >= 3,
                         "victim progress", 60)
                quick = tmp_path / "soak-prod.py"
                quick.write_text("import time; time.sleep(1)\n")
                h, t, r = submit_async(tmp_tony_root, {
                    **FAST,
                    keys.TPU_POOL_SPEC: "rm:%s:%d" % svc.address,
                    keys.TPU_POOL_SECRET: SECRET,
                    keys.APPLICATION_QUEUE: "prod",
                    "tony.worker.instances": "1", "tony.worker.memory": "2g",
                    keys.EXECUTES: f"{sys.executable} {quick}",
                })
                t.join(timeout=120)

            monkeypatch.setenv("TONY_ROOT", str(tmp_tony_root))
            th = threading.Thread(target=aggressor, daemon=True)
            th.start()
            rc = chaos_main([
                "--spec", "rpc-delay:p=0.05",
                "--seed", "3",
                "--executes", fixture_cmd("preempt_train.py", shared, 12, 150),
                "--conf", f"{keys.TPU_POOL_SPEC}=rm:%s:%d" % svc.address,
                "--conf", f"{keys.TPU_POOL_SECRET}={SECRET}",
                "--conf", f"{keys.APPLICATION_QUEUE}=dev",
                "--conf", "tony.worker.instances=1",
                "--conf", "tony.worker.memory=3g",
                "--conf", f"{keys.TASK_METRICS_INTERVAL_MS}=200",
                "--conf", f"{keys.PROFILE_POLL_INTERVAL_MS}=100",
                "--conf", f"{keys.AM_MONITOR_INTERVAL_MS}=50",
                "--expect-preempt-drain",
            ])
            th.join(timeout=120)
            out = capsys.readouterr().out
            assert rc == 0, out
            assert "pool preemptions: 1 requested, 1 yielded, 0 escalated" in out
        finally:
            if agent.poll() is None:
                agent.terminate()
            try:
                agent.wait(timeout=5)
            except Exception:
                agent.kill()
            svc.stop()
