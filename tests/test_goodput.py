"""Goodput accounting, straggler detection, and the alert engine.

Covers the phase ledger's exact-partition contract (unit + randomized
property test), restart-rework and resize attribution, the straggler
detector's streak/median semantics, the alert engine's edge-triggered
transitions + sink, the history-store goodput columns and finalized-job
alert evaluation, the `tony goodput` CLI, and the headline e2e: a fixture
gang under chaos (one gang restart + one elastic resize) whose `tony
goodput` report partitions wall-time exactly, attributes the restart's lost
work to ``restart_rework``, flags the injected slow rank as a straggler,
and fires + resolves a configured goodput alert visible in portal
``/alerts``, the event stream, and the history store.
"""

import json
import os
import random
import threading
import time
import urllib.request

import pytest

from tony_tpu.cluster.events import Event, EventType
from tony_tpu.config import TonyConfig, keys
from tony_tpu.obs import alerts as obs_alerts
from tony_tpu.obs import goodput as obs_goodput

pytestmark = [pytest.mark.goodput]


def ev(t, ts, **payload):
    return Event(EventType(t), payload, ts)


def snap(ts, **task_steps):
    return ev("METRICS_SNAPSHOT", ts, tasks=[
        {"task": task, "metrics": {"train": {"step": step}}}
        for task, step in task_steps.items()
    ])


def assert_exact(ledger):
    """THE invariant: phases are non-overlapping and sum to wall-time."""
    assert sum(ledger.phases_ms.values()) == ledger.wall_ms
    covered = 0
    prev_end = ledger.t0_ms
    for phase, start, end in ledger.episodes:
        assert start == prev_end, "episodes must tile [t0, t1] with no gaps"
        assert end > start
        assert phase in obs_goodput.PHASE_ORDER
        covered += end - start
        prev_end = end
    if ledger.episodes:
        assert prev_end == ledger.t1_ms
    assert covered == ledger.wall_ms


# ---------------------------------------------------------------------------
# ledger units
# ---------------------------------------------------------------------------
class TestLedger:
    def test_simple_lifecycle(self):
        events = [
            ev("APPLICATION_INITED", 1000),
            ev("QUEUE_WAIT", 1000, state="waiting"),
            ev("QUEUE_WAIT", 3000, state="admitted"),
            ev("TASK_STARTED", 3100, task="worker:0"),
            ev("TASK_REGISTERED", 3500, task="worker:0"),
            ev("GANG_COMPLETE", 4000, tasks=1),
            snap(6000, **{"worker:0": 3}),
            ev("TASK_FINISHED", 9000, task="worker:0", exit_code=0),
            ev("APPLICATION_FINISHED", 9500, status="SUCCEEDED"),
        ]
        led = obs_goodput.build_ledger("a", events)
        assert_exact(led)
        assert not led.live
        assert led.phases_ms["queue_wait"] == 2000
        assert led.phases_ms["startup"] == 500       # 3000→3500 (reg takes over)
        assert led.phases_ms["registration"] == 500  # 3500→4000
        assert led.phases_ms["compile"] == 2000      # gang → first step evidence
        assert led.phases_ms["productive"] == 3000   # 6000→9000
        assert led.phases_ms["drain"] == 500
        assert 0 < led.goodput_fraction < 1

    def test_live_requires_now(self):
        events = [ev("APPLICATION_INITED", 1000), ev("GANG_COMPLETE", 2000)]
        with pytest.raises(ValueError, match="now_ms"):
            obs_goodput.build_ledger("a", events)
        led = obs_goodput.build_ledger("a", events, now_ms=5000)
        assert led.live and led.t1_ms == 5000
        assert_exact(led)
        # no step evidence: everything after the barrier counts productive
        assert led.phases_ms["productive"] == 3000

    def test_unterminated_queue_wait_runs_to_now(self):
        events = [ev("QUEUE_WAIT", 1000, state="waiting")]
        led = obs_goodput.build_ledger("a", events, now_ms=4000)
        assert_exact(led)
        assert led.phases_ms["queue_wait"] == 3000

    def test_restart_rework_attribution(self):
        events = [
            ev("APPLICATION_INITED", 100),  # ts 0 would be re-stamped to now
            ev("GANG_COMPLETE", 1000),
            snap(2000, **{"worker:0": 2}),
            snap(4000, **{"worker:0": 4}),   # last checkpoint was at step 3
            snap(6000, **{"worker:0": 6}),
            ev("HEARTBEAT_LOST", 7000, reason="gang restart: task worker:1 LOST"),
            ev("GANG_COMPLETE", 8000),
            snap(9000, **{"worker:0": 4}),   # resumed from ckpt step 3 → step 4
            ev("APPLICATION_FINISHED", 12000, status="SUCCEEDED"),
        ]
        led = obs_goodput.build_ledger("a", events)
        assert_exact(led)
        # steps (3, 6] were lost: first reached step>=4 at ts 4000, died 7000
        assert led.phases_ms["restart_rework"] == 3000
        assert led.restarts == 1

    def test_restart_without_step_evidence_has_no_rework(self):
        events = [
            ev("GANG_COMPLETE", 1000),
            ev("HEARTBEAT_LOST", 4000, reason="gang restart: worker:0 FAILED"),
            ev("GANG_COMPLETE", 5000),
            ev("APPLICATION_FINISHED", 8000, status="SUCCEEDED"),
        ]
        led = obs_goodput.build_ledger("a", events)
        assert_exact(led)
        assert "restart_rework" not in led.phases_ms

    def test_lost_task_heartbeat_is_not_a_restart_marker(self):
        events = [
            ev("GANG_COMPLETE", 1000),
            ev("HEARTBEAT_LOST", 3000, task="worker:1"),  # task lost, no restart
            ev("APPLICATION_FINISHED", 5000, status="FAILED"),
        ]
        led = obs_goodput.build_ledger("a", events)
        assert led.restarts == 0
        assert_exact(led)

    def test_resize_episode(self):
        events = [
            ev("GANG_COMPLETE", 1000),
            snap(2000, **{"worker:0": 5}),
            ev("GANG_RESIZED", 3000, resized={"worker": 4}, trigger="rpc"),
            ev("HEARTBEAT_LOST", 3000, reason="gang restart: resize worker: 2→4"),
            ev("GANG_COMPLETE", 5000),
            ev("APPLICATION_FINISHED", 9000, status="SUCCEEDED"),
        ]
        led = obs_goodput.build_ledger("a", events)
        assert_exact(led)
        assert led.phases_ms["resize"] == 2000
        assert led.resizes == 1

    def test_rejected_resize_claims_nothing(self):
        events = [
            ev("GANG_COMPLETE", 1000),
            ev("GANG_RESIZED", 2000, rejected=True, resized={"worker": 9}),
            ev("APPLICATION_FINISHED", 5000, status="SUCCEEDED"),
        ]
        led = obs_goodput.build_ledger("a", events)
        assert led.resizes == 0 and "resize" not in led.phases_ms

    def test_checkpoint_and_takeover_spans(self):
        events = [
            ev("GANG_COMPLETE", 1000),
            snap(1500, **{"worker:0": 1}),
            ev("AM_TAKEOVER", 6000, am_attempt=1),
            ev("APPLICATION_FINISHED", 10000, status="SUCCEEDED"),
        ]
        spans = [
            {"name": "ckpt.save", "start_ms": 3000.0, "end_ms": 3800.0},
            {"name": "am.takeover", "start_ms": 5500.0, "end_ms": 6000.0},
            {"name": "train.first_step", "start_ms": 1000.0, "end_ms": 1300.0},
        ]
        led = obs_goodput.build_ledger("a", events, spans)
        assert_exact(led)
        assert led.phases_ms["checkpoint"] == 800
        assert led.phases_ms["takeover"] == 500
        # the traced first-step span beats the snapshot estimate
        assert led.phases_ms["compile"] == 300
        assert led.takeovers == 1

    def test_window_fraction_recovers(self):
        events = [
            ev("GANG_COMPLETE", 100),
            snap(1000, **{"worker:0": 1}),
            ev("APPLICATION_FINISHED", 10_000, status="SUCCEEDED"),
        ]
        led = obs_goodput.build_ledger("a", events)
        # trailing 2s of a run whose tail is all productive
        assert led.window_fraction(2000) == 1.0
        assert led.window_fraction(100_000) == led.goodput_fraction

    def test_empty_events(self):
        led = obs_goodput.build_ledger("a", [], now_ms=123)
        assert led.wall_ms == 0 and led.goodput_fraction == 0.0

    def test_step_time_and_skew_by_task(self):
        events = [
            snap(0, **{"worker:0": 0, "worker:1": 0, "worker:2": 0}),
            snap(1000, **{"worker:0": 10, "worker:1": 10, "worker:2": 2}),
            snap(2000, **{"worker:0": 20, "worker:1": 20, "worker:2": 4}),
        ]
        times = obs_goodput.step_time_by_task(events)
        assert times["worker:0"] == pytest.approx(100.0)
        assert times["worker:2"] == pytest.approx(500.0)
        led = obs_goodput.build_ledger(
            "a", events + [ev("APPLICATION_FINISHED", 3000, status="SUCCEEDED")])
        skew = led.skew_by_task()
        assert skew["worker:2"] == pytest.approx(5.0)
        assert skew["worker:0"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# satellite: randomized-history property test — the partition is EXACT
# ---------------------------------------------------------------------------
class TestPartitionProperty:
    def _random_history(self, rng, stamps=False):
        """A randomized event/span history with restarts, resizes,
        takeovers, queue waits, snapshots — including degenerate orderings
        a torn stream can produce. With ``stamps`` it also carries the
        start-up account: a client's ``submitted_ms`` (before or after the
        AM's first event), TASK_STARTUP_STAMPS of either kind with any subset
        of the stamps missing and their times out of order, and URL
        registrations. Without, it draws exactly what the parent's did."""
        t = rng.randrange(0, 10_000)
        events, spans = [], []
        step = 0
        if stamps and rng.random() < 0.6:
            events.append(ev("APPLICATION_INITED", t + 1, **(
                {"submitted_ms": rng.choice([t - rng.randrange(0, 3000), t + rng.randrange(0, 500), "x", None])}
                if rng.random() < 0.8 else {})))
        for _ in range(rng.randrange(1, 40)):
            t += rng.randrange(0, 2000)
            kind = rng.randrange(13 if stamps else 10)
            if kind == 0:
                events.append(ev("QUEUE_WAIT", t,
                                 state=rng.choice(["waiting", "admitted"])))
            elif kind == 1:
                events.append(ev("GANG_COMPLETE", t))
            elif kind == 2:
                events.append(ev("HEARTBEAT_LOST", t,
                                 reason="gang restart: chaos"))
                step = max(step - rng.randrange(0, 5), 0)  # resumed earlier
            elif kind == 3:
                events.append(ev("GANG_RESIZED", t,
                                 resized={"worker": rng.randrange(1, 8)},
                                 rejected=rng.random() < 0.2))
            elif kind == 4:
                events.append(ev("AM_TAKEOVER", t, am_attempt=1))
            elif kind == 5:
                events.append(ev("TASK_REGISTERED", t, task="worker:0"))
            elif kind == 6:
                events.append(ev("TASK_FINISHED", t, task="worker:0"))
            elif kind == 7:
                s0 = t - rng.randrange(0, 3000)
                name = rng.choice(
                    ["ckpt.save", "am.takeover", "train.first_step", "other.span"])
                spans.append({"name": name, "start_ms": float(s0),
                              "end_ms": float(s0 + rng.randrange(0, 2500))})
            elif kind in (10, 11):
                names = ["child_spawned", "main_entered", "devices_ready", "weights_ready",
                         "first_step_done", "registered"]
                taken = {n: t - rng.randrange(-500, 6000)  # any order, some in the future
                         for n in names if rng.random() < 0.7}
                if rng.random() < 0.1:
                    taken["devices_ready"] = rng.choice(["soon", None, float("nan"), True])
                events.append(ev("TASK_STARTUP_STAMPS", t, task=f"worker:{rng.randrange(2)}",
                                 attempt=rng.randrange(2), stamps=taken,
                                 kind=rng.choice(["train", "serve", None])))
            elif kind == 12:
                events.append(ev("TASK_URL_REGISTERED", t, task=f"worker:{rng.randrange(2)}", url="u"))
            else:
                step += rng.randrange(0, 4)
                events.append(snap(t, **{
                    f"worker:{i}": max(step - rng.randrange(0, 3), 0)
                    for i in range(rng.randrange(1, 4))
                }))
        if rng.random() < 0.7:
            t += rng.randrange(0, 1500)
            events.append(ev("APPLICATION_FINISHED", t, status="SUCCEEDED"))
        return events, spans, t + rng.randrange(0, 5000)

    @pytest.mark.parametrize("stamps", [False, True], ids=["stampless", "stamped"])
    def test_partition_is_exact_over_random_histories(self, stamps):
        claimed = set()
        for seed in range(300):
            rng = random.Random(seed)
            events, spans, now = self._random_history(rng, stamps=stamps)
            led = obs_goodput.build_ledger("r", events, spans, now_ms=now)
            claimed |= set(led.phases_ms)
            try:
                assert_exact(led)
                assert all(v >= 0 for v in led.phases_ms.values())
                assert 0.0 <= led.goodput_fraction <= 1.0
                for w in (1, 1000, 10_000_000):
                    assert 0.0 <= led.window_fraction(w) <= 1.0
            except AssertionError as e:  # pragma: no cover - diagnostics
                raise AssertionError(f"seed {seed}: {e}") from e
        # the generator reaches what it is there for: every start-up stage, or none of them
        new = {"submit", "runtime_init", "weights", "warmup"}
        assert (claimed & new) == (new if stamps else set())

    def test_a_history_without_stamps_partitions_as_the_parent_did(self):
        """Digest of phases and episodes over the 300 stampless histories,
        taken with the parent commit's obs/goodput.py (3966ea8) on the very
        same draws: the stages claim nothing a `.jhist` does not stamp."""
        import hashlib

        h = hashlib.sha256()
        for seed in range(300):
            events, spans, now = self._random_history(random.Random(seed))
            led = obs_goodput.build_ledger("r", events, spans, now_ms=now)
            assert led.stamps == {}
            h.update(json.dumps([seed, sorted(led.phases_ms.items()), led.episodes]).encode())
        assert h.hexdigest() == "70765193ba4a669a6851bb7b13e752e4b338f9bf14ee97e484c1f43976283e03"

    def test_shuffled_span_order_is_irrelevant(self):
        rng = random.Random(42)
        events, spans, now = self._random_history(rng, stamps=True)
        led1 = obs_goodput.build_ledger("r", events, spans, now_ms=now)
        rng.shuffle(spans)
        led2 = obs_goodput.build_ledger("r", events, spans, now_ms=now)
        assert led1.phases_ms == led2.phases_ms


# ---------------------------------------------------------------------------
# the start-up account: stages claimed from the stamps the .jhist carries
# ---------------------------------------------------------------------------
def stamps_ev(ts, task, kind, attempt=0, **taken):
    return ev("TASK_STARTUP_STAMPS", ts, task=task, attempt=attempt, kind=kind, stamps=taken)


def serving_history(stamped=True, replicas=1):
    """`tony serve`: GANG_COMPLETE at 2000, replica i ready at 9000 + 1000 i."""
    events = [
        ev("APPLICATION_INITED", 1000, **({"submitted_ms": 400} if stamped else {})),
        ev("TASK_REGISTERED", 1900, task="serve:0"),
        ev("GANG_COMPLETE", 2000, tasks=replicas),
    ]
    for i in range(replicas):
        events.append(ev("TASK_URL_REGISTERED", 9000 + 1000 * i, task=f"serve:{i}", url="http://x"))
        if stamped:
            events.append(stamps_ev(
                11500 + i, f"serve:{i}", "serve", child_spawned=2150, main_entered=4000,
                devices_ready=6000 + 1000 * i, weights_ready=8500 + 1000 * i, registered=9001 + 1000 * i))
    events += [snap(12000, **{"serve:0": 1}), snap(14000, **{"serve:0": 2}),
               ev("APPLICATION_FINISHED", 20000, status="KILLED")]
    return events


class TestStartupStages:
    @pytest.mark.parametrize("case,phases", [
        # GANG_COMPLETE -> ready is runtime_init + weights + warmup to the ms, none of it productive
        ("serving", {"submit": 600, "startup": 900, "registration": 100, "runtime_init": 4000,
                     "weights": 2500, "warmup": 500, "productive": 11000}),
        # the earlier stage wins among replicas: productive starts when the LAST one is ready
        ("serving-2-replicas", {"submit": 600, "startup": 900, "registration": 100, "runtime_init": 5000,
                                "weights": 2500, "warmup": 500, "productive": 10000}),
        # what the parent's ledger says of the same job without stamps: the replica's start-up is
        # filed under compile (to the first snapshot with a step) and productive, and t0 is the AM's
        ("serving-stampless", {"startup": 900, "registration": 100, "compile": 10000, "productive": 8000}),
    ])
    def test_a_serving_job_by_stage(self, case, phases):
        led = obs_goodput.build_ledger("s", serving_history(
            stamped="stampless" not in case, replicas=2 if "2" in case else 1))
        assert_exact(led)
        assert led.phases_ms == phases
        if "stampless" in case:
            assert led.t0_ms == 1000 and led.stamps == {}
            return
        assert led.t0_ms == 400 and led.stamps["client"] == {"submitted": 400}
        assert led.stamps["serve:0@2000"]["ready"] == 9000
        last_ready = 10000 if "2" in case else 9000
        assert sum(led.phases_ms[p] for p in ("runtime_init", "weights", "warmup")) == last_ready - 2000
        assert next(start for ph, start, _end in led.episodes if ph == "productive") == last_ready

    def test_a_training_job_by_stage_and_the_estimate_it_replaces(self):
        events = [
            ev("APPLICATION_INITED", 1000, submitted_ms=700),
            ev("GANG_COMPLETE", 2000),
            stamps_ev(7000, "worker:0", "train", child_spawned=2100, main_entered=3000, devices_ready=5000),
            stamps_ev(12000, "worker:0", "train", child_spawned=2100, main_entered=3000, devices_ready=5000,
                      weights_ready=6000, first_step_done=9000),
            snap(15000, **{"worker:0": 10}),
            ev("APPLICATION_FINISHED", 20000, status="SUCCEEDED"),
        ]
        # a traced job's first-step span is not consulted once the epoch is stamped
        spans = [{"name": "train.first_step", "start_ms": 6500.0, "end_ms": 9050.0}]
        led = obs_goodput.build_ledger("t", events, spans)
        assert_exact(led)
        assert led.phases_ms == {"submit": 300, "startup": 1000, "runtime_init": 3000, "weights": 1000,
                                 "compile": 3000, "productive": 11000}
        # the latest report of the task and epoch is the one read
        assert led.stamps["worker:0@2000"]["first_step_done"] == 9000
        stampless = [e for e in events if e.type.value != "TASK_STARTUP_STAMPS"]
        assert obs_goodput.build_ledger("t", stampless).phases_ms["compile"] == 13000  # GC -> first snapshot
        assert obs_goodput.build_ledger("t", stampless, spans).phases_ms["compile"] == 7050  # GC -> span's end

    def test_two_gang_epochs_and_a_restart_between_devices_and_weights(self):
        events = [
            ev("GANG_COMPLETE", 1000),
            stamps_ev(4000, "worker:0", "train", main_entered=2000, devices_ready=3000),
            ev("HEARTBEAT_LOST", 5000, reason="gang restart: worker:0 FAILED"),
            ev("GANG_COMPLETE", 6000),
            stamps_ev(11000, "worker:0", "train", attempt=1, main_entered=6500, devices_ready=7000,
                      weights_ready=8000, first_step_done=10000),
            ev("APPLICATION_FINISHED", 15000, status="SUCCEEDED"),
        ]
        led = obs_goodput.build_ledger("t", events)
        assert_exact(led)
        # epoch 1 died loading its weights: that stage runs to the restart marker, and no compile
        # is estimated from GANG_COMPLETE for an epoch that carries stamps
        assert [e for e in led.episodes if e[1] < 5000] == [
            ("runtime_init", 1000, 3000), ("weights", 3000, 5000)]
        assert led.phases_ms == {"runtime_init": 3000, "weights": 3000, "startup": 1000,
                                 "compile": 2000, "productive": 5000}
        assert set(led.stamps) == {"worker:0@1000", "worker:0@6000"}

    @pytest.mark.parametrize("taken,phases", [
        # a live child still initialising its runtime: the stage is open to now
        ({"main_entered": 2500}, {"runtime_init": 7000}),
        # compiling now
        ({"devices_ready": 3000, "weights_ready": 4000}, {"runtime_init": 2000, "weights": 1000, "compile": 4000}),
        # an edge never stamped though the child got past it is nobody's stage: the filler's
        ({"weights_ready": 4000, "first_step_done": 6000}, {"compile": 2000, "productive": 5000}),
        # stamps out of order claim nothing they cannot: no weights stage runs backwards, and
        # the compile claim lies inside the earlier stage, which wins
        ({"devices_ready": 5000, "weights_ready": 3000, "first_step_done": 4000},
         {"runtime_init": 4000, "productive": 3000}),
    ])
    def test_subsets_and_orders_of_a_live_training_childs_stamps(self, taken, phases):
        events = [ev("GANG_COMPLETE", 1000), stamps_ev(7500, "worker:0", "train", **taken)]
        led = obs_goodput.build_ledger("t", events, now_ms=8000)
        assert_exact(led)
        assert led.phases_ms == phases

    def test_the_new_rows_reach_the_cli_and_json(self, capsys):
        led = obs_goodput.build_ledger("s", serving_history())
        from tony_tpu.cli import goodput as cli_goodput

        text = cli_goodput.render(led, None, [], [], 60000)
        rows = [ln.split()[0] for ln in text.split("phase ledger")[1].split("total")[0].splitlines()[1:] if ln.strip()]
        assert rows == [p for p in obs_goodput.PHASE_ORDER if led.phases_ms.get(p)]
        assert {"submit", "runtime_init", "weights", "warmup"} <= set(rows)
        assert led.to_dict()["stamps"]["serve:0@2000"]["weights_ready"] == 8500
        # the priorities the issue fixes: above the wide claims, below the narrow precise ones
        pr = obs_goodput._PRIORITY
        for new in ("submit", "runtime_init", "weights", "warmup"):
            assert max(pr["startup"], pr["registration"], pr["productive"]) < pr[new] < min(pr["checkpoint"], pr["takeover"])


# ---------------------------------------------------------------------------
# straggler detector
# ---------------------------------------------------------------------------
class TestStragglerDetector:
    @staticmethod
    def feed(det, *ticks):
        out = []
        for stats in ticks:
            out.extend(det.observe(stats))
        return out

    def test_detects_after_streak_and_resolves(self):
        det = obs_goodput.StragglerDetector(factor=2.0, min_checks=2)
        base = {"worker:0": (0, 0.0), "worker:1": (0, 0.0), "worker:2": (0, 0.0)}
        t1 = {"worker:0": (10, 1.0), "worker:1": (10, 1.0), "worker:2": (10, 5.0)}
        t2 = {"worker:0": (20, 2.0), "worker:1": (20, 2.0), "worker:2": (20, 10.0)}
        t3 = {"worker:0": (30, 3.0), "worker:1": (30, 3.0), "worker:2": (30, 15.0)}
        out = self.feed(det, base, t1)
        assert out == []  # one evaluated tick over: streak 1 < min_checks
        out = det.observe(t2)
        assert [(a, t) for a, t, *_ in out] == [("detected", "worker:2")]
        assert det.observe(t3) == []  # already flagged: no re-detection
        assert det.flagged == {"worker:2"}
        assert det.skew["worker:2"] == pytest.approx(5.0)
        # back to normal step times → resolved
        t4 = {"worker:0": (40, 4.0), "worker:1": (40, 4.0), "worker:2": (40, 16.0)}
        out = det.observe(t4)
        assert [(a, t) for a, t, *_ in out] == [("resolved", "worker:2")]
        assert det.flagged == set()

    def test_needs_three_reporting_ranks(self):
        det = obs_goodput.StragglerDetector(factor=1.2, min_checks=1)
        a = {"worker:0": (0, 0.0), "worker:1": (0, 0.0)}
        b = {"worker:0": (10, 1.0), "worker:1": (10, 9.0)}
        assert self.feed(det, a, b) == []
        assert det.flagged == set()

    def test_vanished_flagged_task_resolves(self):
        det = obs_goodput.StragglerDetector(factor=1.5, min_checks=1)
        a = {f"worker:{i}": (0, 0.0) for i in range(3)}
        b = {"worker:0": (10, 1.0), "worker:1": (10, 1.0), "worker:2": (10, 9.0)}
        out = self.feed(det, a, b)
        assert [(x, t) for x, t, *_ in out] == [("detected", "worker:2")]
        # resized away: its row disappears → silent resolve
        c = {"worker:0": (20, 2.0), "worker:1": (20, 2.0)}
        out = det.observe(c)
        assert [(x, t) for x, t, *_ in out] == [("resolved", "worker:2")]

    def test_stalled_rank_lower_bound_detection(self):
        det = obs_goodput.StragglerDetector(factor=2.0, min_checks=1)
        a = {f"worker:{i}": (0, 0.0) for i in range(3)}
        det.observe(a, now_s=0.0)
        b = {"worker:0": (10, 1.0), "worker:1": (10, 1.0), "worker:2": (10, 1.0)}
        assert det.observe(b, now_s=1.0) == []
        # worker:2 stops advancing; 0.15s of silence is only 1.5x the 0.1s
        # median — could just be mid-step, so its state holds
        c = {"worker:0": (20, 2.0), "worker:1": (20, 2.0), "worker:2": (10, 1.0)}
        assert det.observe(c, now_s=1.15) == []
        # 0.85s of silence is a 8.5x lower bound on its step time → detected
        d = {"worker:0": (30, 3.0), "worker:1": (30, 3.0), "worker:2": (10, 1.0)}
        out = det.observe(d, now_s=2.0)
        assert [(x, t) for x, t, *_ in out] == [("detected", "worker:2")]
        # stepping again at normal speed → resolved
        e = {"worker:0": (40, 4.0), "worker:1": (40, 4.0), "worker:2": (20, 2.0)}
        out = det.observe(e, now_s=3.0)
        assert [(x, t) for x, t, *_ in out] == [("resolved", "worker:2")]

    def test_lone_advancer_is_never_evaluated(self):
        # only one rank still advancing (others finished/stalled): no median
        # quorum — the survivor must not be judged against itself
        det = obs_goodput.StragglerDetector(factor=1.5, min_checks=1)
        a = {f"worker:{i}": (0, 0.0) for i in range(3)}
        det.observe(a, now_s=0.0)
        b = {"worker:0": (10, 1.0), "worker:1": (0, 0.0), "worker:2": (0, 0.0)}
        assert det.observe(b, now_s=100.0) == []


class TestJhistFollower:
    def test_incremental_and_torn_tail(self, tmp_path):
        p = tmp_path / "x.jhist"
        f = obs_goodput.JhistFollower(str(p))
        assert f.poll() == []
        p.write_text(ev("GANG_COMPLETE", 1000).to_json() + "\n")
        assert [e.type.value for e in f.poll()] == ["GANG_COMPLETE"]
        # a torn tail (no newline yet) is not consumed...
        with open(p, "a") as fh:
            fh.write('{"type": "TASK_FIN')
        assert len(f.poll()) == 1
        # ...and is parsed whole once its newline lands
        with open(p, "a") as fh:
            fh.write('ISHED", "timestamp_ms": 2000, "payload": {}}\n')
        assert [e.type.value for e in f.poll()] == ["GANG_COMPLETE", "TASK_FINISHED"]


class TestHistogramPercentile:
    def test_merged_percentile(self):
        buckets = [0.1, 0.5, 1.0]
        snapa = [{"name": "tony_train_step_seconds", "type": "histogram",
                  "buckets": buckets,
                  "samples": [{"labels": {}, "counts": [90, 0, 0, 0],
                               "sum": 9.0, "count": 90}]}]
        snapb = [{"name": "tony_train_step_seconds", "type": "histogram",
                  "buckets": buckets,
                  "samples": [{"labels": {}, "counts": [0, 0, 10, 0],
                               "sum": 10.0, "count": 10}]}]
        p50 = obs_goodput.histogram_percentile([snapa, snapb], "tony_train_step_seconds", 0.5)
        p99 = obs_goodput.histogram_percentile([snapa, snapb], "tony_train_step_seconds", 0.99)
        assert p50 == pytest.approx(0.1)
        assert p99 == pytest.approx(1.0)

    def test_no_samples(self):
        assert obs_goodput.histogram_percentile([[]], "x", 0.99) is None

    def test_overflow_bucket(self):
        s = [{"name": "h", "type": "histogram", "buckets": [0.1],
              "samples": [{"labels": {}, "counts": [0, 5], "sum": 5.0, "count": 5}]}]
        assert obs_goodput.histogram_percentile([s], "h", 0.99) == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# alert engine
# ---------------------------------------------------------------------------
class TestAlertEngine:
    RULES = [obs_alerts.AlertRule("goodput-floor", 0.8, "below", "fraction"),
             obs_alerts.AlertRule("queue-depth", 5, "above", "requests")]

    def test_edge_triggered_transitions(self, tmp_path):
        sink = tmp_path / "alerts.jsonl"
        eng = obs_alerts.AlertEngine(
            self.RULES, sink=obs_alerts.AlertSink(str(sink)), app_id="app")
        out = eng.evaluate({"goodput-floor": 0.5, "queue-depth": 2}, now_ms=1000)
        assert [(r["rule"], r["state"]) for r in out] == [("goodput-floor", "fired")]
        # still firing: no new transition, value refreshed
        assert eng.evaluate({"goodput-floor": 0.4}, now_ms=2000) == []
        assert eng.active()[0]["value"] == 0.4
        out = eng.evaluate({"goodput-floor": 0.9}, now_ms=3000)
        assert [(r["rule"], r["state"]) for r in out] == [("goodput-floor", "resolved")]
        assert out[0]["active_ms"] == 2000
        assert eng.active() == []
        recs = [json.loads(line) for line in sink.read_text().splitlines()]
        assert [r["state"] for r in recs] == ["fired", "resolved"]

    def test_none_holds_state(self):
        eng = obs_alerts.AlertEngine(self.RULES, app_id="app")
        eng.evaluate({"goodput-floor": 0.1}, now_ms=0)
        # a scrape gap must neither fire nor resolve
        assert eng.evaluate({"goodput-floor": None}, now_ms=1) == []
        assert len(eng.active()) == 1

    def test_resolve_all(self, tmp_path):
        sink = tmp_path / "alerts.jsonl"
        eng = obs_alerts.AlertEngine(
            self.RULES, sink=obs_alerts.AlertSink(str(sink)), app_id="app")
        eng.evaluate({"goodput-floor": 0.1, "queue-depth": 9}, now_ms=0)
        out = eng.resolve_all("job finalized", now_ms=500)
        assert {r["rule"] for r in out} == {"goodput-floor", "queue-depth"}
        assert all(r["reason"] == "job finalized" for r in out)
        assert eng.active() == []

    def test_webhook_delivery(self, tmp_path):
        from http.server import BaseHTTPRequestHandler, HTTPServer

        got = []

        class H(BaseHTTPRequestHandler):
            def do_POST(self):
                got.append(json.loads(
                    self.rfile.read(int(self.headers["Content-Length"]))))
                self.send_response(200)
                self.end_headers()

            def log_message(self, *a):
                pass

        srv = HTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            sink = obs_alerts.AlertSink(
                None, f"http://127.0.0.1:{srv.server_address[1]}/hook")
            eng = obs_alerts.AlertEngine(self.RULES, sink=sink, app_id="app")
            eng.evaluate({"queue-depth": 50}, now_ms=0)
            assert got and got[0]["rule"] == "queue-depth"
        finally:
            srv.shutdown()

    def test_dead_webhook_is_not_an_outage(self):
        sink = obs_alerts.AlertSink(None, "http://127.0.0.1:1/hook", timeout_s=0.2)
        eng = obs_alerts.AlertEngine(self.RULES, sink=sink, app_id="app")
        out = eng.evaluate({"queue-depth": 50}, now_ms=0)  # must not raise
        assert out[0]["state"] == "fired"

    def test_rules_from_config(self):
        cfg = TonyConfig({
            keys.ALERTS_GOODPUT_FLOOR: "0.75",
            keys.ALERTS_QUEUE_DEPTH: "8",
        })
        rules = {r.name: r for r in obs_alerts.rules_from_config(cfg)}
        assert set(rules) == {"goodput-floor", "queue-depth"}
        assert rules["goodput-floor"].direction == "below"
        assert rules["goodput-floor"].threshold == 0.75
        assert rules["queue-depth"].breached(9) and not rules["queue-depth"].breached(5)

    def test_bad_threshold_is_loud(self):
        cfg = TonyConfig({keys.ALERTS_GOODPUT_FLOOR: "lots"})
        with pytest.raises(ValueError, match="not a number"):
            obs_alerts.rules_from_config(cfg)


# ---------------------------------------------------------------------------
# history-store integration: goodput columns, trend, finalized-alert evals
# ---------------------------------------------------------------------------
@pytest.mark.history
class TestHistoryIntegration:
    def test_ingest_distills_goodput_columns(self, tmp_path):
        from tests.test_history_server import make_job
        from tony_tpu.histserver import ingest as hist_ingest
        from tony_tpu.histserver.store import HistoryStore
        from tony_tpu.obs import artifacts as obs_artifacts

        make_job(tmp_path, "appg")
        store = HistoryStore(":memory:")
        art = obs_artifacts.index(str(tmp_path), "appg")
        assert hist_ingest.ingest_job(store, art) == "ingested"
        row = store.get_job("appg")
        assert row["goodput_s"] > 0
        assert row["badput_s"] > 0  # queue wait + startup are real time here
        assert 0 < row["goodput_fraction"] <= 1
        assert "phases_ms" in row["summary"]["goodput"]
        trend = store.trend("goodput_fraction")
        assert [p["app_id"] for p in trend] == ["appg"]
        store.close()

    def test_store_migration_adds_goodput_columns(self, tmp_path):
        import sqlite3

        path = str(tmp_path / "old.sqlite")
        db = sqlite3.connect(path)
        # a pre-goodput store: the PR-8 jobs schema, without the new columns
        db.execute("""CREATE TABLE jobs (
          app_id TEXT PRIMARY KEY, status TEXT NOT NULL, user TEXT DEFAULT '',
          started_ms INTEGER DEFAULT 0, completed_ms INTEGER DEFAULT 0,
          duration_ms INTEGER DEFAULT 0, incomplete INTEGER DEFAULT 0,
          tasks INTEGER DEFAULT 0, gang_epochs INTEGER DEFAULT 0,
          resizes INTEGER DEFAULT 0, takeovers INTEGER DEFAULT 0,
          queue_wait_s REAL DEFAULT 0.0, staging_dir TEXT DEFAULT '',
          source_path TEXT DEFAULT '', source_mtime_ns INTEGER DEFAULT 0,
          ingested_ms INTEGER DEFAULT 0, summary TEXT DEFAULT '{}',
          config TEXT DEFAULT '{}')""")
        db.execute("CREATE TABLE series (app_id TEXT, metric TEXT, seq INTEGER, "
                   "ts_ms INTEGER, value REAL, PRIMARY KEY (app_id, metric, seq))")
        db.commit()
        db.close()
        from tony_tpu.histserver.store import HistoryStore

        store = HistoryStore(path)  # must migrate, not explode
        store.put_job({"app_id": "x", "status": "SUCCEEDED",
                       "goodput_s": 1.5, "goodput_fraction": 0.5})
        assert store.get_job("x")["goodput_fraction"] == 0.5
        store.close()

    def test_finalized_alert_evaluation_counts(self, tmp_path):
        from tony_tpu.histserver.server import _ALERT_EVALS, HistoryServer

        srv = HistoryServer([str(tmp_path)], store_path=":memory:", port=0)
        srv.start()  # stop() joins the serve loop — it must actually run
        try:
            before = {o: _ALERT_EVALS.value(outcome=o)
                      for o in ("fired", "ok", "none", "error")}
            srv.store.put_job(
                {"app_id": "low", "status": "SUCCEEDED", "goodput_fraction": 0.2},
                config={keys.ALERTS_GOODPUT_FLOOR: "0.9"})
            srv._evaluate_final_alerts("low", None)
            srv.store.put_job(
                {"app_id": "hi", "status": "SUCCEEDED", "goodput_fraction": 0.95},
                config={keys.ALERTS_GOODPUT_FLOOR: "0.9"})
            srv._evaluate_final_alerts("hi", None)
            srv.store.put_job(
                {"app_id": "none", "status": "SUCCEEDED", "goodput_fraction": 0.1})
            srv._evaluate_final_alerts("none", None)
            assert _ALERT_EVALS.value(outcome="fired") - before["fired"] == 1
            assert _ALERT_EVALS.value(outcome="ok") - before["ok"] == 1
            assert _ALERT_EVALS.value(outcome="none") - before["none"] == 1
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# tony goodput CLI over fabricated artifacts
# ---------------------------------------------------------------------------
class TestGoodputCLI:
    def test_report_and_json(self, tmp_path, capsys):
        from tests.test_history_server import make_job
        from tony_tpu.cli.goodput import main as goodput_main

        make_job(tmp_path, "appc", extra=(
            (EventType.STRAGGLER_DETECTED, {"task": "worker:2", "ratio": 3.1}),
            (EventType.ALERT_FIRED,
             {"rule": "goodput-floor", "value": 0.2, "threshold": 0.8}),
            (EventType.ALERT_RESOLVED,
             {"rule": "goodput-floor", "value": 0.9, "threshold": 0.8}),
        ))
        assert goodput_main(["appc", "--staging", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "phase ledger" in out and "productive" in out
        assert "STRAGGLER" in out
        assert "goodput-floor" in out and "resolved" in out

        assert goodput_main(["appc", "--staging", str(tmp_path), "--json"]) == 0
        data = json.loads(capsys.readouterr().out)
        assert sum(data["phases_ms"].values()) == data["wall_ms"]
        assert data["alert_history"][0]["rule"] == "goodput-floor"
        assert data["straggler_history"][0]["task"] == "worker:2"

    def test_missing_app(self, tmp_path, capsys):
        from tony_tpu.cli.goodput import main as goodput_main

        assert goodput_main(["nope", "--staging", str(tmp_path)]) == 1
        assert "no history events" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# headline e2e: chaos restart + elastic resize + straggler + alert lifecycle
# ---------------------------------------------------------------------------
@pytest.mark.e2e
@pytest.mark.chaos
class TestGoodputHeadlineE2E:
    STEPS = 26
    # the chaos gate's step, which the fixture is told: one past a recorded
    # step past the checkpoint at 4, and the last step the first attempt runs
    DOOMED_STEP = 7

    def _wait(self, fn, timeout_s=90, interval=0.1):
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            got = fn()
            if got:
                return got
            time.sleep(interval)
        return None

    def _wait_observed(self, probe, stall_s=60.0, cap_s=420.0, interval=0.25):
        """Progress-derived deadline: ``probe()`` returns ``(result,
        signal)``; returns ``result`` once truthy. The wait only gives up
        after ``stall_s`` seconds with no change in ``signal`` (hard backstop
        ``cap_s``) — a slow-but-progressing run gets more time, a wedged one
        still fails fast."""
        t0 = last_t = time.time()
        last: object = object()
        while True:
            result, sig = probe()
            if result:
                return result
            now = time.time()
            if sig != last:
                last, last_t = sig, now
            if now - last_t >= stall_s or now - t0 >= cap_s:
                return None
            time.sleep(interval)

    @pytest.mark.skipif(
        (len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity")
         else (os.cpu_count() or 1)) < 2,
        reason="needs >= 2 CPUs: this e2e runs a full 3-worker gang + AM + "
               "pool + portal as real processes/threads on one box, and on a "
               "single CPU the gang's heartbeat/monitor/step loops serialize "
               "behind each other — the straggler-skew and restart timing "
               "assertions then flake on scheduler luck, not on product "
               "bugs (documented flake since PR 16; PR 17's "
               "progress-derived waits fixed the wedge case but cannot "
               "manufacture a second core). The test runs unchanged "
               "wherever nproc >= 2.")
    def test_restart_resize_straggler_and_alert_accounted(
            self, tmp_tony_root, tmp_path, capsys):
        from tests.test_e2e import FAST, fixture_cmd
        from tony_tpu.cli.goodput import main as goodput_main
        from tony_tpu.cluster.client import Client
        from tony_tpu.cluster.session import JobStatus
        from tony_tpu.histserver.store import HistoryStore
        from tony_tpu.histserver import ingest as hist_ingest
        from tony_tpu.obs import artifacts as obs_artifacts
        from tony_tpu.portal.server import serve

        shared = tmp_path / "shared"
        shared.mkdir()
        cfg = TonyConfig({
            **FAST,
            keys.STAGING_ROOT: str(tmp_tony_root),
            # rank 2 runs 3x slow (the injected straggler); ckpt every 4 steps
            keys.EXECUTES: f"{fixture_cmd('goodput_train.py')} {shared} "
                           f"{self.STEPS} 120 2 3.0 4 {self.DOOMED_STEP}",
            "tony.worker.instances": "3",
            keys.TASK_METRICS_INTERVAL_MS: "150",
            keys.TASK_RESTART_ON_FAILURE: "true",
            # one gang restart: a node dies once the AM has seen step 7
            keys.CHAOS_SPEC: f"node-loss:worker:1@step+{self.DOOMED_STEP}",
            keys.CHAOS_SEED: "7",
            keys.GOODPUT_INTERVAL_MS: "250",
            keys.GOODPUT_WINDOW_MS: "2500",
            keys.GOODPUT_STRAGGLER_FACTOR: "2.0",
            keys.GOODPUT_STRAGGLER_CHECKS: "2",
            keys.ALERTS_GOODPUT_FLOOR: "0.5",
        })
        client = Client(cfg)
        handle = client.submit()
        app_id = handle.app_id

        # mid-run elastic resize: once the post-restart gang has made
        # PROGRESS (fresh step reports past the resume point — the ledger's
        # rework derivation needs the resumed epoch's snapshots on disk),
        # grow worker 3 → 4 over the same lever the autoscaler uses
        def restarted_and_progressing():
            rpc = handle.rpc(timeout_s=5)
            if rpc is None:
                return None, None
            sig = None
            try:
                st = rpc.call("get_application_status")
                infos = rpc.call("get_task_infos")
                steps = [
                    ((t.get("metrics") or {}).get("train") or {}).get("step") or 0
                    for t in infos
                ]
                running = sum(1 for t in infos if t["status"] == "RUNNING")
                sig = (st.get("restart_attempt", 0), running,
                       max(steps, default=0))
                if sig[0] >= 1 and running >= 3 and sig[2] >= 8:
                    return rpc, sig
            except Exception:  # noqa: BLE001 — AM mid-restart
                pass
            rpc.close()
            return None, sig

        # deadline derived from observed progress: restart attempts landing
        # and step reports advancing extend the wait; only a stall fails
        rpc = self._wait_observed(restarted_and_progressing,
                                  stall_s=60, cap_s=300)
        assert rpc is not None, "gang restart never landed (or never progressed)"
        try:
            # give the straggler detector a couple of ticks on the restarted
            # gang before the resize tears it down again
            time.sleep(1.0)
            assert rpc.call("resize_jobtype", job_name="worker", instances=4)["ack"]
        finally:
            rpc.close()

        final = client.monitor_application(handle, quiet=True)
        assert final == JobStatus.SUCCEEDED, handle.final_status()

        art = obs_artifacts.index(str(tmp_tony_root), app_id)
        events, complete = art.read_events()
        assert complete
        types = [e.type.value for e in events]

        # --- the event stream carries the whole story
        assert "STRAGGLER_DETECTED" in types
        straggled = {e.payload["task"] for e in events
                     if e.type.value == "STRAGGLER_DETECTED"}
        assert "worker:2" in straggled
        fired = [e for e in events if e.type.value == "ALERT_FIRED"]
        resolved = [e for e in events if e.type.value == "ALERT_RESOLVED"]
        assert fired and resolved
        assert fired[0].payload["rule"] == "goodput-floor"
        assert resolved[-1].timestamp_ms >= fired[0].timestamp_ms
        assert "GANG_RESIZED" in types

        # --- the sink received the same transitions
        sink = os.path.join(art.staging_dir, "alerts.jsonl")
        recs = [json.loads(line) for line in open(sink)]
        assert {r["state"] for r in recs} >= {"fired", "resolved"}

        # --- tony goodput: exact partition + attribution + straggler flag
        capsys.readouterr()
        assert goodput_main([app_id, "--staging", str(tmp_tony_root), "--json"]) == 0
        data = json.loads(capsys.readouterr().out)
        assert sum(data["phases_ms"].values()) == data["wall_ms"]
        assert data["phases_ms"].get("restart_rework", 0) > 0, data["phases_ms"]
        assert data["phases_ms"].get("resize", 0) > 0, data["phases_ms"]
        assert data["phases_ms"]["productive"] > 0
        assert data["restarts"] >= 2 and data["resizes"] == 1
        # ordinal, not a hard ratio: scheduling noise on a loaded CI box can
        # compress the margin, but the 3x-sleeping rank is always slowest
        skews = data["skew_by_task"]
        assert skews and max(skews, key=skews.get) == "worker:2", skews
        assert skews["worker:2"] > 1.0, skews

        assert goodput_main([app_id, "--staging", str(tmp_tony_root)]) == 0
        report = capsys.readouterr().out
        assert "restart_rework" in report and "resize" in report
        assert "worker:2" in report and "STRAGGLER" in report
        assert "goodput-floor" in report

        # --- history store: goodput columns + alert/straggler history
        store = HistoryStore(str(tmp_path / "store.sqlite"))
        counts = hist_ingest.sweep(store, [str(tmp_tony_root)])
        assert counts["ingested"] == 1
        row = store.get_job(app_id)
        assert 0 < row["goodput_fraction"] < 1
        assert row["goodput_s"] > 0
        assert any(h["rule"] == "goodput-floor" for h in row["summary"]["alerts"])
        assert "worker:2" in row["summary"]["stragglers"]
        store.close()

        # --- portal: /job/<id>/goodput and the fleet /alerts page
        server = serve(os.path.join(str(tmp_tony_root), "history"), 0,
                       str(tmp_tony_root),
                       history_db=str(tmp_path / "store.sqlite"))
        threading.Thread(target=server.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{server.server_address[1]}"
        try:
            body = urllib.request.urlopen(f"{base}/job/{app_id}/goodput").read().decode()
            assert "phase ledger" in body and "restart_rework" in body
            assert "STRAGGLER" in body
            alerts_page = urllib.request.urlopen(f"{base}/alerts").read().decode()
            assert app_id in alerts_page
            assert "goodput-floor" in alerts_page
            api = json.loads(
                urllib.request.urlopen(f"{base}/api/goodput/{app_id}").read())
            assert sum(api["phases_ms"].values()) == api["wall_ms"]
        finally:
            server.shutdown()

        # --- the optional bench goodput gate sees the same ledger
        from tests.test_bench_gate import write_train_trajectory
        from tony_tpu.cli.history import main_bench

        # the train family has no checked-in round (test_bench_gate.py)
        trajectory = str(tmp_path / "trajectory")
        os.mkdir(trajectory)
        write_train_trajectory(trajectory)
        capsys.readouterr()
        rc_hi = main_bench(["--gate", "--trajectory-dir", trajectory,
                            "--goodput-floor", "0.999", "--goodput-app", app_id,
                            "--staging", str(tmp_tony_root)])
        assert rc_hi == 1
        assert "GOODPUT REGRESSION" in capsys.readouterr().out
        rc_lo = main_bench(["--gate", "--trajectory-dir", trajectory,
                            "--goodput-floor", "0.0", "--goodput-app", app_id,
                            "--staging", str(tmp_tony_root)])
        assert rc_lo == 0
