"""SLO engine: error budgets, burn-rate alerts, request tracing, verdict.

Covers (tony_tpu/obs/slo.py; docs/observability.md "SLOs & error budgets"):

- objective parsing from ``tony.slo.*`` (market-threshold inheritance, loud
  misconfiguration);
- the BudgetLedger's EXACT accounting — unit cases plus the 300-seed
  randomized property test mirroring goodput's partition contract:
  everything ever ingested == expired out the window + still banked, for
  any interleaving of ingests, counter resets, and window boundaries;
- good/bad extraction from registry snapshots (TTFT histogram with the
  SLO-aligned bucket edge → exact counts; availability by outcome label;
  worst-offender exemplars);
- multi-window multi-burn-rate rule compilation + evaluation through the
  real AlertEngine (fast-burn fires, short-window confirmation resolves,
  no data holds state);
- the zero-allocation contract: with tracing disabled the per-request span
  chain and request-id plumbing allocate no Span objects;
- the router's X-Tony-Request-Id assignment/echo;
- slo.jsonl → history-store ``slo_series`` ingestion (REPLACE idempotence,
  torn tails, retention) and the merged-row dedupe the CLI verdict relies
  on;
- ``verdict_from_rows`` pass/fail/no-data semantics + the ``tony slo``
  CLI (status fallback + verdict exit codes);
- ``tony bench --gate``'s ``slo_verdict`` contract and
  ``budget_burned_pct`` direction;
- the diurnal arrival profile and the autoscaler's SLO-burn pressure;
- headline e2e: a diurnal loadtest over a live router/fleet with an
  injected mid-spike error burst — the fast-burn rule fires
  ``SLO_BURN_ALERT`` during the spike and resolves after, rows persist
  through the store sweep, and ``tony slo verdict`` reads PASS from
  history (exit 0), never from in-process state.
"""

import json
import random
import threading
import time
import types

import pytest

from tony_tpu.config import TonyConfig, keys
from tony_tpu.histserver import gate as bench_gate
from tony_tpu.histserver import ingest as hist_ingest
from tony_tpu.histserver.store import HistoryStore
from tony_tpu.obs import alerts as obs_alerts
from tony_tpu.obs import metrics as obs_metrics
from tony_tpu.obs import slo as obs_slo
from tony_tpu.obs import trace as obs_trace
from tony_tpu.serve.autoscaler import AutoscalePolicy, Autoscaler
from tony_tpu.serve.loadgen import LoadGenerator, LoadSpec, arrival_offsets

pytestmark = pytest.mark.slo


def cfg(**overrides):
    base = {"tony.worker.instances": "1"}
    base.update({k: str(v) for k, v in overrides.items()})
    c = TonyConfig(base)
    c.freeze()
    return c


def slo_cfg(**overrides):
    overrides.setdefault(keys.SLO_SERVE_TTFT_TARGET, "0.99")
    return cfg(**overrides)


# ---------------------------------------------------------------------------
# objective parsing
# ---------------------------------------------------------------------------
class TestObjectivesFromConfig:
    def test_disabled_by_default(self):
        assert obs_slo.objectives_from_config(cfg()) == []
        engine = obs_slo.SloEngine(cfg())
        assert not engine.enabled and engine.burn_rules() == []

    def test_ttft_threshold_inherits_market_key(self):
        objs = obs_slo.objectives_from_config(slo_cfg())
        assert [o.name for o in objs] == ["serve-ttft"]
        assert objs[0].threshold_ms == 2000.0  # market default
        objs = obs_slo.objectives_from_config(slo_cfg(**{
            keys.SERVE_MARKET_SLO_TTFT_MS: "750"}))
        assert objs[0].threshold_ms == 750.0
        objs = obs_slo.objectives_from_config(slo_cfg(**{
            keys.SERVE_MARKET_SLO_TTFT_MS: "750",
            keys.SLO_SERVE_TTFT_THRESHOLD_MS: "1500"}))
        assert objs[0].threshold_ms == 1500.0  # explicit beats inherited

    def test_all_three_objectives(self):
        c = cfg(**{keys.SLO_SERVE_TTFT_TARGET: "0.95",
                   keys.SLO_SERVE_AVAILABILITY_TARGET: "0.999",
                   keys.SLO_TRAIN_GOODPUT_TARGET: "0.8"})
        objs = {o.name: o for o in obs_slo.objectives_from_config(c)}
        assert set(objs) == {"serve-ttft", "serve-availability", "train-goodput"}
        assert objs["train-goodput"].unit == "ms"
        assert objs["serve-availability"].unit == "requests"

    def test_bad_target_is_loud(self):
        with pytest.raises(ValueError, match="not a number"):
            obs_slo.objectives_from_config(
                cfg(**{keys.SLO_SERVE_TTFT_TARGET: "ninety-nine"}))
        with pytest.raises(ValueError, match=r"fraction in \(0, 1\)"):
            obs_slo.objectives_from_config(
                cfg(**{keys.SLO_SERVE_AVAILABILITY_TARGET: "1.0"}))
        with pytest.raises(ValueError, match="must be > 0 ms"):
            obs_slo.objectives_from_config(slo_cfg(**{
                keys.SLO_SERVE_TTFT_THRESHOLD_MS: "-5"}))


# ---------------------------------------------------------------------------
# budget ledger units
# ---------------------------------------------------------------------------
def ledger(target=0.9, window_ms=60_000, bucket_ms=1_000, name="serve-ttft"):
    return obs_slo.BudgetLedger(
        obs_slo.Objective(name, target, "requests"), window_ms, bucket_ms)


class TestBudgetLedger:
    def test_cumulative_deltas(self):
        led = ledger()
        assert led.ingest("a", 10, 1, 1000) == (10, 1)
        assert led.ingest("a", 15, 1, 1500) == (5, 0)
        assert led.ingest("a", 15, 1, 2000) == (0, 0)  # no traffic: no-op
        assert (led.total_good, led.total_bad) == (15, 1)
        assert led.window_counts(2000) == (15, 1)

    def test_counter_reset_banks_fresh_totals(self):
        led = ledger()
        led.ingest("a", 100, 10, 1000)
        # the replica restarted: its counters start over — the fresh totals
        # ARE the delta, nothing lost and nothing double-counted
        assert led.ingest("a", 3, 1, 2000) == (3, 1)
        assert (led.total_good, led.total_bad) == (103, 11)

    def test_sources_are_independent(self):
        led = ledger()
        led.ingest("a", 10, 0, 1000)
        led.ingest("b", 20, 2, 1000)
        led.forget("a")
        led.ingest("a", 4, 0, 2000)  # re-appeared: fresh watermark
        assert led.total_good == 34

    def test_window_expiry_is_exact(self):
        led = ledger(window_ms=10_000, bucket_ms=1_000)
        led.ingest("a", 7, 3, 500)
        led.advance(5_000)
        assert led.window_counts(5_000) == (7, 3)
        led.advance(12_000)  # bucket [0,1000) wholly out of [2000, 12000]
        assert led.window_counts(12_000) == (0, 0)
        assert (led.expired_good, led.expired_bad) == (7, 3)
        assert led.total_good == led.expired_good == 7

    def test_burn_rate_semantics(self):
        led = ledger(target=0.9)  # 10% budget
        assert led.burn_rate(1000) is None  # no data ≠ zero burn
        led.ingest("a", 90, 10, 1000)
        assert led.burn_rate(1000) == pytest.approx(1.0)  # exactly sustainable
        led.ingest("a", 90, 30, 1000)  # cumulative: +20 bad
        # 30 bad / 120 total = 25% bad fraction over a 10% allowance
        assert led.burn_rate(1000) == pytest.approx((30 / 120) / 0.1)

    def test_budget_remaining(self):
        led = ledger(target=0.9)
        assert led.budget_remaining(1000) == 1.0  # untouched
        led.ingest("a", 95, 5, 1000)
        assert led.budget_remaining(1000) == pytest.approx(0.5)
        led.ingest("a", 95, 20, 1000)
        assert led.budget_remaining(1000) == 0.0  # clamped, over-spent

    def test_subwindow_counts_at_bucket_grain(self):
        led = ledger(window_ms=60_000, bucket_ms=1_000)
        led.ingest("a", 5, 0, 500)       # bucket [0, 1000)
        led.ingest("a", 9, 1, 10_500)    # bucket [10000, 11000)
        good, bad = led.window_counts(10_900, window_ms=2_000)
        assert (good, bad) == (4, 1)     # only the recent bucket
        good, bad = led.window_counts(10_900)
        assert (good, bad) == (9, 1)

    def test_bad_geometry_is_loud(self):
        with pytest.raises(ValueError, match="bucket-ms"):
            ledger(window_ms=1_000, bucket_ms=5_000)


# ---------------------------------------------------------------------------
# satellite: randomized property test — the accounting is EXACT
# ---------------------------------------------------------------------------
class TestBudgetPartitionProperty:
    """Mirror of goodput's exact-partition property: for ANY interleaving of
    cumulative samples (including counter resets), multiple sources, time
    jumps across bucket and window boundaries, and advances:

      ingested == expired + banked          (good and bad, to the count)
      consumed + remaining == window budget (when the budget is positive)
    """

    def _drive(self, rng):
        window_ms = rng.choice([5_000, 10_000, 60_000])
        bucket_ms = rng.choice([250, 1_000, window_ms])
        target = rng.choice([0.5, 0.9, 0.99])
        led = ledger(target=target, window_ms=window_ms, bucket_ms=bucket_ms)
        sources = [f"task:{i}" for i in range(rng.randint(1, 4))]
        watermark = {s: (0, 0) for s in sources}
        ingested_good = ingested_bad = 0
        now = rng.randint(0, 10_000)
        for _ in range(rng.randint(5, 60)):
            now += rng.choice([0, 1, bucket_ms // 2 or 1, bucket_ms,
                               window_ms // 3, window_ms * 2])
            op = rng.random()
            if op < 0.6:
                s = rng.choice(sources)
                g, b = watermark[s]
                if rng.random() < 0.15:
                    g, b = 0, 0  # process restart: counters start over
                ng, nb = g + rng.randint(0, 50), b + rng.randint(0, 10)
                dg, db = led.ingest(s, ng, nb, now)
                watermark[s] = (ng, nb)
                ingested_good += dg
                ingested_bad += db
            elif op < 0.8:
                led.advance(now)
            else:
                led.forget(rng.choice(sources))
            # THE invariant, checked after every single operation
            banked_g = sum(g for g, _ in led._buckets.values())
            banked_b = sum(b for _, b in led._buckets.values())
            assert led.total_good == ingested_good
            assert led.total_bad == ingested_bad
            assert led.expired_good + banked_g == ingested_good
            assert led.expired_bad + banked_b == ingested_bad
            # window budget partition: consumed + remaining == budget
            good, bad = led.window_counts(now)
            budget = led.objective.allowed_bad_fraction * (good + bad)
            if budget > 0:
                remaining = led.budget_remaining(now) * budget
                consumed = min(bad, budget)  # remaining clamps at 0
                assert consumed + remaining == pytest.approx(budget)

    def test_partition_is_exact_over_random_histories(self):
        for seed in range(300):
            try:
                self._drive(random.Random(seed))
            except AssertionError as e:
                raise AssertionError(f"seed {seed}: {e}") from e


# ---------------------------------------------------------------------------
# snapshot extraction: exact TTFT split, availability, exemplars
# ---------------------------------------------------------------------------
class TestExtraction:
    def _ttft_snapshot(self):
        reg = obs_metrics.MetricsRegistry()
        h = reg.histogram("tony_serve_ttft_seconds", "t",
                          buckets=(0.1, 1.0, 10.0))
        h.ensure_bucket(0.5)  # the SLO-aligned edge (500ms threshold)
        for v, rid in ((0.05, "r1"), (0.4, "r2"), (0.5, "r3"),
                       (0.9, "r4"), (4.0, "r5")):
            h.observe(v, exemplar=rid)
        return reg.snapshot()

    def test_ttft_good_bad_is_exact_at_the_aligned_edge(self):
        snap = self._ttft_snapshot()
        # good = cumulative count at the 0.5s edge: 0.05, 0.4, 0.5 land in
        good, bad = obs_slo.ttft_good_bad(snap, threshold_ms=500.0)
        assert (good, bad) == (3, 2)

    def test_ttft_missing_metric_is_none(self):
        assert obs_slo.ttft_good_bad([], 500.0) is None

    def test_exemplars_are_worst_first_and_capped(self):
        ex = obs_slo.ttft_exemplars(self._ttft_snapshot())
        assert ex[0] == (4.0, "r5")
        assert [rid for _, rid in ex[:2]] == ["r5", "r4"]
        assert len(ex) <= obs_metrics.EXEMPLAR_K

    def test_availability_by_outcome_label(self):
        snap = [{
            "name": "tony_serve_requests_total", "kind": "counter",
            "samples": [
                {"labels": {"outcome": "ok"}, "value": 90},
                {"labels": {"outcome": "forwarded"}, "value": 5},
                {"labels": {"outcome": "error"}, "value": 4},
                {"labels": {"outcome": "cancelled"}, "value": 7},
            ],
        }]
        # a client cancel spends no availability budget
        assert obs_slo.availability_good_bad(snap) == (102, 4)


# ---------------------------------------------------------------------------
# burn rules through the real AlertEngine
# ---------------------------------------------------------------------------
def engine_cfg(**overrides):
    base = {
        keys.SLO_SERVE_AVAILABILITY_TARGET: "0.9",
        keys.SLO_WINDOW_MS: "60000",
        keys.SLO_BUCKET_MS: "1000",
        keys.SLO_FAST_BURN: "8.0",
        keys.SLO_FAST_WINDOW_MS: "12000",
        keys.SLO_SLOW_BURN: "2.0",
        keys.SLO_SLOW_WINDOW_MS: "48000",
    }
    base.update({k: str(v) for k, v in overrides.items()})
    return cfg(**base)


def avail_snap(ok, err):
    return [{"name": "tony_serve_requests_total", "samples": [
        {"labels": {"outcome": "ok"}, "value": ok},
        {"labels": {"outcome": "error"}, "value": err},
    ]}]


class TestBurnRules:
    def test_rule_compilation(self):
        eng = obs_slo.SloEngine(engine_cfg(**{
            keys.SLO_SERVE_TTFT_TARGET: "0.99"}))
        rules = {r.name: r for r in eng.burn_rules()}
        assert set(rules) == {
            "slo-serve-ttft-fast-burn", "slo-serve-ttft-slow-burn",
            "slo-serve-availability-fast-burn",
            "slo-serve-availability-slow-burn"}
        fast = rules["slo-serve-ttft-fast-burn"]
        assert fast.threshold == 8.0 and fast.direction == "above"
        assert all(r.name.startswith(obs_slo.RULE_PREFIX) for r in rules.values())

    def test_fast_burn_fires_and_short_window_resolves(self):
        eng = obs_slo.SloEngine(engine_cfg())
        alerts = obs_alerts.AlertEngine(eng.burn_rules(), app_id="app")
        # sustained 50% errors over a 10% allowance: burn 5× → slow (2×)
        # fires, fast (8×) does not
        now = 0
        fired = set()
        for i in range(12):
            now = i * 1000
            eng.observe_serve("t", avail_snap(ok=(i + 1) * 5, err=(i + 1) * 5), now)
            for rec in alerts.evaluate(eng.tick(now)):
                fired.add((rec["rule"], rec["state"]))
        assert ("slo-serve-availability-slow-burn", "fired") in fired
        assert ("slo-serve-availability-fast-burn", "fired") not in fired
        # burst to ~90% errors across the fast window → burn past 8× → page
        for i in range(12, 18):
            now = i * 1000
            eng.observe_serve("t", avail_snap(ok=60, err=60 + (i - 11) * 40), now)
            for rec in alerts.evaluate(eng.tick(now)):
                fired.add((rec["rule"], rec["state"]))
        assert ("slo-serve-availability-fast-burn", "fired") in fired
        # the burn stops: fresh all-good buckets drain the SHORT confirm
        # window first, so the page resolves long before the fast window
        # itself is clean (the workbook's prompt-resolve property)
        for i in range(18, 24):
            now = i * 1000
            eng.observe_serve("t", avail_snap(ok=1000 + i * 200, err=300), now)
            for rec in alerts.evaluate(eng.tick(now)):
                fired.add((rec["rule"], rec["state"]))
        assert ("slo-serve-availability-fast-burn", "resolved") in fired

    def test_no_data_returns_none_and_holds_state(self):
        eng = obs_slo.SloEngine(engine_cfg())
        values = eng.tick(1000)
        assert values == {"slo-serve-availability-fast-burn": None,
                          "slo-serve-availability-slow-burn": None}
        alerts = obs_alerts.AlertEngine(eng.burn_rules(), app_id="app")
        assert alerts.evaluate(values) == []  # nothing fires, nothing resolves

    def test_gauges_track_the_ledger(self):
        eng = obs_slo.SloEngine(engine_cfg())
        eng.observe_serve("t", avail_snap(ok=50, err=50), 1000)
        eng.tick(1000)
        snap = obs_metrics.REGISTRY.snapshot()
        rem = burn = None
        for m in snap:
            if m["name"] == "tony_slo_budget_remaining":
                for s in m["samples"]:
                    if s["labels"].get("objective") == "serve-availability":
                        rem = s["value"]
            if m["name"] == "tony_slo_burn_rate":
                for s in m["samples"]:
                    if (s["labels"].get("objective") == "serve-availability"
                            and s["labels"].get("window") == "fast"):
                        burn = s["value"]
        assert rem == 0.0  # 50% errors vs a 10% budget: spent
        assert burn == pytest.approx(5.0)

    def test_observe_train_uses_the_ledger_partition(self):
        eng = obs_slo.SloEngine(engine_cfg(**{
            keys.SLO_SERVE_AVAILABILITY_TARGET: "",
            keys.SLO_TRAIN_GOODPUT_TARGET: "0.5"}))
        led = types.SimpleNamespace(
            wall_ms=10_000, phases_ms={"productive": 8_000, "compile": 2_000})
        eng.observe_train("app", led, 1000)
        doc = eng.status(1000)
        o = doc["objectives"]["train-goodput"]
        assert (o["good"], o["bad"]) == (8_000, 2_000)
        assert o["unit"] == "ms"


# ---------------------------------------------------------------------------
# status / window rows / jsonl sink
# ---------------------------------------------------------------------------
class TestEngineSurfaces:
    def test_status_document_shape(self):
        eng = obs_slo.SloEngine(engine_cfg(), app_id="app-1")
        eng.observe_serve("t", avail_snap(ok=99, err=1), 500)
        doc = eng.status(500)
        assert doc["app_id"] == "app-1" and doc["enabled"]
        o = doc["objectives"]["serve-availability"]
        assert (o["good"], o["bad"]) == (99, 1)
        assert 0.0 <= o["budget_remaining"] <= 1.0
        assert o["exemplars"] == []

    def test_window_rows_and_sink(self, tmp_path):
        sink = tmp_path / "slo.jsonl"
        eng = obs_slo.SloEngine(engine_cfg(), app_id="app-1",
                                sink_path=str(sink))
        eng.observe_serve("t", avail_snap(ok=10, err=2), 1500)
        eng.append_windows(1500)
        eng.observe_serve("t", avail_snap(ok=20, err=2), 1800)  # same bucket
        eng.append_windows(1800)
        rows = [json.loads(line) for line in sink.read_text().splitlines()]
        assert len(rows) == 2
        assert all(r["window_start_ms"] == 1000 for r in rows)
        # the bucket is re-emitted as it fills: the LAST write is the fullest
        assert (rows[0]["good"], rows[1]["good"]) == (10, 20)
        assert rows[1]["app_id"] == "app-1"
        assert rows[1]["objective"] == "serve-availability"
        assert rows[1]["window_end_ms"] == 2000

    def test_ttft_exemplars_merge_worst_across_snapshots(self):
        eng = obs_slo.SloEngine(engine_cfg(**{
            keys.SLO_SERVE_TTFT_TARGET: "0.99",
            keys.SLO_SERVE_AVAILABILITY_TARGET: ""}))
        snap = [{"name": "tony_serve_ttft_seconds", "buckets": [0.5, 2.0],
                 "samples": [{"counts": [1, 0], "count": 2,
                              "exemplars": [[3.0, "slow-1"], [0.2, "fast"]]}]}]
        eng.observe_serve("t", snap, 1000)
        snap2 = [{"name": "tony_serve_ttft_seconds", "buckets": [0.5, 2.0],
                  "samples": [{"counts": [1, 0], "count": 1,
                               "exemplars": [[7.0, "slow-2"]]}]}]
        eng.observe_serve("t", snap2, 2000)
        ex = eng.status(2000)["objectives"]["serve-ttft"]["exemplars"]
        assert [e["request_id"] for e in ex[:2]] == ["slow-2", "slow-1"]


# ---------------------------------------------------------------------------
# verdict
# ---------------------------------------------------------------------------
def row(objective, start, good, bad, target=0.9, source="app"):
    return {"app_id": source, "objective": objective, "target": target,
            "unit": "requests", "window_start_ms": start,
            "window_end_ms": start + 1000, "good": good, "bad": bad}


class TestVerdict:
    def test_pass_fail_no_data(self):
        rows = [row("serve-availability", 1000, 95, 5)]
        v = obs_slo.verdict_from_rows(rows, 60_000, 5_000)
        assert v["verdict"] == "PASS"
        o = v["objectives"]["serve-availability"]
        assert o["achieved"] == pytest.approx(0.95) and o["passed"]
        assert o["budget_burned_pct"] == pytest.approx(50.0)

        v = obs_slo.verdict_from_rows(
            [row("serve-availability", 1000, 80, 20)], 60_000, 5_000)
        assert v["verdict"] == "FAIL"
        assert v["objectives"]["serve-availability"]["budget_burned_pct"] == (
            pytest.approx(200.0))

        assert obs_slo.verdict_from_rows([], 60_000, 5_000)["verdict"] == "NO_DATA"

    def test_window_filter_sums_only_recent_rows(self):
        rows = [row("serve-availability", 0, 0, 100),        # ancient disaster
                row("serve-availability", 90_000, 99, 1)]
        v = obs_slo.verdict_from_rows(rows, 10_000, 95_000)
        o = v["objectives"]["serve-availability"]
        assert (o["good"], o["bad"]) == (99, 1) and v["verdict"] == "PASS"

    def test_one_failing_objective_fails_overall(self):
        rows = [row("serve-availability", 1000, 99, 1),
                row("serve-ttft", 1000, 50, 50, target=0.99)]
        v = obs_slo.verdict_from_rows(rows, 60_000, 5_000)
        assert v["verdict"] == "FAIL"
        assert v["objectives"]["serve-availability"]["passed"]
        assert not v["objectives"]["serve-ttft"]["passed"]

    def test_malformed_rows_are_skipped(self):
        rows = [{"objective": "x"}, {"window_start_ms": "?"}, None and {},
                row("serve-availability", 1000, 9, 1)]
        v = obs_slo.verdict_from_rows([r for r in rows if r], 60_000, 5_000)
        assert v["objectives"]["serve-availability"]["rows"] == 1


# ---------------------------------------------------------------------------
# history store: slo_series
# ---------------------------------------------------------------------------
class TestStoreSloSeries:
    def test_put_is_replace_idempotent(self, tmp_path):
        store = HistoryStore(str(tmp_path / "h.sqlite"))
        try:
            early = row("serve-availability", 1000, 10, 1)
            full = row("serve-availability", 1000, 30, 2)
            assert store.put_slo_windows("app", [early]) == 1
            assert store.put_slo_windows("app", [full, early]) == 2
            # re-sweeping converges: one row per (source, objective, bucket)
            got = store.slo_series(source="app")
            assert len(got) == 1
            assert (got[0]["good"], got[0]["bad"]) == (10, 1) or (
                got[0]["good"], got[0]["bad"]) == (30, 2)
            # the LAST write wins (REPLACE): early re-put after full
            assert (got[0]["good"], got[0]["bad"]) == (10, 1)
        finally:
            store.close()

    def test_filters_and_purge(self, tmp_path):
        store = HistoryStore(str(tmp_path / "h.sqlite"))
        try:
            store.put_slo_windows("a", [row("serve-ttft", 1000, 5, 0),
                                        row("serve-ttft", 2000, 5, 1)])
            store.put_slo_windows("b", [row("serve-availability", 1000, 9, 0)])
            assert len(store.slo_series()) == 3
            assert len(store.slo_series(objective="serve-ttft")) == 2
            assert len(store.slo_series(source="b")) == 1
            assert len(store.slo_series(since_ms=1500)) == 1
            assert store.purge_slo_older_than(2500) == 2
            assert len(store.slo_series()) == 1
        finally:
            store.close()

    def test_rows_without_keys_are_skipped(self, tmp_path):
        store = HistoryStore(str(tmp_path / "h.sqlite"))
        try:
            n = store.put_slo_windows("a", [{"good": 1}, {"objective": "x"},
                                            row("serve-ttft", 1000, 1, 0)])
            assert n == 1
        finally:
            store.close()


class TestSweepSloSeries:
    def _stage(self, tmp_path, app_id, rows, torn=False):
        d = tmp_path / app_id
        d.mkdir(parents=True, exist_ok=True)
        (d / "am_status.json").write_text("{}")  # staged_ids discovery marker
        with open(d / "slo.jsonl", "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
            if torn:
                f.write('{"objective": "serve-ttft", "window_')  # torn tail

    def test_sweep_ingests_and_tolerates_torn_tail(self, tmp_path):
        self._stage(tmp_path, "app-1",
                    [row("serve-ttft", 1000, 5, 1, source="app-1"),
                     row("serve-ttft", 1000, 9, 1, source="app-1")],  # re-emit
                    torn=True)
        store = HistoryStore(str(tmp_path / "h.sqlite"))
        try:
            counts = hist_ingest.sweep_slo_series(store, [str(tmp_path)])
            assert counts["files"] == 1 and counts["errors"] == 0
            got = store.slo_series(source="app-1")
            assert len(got) == 1
            assert (got[0]["good"], got[0]["bad"]) == (9, 1)  # last = fullest
            # idempotent: re-sweep converges to the same row
            hist_ingest.sweep_slo_series(store, [str(tmp_path)])
            assert len(store.slo_series(source="app-1")) == 1
        finally:
            store.close()

    def test_retention_purges_old_buckets(self, tmp_path):
        now_ms = 100 * 86_400_000
        old = row("serve-ttft", 1000, 5, 0, source="app-1")
        fresh = row("serve-ttft", now_ms - 1000, 5, 0, source="app-1")
        self._stage(tmp_path, "app-1", [old, fresh])
        store = HistoryStore(str(tmp_path / "h.sqlite"))
        try:
            counts = hist_ingest.sweep_slo_series(
                store, [str(tmp_path)], retention_days=7.0, now_ms=now_ms)
            assert counts["purged_rows"] == 1
            got = store.slo_series(source="app-1")
            assert len(got) == 1 and got[0]["window_start_ms"] == now_ms - 1000
        finally:
            store.close()


# ---------------------------------------------------------------------------
# CLI: merged rows dedupe + verdict exit codes + status fallback
# ---------------------------------------------------------------------------
class TestSloCli:
    def _stage(self, tmp_path, app_id, rows):
        d = tmp_path / app_id
        d.mkdir(parents=True, exist_ok=True)
        with open(d / "slo.jsonl", "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")

    def test_merged_rows_never_double_count(self, tmp_path):
        """The verdict sums rows — a bucket present in BOTH the store and
        the jsonl must be counted once (the jsonl copy, at least as fresh
        as the last sweep, wins)."""
        from tony_tpu.cli import slo as cli_slo

        jsonl = [row("serve-availability", 1000, 10, 1, source="app-1"),
                 row("serve-availability", 1000, 50, 2, source="app-1")]
        self._stage(tmp_path, "app-1", jsonl)
        store_path = str(tmp_path / "h.sqlite")
        store = HistoryStore(store_path)
        store.put_slo_windows("app-1", jsonl[:1])  # the sweep saw the early copy
        store.close()
        merged = cli_slo._merged_rows(str(tmp_path), "app-1", store_path)
        assert len(merged) == 1
        assert (merged[0]["good"], merged[0]["bad"]) == (50, 2)

    def test_verdict_exit_codes_from_persisted_rows(self, tmp_path, capsys):
        from tony_tpu.cli import slo as cli_slo

        now_ms = int(time.time() * 1000)
        self._stage(tmp_path, "app-1",
                    [row("serve-availability", now_ms - 5000, 99, 1)])
        rc = cli_slo.main(["verdict", "app-1", "--staging", str(tmp_path),
                           "--store", str(tmp_path / "h.sqlite")])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0 and doc["verdict"] == "PASS"
        assert doc["app_id"] == "app-1"

        self._stage(tmp_path, "app-2",
                    [row("serve-availability", now_ms - 5000, 50, 50)])
        assert cli_slo.main(["verdict", "app-2", "--staging", str(tmp_path),
                             "--store", str(tmp_path / "h.sqlite")]) == 1
        capsys.readouterr()
        assert cli_slo.main(["verdict", "absent", "--staging", str(tmp_path),
                             "--store", str(tmp_path / "h.sqlite")]) == 2

    def test_status_falls_back_to_persisted_rows(self, tmp_path, capsys):
        from tony_tpu.cli import slo as cli_slo

        self._stage(tmp_path, "app-1", [
            dict(row("serve-availability", 1000, 95, 5),
                 burn_fast=0.5, burn_slow=0.4, budget_remaining=0.5)])
        # bare `tony slo <app_id>` means status; no AM registered → replay
        rc = cli_slo.main(["app-1", "--staging", str(tmp_path),
                           "--store", str(tmp_path / "h.sqlite")])
        out = capsys.readouterr().out
        assert rc == 0
        assert "last persisted state" in out
        assert "serve-availability" in out and "good 95 bad 5" in out

    def test_status_missing_app_is_an_error(self, tmp_path, capsys):
        from tony_tpu.cli import slo as cli_slo

        rc = cli_slo.main(["nothing-here", "--staging", str(tmp_path),
                           "--store", str(tmp_path / "h.sqlite")])
        assert rc == 1
        assert "no SLO data" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# gate: slo_verdict contract + budget_burned_pct direction
# ---------------------------------------------------------------------------
def bench_record(n, **parsed):
    base = {"metric": "serve_tokens_per_sec", "value": 100.0, "unit": "tok/s",
            "vs_baseline": 1.0}
    base.update(parsed)
    return {"n": n, "rc": 0, "parsed": base}


class TestGateSloContract:
    def test_verdict_pass_is_a_passing_contract(self):
        res = bench_gate.evaluate(bench_record(2, slo_verdict="PASS"),
                                  [("r1.json", bench_record(1))])
        checks = {c.metric: c for c in res.checks}
        assert checks["slo_verdict"].passed
        assert checks["slo_verdict"].reference_from == "contract"

    def test_verdict_fail_and_no_data_fail_the_gate(self):
        for bad in ("FAIL", "NO_DATA"):
            res = bench_gate.evaluate(bench_record(2, slo_verdict=bad),
                                      [("r1.json", bench_record(1))])
            checks = {c.metric: c for c in res.checks}
            assert not checks["slo_verdict"].passed
            assert not res.passed

    def test_absent_verdict_is_not_checked(self):
        res = bench_gate.evaluate(bench_record(2),
                                  [("r1.json", bench_record(1))])
        assert "slo_verdict" not in {c.metric for c in res.checks}

    def test_budget_burned_gates_downward(self):
        res = bench_gate.evaluate(
            bench_record(2, budget_burned_pct=80.0),
            [("r1.json", bench_record(1, budget_burned_pct=10.0))])
        checks = {c.metric: c for c in res.checks}
        assert "budget_burned_pct" in checks
        assert not checks["budget_burned_pct"].passed

    def test_validate_record_rejects_unknown_verdicts(self):
        errs = bench_gate.validate_record(bench_record(1, slo_verdict="MAYBE"))
        assert any("slo_verdict" in e for e in errs)
        assert bench_gate.validate_record(bench_record(1, slo_verdict="PASS")) == []


# ---------------------------------------------------------------------------
# diurnal arrival profile
# ---------------------------------------------------------------------------
class TestArrivalOffsets:
    def test_uniform_is_fixed_spacing(self):
        assert arrival_offsets(4, 2.0) == [0.0, 0.5, 1.0, 1.5]

    def test_diurnal_keeps_total_duration_and_is_deterministic(self):
        a = arrival_offsets(40, 8.0, profile="diurnal")
        b = arrival_offsets(40, 8.0, profile="diurnal")
        assert a == b  # the spike's timing is part of the spec
        assert len(a) == 40
        assert a == sorted(a)
        assert a[-1] <= 40 / 8.0  # same total duration as uniform

    def test_diurnal_is_denser_mid_run(self):
        offs = arrival_offsets(60, 6.0, profile="diurnal", amp=3.0)
        total = 60 / 6.0
        head = sum(1 for t in offs if t < total / 3)
        mid = sum(1 for t in offs if total / 3 <= t <= 2 * total / 3)
        tail = sum(1 for t in offs if t > 2 * total / 3)
        # the spike: the middle third out-draws EACH shoulder by far
        assert mid > 1.5 * head and mid > 1.5 * tail

    def test_degenerate_inputs(self):
        assert arrival_offsets(0, 5.0, "diurnal") == []
        assert arrival_offsets(3, 0.0, "diurnal") == [0.0, 0.0, 0.0]


# ---------------------------------------------------------------------------
# autoscaler: SLO burn is up-pressure and a scale-down veto
# ---------------------------------------------------------------------------
class TestAutoscalerSloBurn:
    def _scaler(self, burn=None):
        from tests.test_serve_fleet import FakeAM, make_health

        am = FakeAM()
        h = make_health(am)
        p = AutoscalePolicy(min_replicas=1, max_replicas=4,
                            scale_up_ticks=2, scale_down_ticks=2)
        return Autoscaler(h, lambda job, n: am.call(
            "resize_jobtype", job_name=job, instances=n), p, burn=burn), am

    def _sig(self, healthy=2, queue=0, active=0, total=16):
        from tony_tpu.serve.health import FleetSignals

        return FleetSignals(replicas_known=healthy, replicas_healthy=healthy,
                            queue_depth=queue, slots_active=active,
                            slots_total=total)

    def test_burning_is_up_pressure_on_an_idle_fleet(self):
        sc, _ = self._scaler()
        sig = self._sig()  # zero queue, zero utilization
        assert sc.decide(2, sig, burning=True) == 2   # tick 1 of 2
        assert sc.decide(2, sig, burning=True) == 3   # sustained burn → +1

    def test_burning_vetoes_scale_down(self):
        sc, _ = self._scaler()
        sig = self._sig()
        assert sc.decide(2, sig, burning=True) == 2
        # without burn this second idle tick would shrink (down_ticks=2)
        assert sc.decide(2, sig, burning=True) == 3
        sc2, _ = self._scaler()
        assert sc2.decide(2, sig) == 2
        assert sc2.decide(2, sig) == 1  # the control: idle fleet shrinks

    def test_tick_consults_the_burn_supplier(self):
        from tests.test_serve_fleet import FakeReplica

        burns = iter([5.0, 5.0])
        sc, am = self._scaler(burn=lambda: next(burns))
        rep = FakeReplica()
        try:
            am.set_replica(0, rep.url)
            sc.health._resolve()
            sc.health.tick()
            sc.tick()
            sc.tick()  # burn ≥ 1 for scale_up_ticks samples → resize up
            assert am.resizes == [("serve", 2)]
        finally:
            rep.close()

    def test_burn_supplier_failure_never_breaks_the_tick(self):
        def boom():
            raise RuntimeError("AM mid-exit")

        from tests.test_serve_fleet import FakeReplica

        sc, am = self._scaler(burn=boom)
        rep = FakeReplica()
        try:
            am.set_replica(0, rep.url)
            sc.health._resolve()
            sc.health.tick()
            sc.tick()  # must not raise; load signals still decide
            assert am.resizes == []
        finally:
            rep.close()


# ---------------------------------------------------------------------------
# zero-allocation request-span path (tracing disabled)
# ---------------------------------------------------------------------------
class TestRequestSpanAllocationFree:
    def test_disabled_tracing_allocates_no_spans(self, monkeypatch):
        """The acceptance contract: with tracing off, the per-request span
        chain is a single attribute check — constructing a Span at all is a
        regression. Enforced by making the constructor explosive."""
        from tony_tpu.models.serving_http import RequestStream

        monkeypatch.setattr(obs_trace, "_tracer", None)

        def explode(*a, **k):
            raise AssertionError("Span allocated with tracing disabled")

        monkeypatch.setattr(obs_trace, "Span", explode)
        assert obs_trace.start_manual("serve.request", rid="r-1") is None
        stream = RequestStream(request_id="r-1")
        stream.open_trace()
        # the stage seconds are computed either way (they feed the histogram);
        # only the spans are conditional
        assert [label for label, _ in stream.stages(time.time())] == ["queue"]
        stream.begin_stage("serve.decode", ttft_s=0.1)
        stream.finish_trace("ok")
        assert stream.span is None and stream.stage is None

    def test_enabled_tracing_builds_the_chain(self, tmp_path, monkeypatch):
        from tony_tpu.models.serving_http import RequestStream

        tracer = obs_trace.Tracer("trace-1", "serve:0", str(tmp_path))
        monkeypatch.setattr(obs_trace, "_tracer", tracer)
        from tony_tpu.models.serving import _Request

        stream = RequestStream(request_id="req-42")
        stream.open_trace()
        root_id = stream.span.span_id
        assert stream.span.attrs["rid"] == "req-42"
        # the engine stamps the boundaries; the chain is written from them
        # at the first fanout (tests/test_serve_trace.py drives a real engine)
        t0 = stream.submitted_s
        stream.req = _Request(0, [1, 2, 3], 4, slot=1, staged_s=t0 + 0.01, slot_s=t0 + 0.04)
        assert [round(s, 6) for _, s in stream.stages(t0 + 0.05)] == [0.01, 0.03, 0.01]
        stream.begin_stage("serve.decode", ttft_s=0.05)
        stream.finish_trace("ok")
        tracer.close()
        spans = [json.loads(line)
                 for p in tmp_path.glob("*.jsonl")
                 for line in open(p).read().splitlines()]
        by_name = {s["name"]: s for s in spans}
        assert {"serve.request", "serve.queue", "serve.prefill", "serve.emit",
                "serve.decode"} == set(by_name)
        for stage in ("serve.queue", "serve.prefill", "serve.emit", "serve.decode"):
            assert by_name[stage]["parent_id"] == root_id
        assert by_name["serve.queue"]["end_ms"] == by_name["serve.prefill"]["start_ms"]
        assert by_name["serve.prefill"]["end_ms"] == by_name["serve.emit"]["start_ms"]
        assert by_name["serve.emit"]["attrs"]["slot"] == 1
        assert by_name["serve.decode"]["attrs"]["ttft_s"] == 0.05


# ---------------------------------------------------------------------------
# router request ids
# ---------------------------------------------------------------------------
class TestRouterRequestIds:
    def test_router_assigns_and_echoes_request_id(self):
        from tests.test_serve_fleet import (
            FakeAM, FakeReplica, inject, make_health, make_router, post_router)

        rep, am = FakeReplica(), FakeAM()
        h = make_health(am)
        router = make_router(h)
        try:
            inject(h, 0, rep.url)
            _, hdrs, _ = post_router(router.url, {"prompt_tokens": [1]})
            rid = hdrs.get("X-Tony-Request-Id")
            assert rid  # assigned at the front door
            _, hdrs2, _ = post_router(router.url, {"prompt_tokens": [1]})
            assert hdrs2["X-Tony-Request-Id"] != rid  # unique per request
        finally:
            router.stop()
            rep.close()

    def test_client_supplied_id_is_kept(self):
        import urllib.request

        from tests.test_serve_fleet import (
            FakeAM, FakeReplica, inject, make_health, make_router)

        rep, am = FakeReplica(), FakeAM()
        h = make_health(am)
        router = make_router(h)
        try:
            inject(h, 0, rep.url)
            req = urllib.request.Request(
                router.url + "/v1/completions",
                json.dumps({"prompt_tokens": [1]}).encode(),
                {"Content-Type": "application/json",
                 "X-Tony-Request-Id": "client-rid-7"})
            resp = urllib.request.urlopen(req, timeout=30)
            assert resp.headers["X-Tony-Request-Id"] == "client-rid-7"
        finally:
            router.stop()
            rep.close()


# ---------------------------------------------------------------------------
# headline e2e
# ---------------------------------------------------------------------------
@pytest.mark.e2e
class TestSloHeadlineE2E:
    """Diurnal load over a live router/fleet: a mid-spike error burst burns
    the availability budget fast enough to page, the page resolves once the
    burst ends, the budget rows persist through slo.jsonl → the history
    store, and `tony slo verdict` reads PASS from those PERSISTED rows.

    The replica fleet is the suite's fake (real HTTP, injectable failures)
    so the burst is deterministic — the capacity market's live spike e2e
    (tests/test_market.py) already drives real replicas; this headline
    pins down the SLO plane's seams end to end: router rids → loadtest
    worst-TTFT exemplars, live turns → ledgers → AlertEngine transitions →
    jsonl → store sweep → CLI verdict.
    """

    def test_diurnal_burn_fires_resolves_and_verdict_passes(
            self, tmp_path, capsys):
        from tony_tpu.cli import slo as cli_slo
        from tests.test_serve_fleet import (
            FakeAM, FakeReplica, make_health, make_router)

        app_id = "app-slo-e2e"
        staging = tmp_path / app_id
        staging.mkdir()
        (staging / "am_status.json").write_text("{}")  # staged_ids marker
        reps = [FakeReplica(), FakeReplica()]
        am = FakeAM()
        # a LIVE monitor (unlike the hand-ticked unit tests): the 500-burst
        # passively ejects both replicas from the router's rotation, and the
        # probe loop is what brings them back once the burst ends
        h = make_health(am, interval_s=0.1)
        router = make_router(h)
        c = cfg(**{
            keys.SLO_SERVE_AVAILABILITY_TARGET: "0.5",  # lenient: PASS overall
            keys.SLO_WINDOW_MS: "60000",
            keys.SLO_BUCKET_MS: "250",
            keys.SLO_FAST_BURN: "1.05",         # page on any unsustainable burn
            keys.SLO_FAST_WINDOW_MS: "750",
            keys.SLO_SLOW_BURN: "100.0",        # keep the slow rule quiet
            keys.SLO_SLOW_WINDOW_MS: "12000",
        })
        eng = obs_slo.SloEngine(c, app_id=app_id,
                                sink_path=str(staging / "slo.jsonl"))
        alert_engine = obs_alerts.AlertEngine(eng.burn_rules(), app_id=app_id)
        transitions = []
        try:
            for i, rep in enumerate(reps):
                am.set_replica(i, rep.url)
            h.tick()
            h.start()

            spec = LoadSpec(url=router.url, sessions=48, turns=1, rate=12.0,
                            profile="diurnal", stream=False, timeout_s=30.0)
            gen = LoadGenerator(spec)
            total_s = spec.sessions / spec.rate  # 4s

            stop = threading.Event()

            def flip_errors():
                # the burst sits inside the diurnal spike (dense middle):
                # 20% of wall time but ~1.6× the mean arrival density, so it
                # claims ~1/3 of the turns — enough to page, not to FAIL a
                # 0.5 availability target over the whole run
                time.sleep(total_s * 0.40)
                for rep in reps:
                    rep.cfg["status"] = 500
                time.sleep(total_s * 0.20)
                for rep in reps:
                    rep.cfg["status"] = 200

            def ticker():
                # the AM's goodput-tick analogue: live cumulative counters
                # from the real run's finished turns → ledger → alert engine
                while not stop.is_set():
                    with gen._lock:
                        turns = list(gen._results)
                    ok = sum(1 for t in turns if t.ok)
                    bad = len(turns) - ok
                    now_ms = int(time.time() * 1000)
                    if turns:
                        eng.observe_serve(
                            "serve:0",
                            avail_snap(ok=ok, err=bad), now_ms)
                    transitions.extend(
                        alert_engine.evaluate(eng.tick(now_ms)))
                    eng.append_windows(now_ms)
                    stop.wait(0.2)

            flipper = threading.Thread(target=flip_errors, daemon=True)
            tick_thread = threading.Thread(target=ticker, daemon=True)
            flipper.start()
            tick_thread.start()
            report = gen.run()
            flipper.join()
            stop.set()
            tick_thread.join(timeout=5)
            # keep ticking after the run: with the burst over, the SHORT
            # confirm window drains of error traffic and the page RESOLVES
            # long before the fast window itself is clean (the workbook's
            # prompt-resolve property) — no synthetic traffic needed
            with gen._lock:
                ok = sum(1 for t in gen._results if t.ok)
                bad = len(gen._results) - ok
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                now_ms = int(time.time() * 1000)
                eng.observe_serve("serve:0", avail_snap(ok=ok, err=bad), now_ms)
                transitions.extend(alert_engine.evaluate(eng.tick(now_ms)))
                eng.append_windows(now_ms)
                states = {(t["rule"], t["state"]) for t in transitions}
                if ("slo-serve-availability-fast-burn", "resolved") in states:
                    break
                time.sleep(0.2)

            states = {(t["rule"], t["state"]) for t in transitions}
            assert ("slo-serve-availability-fast-burn", "fired") in states, (
                f"fast burn never fired; transitions={transitions}, "
                f"errors={len(report.errors)}/{len(report.turns)}")
            assert ("slo-serve-availability-fast-burn", "resolved") in states

            # the run really was diurnal and really failed mid-spike
            d = report.to_dict()
            assert d["profile"] == "diurnal"
            assert report.errors, "the burst produced no failed turns"
            # worst-TTFT exemplars carry router-assigned request ids
            assert d.get("worst_ttft"), "no worst-TTFT exemplars in the report"
            assert all(w["request_id"] for w in d["worst_ttft"])

            # persisted rows survive the AM: sweep slo.jsonl into the store,
            # then judge the verdict from PERSISTED state only
            store_path = str(tmp_path / "history.sqlite")
            store = HistoryStore(store_path)
            try:
                counts = hist_ingest.sweep_slo_series(store, [str(tmp_path)])
                assert counts["rows"] > 0 and counts["errors"] == 0
            finally:
                store.close()
            rc = cli_slo.main([
                "verdict", app_id, "--staging", str(tmp_path),
                "--store", store_path, "--window", "3600"])
            verdict = json.loads(capsys.readouterr().out)
            assert rc == 0, f"verdict not PASS: {verdict}"
            assert verdict["verdict"] == "PASS"
            o = verdict["objectives"]["serve-availability"]
            assert o["bad"] > 0  # the burst is in the history
            assert 0.0 < o["budget_burned_pct"] < 100.0
        finally:
            stop.set()
            router.stop()
            h.stop()
            for rep in reps:
                rep.close()
