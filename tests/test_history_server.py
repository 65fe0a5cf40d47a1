"""Persistent history tier suite (docs/history.md).

Covers the three layers of the history subsystem: the shared artifact index
(obs/artifacts.py) and the discovery-parity contracts of the consumers it
replaced (portal scrape, ``tony trace``, ``tony logs``); ``.jhist``
torn-file tolerance (a byte-chopped history ingests its intact prefix as
``incomplete``); the SQLite store (idempotent re-ingest, compaction,
retention); the ingestion sweep and staging-dir GC; the ``tony history``
CLI; the ``tony history-server`` daemon; and the headline e2e — two real
fixture jobs ingested by a live daemon, compared, trend-rendered by the
portal, with ``tony bench --gate`` enforcing the perf trajectory.
"""

import json
import os
import threading
import time
import urllib.request

import pytest

from tony_tpu import constants
from tony_tpu.cluster.events import EventHandler, EventType
from tony_tpu.cluster.history import finalize_history
from tony_tpu.config import TonyConfig, keys
from tony_tpu.histserver import ingest as hist_ingest
from tony_tpu.histserver.gate import evaluate, parsed_of, validate_record
from tony_tpu.histserver.server import HistoryServer
from tony_tpu.histserver.store import HistoryStore, compact_series
from tony_tpu.obs import artifacts as obs_artifacts
from tony_tpu.obs import logging as obs_logging

pytestmark = [pytest.mark.history]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# fixture tree builders
# ---------------------------------------------------------------------------
def make_staging(root, app_id, conf=None, final=True):
    """A staging dir with the client/AM artifacts the index resolves."""
    d = os.path.join(str(root), app_id)
    os.makedirs(d, exist_ok=True)
    TonyConfig(dict(conf or {})).write_final(d)
    if final:
        with open(os.path.join(d, "am_status.json"), "w") as f:
            json.dump({"app_id": app_id, "status": "SUCCEEDED"}, f)
    return d


def emit_history(root, app_id, *, snapshots=3, finish="SUCCEEDED",
                 extra=(), finalize=True, started_ms=1_000, completed_ms=9_000,
                 user="tester"):
    """One job's .jhist (intermediate, optionally finalized) with a small
    metrics series and the counters the distiller reads."""
    hist = os.path.join(str(root), "history")
    eh = EventHandler(hist, app_id)
    eh.start()
    eh.emit(EventType.APPLICATION_INITED, app_id=app_id)
    eh.emit(EventType.QUEUE_WAIT, state="waiting", reason="test")
    eh.emit(EventType.QUEUE_WAIT, state="admitted")
    eh.emit(EventType.GANG_COMPLETE, tasks=1)
    for ev_type, payload in extra:
        eh.emit(ev_type, **payload)
    for s in range(1, snapshots + 1):
        eh.emit(EventType.METRICS_SNAPSHOT, tasks=[{
            "task": "worker:0",
            "metrics": {"train": {
                "step": s, "loss": 2.0 / s, "mfu": 0.4 + 0.01 * s,
                "tokens_per_sec": 1000.0 + s,
            }},
        }])
        time.sleep(0.012)  # distinct timestamps → derived step_time_ms
    if finish:
        eh.emit(EventType.APPLICATION_FINISHED, status=finish,
                tasks=[{"name": "worker", "index": 0, "status": finish}])
    eh.stop()
    if finalize:
        return finalize_history(
            hist, app_id, eh.intermediate_path, started_ms, completed_ms,
            finish or "FAILED", config_snapshot={"tony.worker.instances": "1"},
            user=user)
    return eh.intermediate_path


def make_job(root, app_id, **kw):
    make_staging(root, app_id)
    return emit_history(root, app_id, **kw)


# ---------------------------------------------------------------------------
# artifact index
# ---------------------------------------------------------------------------
class TestArtifactIndex:
    def test_default_layout(self, tmp_path):
        make_staging(tmp_path, "app1", final=False)
        art = obs_artifacts.index(str(tmp_path), "app1")
        assert art.staging_dir == os.path.join(str(tmp_path), "app1")
        assert art.history_root == os.path.join(str(tmp_path), "history")
        assert art.log_dir == os.path.join(art.staging_dir, "logs")
        assert art.trace_dir == os.path.join(art.staging_dir, "trace")
        assert art.profile_dir == os.path.join(art.staging_dir, "profile")
        assert not art.finalized and art.jhist_path is None
        assert art.am_status() is None

    def test_frozen_config_overrides(self, tmp_path):
        conf = {
            keys.LOG_DIR: str(tmp_path / "elsewhere-logs"),
            keys.TRACE_DIR: str(tmp_path / "elsewhere-trace"),
            keys.HISTORY_LOCATION: str(tmp_path / "elsewhere-history"),
        }
        make_staging(tmp_path, "app2", conf=conf)
        art = obs_artifacts.index(str(tmp_path), "app2")
        assert art.log_dir == conf[keys.LOG_DIR]
        assert art.trace_dir == conf[keys.TRACE_DIR]
        assert art.history_root == conf[keys.HISTORY_LOCATION]

    def test_running_then_finalized(self, tmp_path):
        make_staging(tmp_path, "app3", final=False)
        inter = emit_history(tmp_path, "app3", finalize=False)
        art = obs_artifacts.index(str(tmp_path), "app3")
        assert not art.finalized and art.jhist_path == inter
        assert obs_artifacts.running_ids(art.history_root) == ["app3"]
        dest = finalize_history(
            art.history_root, "app3", inter, 100, 200, "SUCCEEDED", user="u")
        art = obs_artifacts.index(str(tmp_path), "app3")
        assert art.finalized and art.jhist_path == dest
        assert art.history_file.status == "SUCCEEDED"
        assert art.history_file.user == "u"
        assert os.path.dirname(art.config_snapshot_path) == os.path.dirname(dest)
        assert obs_artifacts.running_ids(art.history_root) == []

    def test_staged_ids_recognizes_job_dirs(self, tmp_path):
        make_staging(tmp_path, "appA")
        make_staging(tmp_path, "appB", final=False)
        (tmp_path / "history").mkdir(exist_ok=True)
        (tmp_path / "random-dir").mkdir()
        assert obs_artifacts.staged_ids(str(tmp_path)) == ["appA", "appB"]

    # -- discovery parity: every producer contract the index replaced -------
    def test_logs_discovery_parity(self, tmp_path):
        """`tony logs` resolution == the writer-side resolve_log_dir, with
        and without the tony.log.dir override."""
        make_staging(tmp_path, "appL")
        assert (obs_artifacts.index(str(tmp_path), "appL").log_dir
                == obs_logging.resolve_log_dir(str(tmp_path), "appL"))
        make_staging(tmp_path, "appM", conf={keys.LOG_DIR: str(tmp_path / "ov")})
        assert (obs_artifacts.index(str(tmp_path), "appM").log_dir
                == obs_logging.resolve_log_dir(str(tmp_path), "appM")
                == str(tmp_path / "ov"))

    def test_trace_discovery_parity(self, tmp_path):
        """`tony trace` resolves the span dir (incl. tony.trace.dir) through
        the index, and the shared span reader tolerates torn files."""
        from tony_tpu.cli import trace as trace_cli

        assert trace_cli.load_spans is obs_artifacts.load_spans
        override = tmp_path / "spans-here"
        override.mkdir()
        (override / "am.spans.jsonl").write_text(
            json.dumps({"span_id": "s1", "start_ms": 1.0, "identity": "am"})
            + "\n{torn")
        make_staging(tmp_path, "appT", conf={keys.TRACE_DIR: str(override)})
        art = obs_artifacts.index(str(tmp_path), "appT")
        assert art.trace_dir == str(override)
        assert [s["span_id"] for s in obs_artifacts.load_spans(art.trace_dir)] == ["s1"]

    def test_portal_scrape_parity(self, tmp_path):
        """The portal's running/finished listing and per-job lookups all come
        from the index (same fixture tree, same answers)."""
        from tony_tpu.portal.server import PortalHandler

        make_staging(tmp_path, "appP", final=False)
        emit_history(tmp_path, "appP", finalize=False)
        make_job(tmp_path, "appQ")
        hist_root = os.path.join(str(tmp_path), "history")
        handler = type("H", (PortalHandler,), {
            "history_root": hist_root, "staging_root": str(tmp_path)})
        # class-level helpers only — no HTTP socket needed
        assert handler._running_ids(handler) == ["appP"]
        assert [j.app_id for j in obs_artifacts.finished_jobs(hist_root)] == ["appQ"]
        art = handler._art(handler, "appQ")
        assert art.finalized and art.history_root == hist_root

    def test_no_private_discovery_walks(self):
        """Grep-style contract: the three refactored consumers resolve every
        artifact through obs/artifacts.py — no direct path construction for
        AM advertisements, final status, intermediate history, frozen
        config, or directory walks."""
        forbidden = ("AM_INFO_FILE", "HISTORY_INTERMEDIATE_DIR",
                     "am_status" + ".json", "TONY_FINAL_CONF",
                     "resolve_log_dir", "os.walk(")
        for rel in ("tony_tpu/portal/server.py", "tony_tpu/cli/trace.py",
                    "tony_tpu/cli/introspect.py"):
            src = open(os.path.join(REPO_ROOT, rel)).read()
            assert "artifacts" in src, f"{rel} does not use the artifact index"
            for pat in forbidden:
                assert pat not in src, f"{rel} re-implements discovery: {pat}"


# ---------------------------------------------------------------------------
# torn/truncated .jhist hardening
# ---------------------------------------------------------------------------
class TestTornJhist:
    def test_byte_chopped_tail_keeps_prefix(self, tmp_path):
        path = make_job(tmp_path, "appX", snapshots=4)
        data = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(data[:-17])  # SIGKILL mid final line
        events, complete = obs_artifacts.read_history_events(path)
        assert not complete
        assert events, "intact prefix must survive"
        assert events[0].type == EventType.APPLICATION_INITED
        # the torn final line is dropped, everything before it is kept
        assert len(events) == data.decode().strip().count("\n")

    def test_mid_file_garbage_keeps_intact_prefix(self, tmp_path):
        path = make_job(tmp_path, "appY")
        lines = open(path).read().splitlines()
        with open(path, "w") as f:
            f.write("\n".join(lines[:3]) + "\n}{garbage\n" + "\n".join(lines[3:]) + "\n")
        events, complete = obs_artifacts.read_history_events(path)
        assert not complete and len(events) == 3

    def test_missing_finish_event_is_incomplete(self, tmp_path):
        path = make_job(tmp_path, "appZ", finish=None, finalize=False)
        events, complete = obs_artifacts.read_history_events(path)
        assert events and not complete

    def test_chopped_job_ingests_as_incomplete(self, tmp_path):
        """The satellite contract: a job killed mid-write must ingest its
        intact prefix and be marked incomplete, never raise."""
        path = make_job(tmp_path, "appW", snapshots=5)
        data = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(data[: int(len(data) * 0.7)])
        store = HistoryStore(":memory:")
        art = obs_artifacts.index(str(tmp_path), "appW")
        assert hist_ingest.ingest_job(store, art) == "ingested"
        row = store.get_job("appW")
        assert row["incomplete"] is True
        assert row["status"] == "SUCCEEDED"  # the filename encoding survives
        assert store.series("appW", "mfu")   # prefix series distilled
        store.close()


# ---------------------------------------------------------------------------
# store
# ---------------------------------------------------------------------------
class TestStore:
    def test_put_job_is_idempotent(self, tmp_path):
        store = HistoryStore(str(tmp_path / "h.sqlite"))
        job = {"app_id": "a1", "status": "SUCCEEDED", "completed_ms": 10}
        series = {"mfu": [(1, 0.4), (2, 0.5)]}
        store.put_job(job, series=series, summary={"mfu": {"p50": 0.4}})
        store.put_job(job, series=series, summary={"mfu": {"p50": 0.4}})
        assert store.count() == 1
        assert store.series("a1", "mfu") == [(1, 0.4), (2, 0.5)]
        store.close()

    def test_reingest_replaces_series(self, tmp_path):
        store = HistoryStore(str(tmp_path / "h.sqlite"))
        store.put_job({"app_id": "a1", "status": "FAILED"},
                      series={"mfu": [(1, 0.1)], "loss": [(1, 3.0)]})
        store.put_job({"app_id": "a1", "status": "SUCCEEDED"},
                      series={"mfu": [(1, 0.2)]})
        assert store.get_job("a1")["status"] == "SUCCEEDED"
        assert store.series("a1", "mfu") == [(1, 0.2)]
        assert store.series("a1", "loss") == []  # stale series dropped
        store.close()

    def test_compaction_bounds_series(self):
        points = [(i, float(i)) for i in range(1000)]
        out = compact_series(points, 50)
        assert len(out) <= 50
        assert out[0] == (0, 0.0) and out[-1] == (999, 999.0)
        assert out == sorted(out)
        store = HistoryStore(":memory:", max_series_points=50)
        store.put_job({"app_id": "a", "status": "SUCCEEDED"}, series={"mfu": points})
        assert len(store.series("a", "mfu")) <= 50
        store.close()

    def test_retention_purges_old_jobs(self):
        store = HistoryStore(":memory:")
        store.put_job({"app_id": "old", "status": "SUCCEEDED", "completed_ms": 100},
                      series={"mfu": [(1, 0.4)]})
        store.put_job({"app_id": "new", "status": "SUCCEEDED", "completed_ms": 10_000})
        assert store.purge_older_than(5_000) == ["old"]
        assert [j["app_id"] for j in store.list_jobs()] == ["new"]
        assert store.series("old", "mfu") == []
        store.close()

    def test_trend_orders_by_completion(self):
        store = HistoryStore(":memory:")
        for app, t, mfu in (("b", 200, 0.5), ("a", 100, 0.4), ("c", 300, 0.6)):
            store.put_job({"app_id": app, "status": "SUCCEEDED", "completed_ms": t},
                          summary={"mfu": {"p50": mfu}})
        assert [p["app_id"] for p in store.trend("mfu")] == ["a", "b", "c"]
        assert [p["value"] for p in store.trend("mfu")] == [0.4, 0.5, 0.6]
        # row-level counters trend straight off the jobs table
        assert len(store.trend("gang_epochs")) == 3
        store.close()


# ---------------------------------------------------------------------------
# ingestion
# ---------------------------------------------------------------------------
class TestIngest:
    def test_distill_counters_and_series(self, tmp_path):
        make_job(tmp_path, "appD", snapshots=4, extra=(
            (EventType.GANG_RESIZED, {"job_name": "worker", "to": 2}),
            (EventType.AM_TAKEOVER, {"attempt": 1}),
        ))
        art = obs_artifacts.index(str(tmp_path), "appD")
        job, series, summary = hist_ingest.distill(art)
        assert job["status"] == "SUCCEEDED" and not job["incomplete"]
        assert job["gang_epochs"] == 1 and job["resizes"] == 1 and job["takeovers"] == 1
        assert job["duration_ms"] == 8_000
        assert len(series["mfu"]) == 4 and len(series["loss"]) == 4
        assert "step_time_ms" in series  # derived from step/timestamp deltas
        assert summary["mfu"]["last"] == pytest.approx(0.44)
        assert summary["mfu"]["p50"] <= summary["mfu"]["max"]

    def test_sweep_is_idempotent_until_source_changes(self, tmp_path):
        path = make_job(tmp_path, "appS")
        store = HistoryStore(":memory:")
        assert hist_ingest.sweep(store, [str(tmp_path)])["ingested"] == 1
        counts = hist_ingest.sweep(store, [str(tmp_path)])
        assert counts["ingested"] == 0 and counts["unchanged"] >= 1
        os.utime(path, ns=(1, 1))  # source changed → re-ingest
        assert hist_ingest.sweep(store, [str(tmp_path)])["ingested"] == 1
        store.close()

    def test_sweep_skips_live_jobs_and_survives_garbage(self, tmp_path):
        make_staging(tmp_path, "appLive", final=False)
        emit_history(tmp_path, "appLive", finalize=False)
        make_job(tmp_path, "appDone")
        (tmp_path / "appGarbage").mkdir()
        (tmp_path / "appGarbage" / constants.TONY_FINAL_CONF).write_text("{not json")
        store = HistoryStore(":memory:")
        counts = hist_ingest.sweep(store, [str(tmp_path)])
        assert counts["ingested"] == 1
        assert store.get_job("appLive") is None
        store.close()

    def test_sweep_applies_retention(self, tmp_path):
        """Jobs past retention are never ingested in the first place (an
        ingest→purge cycle would otherwise repeat every sweep forever, since
        the finished .jhist deliberately outlives the store row), and rows
        that age past the cutoff in place get purged."""
        make_job(tmp_path, "appOld", completed_ms=1_000)
        make_job(tmp_path, "appFresh", completed_ms=9 * 86_400_000)
        store = HistoryStore(":memory:")
        now = 10 * 86_400_000
        counts = hist_ingest.sweep(store, [str(tmp_path)],
                                   retention_days=5, now_ms=now)
        assert counts["expired"] == 1 and counts["ingested"] == 1
        assert [j["app_id"] for j in store.list_jobs()] == ["appFresh"]
        # ...and the expired job stays out on the NEXT sweep too (no cycle)
        counts = hist_ingest.sweep(store, [str(tmp_path)],
                                   retention_days=5, now_ms=now)
        assert counts["expired"] == 1 and counts["ingested"] == 0
        # a row that ages past the cutoff in place is purged
        counts = hist_ingest.sweep(store, [str(tmp_path)], retention_days=5,
                                   now_ms=now + 10 * 86_400_000)
        assert counts["purged"] == 1 and store.count() == 0
        store.close()

    def test_reingests_after_staging_gc(self, tmp_path):
        """A job whose staging dir was GC'd is still discoverable through the
        finished history tree (fresh store rebuild)."""
        make_job(tmp_path, "appG")
        import shutil

        shutil.rmtree(tmp_path / "appG")
        store = HistoryStore(":memory:")
        assert hist_ingest.sweep(store, [str(tmp_path)])["ingested"] == 1
        assert store.get_job("appG")["status"] == "SUCCEEDED"
        store.close()


# ---------------------------------------------------------------------------
# staging-dir GC
# ---------------------------------------------------------------------------
class TestGC:
    def _prepare(self, tmp_path):
        store = HistoryStore(":memory:")
        make_job(tmp_path, "appOld", completed_ms=1_000)
        make_job(tmp_path, "appFresh", completed_ms=90 * 86_400_000)
        make_staging(tmp_path, "appLive", final=False)
        emit_history(tmp_path, "appLive", finalize=False)
        make_job(tmp_path, "appUningested", completed_ms=1_000)
        hist_ingest.ingest_job(store, obs_artifacts.index(str(tmp_path), "appOld"))
        hist_ingest.ingest_job(store, obs_artifacts.index(str(tmp_path), "appFresh"))
        return store, 100 * 86_400_000  # "now"

    def test_dry_run_lists_but_keeps(self, tmp_path):
        store, now = self._prepare(tmp_path)
        removed = hist_ingest.gc_staging(store, str(tmp_path), retention_days=30,
                                         dry_run=True, now_ms=now)
        assert [a for a, _ in removed] == ["appOld"]
        assert (tmp_path / "appOld").exists()
        store.close()

    def test_gc_removes_only_ingested_old_finalized(self, tmp_path):
        store, now = self._prepare(tmp_path)
        removed = hist_ingest.gc_staging(store, str(tmp_path), retention_days=30,
                                         now_ms=now)
        assert [a for a, _ in removed] == ["appOld"]
        assert not (tmp_path / "appOld").exists()
        # fresh, live, and un-ingested jobs are untouchable
        assert (tmp_path / "appFresh").exists()
        assert (tmp_path / "appLive").exists()
        assert (tmp_path / "appUningested").exists()
        # the finished .jhist (the forensic record) survives its staging dir
        assert obs_artifacts.index(str(tmp_path), "appOld").finalized
        store.close()

    def test_gc_requires_positive_retention(self, tmp_path):
        store, now = self._prepare(tmp_path)
        assert hist_ingest.gc_staging(store, str(tmp_path), retention_days=0,
                                      now_ms=now) == []
        store.close()


# ---------------------------------------------------------------------------
# tony history CLI
# ---------------------------------------------------------------------------
class TestHistoryCLI:
    def test_ingest_list_show_compare(self, tmp_path, capsys):
        from tony_tpu.cli.history import main as history_main

        make_job(tmp_path, "app_one")
        make_job(tmp_path, "app_two", snapshots=5)
        staging = ["--staging", str(tmp_path)]
        assert history_main(["ingest", *staging]) == 0
        capsys.readouterr()
        assert history_main(["list", *staging]) == 0
        out = capsys.readouterr().out
        assert "app_one" in out and "app_two" in out and "epochs=1" in out
        assert history_main(["show", "app_one", *staging]) == 0
        out = capsys.readouterr().out
        assert "mfu_p50" in out and "SUCCEEDED" in out
        assert history_main(["compare", "app_one", "app_two", *staging]) == 0
        out = capsys.readouterr().out
        assert "app_one" in out and "app_two" in out and "tokens_per_sec_p50" in out

    def test_show_falls_back_to_inline_distill(self, tmp_path, capsys):
        from tony_tpu.cli.history import main as history_main

        make_job(tmp_path, "app_ni")
        assert history_main(["show", "app_ni", "--staging", str(tmp_path)]) == 0
        assert "not ingested" in capsys.readouterr().out

    def test_legacy_spelling_dumps_events(self, tmp_path, capsys):
        from tony_tpu.cli.history import main as history_main

        make_job(tmp_path, "app_legacy")
        assert history_main(["app_legacy", "--staging", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "APPLICATION_INITED" in out and "APPLICATION_FINISHED" in out

    def test_legacy_flag_first_spelling(self, tmp_path, capsys):
        """Pre-store muscle memory: `tony history --root <history_dir>
        [app_id]` keeps listing/dumping."""
        from tony_tpu.cli.history import main as history_main

        make_job(tmp_path, "app_flags")
        hist_root = os.path.join(str(tmp_path), "history")
        assert history_main(["--root", hist_root]) == 0
        assert "app_flags" in capsys.readouterr().out
        assert history_main(["--root", hist_root, "app_flags"]) == 0
        assert "APPLICATION_FINISHED" in capsys.readouterr().out

    def test_gc_cli_dry_run(self, tmp_path, capsys):
        from tony_tpu.cli.history import main as history_main

        make_job(tmp_path, "app_gc", completed_ms=1_000)
        staging = ["--staging", str(tmp_path)]
        assert history_main(["ingest", *staging]) == 0
        assert history_main(["gc", "--retention-days", "30", "--dry-run",
                             *staging]) == 0
        out = capsys.readouterr().out
        assert "would remove" in out and "app_gc" in out
        assert (tmp_path / "app_gc").exists()

    def test_unknown_job_errors(self, tmp_path, capsys):
        from tony_tpu.cli.history import main as history_main

        assert history_main(["show", "ghost", "--staging", str(tmp_path)]) == 1


# ---------------------------------------------------------------------------
# daemon
# ---------------------------------------------------------------------------
class TestHistoryServerDaemon:
    def test_serves_health_metrics_and_queries(self, tmp_path):
        make_job(tmp_path, "app_d1")
        srv = HistoryServer([str(tmp_path)], store_path=str(tmp_path / "h.sqlite"),
                            port=0, scan_interval_s=0.2)
        srv.start()
        base = f"http://127.0.0.1:{srv.address[1]}"
        try:
            health = json.loads(urllib.request.urlopen(base + "/healthz").read())
            assert health["ok"] and health["jobs"] == 1
            jobs = json.loads(urllib.request.urlopen(base + "/api/jobs").read())
            assert [j["app_id"] for j in jobs] == ["app_d1"]
            one = json.loads(urllib.request.urlopen(base + "/api/job/app_d1").read())
            assert "mfu" in one["series"]
            series = json.loads(
                urllib.request.urlopen(base + "/api/series/app_d1/mfu").read())
            assert len(series) >= 2
            metrics = urllib.request.urlopen(base + "/metrics").read().decode()
            assert "tony_history_ingests_total" in metrics
            assert "tony_history_jobs 1" in metrics
            # a job finalized while the daemon runs is picked up by the sweep
            make_job(tmp_path, "app_d2")
            deadline = time.time() + 10
            while time.time() < deadline:
                jobs = json.loads(urllib.request.urlopen(base + "/api/jobs").read())
                if len(jobs) == 2:
                    break
                time.sleep(0.1)
            assert len(jobs) == 2
            trend = json.loads(
                urllib.request.urlopen(base + "/api/trend/mfu").read())
            assert len(trend) == 2
        finally:
            srv.stop()

    def test_404_and_root_page(self, tmp_path):
        srv = HistoryServer([str(tmp_path)], store_path=":memory:", port=0)
        srv.start()
        base = f"http://127.0.0.1:{srv.address[1]}"
        try:
            import urllib.error

            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(base + "/api/job/nope")
            body = urllib.request.urlopen(base + "/").read().decode()
            assert "tony history server" in body
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# portal /history pages
# ---------------------------------------------------------------------------
class TestPortalHistoryPages:
    def test_trend_dashboard_and_job_page(self, tmp_path):
        from tony_tpu.portal.server import serve

        for app, base_mfu in (("app_p1", 2), ("app_p2", 4)):
            make_job(tmp_path, app, snapshots=base_mfu)
        store_path = os.path.join(str(tmp_path), "history", "history.sqlite")
        store = HistoryStore(store_path)
        hist_ingest.sweep(store, [str(tmp_path)])
        store.close()
        server = serve(os.path.join(str(tmp_path), "history"), 0, str(tmp_path))
        threading.Thread(target=server.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{server.server_address[1]}"
        try:
            body = urllib.request.urlopen(base + "/history").read().decode()
            assert "app_p1" in body and "app_p2" in body
            assert "<svg" in body  # cross-job trend sparklines
            detail = urllib.request.urlopen(base + "/history/app_p1").read().decode()
            assert "summary" in detail and "mfu" in detail
            # finished job page links its history entry
            job = urllib.request.urlopen(base + "/job/app_p1").read().decode()
            assert "/history/app_p1" in job
            api = json.loads(
                urllib.request.urlopen(base + "/api/history/trend/mfu").read())
            assert len(api) == 2
        finally:
            server.shutdown()

    def test_history_page_without_store(self, tmp_path):
        from tony_tpu.portal.server import serve

        server = serve(str(tmp_path), 0)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{server.server_address[1]}"
        try:
            body = urllib.request.urlopen(base + "/history").read().decode()
            assert "no history store" in body
        finally:
            server.shutdown()


# ---------------------------------------------------------------------------
# finalized-job links (tony top / monitor)
# ---------------------------------------------------------------------------
class TestFinalizedLinks:
    def test_tony_top_points_at_history(self, tmp_path, capsys):
        from tony_tpu.cli.introspect import main_top

        make_job(tmp_path, "app_fin")
        assert main_top(["app_fin", "--staging", str(tmp_path), "--once"]) == 0
        out = capsys.readouterr().out
        assert "finished: SUCCEEDED" in out
        assert "tony history show app_fin" in out

    def test_monitor_final_print_mentions_history(self, tmp_path, capsys):
        from tony_tpu.cluster.client import ApplicationHandle, _print_final

        handle = ApplicationHandle("app_m", str(tmp_path / "app_m"), None)
        _print_final(handle, {"status": "SUCCEEDED", "tasks": []})
        assert "tony history show app_m" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# headline e2e: two real jobs → live daemon → compare/gate/portal
# ---------------------------------------------------------------------------
@pytest.mark.e2e
class TestHistoryE2E:
    def test_two_jobs_ingested_compared_gated_and_rendered(
            self, tmp_tony_root, tmp_path, capsys):
        from tests.test_e2e import FAST, fixture_cmd
        from tony_tpu.cli.history import main as history_main, main_bench
        from tony_tpu.cluster.client import Client
        from tony_tpu.cluster.session import JobStatus
        from tony_tpu.portal.server import serve

        app_ids = []
        for mfu_base in ("0.40", "0.44"):
            cfg = TonyConfig({
                **FAST,
                keys.STAGING_ROOT: str(tmp_tony_root),
                keys.TASK_METRICS_INTERVAL_MS: "100",
                "tony.worker.instances": "1",
                keys.EXECUTES: f"{fixture_cmd('history_train.py')} 8 {mfu_base}",
            })
            client = Client(cfg)
            handle = client.submit()
            final = client.monitor_application(handle, quiet=True)
            assert final == JobStatus.SUCCEEDED, handle.final_status()
            app_ids.append(handle.app_id)

        # a LIVE history server ingests both finalized jobs
        srv = HistoryServer([str(tmp_tony_root)],
                            store_path=str(tmp_path / "e2e.sqlite"),
                            port=0, scan_interval_s=0.2)
        srv.start()
        base = f"http://127.0.0.1:{srv.address[1]}"
        try:
            deadline = time.time() + 15
            jobs = []
            while time.time() < deadline:
                jobs = json.loads(urllib.request.urlopen(base + "/api/jobs").read())
                if len(jobs) >= 2:
                    break
                time.sleep(0.2)
            assert sorted(j["app_id"] for j in jobs) == sorted(app_ids)
            for j in jobs:
                assert j["status"] == "SUCCEEDED" and not j["incomplete"]
                assert j["gang_epochs"] == 1
            # the distilled MFU trend separates the two runs
            trend = json.loads(urllib.request.urlopen(base + "/api/trend/mfu").read())
            assert len(trend) == 2
            health = json.loads(urllib.request.urlopen(base + "/healthz").read())
            assert health["ok"] and health["jobs"] == 2
        finally:
            srv.stop()

        # tony history compare shows both runs side by side
        capsys.readouterr()
        assert history_main([
            "compare", *app_ids, "--staging", str(tmp_tony_root),
            "--store", str(tmp_path / "e2e.sqlite")]) == 0
        out = capsys.readouterr().out
        assert all(a in out for a in app_ids) and "mfu_p50" in out

        # tony bench --gate: PASS on a trajectory of bench.py rounds...
        traj_dir = tmp_path / "trajectory"
        traj_dir.mkdir()
        for n, mfu in enumerate((0.46, 0.48, 0.49), 1):
            (traj_dir / f"BENCH_r{n:02d}.json").write_text(json.dumps({
                "n": n, "cmd": "python bench.py", "rc": 0,
                "parsed": {"metric": "llama_train_mfu_1chip_1chip", "value": mfu,
                           "unit": "mfu", "vs_baseline": round(mfu / 0.45, 4),
                           "warmup_s": 10.0 + n, "device_kind": "TPU v5 lite"}}))
        assert main_bench(["--gate", "--trajectory-dir", str(traj_dir)]) == 0
        # ...and nonzero on a synthetically regressed record
        regressed = json.loads((traj_dir / "BENCH_r03.json").read_text())
        regressed["parsed"]["value"] *= 0.5
        regressed["parsed"]["vs_baseline"] *= 0.5
        reg_path = tmp_path / "regressed.json"
        reg_path.write_text(json.dumps(regressed))
        capsys.readouterr()
        assert main_bench(["--gate", "--trajectory-dir", str(traj_dir),
                           "--record", str(reg_path)]) == 1
        assert "REGRESSION" in capsys.readouterr().out

        # portal /history renders the trend with both runs
        server = serve(os.path.join(str(tmp_tony_root), "history"), 0,
                       str(tmp_tony_root),
                       history_db=str(tmp_path / "e2e.sqlite"))
        threading.Thread(target=server.serve_forever, daemon=True).start()
        pbase = f"http://127.0.0.1:{server.server_address[1]}"
        try:
            body = urllib.request.urlopen(pbase + "/history").read().decode()
            assert all(a in body for a in app_ids)
            assert "<svg" in body  # the cross-job trend chart rendered
        finally:
            server.shutdown()


# ---------------------------------------------------------------------------
# gate units (the trajectory-wide tier-1 check lives in test_bench_gate.py)
# ---------------------------------------------------------------------------
class TestGateUnits:
    TRAJ = [
        ("BENCH_r01.json", {"n": 1, "rc": 0, "parsed": {
            "metric": "m", "value": 0.40, "unit": "mfu", "vs_baseline": 0.9,
            "step_time_ms": 1500.0}}),
        ("BENCH_r02.json", {"n": 2, "rc": 0, "parsed": {
            "metric": "m", "value": 0.45, "unit": "mfu", "vs_baseline": 1.0,
            "step_time_ms": 1400.0}}),
    ]

    def test_pass_when_at_best(self):
        cur = {"metric": "m", "value": 0.45, "unit": "mfu", "vs_baseline": 1.0}
        assert evaluate(cur, self.TRAJ).passed

    def test_fail_past_threshold(self):
        cur = {"metric": "m", "value": 0.40, "unit": "mfu", "vs_baseline": 0.88}
        res = evaluate(cur, self.TRAJ, tolerance_pct=5.0)
        assert not res.passed
        assert any(c.metric == "value" and not c.passed for c in res.checks)

    def test_lower_is_better_direction(self):
        cur = {"metric": "m", "value": 0.45, "unit": "mfu", "vs_baseline": 1.0,
               "step_time_ms": 1600.0}  # 14% slower than best 1400
        res = evaluate(cur, self.TRAJ)
        assert any(c.metric == "step_time_ms" and not c.passed for c in res.checks)

    def test_per_metric_threshold_override(self):
        cur = {"metric": "m", "value": 0.45, "unit": "mfu", "vs_baseline": 1.0,
               "step_time_ms": 1600.0}
        res = evaluate(cur, self.TRAJ, per_metric_pct={"step_time_ms": 20.0})
        assert all(c.passed for c in res.checks if c.metric == "step_time_ms")

    def test_kernel_smoke_failure_gates(self):
        cur = {"metric": "m", "value": 0.45, "unit": "mfu", "vs_baseline": 1.0,
               "kernel_smoke": "7/8"}
        res = evaluate(cur, self.TRAJ)
        assert not res.passed
        assert any(c.metric == "kernel_smoke" and not c.passed for c in res.checks)

    def test_fresh_trajectory_passes_with_note(self):
        """A preset change (renamed headline metric) or a first-ever record
        has nothing to regress against: pass-with-note, it BECOMES the
        trajectory to beat."""
        cur = {"metric": "other", "value": 0.1, "unit": "mfu", "vs_baseline": 0.2}
        res = evaluate(cur, self.TRAJ)
        assert res.passed
        assert "fresh trajectory" in res.checks[-1].note
        # ...but a kernel-smoke failure still gates a fresh trajectory
        cur["kernel_smoke"] = "6/8"
        assert not evaluate(cur, self.TRAJ).passed

    def test_single_record_trajectory_self_check_passes(self):
        only = self.TRAJ[:1]
        assert evaluate(only[0][1], only).passed

    def test_schema_validation(self):
        assert validate_record({"n": 1, "rc": 0, "parsed": {
            "metric": "m", "value": 0.4, "unit": "mfu", "vs_baseline": 1.0}}) == []
        errs = validate_record({"n": 1, "rc": 1, "parsed": {"metric": "m"}})
        assert any("rc" in e for e in errs)
        assert any("value" in e for e in errs)
        assert validate_record({"metric": "m", "value": float("nan"),
                                "unit": "u", "vs_baseline": 1.0}, wrapper=False)

    def test_parsed_of_unwraps(self):
        inner = {"metric": "m", "value": 1.0}
        assert parsed_of({"parsed": inner}) is inner
        assert parsed_of(inner) is inner
