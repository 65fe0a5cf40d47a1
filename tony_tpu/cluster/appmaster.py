"""The Application Master: per-job control plane.

Analog of the reference's ``TonyApplicationMaster.java`` (SURVEY.md §2.1,
§3.1): runs inside the cluster (here: a subprocess the client spawns, playing
YARN-RM-launches-AM), serves the ApplicationRpc surface, drives the
gang/dependency scheduler against a ResourceManager, launches a TaskExecutor
per container, monitors heartbeats, reduces the tracked/untracked verdict,
emits history events, and finalizes the ``.jhist`` on exit.

Implicit invariants carried over from the reference (SURVEY.md §7 hard part
(e)): registration-before-spec (the gang barrier), idempotent task completion,
tracked/untracked verdict reduction, untracked tasks killed at job end.

Rebuild-only addition (SURVEY.md §5.3/§5.4): optional whole-gang restart on
task failure (``tony.task.restart-on-failure``) so jobs resume from their
latest checkpoint instead of failing fast.
"""

from __future__ import annotations

import argparse
import json
import os
import secrets as _secrets
import signal
import sys
import time
from typing import Any

from tony_tpu import constants
from tony_tpu.chaos import ChaosContext
from tony_tpu.config import TonyConfig, keys
from tony_tpu.cluster import history
from tony_tpu.cluster.journal import (
    SNAPSHOT_RECORD,
    Journal,
    JournalError,
    iter_journal,
)
from tony_tpu.obs import alerts as obs_alerts
from tony_tpu.obs import goodput as obs_goodput
from tony_tpu.obs import introspect as obs_introspect
from tony_tpu.obs import locktrace as obs_locktrace
from tony_tpu.obs import logging as obs_logging
from tony_tpu.obs import metrics as obs_metrics
from tony_tpu.obs import slo as obs_slo
from tony_tpu.obs import trace as obs_trace
from tony_tpu.cluster.events import EventHandler, EventType
from tony_tpu.cluster.resources import (
    AllocationError,
    AllocationPending,
    Container,
    LocalResourceManager,
    ResourceManager,
)
from tony_tpu.cluster.scheduler import (
    DependencyTimeout,
    TaskScheduler,
    gang_fits,
    plan_downsize,
    plan_preempt_shrink,
)
from tony_tpu.cluster.rpc import APPLICATION_RPC_METHODS, RpcServer
from tony_tpu.cluster.session import JobStatus, Session, TaskStatus
from tony_tpu.runtime import get_runtime
from tony_tpu.runtime.base import FrameworkRuntime

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_QUEUE_WAIT = obs_metrics.histogram(
    "tony_scheduler_queue_wait_seconds",
    "time a gang spent queued behind other tenants before admission",
    buckets=obs_metrics.WAIT_BUCKETS)
_GANG_RESTARTS = obs_metrics.counter(
    "tony_gang_restarts_total", "whole-gang restarts (failure, preemption, capacity loss)")
_GANG_RESIZES = obs_metrics.counter(
    "tony_gang_resizes_total",
    "requested elastic resizes by outcome (applied, rejected, noop)",
    labelnames=("outcome",))
_PROFILE_REPORTS = obs_metrics.counter(
    "tony_profile_reports_total",
    "per-task on-demand capture reports by status (delivered, captured, error)",
    labelnames=("status",))
_ELASTIC_RESIZES = obs_metrics.counter(
    "tony_elastic_resizes_total",
    "applied elastic resizes by direction (grow, shrink, mixed) and trigger "
    "(rpc, preempt, capacity)",
    labelnames=("direction", "trigger"))
_AM_TAKEOVERS = obs_metrics.counter(
    "tony_am_takeovers_total",
    "relaunched-AM takeover attempts by outcome (adopted: live gang kept "
    "running; degraded: journal missing/corrupt, full gang restart)",
    labelnames=("outcome",))
_TAKEOVER_SECONDS = obs_metrics.histogram(
    "tony_am_takeover_duration_seconds",
    "journal replay + gang adoption latency of a successful AM takeover")
_STRAGGLER_COUNT = obs_metrics.gauge(
    "tony_straggler_count",
    "ranks currently flagged as stragglers (step time persistently over the "
    "gang median by tony.goodput.straggler-factor)")
_STRAGGLER_SKEW = obs_metrics.gauge(
    "tony_straggler_skew_ratio",
    "per-rank step-time / gang-median ratio from the last goodput tick",
    labelnames=("task",))
_GOODPUT_FRACTION = obs_metrics.gauge(
    "tony_goodput_fraction",
    "productive fraction of wall-clock over the trailing "
    "tony.goodput.window-ms (obs/goodput.py phase ledger)")


class InvalidResizeError(ValueError):
    """A ``resize_jobtype`` request that can never be applied: unknown
    jobtype, target < 1, outside the ``tony.elastic.*`` bounds, or a
    conflicting resize for the same jobtype already pending. Reaches remote
    callers BY NAME through the RPC error frame (like AlreadyProfilingError),
    so ``tony resize`` / ``tony serve`` can distinguish a rejected request
    from a transport failure."""


def build_resource_manager(config: TonyConfig, app_id: str = "") -> ResourceManager:
    """Pool factory from ``tony.tpu.pool``:
    - 'local:<accel>[,RxC]' → LocalResourceManager (one host, one slice),
    - 'pool:<accel>-<chips>x<num_slices>' → MultiSliceResourceManager
      (several ICI slices joined by DCN, best-fit gang packing),
    - 'rm:<host>:<port>' → RemoteResourceManager against a running pool
      service + host-agent fleet (cluster/pool.py — the YARN RM/NM split).

    The spec string lives in the frozen config so the same artifact drives
    tests (cpu pool), one TPU VM, a multi-slice emulation, or a real
    multi-host pool.
    """
    spec = config.get(keys.TPU_POOL_SPEC) or "local:cpu"
    if spec.startswith("local:"):
        return LocalResourceManager(spec)
    if spec.startswith("pool:"):
        from tony_tpu.cluster.resources import MultiSliceResourceManager

        return MultiSliceResourceManager(spec)
    if spec.startswith("rm:"):
        from tony_tpu.cluster.pool import RemoteResourceManager

        _, host, port = spec.split(":")
        secret = _pool_credential(config)
        return RemoteResourceManager(host, int(port), secret=secret, app_id=app_id)
    raise ValueError(f"unknown resource pool spec: {spec!r}")


def _pool_credential(config: TonyConfig) -> str:
    """Credential for a secured pool service, resolved in order: explicit
    ``tony.tpu.pool.secret`` → TONY_POOL_SECRET env → the keytab file
    (``tony.keytab.location`` — the reference's Kerberos-keytab analog: a
    file on disk carrying the cluster credential). ``tony.keytab.user``,
    when set, asserts the submitting identity the way a kinit would."""
    user = config.get(keys.KEYTAB_USER)
    if user:
        import getpass

        actual = getpass.getuser()
        if user != actual:
            raise PermissionError(
                f"tony.keytab.user={user!r} but submitting as {actual!r}"
            )
    secret = config.get(keys.TPU_POOL_SECRET) or os.environ.get(constants.ENV_POOL_SECRET, "")
    if not secret:
        keytab = config.get(keys.KEYTAB_LOCATION)
        if keytab:
            if not os.path.exists(keytab):
                raise FileNotFoundError(f"tony.keytab.location={keytab} does not exist")
            with open(keytab) as f:
                secret = f.read().strip()
    return secret


class _JournalState:
    """Recoverable AM state reconstructed from the takeover journal."""

    def __init__(self) -> None:
        self.attempt = 0                                      # gang epoch
        self.resized: dict[str, int] = {}                     # elastic resizes applied
        self.pending: dict[str, int] = {}                     # acked-unapplied resizes
        self.failures = 0                                     # spent restart budget
        self.gang_complete = False
        self.chaos_step = 0                                   # @step+N watermark
        self.registered: dict[tuple[str, int], tuple[str, int]] = {}
        self.done: dict[tuple[str, int], int] = {}
        self.containers: dict[str, dict[str, Any]] = {}       # cid → task_started rec

    def _reset_epoch(self, attempt: int, resized: dict[str, int]) -> None:
        self.attempt = attempt
        self.resized = resized
        self.gang_complete = False
        self.registered = {}
        self.done = {}
        self.containers = {}


def _replay_am_journal(records) -> _JournalState:
    """Fold journal records (any iterable — takeover streams them) into the
    state a takeover AM adopts.

    Each ``epoch`` record marks a session rebuild (gang restart / queued
    resize): everything task-scoped before it is obsolete. Cross-epoch
    state (failure budget, pending resizes, chaos watermark) accumulates
    with last-record-wins semantics. A compaction ``snapshot`` record is a
    barrier: everything before it is folded history — replay resets and
    folds the embedded records (which carry their own epoch) instead.
    """
    state = _JournalState()
    saw_epoch = False
    for rec in records:
        t = rec.get("t")
        if t == SNAPSHOT_RECORD:
            inner = rec.get("records")
            if not isinstance(inner, list):
                raise JournalError("snapshot record carries no records")
            state = _replay_am_journal(inner)  # raises unless it has an epoch
            saw_epoch = True
        elif t == "epoch":
            saw_epoch = True
            state._reset_epoch(int(rec.get("attempt", 0)),
                               {k: int(v) for k, v in (rec.get("resized") or {}).items()})
        elif t == "registered":
            state.registered[(str(rec["job"]), int(rec["index"]))] = (
                str(rec["host"]), int(rec["port"]))
        elif t == "gang_complete":
            state.gang_complete = True
        elif t == "task_started":
            state.containers[str(rec["cid"])] = rec
        elif t == "task_done":
            state.done[(str(rec["job"]), int(rec["index"]))] = int(rec["exit_code"])
        elif t == "pending_resize":
            state.pending = {k: int(v) for k, v in (rec.get("resizes") or {}).items()}
        elif t == "failures":
            state.failures = int(rec.get("n", 0))
        elif t == "chaos_step":
            state.chaos_step = max(state.chaos_step, int(rec.get("step", 0)))
        elif t == "takeover":
            pass  # informational: a predecessor attempt adopted successfully
        else:
            # an unknown record type means a NEWER tony wrote this journal —
            # adopting a state we only half understand risks silent data
            # loss, which is exactly what the degraded path is for
            raise JournalError(f"unknown journal record type {t!r}")
    if not saw_epoch:
        raise JournalError("journal carries no epoch record")
    return state


class ApplicationMaster:
    def __init__(
        self,
        config: TonyConfig,
        app_id: str,
        staging_dir: str,
        rm: ResourceManager | None = None,
        takeover: bool = False,
        am_attempt: int = 0,
    ):
        self.config = config
        self.app_id = app_id
        self.staging_dir = staging_dir
        # work-preserving restart (tony.am.takeover.enabled): this process
        # journals its recoverable state; a retried attempt launched with
        # --takeover replays the journal and ADOPTS the live gang
        self.am_attempt = am_attempt
        self._takeover_enabled = config.get_bool(keys.AM_TAKEOVER_ENABLED, True)
        self._takeover_requested = takeover and self._takeover_enabled
        self._takeover_outcome: str | None = None  # "adopted" | "degraded" | None
        self._journal: Journal | None = (
            Journal(os.path.join(staging_dir, constants.AM_JOURNAL_FILE))
            if self._takeover_enabled else None
        )
        # takeover-journal compaction (tony.am.journal.compact-every): the
        # monitor loop — never an RPC handler — folds the recoverable state
        # into a snapshot record and rotates once this many appends pile up.
        # 0 (the default) keeps the append-forever behavior.
        self._journal_compact_every = config.get_int(keys.AM_JOURNAL_COMPACT_EVERY, 0)
        self._journal_chaos_step = 0
        obs_metrics.set_enabled(config.get_bool(keys.METRICS_ENABLED, True))
        # structured logging (tony.log.*): JSONL records under <staging>/logs
        # that `tony logs` merges with every other process's; the console
        # echo keeps am.log human-readable exactly as before
        obs_logging.init_from_config(config, identity="am", staging_dir=staging_dir)
        # tracing (tony.trace.*): None — and zero-cost — unless enabled; the
        # root span parent arrives from the submitting client via env
        self.tracer = obs_trace.init_from_config(
            config, identity="am", staging_dir=staging_dir, app_id=app_id,
            parent_id=os.environ.get(constants.ENV_TRACE_PARENT),
        )
        self._root_span: obs_trace.Span | None = None
        self._root_token = None
        self._queue_wait_started: float | None = None
        # fault injection (tony.chaos.*): None — and zero-cost — unless
        # configured; container faults ride the RM's poll_exited seam
        self.chaos = ChaosContext.from_config(config, identity="am", staging_dir=staging_dir)
        # @step+N gates need the per-tick progress scan; other schedules don't
        self._chaos_step_gated = self.chaos is not None and any(
            f.step_gate for f in self.chaos.schedule.faults)
        self.rm = rm or build_resource_manager(config, app_id)
        self.rm.chaos = self.chaos
        self.runtime = get_runtime(config)
        self.session = Session(config)
        self.scheduler = TaskScheduler(config, self.session, self.rm)
        self.secret = _secrets.token_hex(16)
        self.rpc = RpcServer(host=_local_host(), port=config.get_int(keys.AM_RPC_PORT, 0), secret=self.secret)
        history_root = config.get(keys.HISTORY_LOCATION) or os.path.join(
            os.path.dirname(staging_dir.rstrip("/")), "history"
        )
        self.history_root = history_root
        self.events = EventHandler(history_root, app_id)
        self.started_ms = int(time.time() * 1000)
        self.tensorboard_url: str | None = None
        self._kill_requested = False
        self._containers: dict[str, Container] = {}          # container_id → Container
        self._by_task: dict[tuple[str, int], Container] = {}  # (job, idx) → Container
        self._gang_started_ms: float | None = None
        self._restart_attempt = 0
        self._failures_seen = 0
        self._gang_complete_fired = False
        self._queue_waiting = False
        self._resized: dict[str, int] = {}  # elastic resize: type → instances
        # externally-requested resizes (resize_jobtype RPC — the serving
        # autoscaler's lever) awaiting application by the monitor loop; the
        # RPC handler must never drive the restart machinery itself. Keyed
        # by jobtype so concurrent resizes of different types never clobber
        # an acknowledged-but-unapplied request.
        self._pending_resize: dict[str, int] = {}
        self._client_obs: dict[str, Any] = {}  # submitter-side registries (fleet router)
        #: (task, gang epoch) → the start-up stamps last written to the .jhist
        self._startup_seen: dict[tuple[str, int], dict[str, Any]] = {}
        # hot spares (tony.elastic.spares): pre-allocated, pre-registered
        # executors of the elastic jobtype parked next to the gang. spare_id →
        # {"container", "ready", "assignment"}; assignment != None means the
        # spare was promoted into a gang slot and is no longer spare capacity.
        self._spares: dict[str, dict[str, Any]] = {}
        self._spare_seq = 0
        self._last_spare_topup = 0.0
        # on-demand profiler capture (tony profile): single-slot request
        # state machine, internally locked — RPC handler threads race on it
        self._profile = obs_introspect.ProfileCoordinator()
        # cooperative preemption (docs/scheduling.md): the pool's drain /
        # shrink notice and this AM's response to it — urgent-checkpoint
        # fan-out over the heartbeat piggyback, then yield. Guarded by
        # _epoch_lock: heartbeat/report handler threads race the monitor loop.
        self._drain: dict[str, Any] | None = None
        self._drain_handled: set[str] = set()  # req_ids already acted on
        # per-task drain episodes (request_task_drain): the serving
        # autoscaler's pre-scale-down lever — one task is asked to drain over
        # the same heartbeat/DrainCourier contract the gang-wide preemption
        # fan-out uses; {task_id: {"req_id", "step"}} with step None until
        # the task's done-file ack lands via report_drain_saved
        self._task_drains: dict[str, dict[str, Any]] = {}
        # goodput accounting plane (tony.goodput.*): the monitor loop's
        # throttled tick classifies wall-time, watches for stragglers, and
        # evaluates the declarative tony.alerts.* rules
        self._goodput_enabled = config.get_bool(keys.GOODPUT_ENABLED, True)
        self._goodput_interval_s = config.get_time_ms(keys.GOODPUT_INTERVAL_MS, 5000) / 1000
        self._goodput_window_ms = config.get_time_ms(keys.GOODPUT_WINDOW_MS, 60_000)
        self._straggler = obs_goodput.StragglerDetector(
            factor=float(config.get(keys.GOODPUT_STRAGGLER_FACTOR) or 1.5),
            min_checks=config.get_int(keys.GOODPUT_STRAGGLER_CHECKS, 3),
        )
        self._alerts = obs_alerts.AlertEngine(
            obs_alerts.rules_from_config(config),  # ValueError → fail LOUD at start
            sink=obs_alerts.AlertSink(
                config.get(keys.ALERTS_SINK) or os.path.join(staging_dir, "alerts.jsonl"),
                config.get(keys.ALERTS_WEBHOOK) or None,
            ),
            app_id=app_id,
        )
        # SLO plane (tony.slo.*): declarative objectives with error-budget
        # ledgers; their multi-window burn-rate rules ride THIS SAME alert
        # engine, name-prefixed "slo-" so the tick's emit loop publishes
        # them as SLO_BURN_ALERT/SLO_BURN_RESOLVED instead of ALERT_*
        self._slo = obs_slo.SloEngine(
            config, app_id=app_id,
            sink_path=config.get(keys.SLO_SINK)
            or os.path.join(staging_dir, "slo.jsonl"),
        )
        if self._slo.enabled:
            self._alerts.rules.extend(self._slo.burn_rules())
        self._last_goodput_tick = 0.0
        # incremental .jhist reader: the tick/RPC pay O(new events), not a
        # full re-parse of a multi-day job's history every few seconds
        self._jhist = obs_goodput.JhistFollower(self.events.intermediate_path)
        self._last_capacity_probe = 0.0
        self._capacity_short_since: float | None = None  # downsize hysteresis
        # capacity market (tony.serve.market.enabled): while our allocation
        # pends, publish the unmet deficit to the pool (update_demand) so the
        # preemption policy can fund it by partially shrinking elastic
        # borrowers; cleared the moment the gang places. Advisory: every
        # failure degrades to silence, never to failing the AM.
        self._market_enabled = config.get_bool(keys.SERVE_MARKET_ENABLED, False)
        self._market_slo_ttft_ms = config.get_int(
            keys.SERVE_MARKET_SLO_TTFT_MS, 2000)
        self._market_published = False
        self._last_market_publish = 0.0
        # guards (attempt, session) as one unit: RPC handlers capture both
        # atomically so a stale-attempt call can never touch a fresh session
        self._epoch_lock = obs_locktrace.make_lock(
            "appmaster.ApplicationMaster._epoch_lock")

    # ------------------------------------------------------ takeover journal
    def _jlog(self, t: str, **fields: Any) -> None:
        """Durably journal a recoverable state transition (fsync'd): the
        record vocabulary _replay_am_journal understands. No-op when
        takeover is disabled."""
        if self._journal is not None:
            self._journal.append(t, **fields)

    def _journal_snapshot_records(self) -> list[dict[str, Any]]:
        """The minimal record list that replays to the CURRENT recoverable
        state — the vocabulary ``_replay_am_journal`` folds, captured
        atomically under the epoch lock (+ session lock for task fields).
        A container the RM cannot describe (mid-launch, no pid yet) is
        omitted, the same degrade-on-takeover stance ``_journal_task_started``
        takes."""
        with self._epoch_lock:
            session = self.session
            recs: list[dict[str, Any]] = [
                {"t": "epoch", "attempt": self._restart_attempt,
                 "resized": dict(self._resized)},
                {"t": "failures", "n": self._failures_seen},
                {"t": "pending_resize", "resizes": dict(self._pending_resize)},
            ]
            if self._journal_chaos_step:
                recs.append({"t": "chaos_step", "step": self._journal_chaos_step})
            with session.lock:
                for task in session.all_tasks():
                    if task.host and task.port:
                        recs.append({"t": "registered", "job": task.job_name,
                                     "index": task.index, "host": task.host,
                                     "port": task.port})
                if self._gang_complete_fired:
                    recs.append({"t": "gang_complete"})
                for (job, idx), c in self._by_task.items():
                    task = session.get_task(job, idx)
                    if task.status.terminal:
                        continue
                    info = self.rm.journal_info(c)
                    if info is None:
                        continue
                    recs.append({"t": "task_started", "job": job, "index": idx,
                                 "cid": c.id, "log_dir": task.log_dir,
                                 "started_ms": task.start_time_ms,
                                 "container": info})
                for task in session.all_tasks():
                    if task.status.terminal and task.exit_code is not None:
                        recs.append({"t": "task_done", "job": task.job_name,
                                     "index": task.index,
                                     "exit_code": task.exit_code})
        return recs

    def _maybe_compact_journal(self) -> None:
        """Monitor-loop compaction tick: snapshot + rotate the takeover
        journal once enough appends piled up (tony.am.journal.compact-every;
        docs/performance.md "Control-plane scalability"). Runs only here so
        the snapshot builder may take the epoch lock without deadlocking the
        RPC handlers that journal while holding it."""
        if (
            self._journal is None
            or self._journal_compact_every <= 0
            or self._journal.appends_since_compact < self._journal_compact_every
        ):
            return
        # optimistic: RPC handlers journal WITHOUT the locks the snapshot is
        # built under, so an append racing the build would sort before the
        # stale snapshot and be discarded by the replay barrier. The token
        # makes compact a no-op in that case — retried next tick, when the
        # burst has usually quiesced.
        expected = self._journal.total_appends
        self._journal.compact(self._journal_snapshot_records(),
                              expected_total=expected)

    # ------------------------------------------------------------------ rpc
    def _fenced_session(self, attempt: int) -> Session | None:
        """Fence RPCs from executors of a killed previous gang attempt: their
        (job_name, index) identities recur, so without the epoch a dying old
        executor could poison the replacement session's state. The session is
        captured atomically with the attempt check (same lock as the restart
        swap) so a stale caller can never touch a fresh session."""
        with self._epoch_lock:
            return self.session if attempt == self._restart_attempt else None

    def register_worker_spec(
        self, job_name: str, index: int, host: str, port: int, attempt: int = 0
    ) -> dict[str, Any]:
        session = self._fenced_session(attempt)
        if session is None:
            return {"spec_complete": False, "stale": True}
        session.register_worker_spec(job_name, index, host, port)
        self._jlog("registered", job=job_name, index=index, host=host, port=port)
        self.events.emit(EventType.TASK_REGISTERED, task=f"{job_name}:{index}", host=host, port=port)
        complete = session.cluster_spec_complete()
        fire = False
        if complete:
            # atomic check-and-set: the gang's last two registrations race on
            # separate RPC handler threads, and on_gang_complete must fire
            # exactly once per gang epoch (it assigns collective ranks)
            with self._epoch_lock:
                if not self._gang_complete_fired and session is self.session:
                    self._gang_complete_fired = True
                    fire = True
        if fire:
            self.runtime.on_gang_complete(session)
            self._jlog("gang_complete")
            self.events.emit(EventType.GANG_COMPLETE, tasks=session.total_tasks())
        return {"spec_complete": complete}

    def resync_task(
        self, job_name: str, index: int, host: str, port: int, attempt: int = 0
    ) -> dict[str, Any]:
        """Post-takeover re-attach: an executor that lost its AM and found a
        refreshed ``am_info`` endpoint announces it is still alive (idempotent,
        epoch-fenced like ``get_cluster_spec``). Only an AM that actually
        ADOPTED the gang accepts — on the degraded path the old gang epoch is
        over, and ``stale`` tells the orphaned executor to kill its child and
        exit instead of poisoning the fresh gang's identities."""
        if self._takeover_outcome != "adopted":
            return {"ack": False, "stale": True}
        session = self._fenced_session(attempt)
        if session is None:
            return {"ack": False, "stale": True}
        try:
            with session.lock:
                task = session.get_task(job_name, index)
                task.host, task.port = host, port
                if not task.status.terminal:
                    task.last_heartbeat_ms = time.time() * 1000
                    task.missed_heartbeats = 0
        except KeyError:
            return {"ack": False, "stale": True}
        self.events.emit(EventType.TASK_RESYNCED, task=f"{job_name}:{index}")
        obs_logging.info(f"[tony-am] task {job_name}:{index} re-synced after takeover")
        return {"ack": True}

    def get_cluster_spec(self, job_name: str, index: int, attempt: int = 0) -> dict[str, Any]:
        # epoch-fenced like every other executor-facing RPC: a dying executor
        # from a killed gang epoch must never receive the NEW gang's spec and
        # proceed with the wrong ranks
        session = self._fenced_session(attempt)
        if session is None:
            return {"spec": None, "stale": True}
        spec = session.cluster_spec()
        with self._epoch_lock:
            # capture (fired, attempt) atomically with respect to a
            # concurrent gang restart on the monitor thread: a spec handed
            # out with the OLD attempt but the NEW gang's fired flag would
            # let a stale executor proceed with the wrong ranks
            fired = self._gang_complete_fired
            attempt = self._restart_attempt
        if spec is None or not fired:
            return {"spec": None}
        return {
            "spec": spec,
            "extra_env": self.runtime.am_extra_env(session, job_name, index),
            "restart_attempt": attempt,
        }

    def register_execution_result(
        self, job_name: str, index: int, exit_code: int, attempt: int = 0, reason: str = ""
    ) -> dict[str, Any]:
        session = self._fenced_session(attempt)
        if session is None:
            return {"ack": False, "stale": True}
        try:
            with session.lock:
                session.get_task(job_name, index)
        except KeyError:
            return {"ack": False}
        payload: dict[str, Any] = {"task": f"{job_name}:{index}", "exit_code": exit_code}
        if reason:
            # e.g. "execution timeout": lets the .jhist distinguish an
            # executor-enforced kill from a user-code failure
            payload["reason"] = reason
        # event queued BEFORE the task flips terminal: the monitor loop
        # breaks the instant the LAST tracked task is terminal, and stop()'s
        # APPLICATION_FINISHED + queue sentinel would race ahead of an
        # emit-after — losing the final task's finish record from the .jhist
        self.events.emit(EventType.TASK_FINISHED, **payload)
        session.on_task_completed(job_name, index, exit_code)
        self._jlog("task_done", job=job_name, index=index, exit_code=exit_code)
        return {"ack": True}

    def register_tensorboard_url(self, url: str) -> dict[str, Any]:
        self.tensorboard_url = url
        return {"ack": True}

    def register_task_url(
        self, job_name: str, index: int, url: str, attempt: int = 0
    ) -> dict[str, Any]:
        """Interactive tasks (notebook, tensorboard, ...) publish their URL so
        the submitter can proxy it (SURVEY.md §3.4 NotebookSubmitter path)."""
        session = self._fenced_session(attempt)
        if session is None:
            return {"ack": False, "stale": True}
        with session.lock:
            session.get_task(job_name, index).url = url
        self.events.emit(EventType.TASK_URL_REGISTERED, task=f"{job_name}:{index}", url=url)
        return {"ack": True}

    def task_executor_heartbeat(self, job_name: str, index: int, attempt: int = 0) -> dict[str, Any]:
        tid = f"{job_name}:{index}"
        # ONE epoch-lock acquisition capturing (session, drain piggyback)
        # atomically; the beat itself then lands in the session's lock-free
        # heartbeat ledger (docs/performance.md "Control-plane scalability").
        # At thousand-executor fan-in this handler is the AM's hottest path:
        # it must never serialize behind the monitor loop's whole-gang
        # snapshots or a second lock round-trip.
        with self._epoch_lock:
            if attempt != self._restart_attempt:
                return {"ack": False, "stale": True}
            session = self.session
            drain = self._drain
            drain_payload: dict[str, Any] | None = None
            if (
                drain is not None
                and tid in drain["targets"]  # only the captured target set:
                # a task appearing mid-drain (promoted spare, untracked
                # sidecar) is not waited on and must not pay a forced save
                and tid not in drain["acks"]
            ):
                # urgent-checkpoint fan-out: re-sent until the task's saved
                # step is reported (the courier dedups by req_id)
                drain_payload = {"req_id": drain["req_id"]}
            else:
                # per-task drain (autoscaler pre-scale-down): same courier
                # contract, one task only — a gang-wide episode outranks it
                td = self._task_drains.get(tid)
                if td is not None and td["step"] is None:
                    drain_payload = {"req_id": td["req_id"]}
        session.on_heartbeat(job_name, index)
        resp: dict[str, Any] = {"ack": True}
        # the AM cannot push to executors, but they knock every heartbeat:
        # an in-flight capture request rides back on the response until the
        # task reports a terminal status (the courier dedups by req_id)
        profile = self._profile.pending_for(tid)
        if profile is not None:
            resp["profile"] = profile
        if drain_payload is not None:
            resp["drain"] = drain_payload
        return resp

    def report_drain_saved(
        self, job_name: str, index: int, req_id: str, step: int = 0, attempt: int = 0
    ) -> dict[str, Any]:
        """A task's urgent pre-preemption checkpoint landed (drain courier):
        record which step is safe. The monitor loop yields the gang once
        every live tracked task has reported (or at the drain margin)."""
        if self._fenced_session(attempt) is None:
            return {"ack": False, "stale": True}
        with self._epoch_lock:
            drain = self._drain
            tid = f"{job_name}:{index}"
            if (drain is not None and drain["req_id"] == req_id
                    and tid in drain["targets"]):
                drain["acks"][tid] = int(step)
            else:
                td = self._task_drains.get(tid)
                if td is None or td["req_id"] != req_id:
                    return {"ack": False}
                td["step"] = int(step)  # per-task drain (scale-down) acked
        obs_logging.info(
            f"[tony-am] {job_name}:{index} drained at step {step} "
            f"for request {req_id}")
        return {"ack": True}

    def request_task_drain(self, job_name: str, index: int) -> dict[str, Any]:
        """Ask ONE task to drain (stop admitting, finish in-flight work, ack
        through the DrainCourier done-file) — the serving autoscaler calls
        this before ``resize_jobtype`` removes a replica, so scale-down
        stops being an abrupt kill. Idempotent: repeated calls poll the same
        episode; callers resize once ``drained`` flips true (or their own
        deadline passes). The episode is cleared by the resize's gang
        rebuild like every other drain state."""
        tid = f"{job_name}:{index}"
        try:
            with self.session.lock:
                self.session.get_task(job_name, index)
        except KeyError:
            return {"ack": False, "error": f"unknown task {tid}"}
        with self._epoch_lock:
            td = self._task_drains.get(tid)
            if td is None:
                td = {
                    "req_id": f"taskdrain-{self._restart_attempt}-{tid}",
                    "step": None,
                }
                self._task_drains[tid] = td
                obs_logging.info(
                    f"[tony-am] task drain requested for {tid} "
                    f"({td['req_id']}) — fanning out on its heartbeat")
            return {
                "ack": True,
                "req_id": td["req_id"],
                "drained": td["step"] is not None,
                "step": td["step"],
            }

    def get_task_infos(self) -> list[dict[str, Any]]:
        return self.session.task_infos()

    def get_application_status(self) -> dict[str, Any]:
        st = self.session.job_status
        cfg = self._effective_config()
        return {
            "app_id": self.app_id,
            "state": st.value,
            "final": st not in (JobStatus.NEW, JobStatus.RUNNING),
            "reason": self.session.failure_reason,
            "tensorboard_url": self.tensorboard_url,
            "restart_attempt": self._restart_attempt,
            # which AM attempt is serving (0 = the original), and whether it
            # adopted the gang or degraded — a takeover must be visible to
            # the submitter (monitor output, tony top, portal), not silent
            "am_attempt": self.am_attempt,
            "takeover": self._takeover_outcome,
            # effective per-type instance counts AFTER any elastic resize —
            # `tony top` / the portal drop task rows a shrink removed instead
            # of showing them dead forever
            "instances": {t: cfg.instances(t) for t in cfg.job_types()},
        }

    def finish_application(self) -> dict[str, Any]:
        self._kill_requested = True
        return {"ack": True}

    def push_metrics(
        self, job_name: str, index: int, metrics: dict[str, Any], attempt: int = 0
    ) -> dict[str, Any]:
        session = self._fenced_session(attempt)
        if session is None:
            return {"ack": False, "stale": True}
        # the child's start-up stamps ride the push (executor._metrics_loop)
        # and leave it here: executors re-push them until the child ends, and
        # the .jhist takes them once a task, gang epoch and stamp taken
        startup = metrics.pop("startup", None)
        with session.lock:
            session.get_task(job_name, index).metrics = metrics
        if isinstance(startup, dict) and startup.get("stamps"):
            key = (f"{job_name}:{index}", int(attempt))
            if self._startup_seen.get(key) != startup:
                self._startup_seen[key] = startup
                self.events.emit(
                    EventType.TASK_STARTUP_STAMPS, task=key[0], attempt=key[1],
                    kind=startup.get("kind"), stamps=startup["stamps"])
        return {"ack": True}

    def push_client_metrics(self, identity: str, metrics: Any) -> dict[str, Any]:
        """Submitter-side processes with no executor (the fleet router runs in
        the ``tony serve`` client) push their metrics-registry snapshots here;
        ``get_metrics`` re-exports them like executor piggybacks, so router
        request/retry/hedge counters reach the portal's /metrics."""
        if not isinstance(identity, str) or not identity or len(identity) > 64:
            return {"ack": False}
        self._client_obs[identity] = metrics
        return {"ack": True}

    def resize_jobtype(self, job_name: str, instances: int) -> dict[str, Any]:
        """Elastic-resize request (the serving autoscaler's / ``tony
        resize``'s lever): retarget ``tony.<job_name>.instances`` without
        re-submitting. The monitor loop applies it via the existing rebuild
        path — in place while queued, or a budget-exempt whole-gang restart
        while running (workers restore the checkpoint onto the resized mesh;
        serve replicas re-register onto the new fleet size).

        Invalid requests raise the typed :class:`InvalidResizeError` through
        the RPC error frame instead of a generic error payload."""
        n = int(instances)
        if job_name not in self.config.job_types():
            raise InvalidResizeError(
                f"unknown job type {job_name!r} "
                f"(declared: {', '.join(sorted(self.config.job_types()))})"
            )
        if n < 1:
            raise InvalidResizeError(f"target instances must be >= 1, got {n}")
        if job_name == self._elastic_jobtype():
            floor = self.config.get_int(keys.ELASTIC_MIN_WORKERS, 0)
            ceiling = self.config.get_int(keys.ELASTIC_MAX_WORKERS, 0)
            if floor and n < floor:
                raise InvalidResizeError(
                    f"target {n} below tony.elastic.min-workers={floor}")
            if ceiling and n > ceiling:
                raise InvalidResizeError(
                    f"target {n} above tony.elastic.max-workers={ceiling}")
        with self._epoch_lock:
            current = self._effective_config().instances(job_name)
            if n == current:
                cancelled = self._pending_resize.pop(job_name, None)
                if cancelled is not None:
                    self._jlog("pending_resize", resizes=dict(self._pending_resize))
                _GANG_RESIZES.inc(outcome="noop")
                if cancelled is None:
                    return {"ack": True, "current": current, "noop": True}
                # asking for the CURRENT size is the explicit way to abort an
                # acked-but-unapplied resize — report the cancellation rather
                # than silently making the first caller's ack a lie
                obs_logging.info(
                    f"[tony-am] resize {job_name}→{cancelled} cancelled by a "
                    f"request for the current size {current}")
                return {"ack": True, "current": current, "noop": True,
                        "cancelled_pending": cancelled}
            pending = self._pending_resize.get(job_name)
            if pending is not None and pending != n:
                # acknowledged-but-unapplied request in flight: silently
                # clobbering it would make the first caller's ack a lie
                raise InvalidResizeError(
                    f"a resize of {job_name!r} to {pending} is already "
                    "pending; retry after it applies")
            self._pending_resize[job_name] = n
            self._jlog("pending_resize", resizes=dict(self._pending_resize))
        return {"ack": True, "current": current}

    # ------------------------------------------------------------ hot spares
    def register_spare(self, spare_id: str, host: str, port: int) -> dict[str, Any]:
        """A hot-spare executor (``tony.elastic.spares``) announces it is up
        and parked: from here, promoting it into a gang slot costs a spec
        re-fence instead of container allocation + executor startup."""
        with self._epoch_lock:
            sp = self._spares.get(spare_id)
            if sp is None:
                return {"ack": False, "stale": True}  # reaped spare: executor exits
            sp["ready"] = True
        self.events.emit(EventType.SPARE_READY, spare=spare_id, host=host, port=port)
        obs_logging.info(f"[tony-am] hot spare {spare_id} ready on {host}:{port}")
        return {"ack": True}

    def poll_spare_assignment(self, spare_id: str) -> dict[str, Any]:
        """Parked spares poll for a promotion. ``stale`` → the spare was
        reaped (job ending, or its generation was dropped) and must exit;
        a non-None assignment carries the (job, index, attempt) identity the
        executor adopts before walking the normal register→barrier path."""
        with self._epoch_lock:
            sp = self._spares.get(spare_id)
            if sp is None:
                return {"stale": True}
            return {"assignment": sp.get("assignment")}

    def _elastic_jobtype(self) -> str:
        return self.config.get(keys.ELASTIC_JOBTYPE) or constants.WORKER_JOB_NAME

    def _register_with_pool(self) -> None:
        """Announce queue/priority/whole-gang demand to the pool, plus the
        elastic partial-reclaim contract (what one shed worker frees and how
        many the gang may shed) so the pool can ask this job to SHRINK
        instead of whole-gang-evicting it under reclaim pressure."""
        unit, slack = None, 0
        if self.config.get_bool(keys.ELASTIC_SHRINK_ON_PREEMPT):
            et = self._elastic_jobtype()
            plan = self.scheduler.plans.get(et)
            floor = self._elastic_floors().get(et, 0)
            if plan is not None and floor >= 1:
                unit = plan.resources
                slack = max(self._effective_config().instances(et) - floor, 0)
        self.rm.register_app(
            queue=self.config.get(keys.APPLICATION_QUEUE) or "default",
            priority=self.config.get_int(keys.APPLICATION_PRIORITY, 0),
            demand=self.scheduler.total_demand(),
            elastic_unit=unit,
            elastic_slack=slack,
        )

    def _elastic_floors(self) -> dict[str, int]:
        """Per-type shrink floors: ``tony.<type>.min-instances`` merged with
        ``tony.elastic.min-workers`` for the elastic jobtype (either spelling
        enables elasticity for the training data axis)."""
        floors = {
            t: self.config.get_int(keys.jobtype_key(t, keys.MIN_INSTANCES_SUFFIX), 0)
            for t in self.config.job_types()
        }
        et = self._elastic_jobtype()
        if et in floors:
            floors[et] = max(floors[et], self.config.get_int(keys.ELASTIC_MIN_WORKERS, 0))
        return floors

    def start_profile(self, steps: int | None = None, memory: bool = False) -> dict[str, Any]:
        """Arm an on-demand profiler capture (``tony profile <app_id>``): fan
        the request out to every live tracked task via the heartbeat
        piggyback. One capture may be in flight at a time — a concurrent
        request fails with the typed AlreadyProfilingError in the RPC error
        frame."""
        num_steps = int(steps or self.config.get_int(keys.PROFILE_STEPS, 5))
        capture_memory = bool(memory) or self.config.get_bool(keys.PROFILE_MEMORY)
        untracked = self.session.untracked
        targets = [
            f"{i['name']}:{i['index']}"
            for i in self.session.task_infos()
            if i["name"] not in untracked
            and i["status"] in (TaskStatus.REGISTERED.value, TaskStatus.RUNNING.value)
        ]
        result = self._profile.start(targets, num_steps, capture_memory)
        self.events.emit(
            EventType.PROFILE_REQUESTED,
            req_id=result["req_id"], num_steps=num_steps, tasks=result["tasks"],
        )
        obs_logging.info(
            f"[tony-am] profile {result['req_id']}: capturing {num_steps} "
            f"step(s) on {len(result['tasks'])} task(s)"
        )
        return result

    def get_profile_status(self, req_id: str = "") -> dict[str, Any]:
        """The current/last capture request's per-task status (the surface
        ``tony profile`` blocks on)."""
        return {"profile": self._profile.status(req_id)}

    def report_profile_status(
        self, job_name: str, index: int, req_id: str, status: str,
        dir: str = "", artifacts: list[str] | None = None,
        summary: dict[str, Any] | None = None, error: str = "", attempt: int = 0,
    ) -> dict[str, Any]:
        """Executors report capture progress (delivered → captured/error)."""
        if self._fenced_session(attempt) is None:
            return {"ack": False, "stale": True}
        acked, completed = self._profile.report(
            f"{job_name}:{index}", req_id, status,
            dir=dir, artifacts=artifacts, summary=summary, error=error or None,
        )
        if acked:
            _PROFILE_REPORTS.inc(status=status)
        if completed:
            st = self._profile.status(req_id) or {}
            self.events.emit(
                EventType.PROFILE_FINISHED,
                req_id=req_id,
                tasks={
                    tid: e.get("status")
                    for tid, e in (st.get("tasks") or {}).items()
                },
            )
            obs_logging.info(f"[tony-am] profile {req_id}: all tasks reported")
        return {"ack": acked}

    def get_metrics(self) -> dict[str, Any]:
        """This AM process's metrics-registry snapshot (obs/metrics.py) plus
        the latest registry snapshot each executor piggybacked on its metrics
        push — the portal merges them into /metrics under app=<id> (and
        task=<job:idx> for the executor groups). Submitter-side snapshots
        pushed via ``push_client_metrics`` (fleet router) ride the same dict
        under their identity."""
        tasks: dict[str, Any] = {}
        for t in self.session.task_infos():
            obs = (t.get("metrics") or {}).get("obs_metrics")
            if obs:
                tasks[f"{t['name']}:{t['index']}"] = obs
        tasks.update(self._client_obs)
        return {
            "app_id": self.app_id,
            "identity": "am",
            "metrics": obs_metrics.REGISTRY.snapshot(),
            "tasks": tasks,
        }

    # --------------------------------------------------- goodput accounting
    def _live_ledger(self) -> "obs_goodput.Ledger | None":
        """The job-so-far phase ledger from this AM's own artifacts: the
        incrementally-followed intermediate ``.jhist`` (events already
        flushed by the handler thread) plus the span sink when traced. None
        when nothing has been written yet."""
        events = self._jhist.poll()
        if not events:
            return None
        spans: list[dict[str, Any]] = []
        if self.tracer is not None:
            from tony_tpu.obs import artifacts as obs_artifacts

            spans = obs_artifacts.load_spans(self.tracer.trace_dir)
        return obs_goodput.build_ledger(
            self.app_id, events, spans, now_ms=int(time.time() * 1000))

    def _alert_values(
        self, infos: list[dict[str, Any]], task_obs: dict[str, Any],
        ledger: "obs_goodput.Ledger | None",
    ) -> dict[str, float | None]:
        """Current value per configured rule (None = no data this tick)."""
        values: dict[str, float | None] = {}
        rule_names = {r.name for r in self._alerts.rules}
        if "goodput-floor" in rule_names:
            values["goodput-floor"] = (
                ledger.window_fraction(self._goodput_window_ms)
                if ledger is not None else None)
        if "step-time-p99-ms" in rule_names:
            p99_s = obs_goodput.histogram_percentile(
                task_obs.values(), "tony_train_step_seconds", 0.99)
            values["step-time-p99-ms"] = p99_s * 1000.0 if p99_s is not None else None
        if "heartbeat-age-ms" in rule_names:
            now_ms = time.time() * 1000
            ages = [
                now_ms - float(t["last_heartbeat_ms"])
                for t in infos
                if t.get("last_heartbeat_ms")
                and t.get("status") in (TaskStatus.REGISTERED.value, TaskStatus.RUNNING.value)
            ]
            values["heartbeat-age-ms"] = max(ages) if ages else None
        if "queue-depth" in rule_names:
            depths = [
                obs_introspect.metric_value(obs, "tony_serve_queue_depth")
                for obs in task_obs.values()
            ]
            depths = [d for d in depths if d is not None]
            values["queue-depth"] = max(depths) if depths else None
        return values

    def _goodput_tick(self) -> None:
        """Throttled straggler + alert evaluation from the monitor loop (the
        same piggybacked state every other introspection surface reads)."""
        if not self._goodput_enabled:
            return
        now = time.monotonic()
        if now - self._last_goodput_tick < self._goodput_interval_s:
            return
        self._last_goodput_tick = now
        infos = self.session.task_infos()
        task_obs = {
            f"{t['name']}:{t['index']}": (t.get("metrics") or {}).get("obs_metrics")
            for t in infos
        }
        # only LIVE ranks feed the detector: a finished task's frozen stats
        # would otherwise read as an ever-growing stall
        live = [
            t for t in infos
            if t.get("status") in (TaskStatus.REGISTERED.value, TaskStatus.RUNNING.value)
        ]
        # the tick that ends the job (every tracked task terminal) sees its
        # ranks gone; a rank flagged until it finished was dragging the gang
        # at the end (goodput.flagged_stragglers), and whether this throttled
        # tick falls before the loop's exit must not decide that
        transitions = [] if self.session.tracked_all_terminal() else self._straggler.observe(
            obs_introspect.step_stats_by_task(live, task_obs))
        for action, task, ratio, median in transitions:
            if action == "detected":
                self.events.emit(
                    EventType.STRAGGLER_DETECTED,
                    task=task, ratio=round(ratio, 3),
                    median_step_s=round(median, 4),
                    factor=self._straggler.factor,
                )
                obs_logging.warning(
                    f"[tony-am] straggler: {task} step time {ratio:.2f}x the "
                    f"gang median ({median * 1000:.1f}ms)")
            else:
                self.events.emit(
                    EventType.STRAGGLER_RESOLVED, task=task, ratio=round(ratio, 3))
                obs_logging.info(f"[tony-am] straggler resolved: {task}")
        _STRAGGLER_COUNT.set(len(self._straggler.flagged))
        for task, ratio in self._straggler.skew.items():
            _STRAGGLER_SKEW.set(round(ratio, 4), task=task)
        # the gauge is the tick's contract, alert rule or not — dashboards
        # scrape it on healthy jobs too
        ledger = self._live_ledger()
        if ledger is not None:
            _GOODPUT_FRACTION.set(
                round(ledger.window_fraction(self._goodput_window_ms), 6))
        values = self._alert_values(infos, task_obs, ledger)
        if self._slo.enabled:
            now_ms = int(time.time() * 1000)
            for tid, obs in task_obs.items():
                if obs:
                    self._slo.observe_serve(tid, obs, now_ms)
            if ledger is not None:
                self._slo.observe_train(self.app_id, ledger, now_ms)
            values.update(self._slo.tick(now_ms))
            self._slo.append_windows(now_ms)
        if self._alerts.rules:
            for rec in self._alerts.evaluate(values):
                if rec["rule"].startswith(obs_slo.RULE_PREFIX):
                    etype = (EventType.SLO_BURN_ALERT if rec["state"] == "fired"
                             else EventType.SLO_BURN_RESOLVED)
                else:
                    etype = (EventType.ALERT_FIRED if rec["state"] == "fired"
                             else EventType.ALERT_RESOLVED)
                self.events.emit(
                    etype, **{k: v for k, v in rec.items() if k != "app_id"})
                obs_logging.warning(
                    f"[tony-am] alert {rec['rule']} {rec['state']}: "
                    f"value {rec.get('value')} vs threshold {rec.get('threshold')}")

    def get_goodput(self) -> dict[str, Any]:
        """Live goodput surface (`tony goodput` / `tony top` / portal): the
        job-so-far ledger, the trailing-window fraction, per-rank skew, and
        the active alerts."""
        ledger = self._live_ledger() if self._goodput_enabled else None
        return {
            "goodput": ledger.to_dict() if ledger is not None else None,
            "window_ms": self._goodput_window_ms,
            "window_fraction": (
                ledger.window_fraction(self._goodput_window_ms)
                if ledger is not None else None),
            "skew": {t: round(r, 4) for t, r in sorted(self._straggler.skew.items())},
            "stragglers": sorted(self._straggler.flagged),
            "alerts": self._alerts.active(),
        }

    def get_slo(self) -> dict[str, Any]:
        """Live SLO surface (`tony slo` / portal `/slo`): per-objective
        budgets, burn rates, worst-offender exemplars, and whichever of the
        alert engine's `slo-` rules are currently firing."""
        doc = self._slo.status(int(time.time() * 1000))
        doc["alerts"] = [
            a for a in self._alerts.active()
            if a["rule"].startswith(obs_slo.RULE_PREFIX)
        ]
        return doc

    # ------------------------------------------------------------ lifecycle
    def prepare(self) -> None:
        if self.tracer is not None:
            # the root span stays open for the AM's whole life (ended in
            # stop()); re-pointing root_parent at it makes every span opened
            # on a bare thread (RPC handlers, monitor loop) nest under it
            self._root_span, self._root_token = self.tracer.start_span("am.run")
            self._root_span.set(app_id=self.app_id)
            self.tracer.root_parent = self._root_span.span_id
        self.runtime.validate()
        self.rpc.register_object(self, APPLICATION_RPC_METHODS)
        self.rpc.start()
        self.events.start()
        adopted = False
        if self._takeover_requested:
            adopted = self._perform_takeover()
        # announce queue/priority/whole-gang demand to the pool (the
        # ApplicationSubmissionContext analog): multi-tenant pools queue us
        # when capacity is short instead of failing the job. After a takeover
        # this re-registers the (possibly resized) demand under the same app
        # id — the pool's claims carry over with the live containers.
        self._register_with_pool()
        if not adopted:
            # fresh gang epoch (initial start, or degraded takeover): every
            # journal record before this one is obsolete for future replays.
            # failures/pending_resize are CROSS-epoch (last record wins), so
            # a degraded reset must re-journal them explicitly — otherwise a
            # later takeover would resurrect the pre-degrade budget/resize.
            with self._epoch_lock:
                # the RPC server is already registered a few lines up, so a
                # resize handler can race this epoch snapshot — capture the
                # cross-epoch fields atomically, then journal outside the
                # lock (appends fsync)
                epoch_attempt = self._restart_attempt
                epoch_resized = dict(self._resized)
                epoch_failures = self._failures_seen
                epoch_pending = dict(self._pending_resize)
            self._jlog("epoch", attempt=epoch_attempt, resized=epoch_resized)
            self._jlog("failures", n=epoch_failures)
            self._jlog("pending_resize", resizes=epoch_pending)
        if self.am_attempt == 0:
            self.events.emit(
                EventType.APPLICATION_INITED,
                app_id=self.app_id,
                job_types={t: self.config.instances(t) for t in self.config.job_types()},
                **self._submit_stamp(),
            )
        host, port = self.rpc.address
        info = {"host": host, "port": port, "secret": self.secret, "pid": os.getpid()}
        info_path = os.path.join(self.staging_dir, constants.AM_INFO_FILE)
        # mode set before publication: the file carries the RPC secret
        # (delegation-token analog) and pollers race the rename. Published
        # AFTER any takeover recovery: an executor re-resolving the AM must
        # only ever reach a session that is ready to resync it.
        _atomic_write_json(info_path, info, mode=0o600)
        self.session.job_status = JobStatus.RUNNING
        obs_logging.info(
            f"[tony-am] application {self.app_id} running "
            f"({self.session.total_tasks()} task(s), rpc {host}:{port}"
            + (f", am attempt {self.am_attempt}" if self.am_attempt else "")
            + ")"
        )

    def _submit_stamp(self) -> dict[str, int]:
        """``{"submitted_ms": ...}`` as the client staged it (Client.submit,
        before staging), or nothing: an AM launched by an older client, or by
        hand, opens the ledger at its own first event as before."""
        try:
            with open(os.path.join(self.staging_dir, constants.SUBMIT_INFO_FILE)) as f:
                return {"submitted_ms": int(json.load(f)["submitted_ms"])}
        except (OSError, ValueError, KeyError, TypeError):
            return {}

    # ------------------------------------------------- work-preserving takeover
    def _perform_takeover(self) -> bool:
        """Replay the predecessor AM's journal and adopt its live gang.

        Success → the executors ride out the outage on their missed-heartbeat
        budget, re-resolve this AM from the refreshed ``am_info``, and resync
        — the training children never stop. Any failure (journal missing or
        corrupt, un-adoptable container, config mismatch) degrades LOUDLY to
        today's full gang restart: the stale gang is killed outright and the
        job resumes from its latest checkpoint, with AM_TAKEOVER_DEGRADED in
        the event stream."""
        t0 = time.perf_counter()
        with obs_trace.maybe_span("am.takeover", am_attempt=self.am_attempt):
            try:
                # streamed, not materialized: a long job's journal may carry
                # hundreds of thousands of records between compactions
                state = _replay_am_journal(
                    iter_journal(os.path.join(self.staging_dir, constants.AM_JOURNAL_FILE))
                )
                self._adopt_state(state)
            except Exception as e:  # noqa: BLE001 — ANY replay fault degrades, never hangs
                reason = f"{type(e).__name__}: {e}"
                obs_logging.error(
                    f"[tony-am] takeover degraded — {reason}; "
                    "killing the stale gang and falling back to a full restart")
                self._kill_stale_gang()
                self._reset_fresh()
                _AM_TAKEOVERS.inc(outcome="degraded")
                self._takeover_outcome = "degraded"
                self.events.emit(
                    EventType.AM_TAKEOVER_DEGRADED,
                    am_attempt=self.am_attempt, reason=reason,
                )
                obs_trace.add_event("am.takeover_degraded", reason=reason)
                return False
            _AM_TAKEOVERS.inc(outcome="adopted")
            _TAKEOVER_SECONDS.observe(time.perf_counter() - t0)
            self._takeover_outcome = "adopted"
            self._jlog("takeover", am_attempt=self.am_attempt)
            self.events.emit(
                EventType.AM_TAKEOVER,
                am_attempt=self.am_attempt,
                attempt=self._restart_attempt,
                containers=len(self._containers),
                registered=self.session.registered_count(),
            )
            obs_logging.info(
                f"[tony-am] attempt {self.am_attempt} adopted the live gang: "
                f"{len(self._containers)} container(s), "
                f"{self.session.registered_count()} registered task(s), "
                f"gang epoch {self._restart_attempt}")
            return True

    def _adopt_state(self, state: "_JournalState") -> None:
        """Rebuild session/scheduler/container tracking from a replayed
        journal, committing only when EVERY piece adopted cleanly."""
        if type(self.runtime).on_gang_complete is not FrameworkRuntime.on_gang_complete:
            # a runtime that rebuilds gang state on completion (the horovod
            # driver) cannot be adopted: the executors hold rendezvous env
            # pointing at a process that died with the old AM
            raise RuntimeError(
                f"runtime {type(self.runtime).__name__} rebuilds state on gang "
                "completion and cannot survive an AM swap")
        self._resized = dict(state.resized)
        cfg = self._effective_config()
        session = Session(cfg)
        session.job_status = JobStatus.RUNNING
        scheduler = TaskScheduler(cfg, session, self.rm)
        for (job, idx), (host, port) in state.registered.items():
            session.register_worker_spec(job, idx, host, port)  # KeyError → degrade
        for (job, idx), rc in state.done.items():
            session.on_task_completed(job, idx, rc)
        containers: dict[str, Container] = {}
        by_task: dict[tuple[str, int], Container] = {}
        adopted: list[Container] = []
        try:
            for rec in state.containers.values():
                job, idx = rec["job"], int(rec["index"])
                task = session.get_task(job, idx)
                if task.status.terminal:
                    continue  # already finished: its process is gone; nothing to track
                c = self.rm.adopt_container(rec.get("container") or {})
                if c is None:
                    raise RuntimeError(
                        f"resource manager could not adopt container "
                        f"{(rec.get('container') or {}).get('id')} for {job}:{idx}")
                adopted.append(c)
                if task.status == TaskStatus.NEW:
                    task.status = TaskStatus.SCHEDULED
                task.container_id = c.id
                task.chip_coords = c.chip_coords
                task.log_dir = rec.get("log_dir")
                task.start_time_ms = int(rec.get("started_ms") or 0)
                containers[c.id] = c
                by_task[(job, idx)] = c
            for job_type, plan in scheduler.plans.items():
                covered = [
                    (job_type, i) in by_task
                    or session.get_task(job_type, i).status.terminal
                    for i in range(plan.instances)
                ]
                if all(covered):
                    plan.launched = True
                elif any((job_type, i) in by_task for i in range(plan.instances)):
                    # allocate_type is all-or-nothing: a half-launched wave
                    # cannot be completed piecemeal — degrade to a restart
                    raise RuntimeError(f"type {job_type!r} was mid-launch when the AM died")
        except Exception:
            for c in adopted:
                try:
                    self.rm.kill_container(c)
                    self.rm.release(c)
                except Exception:  # noqa: BLE001 — best-effort unwind before degrading
                    pass
            raise
        with self._epoch_lock:
            self._restart_attempt = state.attempt
            self._pending_resize = dict(state.pending)
            self._failures_seen = state.failures
            self._gang_complete_fired = state.gang_complete
            self.session = session
            self.scheduler = scheduler
            self._containers = containers
            self._by_task = by_task
        if any(p.launched for p in scheduler.plans.values()) and not session.cluster_spec_complete():
            self._gang_started_ms = time.time() * 1000  # restart the barrier clock
        if self.chaos is not None and state.chaos_step:
            # @step+N gates that already opened must not re-arm, and ones
            # still closed keep their watermark across the AM swap
            self.chaos.set_progress(state.chaos_step)
        self._journal_chaos_step = state.chaos_step
        lg = obs_logging.get()
        if lg is not None:
            lg.epoch = self._restart_attempt

    def _reset_fresh(self) -> None:
        """Degraded takeover: back to the configured gang, attempt 0 — the
        exact state a pre-takeover AM retry would have started from."""
        with self._epoch_lock:
            self._resized = {}
            self._pending_resize = {}
            self._restart_attempt = 0
            self._failures_seen = 0
            self._gang_complete_fired = False
            self._gang_started_ms = None
            self.session = Session(self.config)
            self.scheduler = TaskScheduler(self.config, self.session, self.rm)
            self._containers = {}
            self._by_task = {}

    def _kill_stale_gang(self) -> None:
        """Degraded-path teardown of the predecessor's gang: remote pools
        release everything held under this app id, and every local process
        still carrying the app id in its environment (executors + their
        children, launched by the dead AM) is killed outright. Without this,
        the fresh gang would race the orphans for ports, checkpoints, and
        (job, index) identities."""
        try:
            self.rm.reclaim_orphans()
        except Exception as e:  # noqa: BLE001 — reclaim is best-effort
            obs_logging.warning(f"[tony-am] pool reclaim during degraded takeover failed: {e}")
        if not os.path.isdir("/proc"):
            return
        from tony_tpu.cluster.resources import _kill_process_tree

        needle = f"{constants.ENV_APP_ID}={self.app_id}".encode()
        for name in os.listdir("/proc"):
            if not name.isdigit() or int(name) == os.getpid():
                continue
            try:
                with open(f"/proc/{name}/environ", "rb") as f:
                    if needle not in f.read():
                        continue
            except OSError:
                continue
            _kill_process_tree(int(name))

    def _launch_type(self, job_type: str) -> None:
        if self.tracer is None:
            return self._launch_type_spanned(job_type)
        sp, token = self.tracer.start_span("am.launch")
        sp.set(job_type=job_type, attempt=self._restart_attempt)
        try:
            result = self._launch_type_spanned(job_type)
        except AllocationPending:
            # expected control flow while queued behind other tenants — the
            # monitor loop retries every tick, and one error span per tick
            # would bury the timeline (the wait itself is the am.queue_wait
            # span); drop this span unwritten
            self.tracer.discard_span(sp, token)
            raise
        except BaseException:
            self.tracer.end_span(sp, token, status="error")
            raise
        self.tracer.end_span(sp, token)
        return result

    def _launch_type_spanned(self, job_type: str) -> None:
        # hot-spare promotion: slots covered by a ready spare skip container
        # allocation AND executor startup — the parked executor adopts the
        # slot identity and walks straight into the gang barrier
        spare_slots: dict[int, str] = {}
        if job_type == self._elastic_jobtype():
            with self._epoch_lock:
                ready = [
                    sid for sid, sp in sorted(self._spares.items())
                    if sp.get("ready") and sp.get("assignment") is None
                ]
            n = self.scheduler.plans[job_type].instances
            # highest indices first, and NEVER index 0: the coordinator /
            # chief-like rank always gets a deliberately-placed fresh
            # container, however many spares are parked
            for k, sid in enumerate(ready[:max(n - 1, 0)]):
                spare_slots[n - 1 - k] = sid
        containers = self.scheduler.allocate_type(job_type, skip_indices=set(spare_slots))
        # fresh allocations succeeded (no AllocationPending escape) — binding
        # the spares now means a queued gang never strands a consumed spare
        for idx in sorted(spare_slots):
            self._bind_spare(spare_slots[idx], job_type, idx)
        for container in containers:
            task = self.session.get_task(job_type, container.task_index)
            task.status = TaskStatus.SCHEDULED
            task.container_id = container.id
            task.chip_coords = container.chip_coords
            task.start_time_ms = int(time.time() * 1000)
            self._containers[container.id] = container
            self._by_task[(job_type, container.task_index)] = container
            self._start_executor(container)
            self._journal_task_started(container, task.log_dir)
            self.events.emit(
                EventType.TASK_STARTED,
                task=task.id,
                container=container.id,
                chips=len(container.chip_coords),
            )
        if self._gang_started_ms is None:
            self._gang_started_ms = time.time() * 1000

    def _bind_spare(self, spare_id: str, job_type: str, index: int) -> None:
        """Promote a parked spare into gang slot (job_type, index): its
        container becomes the task's container and its next assignment poll
        hands it the identity + gang epoch to register under."""
        with self._epoch_lock:
            sp = self._spares[spare_id]
            container = sp["container"]
            container.job_type = job_type
            container.task_index = index
            sp["assignment"] = {
                "job_name": job_type, "index": index, "attempt": self._restart_attempt,
            }
        task = self.session.get_task(job_type, index)
        task.status = TaskStatus.SCHEDULED
        task.container_id = container.id
        task.chip_coords = container.chip_coords
        task.start_time_ms = int(time.time() * 1000)
        # the promoted executor keeps writing where it was launched: point
        # the task's log attribution at the spare's directory
        task.log_dir = os.path.join(
            self.staging_dir, constants.TASK_LOG_DIRNAME, f"spare_{spare_id}")
        self._containers[container.id] = container
        self._by_task[(job_type, index)] = container
        self._journal_task_started(container, task.log_dir)
        self.events.emit(
            EventType.SPARE_PROMOTED,
            spare=spare_id, task=f"{job_type}:{index}", container=container.id,
        )
        self.events.emit(
            EventType.TASK_STARTED,
            task=task.id, container=container.id,
            chips=len(container.chip_coords), spare=spare_id,
        )
        obs_logging.info(
            f"[tony-am] promoted hot spare {spare_id} → {job_type}:{index}")

    def _journal_task_started(self, container: Container, log_dir: str | None) -> None:
        """Durably record a gang slot's live container so a takeover attempt
        can adopt it. An RM that cannot describe the container (no pid — not
        yet started) journals nothing: a takeover then sees the type as
        mid-launch and degrades rather than guessing."""
        info = self.rm.journal_info(container)
        if info is None:
            return
        self._jlog(
            "task_started",
            job=container.job_type, index=container.task_index,
            cid=container.id, log_dir=log_dir,
            started_ms=int(time.time() * 1000), container=info,
        )

    def _start_executor(self, container: Container, spare_id: str | None = None) -> None:
        if spare_id is not None:
            log_dir = os.path.join(
                self.staging_dir, constants.TASK_LOG_DIRNAME, f"spare_{spare_id}"
            )
        else:
            log_dir = os.path.join(
                self.staging_dir,
                constants.TASK_LOG_DIRNAME,
                f"{container.job_type}_{container.task_index}"
                + (f"_r{self._restart_attempt}" if self._restart_attempt else ""),
            )
            task = self.session.get_task(container.job_type, container.task_index)
            task.log_dir = log_dir
        host, port = self.rpc.address
        env = dict(os.environ)
        env.update(container.device_env())
        env.update(
            {
                constants.ENV_APP_ID: self.app_id,
                constants.ENV_AM_HOST: host,
                constants.ENV_AM_PORT: str(port),
                constants.ENV_AM_SECRET: self.secret,
                constants.ENV_STAGING_DIR: self.staging_dir,
                constants.ENV_JOB_NAME: container.job_type,
                constants.ENV_TASK_INDEX: str(container.task_index),
                constants.ENV_KILL_GRACE_MS: str(
                    self.config.get_time_ms(keys.TASK_KILL_GRACE_MS, 3000)
                ),
                "TONY_RESTART_ATTEMPT": str(self._restart_attempt),
                "PYTHONPATH": _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", ""),
            }
        )
        if spare_id is not None:
            # spare contract: the executor parks after registering and waits
            # for a promotion instead of joining the gang as (job, index)
            env[constants.ENV_SPARE_ID] = spare_id
        if self.tracer is not None and self._root_span is not None:
            # executor root spans link under am.run (trace dir + enablement
            # come from the frozen config the executor loads itself)
            env[constants.ENV_TRACE_PARENT] = self._root_span.span_id
        cmd = [sys.executable, "-u", "-m", "tony_tpu.cluster.executor"]
        if self.config.get_bool(keys.DOCKER_ENABLED):
            # YARN docker-runtime env passthrough analog: the RM (NM analog)
            # interprets these at container launch (reference: Utils + tony.docker.*).
            # The framework code is bind-mounted (PYTHONPATH stays valid inside)
            # and the image's own `python` runs the executor — the host
            # interpreter path does not exist in the image.
            env[constants.ENV_CONTAINER_RUNTIME_TYPE] = "docker"
            env[constants.ENV_CONTAINER_RUNTIME_IMAGE] = self.config.get(keys.DOCKER_IMAGE) or ""
            env[constants.ENV_CONTAINER_RUNTIME_BINARY] = self.config.get(keys.DOCKER_BINARY) or "docker"
            env[constants.ENV_CONTAINER_MOUNTS] = f"{_REPO_ROOT}:ro"
            cmd = ["python", "-u", "-m", "tony_tpu.cluster.executor"]
        self.rm.start_container(container, cmd, env, log_dir)

    def _fail(self, reason: str) -> None:
        self.session.failure_reason = self.session.failure_reason or reason
        self.session.job_status = JobStatus.FAILED

    def _kill_all_containers(self) -> None:
        for c in list(self._containers.values()):
            self.rm.kill_container(c)

    def _handle_container_exits(self) -> None:
        """NM container-completed callback analog: catches executors that died
        without RPC-reporting a result (OOM-kill, crash, SIGKILL)."""
        for cid, rc in self.rm.poll_exited().items():
            c = self._containers.get(cid)
            if c is None:
                self._reap_dead_spare(cid, rc)
                continue
            task = self.session.get_task(c.job_type, c.task_index)
            if not task.status.terminal:
                # emit before the terminal flip (same shutdown race as
                # register_execution_result)
                self.events.emit(
                    EventType.TASK_FINISHED, task=task.id, exit_code=rc, source="container-exit"
                )
                self.session.on_task_completed(c.job_type, c.task_index, rc)
                self._jlog("task_done", job=c.job_type, index=c.task_index, exit_code=rc)

    # ------------------------------------------------- elastic gang resize
    def _effective_config(self) -> TonyConfig:
        """The job config with any elastic resize (capacity-loss shrink or
        autoscaler retarget) applied to the per-type instance counts
        (everything else untouched)."""
        if not self._resized:
            return self.config
        d = self.config.to_dict()
        for t, n in self._resized.items():
            d[keys.jobtype_key(t, keys.INSTANCES_SUFFIX)] = str(n)
        return TonyConfig(d)

    def _plan_gang_downsize(self) -> dict[str, int] | None:
        """The elastic DECISION (VERDICT r4 #1): does the gang still FIT
        (and PLACE on) the pool's alive capacity? When it doesn't — a node
        was lost for good, so waiting would queue forever — and
        ``tony.<type>.min-instances`` floors permit, return shrunken
        per-type counts. None → keep the current size (fits, no floors,
        capacity unknown, or the shortfall is younger than the downsize
        grace — a blip must not permanently halve the gang)."""
        floors = self._elastic_floors()
        if not any(floors.values()):
            return None  # elasticity not enabled for any type
        # ONE capacity snapshot: totals derived from the same node list the
        # placement check uses (two RPCs would race a node dying in between)
        nodes = self.rm.node_capacities()
        if self.chaos is not None and self.chaos.take("capacity-flap") is not None:
            nodes = []  # this probe sees an empty pool; the hysteresis below must absorb the blip
        if nodes is not None:
            from tony_tpu.cluster.resources import Resources

            cap = Resources(
                memory_bytes=sum(n.memory_bytes for n in nodes),
                vcores=sum(n.vcores for n in nodes),
                chips=sum(n.chips for n in nodes),
            )
        else:
            cap = self.rm.total_capacity()
        if cap is None:
            return None
        cfg = self._effective_config()
        counts = {t: cfg.instances(t) for t in cfg.job_types()}
        per_instance = {t: self.scheduler.plans[t].resources for t in counts}
        plan = plan_downsize(counts, per_instance, floors, cap, nodes=nodes)
        if plan is None:
            self._capacity_short_since = None  # capacity recovered (or fits)
            return None
        now = time.time()
        if self._capacity_short_since is None:
            self._capacity_short_since = now
        grace_s = self.config.get_time_ms(keys.APPLICATION_DOWNSIZE_GRACE_MS, 10_000) / 1000
        if now - self._capacity_short_since < grace_s:
            # inside the hysteresis window: restart/queue at FULL size; the
            # mid-wait probe re-checks and applies the shrink only if the
            # shortfall persists past the grace
            return None
        return plan

    def _announce_resize(
        self, resize: dict[str, int], reason: str,
        trigger: str = "capacity", old: dict[str, int] | None = None,
    ) -> None:
        cfg = self._effective_config()
        if old:
            deltas = [resize[t] - old.get(t, resize[t]) for t in resize]
            if all(d < 0 for d in deltas):
                direction = "shrink"
            elif all(d > 0 for d in deltas):
                direction = "grow"
            else:
                direction = "mixed"
            _ELASTIC_RESIZES.inc(direction=direction, trigger=trigger)
        # the resize episode as a trace span: attrs carry what moved and why,
        # the enclosing am.gang_restart span (when restarting) carries the cost
        with obs_trace.maybe_span("am.resize", trigger=trigger, reason=reason,
                                  resized=dict(resize)):
            self.events.emit(
                EventType.GANG_RESIZED,
                instances={t: cfg.instances(t) for t in cfg.job_types()},
                resized=resize,
                reason=reason,
                trigger=trigger,
            )
            # resized demand re-registers with the pool so queue admission
            # evaluates the gang the AM will actually ask for
            self._register_with_pool()

    def _resize_while_queued(
        self, resize: dict[str, int], reason: str, trigger: str = "capacity"
    ) -> None:
        """A gang waiting in pool admission with NOTHING running re-plans in
        place — capacity permanently lost mid-wait, or an autoscaler retarget
        arriving before admission (the restart path below never fires)."""
        with self._epoch_lock:
            old_cfg = self._effective_config()
            old = {t: old_cfg.instances(t) for t in resize}
            self._resized.update(resize)
            cfg = self._effective_config()
            self.session = Session(cfg)
            self.session.job_status = JobStatus.RUNNING
            self.scheduler = TaskScheduler(cfg, self.session, self.rm)
        # session rebuilt → prior registrations/containers are obsolete for
        # a takeover: a fresh epoch record supersedes them in the journal
        self._jlog("epoch", attempt=self._restart_attempt, resized=dict(self._resized))
        self._announce_resize(resize, reason, trigger=trigger, old=old)

    def _apply_pending_resize(self) -> None:
        """Apply a ``resize_jobtype`` request from the monitor loop (the one
        thread allowed to drive the restart machinery). Grows are guarded by
        the same fits-and-places check the downsize planner uses: a scale-up
        the pool cannot place is rejected with an event, not allowed to take
        a serving fleet down into an endless queue wait."""
        with self._epoch_lock:
            pending, self._pending_resize = self._pending_resize, {}
        if not pending:
            return
        self._jlog("pending_resize", resizes={})
        cfg = self._effective_config()
        resize = {t: n for t, n in pending.items() if n != cfg.instances(t)}
        if not resize:
            _GANG_RESIZES.inc(outcome="noop")
            return
        grows = {t: n for t, n in resize.items() if n > cfg.instances(t)}
        if grows:
            nodes = self.rm.node_capacities()
            if nodes is not None:
                from tony_tpu.cluster.resources import Resources

                cap = Resources(
                    memory_bytes=sum(x.memory_bytes for x in nodes),
                    vcores=sum(x.vcores for x in nodes),
                    chips=sum(x.chips for x in nodes),
                )
            else:
                cap = self.rm.total_capacity()
            if cap is not None:
                counts = {t: cfg.instances(t) for t in cfg.job_types()}
                counts.update(resize)
                per_instance = {t: self.scheduler.plans[t].resources for t in counts}
                if not gang_fits(counts, per_instance, cap, nodes=nodes):
                    _GANG_RESIZES.inc(outcome="rejected")
                    self.events.emit(
                        EventType.GANG_RESIZED,
                        rejected=True,
                        resized=resize,
                        reason=f"scale-up to {grows} does not fit alive capacity",
                    )
                    return
        _GANG_RESIZES.inc(outcome="applied")
        reason = "resize " + ", ".join(
            f"{t}: {cfg.instances(t)}→{n}" for t, n in sorted(resize.items()))
        if not self._containers:
            self._resize_while_queued(resize, reason, trigger="rpc")
        else:
            # budget-exempt like preemption: a requested resize is a cluster
            # action, not a job failure
            self._maybe_restart_gang(
                reason, exit_code=constants.EXIT_PREEMPTED, resize=resize,
                trigger="rpc",
            )

    def _plan_preempt_shrink(self) -> dict[str, int] | None:
        """Shrink-on-preempt (``tony.elastic.shrink-on-preempt``): when the
        pool took K of the elastic type's workers, re-form the survivors at
        the largest divisor count >= the elastic floor instead of re-queuing
        the full gang and waiting for capacity that may never come back.
        None → respond to the preemption the classic way (full-size restart
        through pool admission)."""
        if not self.config.get_bool(keys.ELASTIC_SHRINK_ON_PREEMPT):
            return None
        et = self._elastic_jobtype()
        cfg = self._effective_config()
        if et not in cfg.job_types():
            return None
        current = cfg.instances(et)
        with self.session.lock:
            preempted = sum(
                1 for t in self.session.tasks.get(et, [])
                if t.exit_code == constants.EXIT_PREEMPTED
            )
        floor = self._elastic_floors().get(et, 0)
        target = plan_preempt_shrink(current, current, preempted, floor)
        if target is None:
            return None
        return {et: target}

    # -------------------------------------------- cooperative preemption
    def _plan_drain_shrink(self, workers: int) -> dict[str, int] | None:
        """The pool asked this job to shed ``workers`` elastic workers
        (partial reclaim): the divisor-preserving target the survivors
        re-form at (same rule as shrink-on-preempt — batch/mesh divisibility
        must survive), or None when the ask cannot be honored (elasticity
        off, floor too high) and the pool should escalate."""
        if not self.config.get_bool(keys.ELASTIC_SHRINK_ON_PREEMPT):
            return None
        et = self._elastic_jobtype()
        cfg = self._effective_config()
        if et not in cfg.job_types():
            return None
        current = cfg.instances(et)
        floor = self._elastic_floors().get(et, 0)
        target = plan_preempt_shrink(current, current, max(int(workers), 1), floor)
        if target is None:
            return None
        return {et: target}

    # -------------------------------------------- capacity market
    def _publish_market_deficit(self) -> None:
        """While our allocation pends, publish the unmet deficit to the
        pool's capacity market (docs/scheduling.md "Capacity market"):
        workers = unlaunched instances of the highest-priority pending type,
        unit = its per-instance ask. The pool may fund it by partially
        shrinking elastic borrowers; re-published every ~2s as the demand
        heartbeat the pool's TTL watches. Advisory by design — any failure
        degrades to silence."""
        if not self._market_enabled or not hasattr(self.rm, "update_demand"):
            return
        now = time.monotonic()
        if now - self._last_market_publish < 2.0:
            return
        self._last_market_publish = now
        pending = [p for p in self.scheduler.plans.values() if not p.launched]
        if not pending:
            return
        plan = min(pending, key=lambda p: p.priority)
        # net deficit: instances this plan still needs beyond the containers
        # it already holds — publishing the gross count would tax borrowers
        # for capacity we are already sitting on
        placed = sum(1 for c in self._containers.values()
                     if c.job_type == plan.job_type)
        deficit = max(plan.instances - placed, 0)
        if deficit < 1:
            return
        if self.rm.update_demand(
            deficit, plan.resources,
            reason=(f"pending {plan.job_type} x{deficit}"
                    f" (ttft slo {self._market_slo_ttft_ms}ms)"),
        ):
            self._market_published = True

    def _clear_market_deficit(self) -> None:
        """The gang placed (or is tearing down): retract our published
        demand so the market stops taxing borrowers for a deficit that no
        longer exists."""
        if not self._market_published or not hasattr(self.rm, "update_demand"):
            return
        self._market_published = False
        self._last_market_publish = 0.0
        from tony_tpu.cluster.resources import Resources

        self.rm.update_demand(0, Resources(), reason="placed")

    def _handle_grow_offer(self, req_id: str, workers: int) -> None:
        """A grow-back offer from the pool's capacity market (demand ebbed):
        resize the elastic jobtype back up by the offered workers, capped by
        ``tony.elastic.max-workers``. Acceptance is implicit — the resize
        re-registers the grown demand with the pool, which settles this
        gang's entry in the grow-back ledger; an offer we cannot use simply
        expires pool-side (the debt stays booked)."""
        self._drain_handled.add(req_id)  # offers re-send until resolved
        et = self._elastic_jobtype()
        cfg = self._effective_config()
        if workers < 1 or et not in cfg.job_types():
            return
        current = cfg.instances(et)
        target = current + workers
        ceiling = self.config.get_int(keys.ELASTIC_MAX_WORKERS, 0)
        if ceiling > 0:
            target = min(target, ceiling)
        if target <= current:
            return
        resize = {et: target}
        reason = (f"capacity returned (grow-back {req_id}): "
                  f"{et} {current}→{target}")
        obs_logging.info(f"[tony-am] {reason}")
        if not self._containers:
            self._resize_while_queued(resize, reason, trigger="capacity")
        else:
            # budget-exempt like preemption: growing back is a cluster
            # action, not a job failure
            self._maybe_restart_gang(
                reason, exit_code=constants.EXIT_PREEMPTED,
                resize=resize, trigger="capacity",
            )

    def _poll_preemption_notice(self) -> None:
        """Read the pool's cooperative-preemption piggyback (rode the
        ``poll_exited`` the monitor loop just made) and open a drain episode:
        emit PREEMPTION_REQUESTED and start the urgent-checkpoint fan-out
        over the heartbeat responses."""
        notice = self.rm.poll_preemption()
        if not notice and self.chaos is not None:
            # chaos preempt-drain: a synthesized cooperative notice drives
            # the identical fan-out/yield path on pools that never preempt
            notice = self.chaos.poll_preempt_notice()
        if not notice:
            return
        cancelled = notice.get("cancelled")
        if cancelled:
            hit = False
            with self._epoch_lock:
                if self._drain is not None and self._drain["req_id"] == cancelled:
                    self._drain = None
                    hit = True
            if hit:
                # the terminating event matters beyond logging: it closes
                # the goodput ledger's preempt_drain window — without it
                # everything after the cancellation would classify as drain
                self.events.emit(
                    EventType.PREEMPTION_CANCELLED, req_id=cancelled)
                obs_logging.info(
                    f"[tony-am] preemption {cancelled} cancelled by the pool "
                    "(re-admitted before yielding) — resuming normally")
            return
        req_id = str(notice.get("req_id") or "")
        if not req_id or req_id in self._drain_handled:
            return
        with self._epoch_lock:
            if self._drain is not None:
                return  # one episode at a time; the pool re-sends until resolved
        mode = str(notice.get("mode") or "drain")
        if mode == "grow":
            # capacity market grow-back: no drain episode — a resize back up
            self._handle_grow_offer(req_id, int(notice.get("grow_workers") or 0))
            return
        deadline_s = max(int(notice.get("deadline_ms") or 0), 0) / 1000
        shrink_workers = int(notice.get("shrink_workers") or 0)
        resize = self._plan_drain_shrink(shrink_workers) if mode == "shrink" else None
        untracked = self.session.untracked
        targets = {
            f"{i['name']}:{i['index']}"
            for i in self.session.task_infos()
            if i["name"] not in untracked
            and i["status"] in (TaskStatus.REGISTERED.value, TaskStatus.RUNNING.value)
        }
        # yield early enough that the release beats the pool's kill deadline:
        # two heartbeats of margin (the fan-out and the ack each ride one)
        hb_s = self.config.get_time_ms(keys.TASK_HEARTBEAT_INTERVAL_MS, 1000) / 1000
        now = time.monotonic()
        with self._epoch_lock:
            self._drain = {
                "req_id": req_id, "mode": mode, "resize": resize,
                "workers": shrink_workers, "targets": targets, "acks": {},
                "t0": now,
                "yield_by": now + max(deadline_s - 2 * hb_s, deadline_s * 0.5),
                "done": False,
            }
        self._drain_handled.add(req_id)
        self.events.emit(
            EventType.PREEMPTION_REQUESTED,
            req_id=req_id, mode=mode, deadline_ms=int(deadline_s * 1000),
            shrink_workers=shrink_workers,
            resize=resize, targets=sorted(targets),
        )
        obs_logging.warning(
            f"[tony-am] pool preemption {req_id}: {mode}"
            + (f" {shrink_workers} worker(s) → {resize}" if mode == "shrink" else "")
            + f", deadline {deadline_s:.1f}s — urgent-checkpointing "
            f"{len(targets)} task(s)")

    def _drive_drain(self) -> None:
        """Yield once every targeted task's urgent checkpoint landed (or at
        the margin before the pool's kill deadline): emit PREEMPTION_YIELDED
        with the saved steps and release the gang — a budget-exempt restart
        that re-queues through admission (drain) or re-forms the survivors
        at the shrunken size (shrink)."""
        with self._epoch_lock:
            drain = self._drain
            if drain is None or drain["done"]:
                return
            now = time.monotonic()
            cooperative = drain["targets"] <= set(drain["acks"])
            if not cooperative and now < drain["yield_by"]:
                return
            if drain["mode"] == "shrink" and drain["resize"] is None:
                # cannot honor the shrink (divisor/floor says no): the
                # checkpoints are fresh, but the decision is the pool's —
                # hold and let the deadline escalate to a whole-gang evict
                drain["done"] = True
                obs_logging.warning(
                    f"[tony-am] cannot shed {drain['workers']} worker(s) "
                    "(no divisor target above the elastic floor) — awaiting "
                    "pool escalation with checkpoints fresh")
                return
            self._drain = None
        waited_s = now - drain["t0"]
        if self.tracer is not None:
            # the drain episode as one backdated span (same reconstruction
            # as am.queue_wait) so `tony trace` puts it on the timeline
            with self.tracer.span("am.preempt_drain") as sp:
                sp.start_ms -= waited_s * 1000.0
                sp.set(mode=drain["mode"], cooperative=cooperative,
                       req_id=drain["req_id"])
        self.events.emit(
            EventType.PREEMPTION_YIELDED,
            req_id=drain["req_id"], mode=drain["mode"],
            cooperative=cooperative, saved_steps=drain["acks"],
            waited_ms=int(waited_s * 1000),
        )
        progress = (
            "all" if cooperative else f"{len(drain['acks'])}/{len(drain['targets'])}"
        )
        obs_logging.warning(
            f"[tony-am] yielding to preemption {drain['req_id']} "
            f"({progress} task(s) checkpointed in {waited_s:.1f}s)")
        if drain["mode"] == "shrink":
            self._maybe_restart_gang(
                f"pool partial reclaim: shedding to {drain['resize']}",
                exit_code=constants.EXIT_PREEMPTED,
                resize=drain["resize"], trigger="preempt",
            )
        else:
            self._maybe_restart_gang(
                f"preempted (cooperative drain {drain['req_id']})",
                exit_code=constants.EXIT_PREEMPTED,
            )

    def _maintain_spares(self) -> None:
        """Keep ``tony.elastic.spares`` parked executors of the elastic type
        next to the gang (throttled; the gang always outranks spares — a
        shortage just skips the top-up until capacity frees up)."""
        target = self.config.get_int(keys.ELASTIC_SPARES, 0)
        if target <= 0:
            return
        now = time.monotonic()
        if now - self._last_spare_topup < 1.0:
            return
        self._last_spare_topup = now
        et = self._elastic_jobtype()
        plan = self.scheduler.plans.get(et)
        if plan is None or not plan.launched:
            return  # never hold spare capacity while the main gang still waits
        with self._epoch_lock:
            parked = sum(
                1 for sp in self._spares.values() if sp.get("assignment") is None
            )
        for _ in range(target - parked):
            try:
                container = self.rm.allocate(et, -(self._spare_seq + 1), plan.resources)
            except (AllocationError, AllocationPending):
                return  # spares are opportunistic: retry on a later tick
            self._spare_seq += 1
            spare_id = f"spare-{self._spare_seq}"
            with self._epoch_lock:
                self._spares[spare_id] = {
                    "container": container, "ready": False, "assignment": None,
                }
            self._start_executor(container, spare_id=spare_id)
            obs_logging.info(f"[tony-am] launched hot spare {spare_id} ({et})")

    def _reap_dead_spare(self, container_id: str, exit_code: int) -> None:
        """A PARKED spare's container died (crash, node loss): release it so
        the top-up loop replaces it instead of counting a corpse as spare
        capacity. Promoted spares are ordinary gang containers and never
        reach here."""
        with self._epoch_lock:
            hit = next(
                (
                    (sid, sp) for sid, sp in self._spares.items()
                    if sp.get("assignment") is None and sp["container"].id == container_id
                ),
                None,
            )
            if hit is None:
                return
            sid, sp = hit
            del self._spares[sid]
        self.rm.release(sp["container"])
        obs_logging.warning(
            f"[tony-am] hot spare {sid} died while parked (exit {exit_code})")

    def _kill_all_spares(self) -> None:
        """Teardown: reap parked spares (promoted ones are ordinary gang
        containers and die through ``_kill_all_containers``)."""
        with self._epoch_lock:
            parked = {
                sid: sp for sid, sp in self._spares.items()
                if sp.get("assignment") is None
            }
            for sid in parked:
                del self._spares[sid]
        for sp in parked.values():
            self.rm.kill_container(sp["container"])
            self.rm.release(sp["container"])

    def _maybe_restart_gang(
        self, reason: str, exit_code: int | None = None,
        resize: dict[str, int] | None = None, trigger: str = "capacity",
    ) -> bool:
        """Whole-gang restart from checkpoint (rebuild-only elasticity).

        Preemption (EXIT_PREEMPTED) is a CLUSTER action, not a job failure:
        the gang always restarts (re-queuing through pool admission) and the
        eviction never consumes the failure budget — YARN likewise excludes
        preempted containers from AM failure counts.

        Before relaunching, the AM re-checks the pool's alive capacity: a
        gang that no longer fits (node permanently lost) re-plans to a
        smaller instance count when ``tony.<type>.min-instances`` allows —
        the workers then restore the checkpoint onto the smaller mesh.
        """
        preempted = exit_code == constants.EXIT_PREEMPTED
        if not preempted:
            if not self.config.get_bool(keys.TASK_RESTART_ON_FAILURE):
                return False
            budget = self.config.get_int(keys.TASK_MAX_TOTAL_INSTANCE_FAILURES, 0)
            self._failures_seen += 1
            # durable: a takeover AM must inherit the spent failure budget,
            # or an AM crash would hand every job a fresh allowance
            self._jlog("failures", n=self._failures_seen)
            if self._failures_seen > budget:
                return False
        _GANG_RESTARTS.inc()
        with obs_trace.maybe_span(
            "am.gang_restart", reason=reason,
            attempt=self._restart_attempt + 1, preempted=preempted,
        ):
            return self._restart_gang_spanned(reason, resize, trigger)

    def _restart_gang_spanned(
        self, reason: str, resize: dict[str, int] | None, trigger: str = "capacity"
    ) -> bool:
        self.events.emit(EventType.HEARTBEAT_LOST, reason=f"gang restart: {reason}")
        # an in-flight capture can never complete across the restart: the
        # children that would have captured are being killed, and relaunch
        # clears their control files — fail it now so the next `tony
        # profile` isn't blocked by a ghost request
        self._profile.abort(f"gang restarted: {reason}")
        obs_logging.warning(f"[tony-am] gang restart: {reason}")
        self._kill_all_containers()
        for c in list(self._containers.values()):
            self.rm.release(c)
        self._containers.clear()
        self._by_task.clear()
        announce = resize is not None
        if resize is None:  # a caller may pass the plan it already computed
            resize = self._plan_gang_downsize()
            announce = bool(resize)
            reason = f"capacity lost: {reason}"
        with self._epoch_lock:  # atomic with _fenced_session's capture
            # whatever drove this restart, the old gang's drain episode is
            # over: its acks reference tasks that no longer exist, and a
            # stale episode must not yield the NEW gang later
            self._drain = None
            self._task_drains.clear()  # per-task (scale-down) episodes too
            old_cfg = self._effective_config()
            old = {t: old_cfg.instances(t) for t in (resize or {})}
            if resize:
                self._resized.update(resize)
            cfg = self._effective_config()
            self._restart_attempt += 1
            self._gang_complete_fired = False
            self._gang_started_ms = None
            self.session = Session(cfg)
            self.session.job_status = JobStatus.RUNNING
            self.scheduler = TaskScheduler(cfg, self.session, self.rm)
            # promoted spares died with the gang they joined (their containers
            # were just killed above); parked spares survive the restart —
            # that is the whole point: the relaunch can promote them without
            # touching the allocator
            self._spares = {
                sid: sp for sid, sp in self._spares.items()
                if sp.get("assignment") is None
            }
        lg = obs_logging.get()
        if lg is not None:
            lg.epoch = self._restart_attempt  # stamp the new gang epoch on records
        # the epoch record supersedes every registration/container record
        # before it: a takeover after this restart adopts only the new gang
        self._jlog("epoch", attempt=self._restart_attempt, resized=dict(self._resized))
        if announce:
            self._announce_resize(resize, reason, trigger=trigger, old=old)
        return True

    def run(self) -> JobStatus:
        """The AM monitor loop (SURVEY.md §3.1 middle block)."""
        interval_s = self.config.get_time_ms(keys.AM_MONITOR_INTERVAL_MS, 200) / 1000
        hb_interval = self.config.get_time_ms(keys.TASK_HEARTBEAT_INTERVAL_MS, 1000)
        hb_max_missed = self.config.get_int(keys.TASK_MAX_MISSED_HEARTBEATS, 25)
        gang_timeout = self.config.get_time_ms(keys.AM_GANG_TIMEOUT_MS, 300_000)
        metrics_every_s = self.config.get_time_ms(keys.TASK_METRICS_INTERVAL_MS, 5000) / 1000
        last_metrics_emit = 0.0
        last_snapshot_key = None

        while True:
            if self._kill_requested:
                self._kill_all_containers()
                for t in self.session.all_tasks():
                    self.session.mark_killed(t)
                self.session.job_status = JobStatus.KILLED
                break

            # 0. externally-requested elastic resize (autoscaler / tony
            # resize), then hot-spare top-up for the elastic jobtype, then
            # (when enabled) takeover-journal compaction
            self._apply_pending_resize()
            self._maintain_spares()
            self._maybe_compact_journal()
            if self._chaos_step_gated:
                # progress feed for @step+N-gated container faults: the max
                # TRAINING step any executor has pushed
                step = 0
                for t in self.session.task_infos():
                    s = ((t.get("metrics") or {}).get("train") or {}).get("step")
                    if isinstance(s, (int, float)):
                        step = max(step, int(s))
                if step:
                    self.chaos.set_progress(step)
                    if step > self._journal_chaos_step:
                        # durable watermark: a takeover AM must not re-arm
                        # @step+N gates the dead AM already walked past
                        self._journal_chaos_step = step
                        self._jlog("chaos_step", step=step)
            if self.chaos is not None and self.chaos.take("am-crash") is not None:
                # control-plane death fidelity (same rule as container kills):
                # no stop(), no status file, no event flush — SIGKILL this
                # very process mid-loop. Recovery is the client's AM retry,
                # which replays the journal and adopts the gang.
                os.kill(os.getpid(), signal.SIGKILL)

            # 1. launch job types whose dependencies are satisfied
            try:
                for job_type in self.scheduler.ready_types():
                    self._launch_type(job_type)
                if self._queue_waiting:
                    self._queue_waiting = False
                    self.events.emit(EventType.QUEUE_WAIT, state="admitted")
                    self._clear_market_deficit()
                    if self._queue_wait_started is not None:
                        waited_s = time.monotonic() - self._queue_wait_started
                        self._queue_wait_started = None
                        _QUEUE_WAIT.observe(waited_s)
                        if self.tracer is not None:
                            # reconstruct the wait episode as one span (its
                            # start is backdated to when queueing began) so
                            # `tony trace` can put queue wait on the timeline
                            with self.tracer.span("am.queue_wait") as sp:
                                sp.start_ms -= waited_s * 1000.0
            except AllocationPending as e:
                # queued behind other tenants: wait (don't fail) and retry
                # the whole type next tick; emit one event per wait episode
                if not self._queue_waiting:
                    self._queue_waiting = True
                    self._queue_wait_started = time.monotonic()
                    self.events.emit(EventType.QUEUE_WAIT, state="waiting", reason=str(e))
                # capacity market: tell the pool what is missing so it can
                # fund the wait by shrinking elastic borrowers (throttled)
                self._publish_market_deficit()
                # mid-wait elastic check (throttled): if capacity was lost
                # for good while we queued, shrink instead of waiting forever
                now = time.time()
                if now - self._last_capacity_probe > 2.0:
                    self._last_capacity_probe = now
                    plan = self._plan_gang_downsize()
                    if plan and not self._containers:
                        self._resize_while_queued(plan, "capacity lost while queued")
                    elif plan:
                        # PARTIALLY-allocated gang (some containers running,
                        # the rest waiting on capacity that died): the only
                        # safe shrink is a whole-gang restart — budget-exempt
                        # like preemption, since capacity loss is a cluster
                        # event, not a job failure. The plan is passed in so
                        # a flapping second probe can't kill the gang for a
                        # full-size relaunch.
                        self._maybe_restart_gang(
                            "capacity lost while partially allocated",
                            exit_code=constants.EXIT_PREEMPTED,
                            resize=plan,
                        )
            except (DependencyTimeout, AllocationError) as e:
                self._fail(str(e))
                self._kill_all_containers()
                break

            # 2. container exits (catches silent executor death)
            self._handle_container_exits()

            # 2a. cooperative preemption: drain/shrink notices piggyback on
            # the poll above; urgent-checkpoint then yield inside the
            # pool's deadline (docs/scheduling.md state machine)
            self._poll_preemption_notice()
            self._drive_drain()

            # 2b. periodic METRICS_SNAPSHOT into the .jhist: executors push
            # metrics over RPC onto TaskInfo; snapshotting them into the
            # event stream gives the portal (live view + finished-job
            # charts) a time series without a second storage path
            now = time.time()
            if now - last_metrics_emit >= metrics_every_s:
                last_metrics_emit = now
                snap = [
                    # obs_metrics (the executor's piggybacked registry) is
                    # exposition-only — snapshotting it into the .jhist would
                    # bloat every event with full histogram state
                    {
                        "task": f"{t['name']}:{t['index']}",
                        "metrics": {k: v for k, v in t["metrics"].items() if k != "obs_metrics"},
                    }
                    for t in self.session.task_infos()
                    if t.get("metrics")
                ]
                # dedup on the per-task TRAIN step identity: executors
                # re-push the same step report until the next one lands, and
                # identical snapshots would bloat the .jhist without bound
                key = tuple(
                    (e["task"], (e["metrics"].get("train") or {}).get("step"))
                    for e in snap
                )
                if snap and key != last_snapshot_key:
                    last_snapshot_key = key
                    self.events.emit(EventType.METRICS_SNAPSHOT, tasks=snap)

            # 2c. goodput tick (throttled): straggler skew off the piggybacked
            # step-time histograms + the declarative tony.alerts.* rules
            self._goodput_tick()

            # 3. heartbeat liveness
            for t in self.session.find_dead_tasks(hb_interval, hb_max_missed):
                self.session.mark_lost(t)
                self.events.emit(EventType.HEARTBEAT_LOST, task=t.id)
                c = self._by_task.get((t.job_name, t.index))
                if c is not None:
                    self.rm.kill_container(c)

            # 4. gang-registration timeout
            if (
                not self.session.cluster_spec_complete()
                and self._gang_started_ms is not None
                and self.scheduler.all_launched()
                and time.time() * 1000 - self._gang_started_ms > gang_timeout
            ):
                self._fail(f"gang incomplete after {gang_timeout}ms "
                           f"({self.session.registered_count()}/{self.session.total_tasks()} registered)")
                self._kill_all_containers()
                break

            # 5. fail-fast on tracked failure (or gang-restart if enabled).
            # Preempted workers may additionally SHRINK the elastic data axis
            # (tony.elastic.shrink-on-preempt) so the survivors resume from
            # checkpoint now instead of re-queuing the full gang.
            failed = self.session.any_tracked_failed()
            if failed is not None:
                resize, trigger = None, "capacity"
                if failed.exit_code == constants.EXIT_PREEMPTED:
                    with self._epoch_lock:
                        drain, self._drain = self._drain, None
                    if drain is not None:
                        # the pool killed us before (or while) we yielded:
                        # record the escalation — the urgent checkpoints that
                        # DID land still bound the rework
                        self.events.emit(
                            EventType.PREEMPTION_ESCALATED,
                            req_id=drain["req_id"], mode=drain["mode"],
                            saved_steps=drain["acks"],
                        )
                        obs_logging.warning(
                            f"[tony-am] preemption {drain['req_id']} escalated "
                            f"by the pool ({len(drain['acks'])}/"
                            f"{len(drain['targets'])} task(s) had checkpointed)")
                    resize = self._plan_preempt_shrink()
                    if resize:
                        trigger = "preempt"
                if self._maybe_restart_gang(
                    f"task {failed.id} {failed.status.value}", failed.exit_code,
                    resize=resize, trigger=trigger,
                ):
                    continue
                self._fail(f"tracked task {failed.id} {failed.status.value} "
                           f"(exit_code={failed.exit_code})")
                self._kill_all_containers()
                for t in self.session.all_tasks():
                    self.session.mark_killed(t)
                break

            # 6. normal completion: all tracked done → kill untracked, reduce
            if self.session.tracked_all_terminal() or (
                not self.session.tracked_tasks()
                and all(t.status.terminal for t in self.session.all_tasks())
            ):
                for t in self.session.untracked_tasks():
                    if not t.status.terminal:
                        c = self._by_task.get((t.job_name, t.index))
                        if c is not None:
                            self.rm.kill_container(c)
                        self.session.mark_killed(t)
                break

            time.sleep(interval_s)

        return self.stop()

    def stop(self) -> JobStatus:
        self._kill_all_spares()  # parked spares must not outlive the job
        final = self.session.reduce_final_status()
        completed_ms = int(time.time() * 1000)
        # a finished job's alerts are no longer actionable: resolve them into
        # the event stream + sink instead of leaving ghosts firing forever
        for rec in self._alerts.resolve_all("job finalized"):
            self.events.emit(
                EventType.ALERT_RESOLVED,
                **{k: v for k, v in rec.items() if k != "app_id"})
        obs_logging.info(f"[tony-am] application {self.app_id} finished: {final.value}")
        self.events.emit(
            EventType.APPLICATION_FINISHED,
            status=final.value,
            reason=self.session.failure_reason,
            tasks=self.session.task_infos(),
        )
        self.events.stop()
        try:
            history.finalize_history(
                self.history_root,
                self.app_id,
                self.events.intermediate_path,
                self.started_ms,
                completed_ms,
                final.value,
                config_snapshot=self.config.to_dict(),
            )
        except OSError:
            pass  # history must never change the job verdict
        if self.tracer is not None and self._root_span is not None:
            # flush am.run BEFORE am_status.json: the status file is the
            # client's completion signal, and a `tony trace` run the moment
            # monitor_application returns must find the root span on disk
            self._root_span.set(status=final.value, restart_attempts=self._restart_attempt)
            self.tracer.end_span(self._root_span, self._root_token)
            self._root_span = None
            obs_trace.shutdown()
        _atomic_write_json(
            os.path.join(self.staging_dir, "am_status.json"),
            {
                "app_id": self.app_id,
                "status": final.value,
                "reason": self.session.failure_reason,
                "started_ms": self.started_ms,
                "completed_ms": completed_ms,
                "tensorboard_url": self.tensorboard_url,
                "restart_attempt": self._restart_attempt,
                "am_attempt": self.am_attempt,
                "takeover": self._takeover_outcome,
                "tasks": self.session.task_infos(),
            },
        )
        self.rpc.stop()
        self.rm.shutdown()
        if self._journal is not None:
            self._journal.close()
        return final


def _local_host() -> str:
    return os.environ.get("TONY_BIND_HOST", "127.0.0.1")


def _atomic_write_json(path: str, obj: Any, mode: int = 0o644) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with os.fdopen(os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, mode), "w") as f:
        json.dump(obj, f, indent=1)
    os.replace(tmp, path)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="tony-am")
    p.add_argument("--app-id", required=True)
    p.add_argument("--staging-dir", required=True)
    p.add_argument("--takeover", action="store_true",
                   help="replay am_journal.jsonl and adopt the live gang "
                        "(AM-retry path; degrades to a full restart on a "
                        "missing/corrupt journal)")
    p.add_argument("--am-attempt", type=int, default=0,
                   help="which AM attempt this is (0 = original launch)")
    args = p.parse_args(argv)
    config = TonyConfig.load_final(os.path.join(args.staging_dir, constants.TONY_FINAL_CONF))
    if config.get_bool(keys.DEBUG_LOCKTRACE):
        # before the AM constructs its locks — a plain Lock cannot
        # retroactively grow tracing (obs/locktrace.py)
        obs_locktrace.set_enabled(True)
    am = ApplicationMaster(config, args.app_id, args.staging_dir,
                           takeover=args.takeover, am_attempt=args.am_attempt)
    am.prepare()
    final = am.run()
    return constants.EXIT_SUCCESS if final == JobStatus.SUCCEEDED else constants.EXIT_FAILURE


if __name__ == "__main__":
    sys.exit(main())
