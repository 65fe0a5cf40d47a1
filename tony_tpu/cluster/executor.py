"""The per-container task executor.

Analog of the reference's ``TaskExecutor.java`` (SURVEY.md §2.1, §3.1): runs
inside a container, registers ``jobName:index`` + its rendezvous port with the
AM, blocks on the gang barrier until the full cluster spec is available,
applies the framework runtime's env contract, execs the user process via the
shell, heartbeats and pushes metrics in the background, and reports the exit
code back. The hot training loop lives entirely inside the user process — the
executor never touches tensors.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

from tony_tpu import constants
from tony_tpu.chaos import ChaosContext
from tony_tpu.config import TonyConfig, keys
from tony_tpu.cluster.metrics import MetricsSampler
from tony_tpu.cluster.rpc import RpcClient, RpcError
from tony_tpu.obs import introspect as obs_introspect
from tony_tpu.obs import logging as obs_logging
from tony_tpu.obs import metrics as obs_metrics
from tony_tpu.obs import startup as obs_startup
from tony_tpu.obs import trace as obs_trace
from tony_tpu.runtime import get_runtime

_HB_RTT = obs_metrics.histogram(
    "tony_heartbeat_rtt_seconds", "executor → AM heartbeat round-trip time")


def pick_free_port(host: str = "127.0.0.1") -> int:
    with socket.socket() as s:
        s.bind((host, 0))
        return s.getsockname()[1]


def _own_host(am_host: str) -> str:
    """This container's reachable address: loopback deployments stay on
    loopback; otherwise the host's resolved address."""
    if am_host.startswith("127.") or am_host == "localhost":
        return "127.0.0.1"
    try:
        return socket.gethostbyname(socket.gethostname())
    except OSError:
        return socket.gethostname()


class TaskExecutor:
    def __init__(self, env: dict[str, str] | None = None):
        env = dict(env or os.environ)
        self.app_id = env[constants.ENV_APP_ID]
        self.staging_dir = env[constants.ENV_STAGING_DIR]
        self.job_name = env[constants.ENV_JOB_NAME]
        self.index = int(env[constants.ENV_TASK_INDEX])
        am_host = env.get(constants.ENV_AM_HOST, "127.0.0.1")
        self.config = TonyConfig.load_final(os.path.join(self.staging_dir, constants.TONY_FINAL_CONF))
        obs_metrics.set_enabled(self.config.get_bool(keys.METRICS_ENABLED, True))
        self.attempt = int(env.get("TONY_RESTART_ATTEMPT", "0"))  # gang-epoch fence
        # structured logging (tony.log.*): this supervisor's records join the
        # job-wide <staging>/logs aggregate `tony logs` merges
        obs_logging.init_from_config(
            self.config, identity=f"{self.job_name}:{self.index}",
            staging_dir=self.staging_dir, epoch=self.attempt,
        )
        # tracing (tony.trace.*): the root span parents under the AM's via
        # TONY_TRACE_PARENT; None — and zero-cost — unless enabled
        self.tracer = obs_trace.init_from_config(
            self.config, identity=f"{self.job_name}:{self.index}",
            staging_dir=self.staging_dir, app_id=self.app_id,
            parent_id=env.get(constants.ENV_TRACE_PARENT),
        )
        self._root_span: obs_trace.Span | None = None
        self._root_token = None
        # fault injection (tony.chaos.*, docs/fault-tolerance.md): None —
        # and zero-cost — unless a schedule is configured
        self.chaos = ChaosContext.from_config(
            self.config, identity=f"{self.job_name}:{self.index}", staging_dir=self.staging_dir
        )
        self.rpc = RpcClient(
            am_host,
            int(env[constants.ENV_AM_PORT]),
            secret=env.get(constants.ENV_AM_SECRET, ""),
            chaos=self.chaos,
        )
        self.runtime = get_runtime(self.config)
        # THIS task's rendezvous address — the executor's own host, not the
        # AM's (they differ on any multi-host pool).
        self.host = env.get("TONY_EXECUTOR_HOST") or _own_host(am_host)
        self.port = pick_free_port(self.host)
        self.child: subprocess.Popen | None = None
        self._stop = threading.Event()
        self._hb_failures = 0
        # AM endpoint re-resolution (work-preserving takeover): True once the
        # CURRENT rpc target has acknowledged this executor — the env-provided
        # AM did at registration; a takeover AM must ack a resync_task first
        self._am_synced = True
        # hot-spare contract (tony.elastic.spares): set → park after
        # register_spare and wait for a gang-slot promotion instead of
        # registering as (job_name, index) right away
        self.spare_id = env.get(constants.ENV_SPARE_ID) or None
        # on-demand profile relay (tony profile): control file out to the
        # child, done file back, status reported over RPC — driven entirely
        # from the heartbeat thread
        self._profile_courier = obs_introspect.ProfileCourier(
            self.staging_dir, self.job_name, self.index, self._report_profile
        )
        # cooperative-preemption relay (docs/scheduling.md): urgent-checkpoint
        # request out to the child, saved-step report back — same
        # heartbeat-driven control/done file contract as the profile courier
        self._drain_courier = obs_introspect.DrainCourier(self._report_drain)

    # -- AM endpoint re-resolution (work-preserving takeover) ---------------
    def _read_am_info(self) -> tuple[str, int, str] | None:
        """The staging dir's current AM advertisement, or None (missing — the
        AM is between attempts — or torn mid-read)."""
        try:
            with open(os.path.join(self.staging_dir, constants.AM_INFO_FILE)) as f:
                info = json.load(f)
            return str(info["host"]), int(info["port"]), str(info.get("secret", ""))
        except (OSError, ValueError, KeyError, TypeError):
            return None

    def _resolve_am_move(self) -> bool:
        """The AM stopped answering: check whether a takeover attempt has
        republished ``am_info`` with a fresh endpoint, and if so re-attach.

        Returns True only when a resync against the (re)resolved endpoint was
        acknowledged — the caller may then reset its failure accounting. A
        ``stale`` answer means this gang epoch is over (degraded takeover):
        kill the child and exit rather than poison the replacement gang."""
        info = self._read_am_info()
        if info is None:
            return False
        current = (self.rpc.host, self.rpc.port, self.rpc.secret)
        if info == current and self._am_synced:
            return False  # same AM, just unreachable: keep riding the budget
        if info != current:
            obs_logging.info(
                f"[tony-executor] {self.job_name}:{self.index} re-resolving AM "
                f"→ {info[0]}:{info[1]}")
            self.rpc.retarget(*info)
            self._am_synced = False
        try:
            resp = self.rpc.call(
                "resync_task", job_name=self.job_name, index=self.index,
                host=self.host, port=self.port, attempt=self.attempt,
            )
        except (RpcError, OSError):
            return False  # new AM not serving yet: retry on the next beat
        if resp.get("stale"):
            obs_logging.error(
                f"[tony-executor] {self.job_name}:{self.index} superseded by a "
                "degraded AM takeover — killing child and exiting")
            self._kill_child()
            os._exit(constants.EXIT_HEARTBEAT_LOST)
        self._am_synced = True
        obs_logging.info(
            f"[tony-executor] {self.job_name}:{self.index} re-synced with the "
            f"takeover AM at {self.rpc.host}:{self.rpc.port}")
        return True

    def _am_call_resilient(self, method: str, deadline_s: float, **params):
        """``call_with_retry`` in bounded bursts with AM re-resolution in
        between: registration, spec polling, and the final result report must
        survive an AM takeover mid-call, not just transient flakes."""
        start = time.monotonic()
        last: Exception | None = None
        while True:
            remaining = deadline_s - (time.monotonic() - start)
            if remaining <= 0:
                raise RpcError(
                    f"{method}: AM unreachable for {deadline_s:.0f}s "
                    f"(even across endpoint re-resolution): {last}")
            try:
                return self.rpc.call_with_retry(
                    method, retries=10, delay_s=0.2,
                    deadline_s=max(min(remaining, 3.0), 0.5), **params)
            except (RpcError, OSError) as e:
                last = e
                self._resolve_am_move()

    # -- hot-spare parking -------------------------------------------------
    def _park_as_spare(self) -> bool:
        """Announce this executor as a parked spare, then poll until the AM
        promotes it into a gang slot (adopt that identity and return True)
        or reaps it (return False → clean exit). The whole point of a spare
        is that everything up to here — container allocation, process start,
        registration round-trip — is already paid when a grow or a
        preemption replacement needs a worker."""
        resp = self.rpc.call_with_retry(
            "register_spare", retries=30, delay_s=0.2, deadline_s=30,
            spare_id=self.spare_id, host=self.host, port=self.port,
        )
        if not resp.get("ack"):
            return False  # reaped before we even announced
        obs_logging.info(f"[tony-executor] spare {self.spare_id} parked")
        poll_s = 0.25
        # same AM-outage tolerance the gang heartbeat loop gets: the
        # missed-heartbeat budget is denominated in heartbeat INTERVALS
        # (~1 s each), not in these faster polls
        hb_s = self.config.get_time_ms(keys.TASK_HEARTBEAT_INTERVAL_MS, 1000) / 1000
        tolerance_s = self.config.get_int(keys.TASK_MAX_MISSED_HEARTBEATS, 25) * hb_s
        unreachable_since: float | None = None
        while True:
            try:
                resp = self.rpc.call("poll_spare_assignment", spare_id=self.spare_id)
                unreachable_since = None
            except (RpcError, OSError):
                # a takeover AM does not adopt parked spares: retarget so the
                # next poll reaches it, gets `stale`, and this spare exits
                # cleanly (the new AM's top-up loop launches replacements)
                info = self._read_am_info()
                if info is not None and info != (self.rpc.host, self.rpc.port, self.rpc.secret):
                    self.rpc.retarget(*info)
                now = time.monotonic()
                if unreachable_since is None:
                    unreachable_since = now
                elif now - unreachable_since > tolerance_s:
                    return False  # AM is gone: a spare must not become an orphan
                time.sleep(poll_s)
                continue
            if resp.get("stale"):
                return False
            assignment = resp.get("assignment")
            if assignment:
                self._adopt_assignment(assignment)
                return True
            time.sleep(poll_s)

    def _adopt_assignment(self, assignment: dict) -> None:
        """Become gang member (job_name, index) of the assigned gang epoch:
        the env/courier/logging identity follows so the child contract
        (metrics file, TONY_RESTART_ATTEMPT, JOB_NAME/TASK_INDEX) is
        indistinguishable from a freshly launched executor's."""
        self.job_name = str(assignment["job_name"])
        self.index = int(assignment["index"])
        self.attempt = int(assignment.get("attempt", 0))
        os.environ[constants.ENV_JOB_NAME] = self.job_name
        os.environ[constants.ENV_TASK_INDEX] = str(self.index)
        os.environ["TONY_RESTART_ATTEMPT"] = str(self.attempt)
        self._profile_courier = obs_introspect.ProfileCourier(
            self.staging_dir, self.job_name, self.index, self._report_profile
        )
        self._drain_courier = obs_introspect.DrainCourier(self._report_drain)
        lg = obs_logging.get()
        if lg is not None:
            lg.identity = f"{self.job_name}:{self.index}"
            lg.epoch = self.attempt
        obs_logging.info(
            f"[tony-executor] spare {self.spare_id} promoted → "
            f"{self.job_name}:{self.index} (attempt {self.attempt})"
        )

    # -- gang barrier ------------------------------------------------------
    def register(self) -> None:
        timeout_ms = self.config.get_time_ms(keys.TASK_EXECUTOR_REGISTRATION_TIMEOUT_MS, 60_000)
        if self.chaos is not None:
            f = self.chaos.take("reg-slow")
            if f is not None:
                time.sleep(f.ms(default=1000) / 1000)
        self._am_call_resilient(
            "register_worker_spec",
            deadline_s=timeout_ms / 1000,
            job_name=self.job_name,
            index=self.index,
            host=self.host,
            port=self.port,
            attempt=self.attempt,
        )

    def await_cluster_spec(self) -> tuple[dict[str, list[str]], dict[str, str]]:
        """Poll until the AM has the complete gang (SURVEY.md §3.2)."""
        deadline = time.time() + self.config.get_time_ms(keys.AM_GANG_TIMEOUT_MS, 300_000) / 1000
        while time.time() < deadline:
            try:
                resp = self.rpc.call_with_retry(
                    "get_cluster_spec", retries=5, delay_s=0.2, deadline_s=2.0,
                    job_name=self.job_name, index=self.index,
                    attempt=self.attempt,
                )
            except (RpcError, OSError):
                # the AM may have MOVED (takeover) while we waited at the
                # barrier — re-resolve and keep polling inside the deadline
                self._resolve_am_move()
                continue
            if resp.get("stale"):
                # our gang epoch was killed and replaced while we were still
                # starting: the new gang reuses our (job, index) identity, so
                # proceeding would mean running with another epoch's ranks
                raise RuntimeError(
                    f"gang epoch {self.attempt} superseded while awaiting the "
                    "cluster spec — aborting this executor"
                )
            if resp.get("spec") is not None:
                return resp["spec"], resp.get("extra_env") or {}
            time.sleep(0.2)
        raise TimeoutError("cluster spec never completed (gang barrier timeout)")

    # -- user process ------------------------------------------------------
    def resolve_command(self) -> str:
        per_type = self.config.get(keys.jobtype_key(self.job_name, keys.COMMAND_SUFFIX))
        cmd = per_type or self.config.get(keys.EXECUTES) or ""
        if not cmd:
            raise ValueError(
                f"no command for task type {self.job_name!r} "
                f"(set {keys.EXECUTES} or tony.{self.job_name}.command)"
            )
        return cmd

    def build_child_env(self, spec: dict[str, list[str]], extra_env: dict[str, str]) -> dict[str, str]:
        env = dict(os.environ)
        env.update(self.runtime.executor_env(spec, self.job_name, self.index))
        env.update(extra_env)  # AM-side adapter contribution (e.g. horovod plan)
        # user-specified shell env (csv k=v, reference --shell_env)
        for kv in self.config.get_list(keys.SHELL_ENV):
            k, _, v = kv.partition("=")
            env[k] = v
        # venv activation analog: put the venv's bin first on PATH. An
        # ARCHIVE (--python_venv venv.zip / .tar.gz, reference parity:
        # localized per container) is unpacked once into the container's
        # staging area; a directory is used in place.
        venv = self.config.get(keys.PYTHON_VENV)
        if venv:
            if venv.endswith((".zip", ".tar.gz", ".tgz", ".tar")):
                venv = self._localize_venv_archive(venv)
            env["VIRTUAL_ENV"] = venv
            env["PATH"] = os.path.join(venv, "bin") + os.pathsep + env.get("PATH", "")
        pybin = self.config.get(keys.PYTHON_BINARY_PATH)
        if pybin:
            env["PYTHON_BINARY"] = pybin
        if self.chaos is not None:
            # child-process chaos contract: the training loop's injection
            # points (checkpoint restore) read the schedule from env
            env[constants.ENV_CHAOS_SPEC] = self.config.get(keys.CHAOS_SPEC) or ""
            env[constants.ENV_CHAOS_SEED] = str(self.config.get_int(keys.CHAOS_SEED, 0))
        if self.tracer is not None:
            # child-process tracing contract (train loop + checkpoint spans):
            # the child's root span links under this executor's
            env[constants.ENV_TRACE_ENABLED] = "1"
            env[constants.ENV_TRACE_DIR] = self.tracer.trace_dir
            if self._root_span is not None:
                env[constants.ENV_TRACE_PARENT] = self._root_span.span_id
        if not self.config.get_bool(keys.METRICS_ENABLED, True):
            env[constants.ENV_METRICS_ENABLED] = "0"  # child honors the job's opt-out
        if self.config.get(keys.SLO_SERVE_TTFT_TARGET):
            # SLO contract: serve children align a TTFT bucket edge to the
            # objective threshold (empty → the capacity market's number)
            env[constants.ENV_SLO_TTFT_MS] = str(
                self.config.get(keys.SLO_SERVE_TTFT_THRESHOLD_MS)
                or self.config.get(keys.SERVE_MARKET_SLO_TTFT_MS) or "2000")
        # child-process structured-logging contract: records land in the same
        # <staging>/logs aggregate as this supervisor's (tony logs merges them)
        log_level = self.config.get(keys.LOG_LEVEL) or "info"
        if log_level.lower() != "off":
            env[constants.ENV_LOG_DIR] = self.config.get(keys.LOG_DIR) or os.path.join(
                self.staging_dir, "logs"
            )
            env[constants.ENV_LOG_LEVEL] = log_level
        # on-demand profile contract: how often the child stats the control
        # file the courier drops next to the train-metrics path
        env[constants.ENV_PROFILE_POLL_MS] = str(
            self.config.get_time_ms(keys.PROFILE_POLL_INTERVAL_MS, 500)
        )
        # input-pipeline contract (tony.train.*): the child's overlapped
        # batch assembly depth + the input-wait span floor
        env[constants.ENV_PREFETCH_DEPTH] = str(
            self.config.get_int(keys.TRAIN_PREFETCH_DEPTH, 2)
        )
        env[constants.ENV_INPUT_WAIT_SPAN_MS] = str(
            self.config.get_time_ms(keys.TRAIN_INPUT_WAIT_SPAN_MS, 25)
        )
        if self.config.get_bool(keys.TASK_PROFILE):
            env[constants.ENV_PROFILE_DIR] = os.path.join(
                self.staging_dir, "profile", f"{self.job_name}_{self.index}"
            )
            env[constants.ENV_PROFILE_START_STEP] = self.config.get(keys.TASK_PROFILE_START_STEP)
            env[constants.ENV_PROFILE_NUM_STEPS] = self.config.get(keys.TASK_PROFILE_NUM_STEPS)
        # train-side throughput metrics contract: the loop writes its step
        # report (loss/tokens_per_sec/mfu) here; the metrics push loop
        # attaches it so the AM/portal see TRAINING progress, not just
        # host/TPU counters
        self._train_metrics_path = os.path.join(
            self.staging_dir, "metrics", f"{self.job_name}_{self.index}.json"
        )
        os.makedirs(os.path.dirname(self._train_metrics_path), exist_ok=True)
        env[constants.ENV_TRAIN_METRICS_FILE] = self._train_metrics_path
        if self.job_name == constants.TENSORBOARD_JOB_NAME:
            env[constants.ENV_TB_PORT] = str(self.port)
        if self.job_name == constants.NOTEBOOK_JOB_NAME:
            # the interactive server binds the executor's rendezvous port; the
            # submitter proxies it (NotebookSubmitter/ProxyServer, SURVEY §3.4)
            env[constants.ENV_NOTEBOOK_PORT] = str(self.port)
        return env

    def _localize_venv_archive(self, archive: str) -> str:
        """Unpack a venv archive into this container's staging area (the
        reference ships ``--python_venv venv.zip`` as a localized resource;
        SURVEY.md §3.1). Idempotent per container — keyed on the archive's
        identity (path + mtime + size), so a CHANGED archive re-unpacks
        instead of silently reusing a stale venv. Zip members' permission
        bits are restored from their external attributes (zipfile.extractall
        drops them, which would leave bin/python non-executable). If the
        archive has a single top-level dir, that dir becomes the venv root."""
        import shutil

        st = os.stat(archive)
        stamp = f"{archive}:{st.st_mtime_ns}:{st.st_size}"
        dest = os.path.join(
            self.staging_dir, "venv", f"{self.job_name}_{self.index}"
        )
        marker = os.path.join(dest, ".unpacked")
        current = None
        if os.path.exists(marker):
            with open(marker) as f:
                current = f.read()
        if current != stamp:
            if os.path.isdir(dest):
                shutil.rmtree(dest)
            os.makedirs(dest, exist_ok=True)
            if archive.endswith(".zip"):
                import zipfile

                with zipfile.ZipFile(archive) as z:
                    for info in z.infolist():
                        path = z.extract(info, dest)
                        mode = (info.external_attr >> 16) & 0o7777
                        if mode:
                            os.chmod(path, mode)
            else:
                shutil.unpack_archive(archive, dest)  # tar preserves modes
            with open(marker, "w") as f:
                f.write(stamp)
        entries = [e for e in os.listdir(dest) if e != ".unpacked"]
        if len(entries) == 1 and os.path.isdir(os.path.join(dest, entries[0])):
            return os.path.join(dest, entries[0])
        return dest

    def launch_child(self, command: str, env: dict[str, str]) -> subprocess.Popen:
        """Exec the user process via the shell (Utils.executeShell analog);
        stdio inherits the container's captured stdout/stderr."""
        # clear any previous attempt's train-metrics drop: a stale step
        # report must not masquerade as live progress while the new child
        # is still compiling (likewise a stale profile control/done pair —
        # the new child must not re-arm a dead request)
        path = getattr(self, "_train_metrics_path", None)
        if path:
            for stale in (
                path,
                path + ".obs",
                path + obs_startup.FILE_SUFFIX,
                path + obs_introspect.CONTROL_SUFFIX,
                path + obs_introspect.DONE_SUFFIX,
                path + obs_introspect.DRAIN_CONTROL_SUFFIX,
                path + obs_introspect.DRAIN_DONE_SUFFIX,
            ):
                try:
                    os.unlink(stale)
                except OSError:
                    pass
        cwd = None
        src_dir = self.config.get(keys.SRC_DIR)
        if src_dir:
            staged_src = os.path.join(self.staging_dir, "src")
            cwd = staged_src if os.path.isdir(staged_src) else src_dir
        # the start-up account's opening edge on the child's side (obs/startup.py):
        # interpreter start and the entry's imports lie between this and the
        # child's own first stamp
        env[constants.ENV_CHILD_SPAWNED_MS] = str(int(time.time() * 1000))
        return subprocess.Popen(
            ["/bin/bash", "-c", command],
            env=env,
            cwd=cwd,
            start_new_session=True,
        )

    # -- background loops --------------------------------------------------
    def _heartbeat_loop(self) -> None:
        interval = self.config.get_time_ms(keys.TASK_HEARTBEAT_INTERVAL_MS, 1000) / 1000
        max_missed = self.config.get_int(keys.TASK_MAX_MISSED_HEARTBEATS, 25)
        # interval backoff (tony.heartbeat.backoff-*): a thousand-executor
        # gang launched together beats in lockstep — every interval, one
        # synchronized knock wave hits the AM's RPC server. A per-task
        # seeded jitter de-phases the waves. A stretched gap can span up to
        # (1 + pct) intervals, so between beats the AM's missed counter
        # peaks up to pct intervals higher than without jitter — keep pct
        # well under max-missed (trivial at the defaults: 0.25 vs 25).
        # Off by default.
        jitter_rng = None
        jitter_pct = 0.0
        if self.config.get_bool(keys.HEARTBEAT_BACKOFF_ENABLED):
            import random

            jitter_pct = max(
                self.config.get_float(keys.HEARTBEAT_BACKOFF_JITTER_PCT, 0.25), 0.0)
            jitter_rng = random.Random(f"{self.app_id}:{self.job_name}:{self.index}")

        def wait_s() -> float:
            if jitter_rng is None:
                return interval
            return interval * (1.0 + jitter_rng.uniform(0.0, jitter_pct))

        stalled = False  # chaos hb-stall: a wedged executor — alive but silent
        while not self._stop.wait(wait_s()):
            if not stalled and self.chaos is not None and self.chaos.take("hb-stall") is not None:
                stalled = True
            if stalled:
                continue
            try:
                t0 = time.perf_counter()
                resp = self.rpc.call(
                    "task_executor_heartbeat",
                    job_name=self.job_name,
                    index=self.index,
                    attempt=self.attempt,
                )
                _HB_RTT.observe(time.perf_counter() - t0)
                self._hb_failures = 0
                # on-demand profile piggyback: relay a pending capture
                # request to the child / report its done record back
                self._profile_courier.handle(
                    resp.get("profile") if isinstance(resp, dict) else None,
                    getattr(self, "_train_metrics_path", None),
                )
                self._drain_courier.handle(
                    resp.get("drain") if isinstance(resp, dict) else None,
                    getattr(self, "_train_metrics_path", None),
                )
            except (RpcError, OSError):
                self._hb_failures += 1
                if self._resolve_am_move():
                    # a takeover AM adopted us: the outage is over, the budget
                    # restarts — the child never noticed
                    self._hb_failures = 0
                    continue
                if self._hb_failures > max_missed:
                    # AM is gone: orphaned container must not outlive the job
                    self._kill_child()
                    os._exit(constants.EXIT_HEARTBEAT_LOST)

    def _metrics_loop(self) -> None:
        interval = self.config.get_time_ms(keys.TASK_METRICS_INTERVAL_MS, 5000) / 1000
        # with_tpu stays False here: PJRT device access is exclusive per
        # process, and the chips belong to the CHILD training process — the
        # supervisor must never initialize the TPU runtime. TPU metrics come
        # from inside the training loop (tony_tpu.train reporting).
        sampler = MetricsSampler(
            child_pid=self.child.pid if self.child else None,
            with_tpu=False,
        )
        startup_pushed = None
        while True:
            stopped = self._stop.wait(interval)
            # the child's start-up stamps, dropped next to its step report
            # (obs/startup.py): the AM writes them to the .jhist when they
            # change. Once the child has ended, one more push only if it took
            # a stamp since the last one (a first step that closed inside the
            # interval must not die with this loop)
            startup = obs_startup.read_report(getattr(self, "_train_metrics_path", None))
            if stopped and startup == startup_pushed:
                return
            try:
                m = sampler.sample()
                if startup is not None:
                    m["startup"] = startup
                train = self._read_train_metrics()
                if train is not None:
                    m["train"] = train
                # piggyback this process's metrics registry (heartbeat RTT,
                # rpc client latency, ...) on the push — plus the training
                # child's snapshot (checkpoint/step-time instruments) dropped
                # next to its step report: executors have no exposition
                # endpoint, so the AM re-exports these per task through
                # get_metrics → portal /metrics
                obs_snap = [e for e in obs_metrics.REGISTRY.snapshot() if e["samples"]]
                obs_snap.extend(self._read_child_obs_metrics() or [])
                if obs_snap:
                    m["obs_metrics"] = obs_snap
                self.rpc.call(
                    "push_metrics",
                    job_name=self.job_name,
                    index=self.index,
                    metrics=m,
                    attempt=self.attempt,
                )
                startup_pushed = startup
            except (RpcError, OSError):
                pass  # metrics are best-effort; liveness is the heartbeat's job
            if stopped:
                return

    def _report_profile(self, **params) -> None:
        """Courier callback: capture status back to the AM. Raises on RPC
        failure so the courier retries on a later heartbeat instead of
        marking the request reported."""
        self.rpc.call(
            "report_profile_status",
            job_name=self.job_name,
            index=self.index,
            attempt=self.attempt,
            **params,
        )

    def _report_drain(self, **params) -> None:
        """Drain-courier callback: the child's urgent checkpoint landed —
        tell the AM which step is safe so it can yield. Raises on RPC
        failure so the courier retries on a later heartbeat."""
        self.rpc.call(
            "report_drain_saved",
            job_name=self.job_name,
            index=self.index,
            attempt=self.attempt,
            **params,
        )

    def _read_child_obs_metrics(self):
        """The training child's metrics-registry snapshot (atomic drop at
        <train-metrics-file>.obs, loop.py _drop_obs_metrics), or None."""
        path = getattr(self, "_train_metrics_path", None)
        if not path:
            return None
        try:
            import json as _json

            with open(path + ".obs") as f:
                snap = _json.load(f)
            return snap if isinstance(snap, list) else None
        except (OSError, ValueError):
            return None

    def _read_train_metrics(self):
        """Latest step report the training loop dropped (atomic rename
        write, loop.py), or None. Malformed/missing files are ignored —
        metrics must never take down the supervisor."""
        path = getattr(self, "_train_metrics_path", None)
        if not path:
            return None
        try:
            import json as _json

            with open(path) as f:
                return _json.load(f)
        except (OSError, ValueError):
            return None

    # -- chaos lifecycle points (no-ops unless tony.chaos.spec is set) ------
    def _chaos_point(self, trigger: str) -> None:
        """Fire exec faults tied to a lifecycle trigger (@registered,
        @gang_complete)."""
        if self.chaos is None:
            return
        if self.chaos.take("exec-crash", trigger=trigger) is not None:
            self._kill_child_abruptly()
            os._exit(constants.EXIT_FAILURE)
        if self.chaos.take("exec-hang", trigger=trigger) is not None:
            while True:  # wedge here forever; heartbeats keep flowing
                time.sleep(3600)

    def _start_chaos_timers(self) -> None:
        """Arm trigger-less exec faults: ``@t+5s`` fires that long after
        executor start, no delay at all fires right after child launch.
        Each fires at most once per job (chaos once-latch)."""
        if self.chaos is None:
            return
        for f in self.chaos.schedule.faults:
            if f.kind in ("exec-crash", "exec-hang") and f.trigger is None:
                threading.Thread(
                    target=self._timed_exec_fault, args=(f,), name=f"chaos-{f.kind}", daemon=True
                ).start()

    def _timed_exec_fault(self, f) -> None:
        time.sleep(max(f.delay_ms / 1000 - self.chaos.elapsed_ms() / 1000, 0))
        if self.chaos.take_spec(f) is None:
            return  # not this task's fault, or already fired in a prior attempt
        if f.kind == "exec-crash":
            self._kill_child_abruptly()
            os._exit(constants.EXIT_FAILURE)
        # exec-hang: SIGSTOP the child's process group — it stops making
        # progress while this supervisor stays alive and heartbeating, the
        # classic wedged-worker failure mode
        if self.child and self.child.poll() is None:
            try:
                os.killpg(os.getpgid(self.child.pid), signal.SIGSTOP)
            except ProcessLookupError:
                pass

    def _kill_child(self) -> None:
        grace_s = self.config.get_time_ms(keys.TASK_KILL_GRACE_MS, 3000) / 1000
        if self.child and self.child.poll() is None:
            try:
                os.killpg(os.getpgid(self.child.pid), signal.SIGTERM)
                try:
                    self.child.wait(timeout=grace_s)
                except subprocess.TimeoutExpired:
                    os.killpg(os.getpgid(self.child.pid), signal.SIGKILL)
            except ProcessLookupError:
                pass

    def _kill_child_abruptly(self) -> None:
        """SIGKILL, no grace — the exec-crash fidelity path. The graceful
        kill would let a well-behaved child (a draining serve engine) exit 0
        and the supervisor report SUCCESS before dying, turning an injected
        crash into a clean completion the AM never restarts."""
        if self.child and self.child.poll() is None:
            try:
                os.killpg(os.getpgid(self.child.pid), signal.SIGKILL)
            except ProcessLookupError:
                pass

    # -- main --------------------------------------------------------------
    def run(self) -> int:
        if self.tracer is None:
            return self._run_supervised()
        # root span for this executor's whole life, ended on the way out;
        # root_parent re-points at it so the heartbeat/metrics threads'
        # RPC spans nest under it (os._exit paths lose only open spans)
        self._root_span, self._root_token = self.tracer.start_span("executor.run")
        self._root_span.set(task=f"{self.job_name}:{self.index}", attempt=self.attempt)
        self.tracer.root_parent = self._root_span.span_id
        rc: int | None = None
        try:
            rc = self._run_supervised()
            return rc
        finally:
            self._root_span.set(exit_code=rc)
            self.tracer.end_span(
                self._root_span, self._root_token, status="ok" if rc == 0 else "error"
            )
            obs_trace.shutdown()

    def _run_supervised(self) -> int:
        signal.signal(signal.SIGTERM, lambda *_: (_sigterm(self)))
        if self.spare_id is not None:
            try:
                with obs_trace.maybe_span("executor.spare_park", spare=self.spare_id):
                    promoted = self._park_as_spare()
            except (RpcError, OSError) as e:
                obs_logging.error(f"[tony-executor] spare {self.spare_id} parking failed: {e}")
                return constants.EXIT_EXECUTOR_REGISTRATION_FAILED
            if not promoted:
                obs_logging.info(f"[tony-executor] spare {self.spare_id} reaped unpromoted")
                return constants.EXIT_SUCCESS
        try:
            with obs_trace.maybe_span("executor.register"):
                self.register()
            self._chaos_point("registered")
            # heartbeat starts at registration, not child launch: the gang
            # barrier can legitimately outlast the liveness window (dependency-
            # gated types, slow containers) and REGISTERED tasks are monitored.
            # (A wedged executor whose heartbeats stop while its process lives
            # is simulated by the chaos `hb-stall` fault inside the loop.)
            threading.Thread(target=self._heartbeat_loop, name="heartbeat", daemon=True).start()
            with obs_trace.maybe_span("executor.await_spec"):
                spec, extra_env = self.await_cluster_spec()
            self._chaos_point("gang_complete")
            command = self.resolve_command()
            env = self.build_child_env(spec, extra_env)
        except Exception as e:  # registration/barrier failure
            obs_logging.error(f"[tony-executor] startup failed: {e}")
            try:
                self.rpc.call(
                    "register_execution_result",
                    job_name=self.job_name,
                    index=self.index,
                    exit_code=constants.EXIT_EXECUTOR_REGISTRATION_FAILED,
                    attempt=self.attempt,
                )
            except (RpcError, OSError):
                pass
            return constants.EXIT_EXECUTOR_REGISTRATION_FAILED

        self.child = self.launch_child(command, env)
        obs_logging.info(
            f"[tony-executor] {self.job_name}:{self.index} launched child",
            pid=self.child.pid,
        )
        self._start_chaos_timers()
        metrics_thread = threading.Thread(target=self._metrics_loop, name="metrics", daemon=True)
        metrics_thread.start()

        if self.job_name in (constants.TENSORBOARD_JOB_NAME, constants.NOTEBOOK_JOB_NAME):
            url = f"http://{self.host}:{self.port}"
            try:
                if self.job_name == constants.TENSORBOARD_JOB_NAME:
                    self.rpc.call("register_tensorboard_url", url=url)
                self.rpc.call(
                    "register_task_url",
                    job_name=self.job_name,
                    index=self.index,
                    url=url,
                    attempt=self.attempt,
                )
            except (RpcError, OSError):
                pass

        timeout_ms = self.config.get_time_ms(keys.TASK_EXECUTOR_EXECUTION_TIMEOUT_MS, 0)
        reason = ""
        with obs_trace.maybe_span("executor.child", pid=self.child.pid):
            try:
                rc = self.child.wait(timeout=timeout_ms / 1000 if timeout_ms else None)
            except subprocess.TimeoutExpired:
                self._kill_child()
                rc = constants.EXIT_EXECUTION_TIMEOUT
                reason = f"execution timeout: killed after {timeout_ms}ms (tony.task.execution-timeout-ms)"
                obs_logging.error(f"[tony-executor] {reason}")
            obs_trace.add_event("child.exited", exit_code=rc)
        obs_logging.info(
            f"[tony-executor] {self.job_name}:{self.index} child exited",
            exit_code=rc,
        )
        self._stop.set()
        metrics_thread.join(timeout=2.0)  # its last push: stamps the child took since the one before
        try:
            # final courier sweep: a capture the child finalized in its
            # `finally` (truncated by end-of-training) races the heartbeat
            # loop we just stopped — the done file must still be reported
            self._profile_courier.handle(None, getattr(self, "_train_metrics_path", None))
        except (RpcError, OSError):
            pass  # the AM-side request expires; artifacts remain on disk
        try:
            # resilient: the AM may be mid-takeover exactly when the child
            # finishes — the report must chase the refreshed endpoint or the
            # adopted-container backstop would misread this exit as a failure
            self._am_call_resilient(
                "register_execution_result",
                deadline_s=30,
                job_name=self.job_name,
                index=self.index,
                exit_code=rc,
                reason=reason,
                attempt=self.attempt,
            )
        except RpcError:
            pass  # AM also learns the code from the container exit
        return rc


def _sigterm(executor: TaskExecutor) -> None:
    # kill the child FIRST, stop heartbeating LAST: the supervisor is alive
    # throughout the (up to 3 s) teardown grace, and the AM must keep seeing
    # heartbeats until then — going silent at SIGTERM opens a race where the
    # AM marks the task heartbeat-LOST (a budget-consuming failure) before
    # the container's true exit record (e.g. EXIT_PREEMPTED, which is NOT a
    # failure) can reach it through agent → pool → poll_exited.
    executor._kill_child()
    executor._stop.set()
    sys.exit(constants.EXIT_KILLED)


def main() -> int:
    return TaskExecutor().run()


if __name__ == "__main__":
    sys.exit(main())
