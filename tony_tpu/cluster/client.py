"""Job submission client.

Analog of the reference's ``TonyClient.java`` (SURVEY.md §2.1, §3.1):
``init`` parses CLI + conf layers and freezes ``tony-final``; ``submit``
prepares the per-app staging dir (the ``.tony/<appId>`` HDFS analog), stages
the src dir, and launches the AM (playing YARN-RM-launches-AM: the AM is a
detached subprocess that outlives the client); ``monitor_application`` polls
the AM for task-state transitions and prints them; AM retry re-launches the
whole gang (``tony.am.retry-count``). ``add_listener`` mirrors the reference's
CallbackHandler hook for app-id/URL notifications.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Callable

from tony_tpu import constants
from tony_tpu.config import TonyConfig, keys
from tony_tpu.cluster.rpc import RpcClient, RpcError
from tony_tpu.cluster.session import JobStatus
from tony_tpu.obs import logging as obs_logging
from tony_tpu.obs import metrics as obs_metrics
from tony_tpu.obs import trace as obs_trace

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@dataclass
class ApplicationHandle:
    app_id: str
    staging_dir: str
    am_process: subprocess.Popen | None = None
    _rpc: RpcClient | None = field(default=None, repr=False)

    @property
    def am_info_path(self) -> str:
        return os.path.join(self.staging_dir, constants.AM_INFO_FILE)

    @property
    def am_status_path(self) -> str:
        return os.path.join(self.staging_dir, "am_status.json")

    def rpc(self, timeout_s: float = 30.0) -> RpcClient | None:
        """Connect to the AM once it has advertised itself (YARN report analog)."""
        if self._rpc is not None:
            return self._rpc
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            if os.path.exists(self.am_info_path):
                with open(self.am_info_path) as f:
                    info = json.load(f)
                self._rpc = RpcClient(info["host"], info["port"], secret=info["secret"])
                return self._rpc
            if self.am_process is not None and self.am_process.poll() is not None:
                return None  # AM died before advertising
            time.sleep(0.1)
        return None

    def final_status(self) -> dict[str, Any] | None:
        if os.path.exists(self.am_status_path):
            with open(self.am_status_path) as f:
                return json.load(f)
        return None


class Client:
    """Submission + monitoring front end (TonyClient analog)."""

    def __init__(self, config: TonyConfig):
        self.config = config
        self.listeners: list[Callable[[str, Any], None]] = []

    def add_listener(self, fn: Callable[[str, Any], None]) -> None:
        """fn(event_name, payload); events: app_id, tensorboard_url, task_transition."""
        self.listeners.append(fn)

    def _notify(self, event: str, payload: Any) -> None:
        for fn in self.listeners:
            fn(event, payload)

    # -- submission --------------------------------------------------------
    def submit(self) -> ApplicationHandle:
        if not self.config.job_types():
            raise ValueError("no job types declared (set tony.<type>.instances > 0)")
        # the goodput ledger's t0 (obs/goodput.py `submit` phase): stamped by
        # the process that does the staging, before it starts
        submitted_ms = int(time.time() * 1000)
        app_id = f"application_{int(time.time())}_{uuid.uuid4().hex[:8]}"
        root = self.config.get(keys.STAGING_ROOT) or constants.default_tony_root()
        staging_dir = os.path.join(root, app_id)
        os.makedirs(staging_dir, exist_ok=True)

        # stage user sources (HDFS upload analog)
        src_dir = self.config.get(keys.SRC_DIR)
        if src_dir:
            if not os.path.isdir(src_dir):
                raise FileNotFoundError(f"--src_dir {src_dir} does not exist")
            shutil.copytree(src_dir, os.path.join(staging_dir, "src"), dirs_exist_ok=True)

        # freeze the whole-job config artifact
        if not self.config.frozen:
            self.config.freeze()
        self.config.write_final(staging_dir)
        # staged beside the frozen conf, which holds declared keys only: the AM
        # carries the stamp in APPLICATION_INITED's payload
        info_path = os.path.join(staging_dir, constants.SUBMIT_INFO_FILE)
        with open(info_path + ".tmp", "w") as f:
            json.dump({"submitted_ms": submitted_ms}, f)
        os.replace(info_path + ".tmp", info_path)

        obs_metrics.set_enabled(self.config.get_bool(keys.METRICS_ENABLED, True))
        # structured logging (tony.log.*): the submitter's records join the
        # job's <staging>/logs aggregate; console output is unchanged (echo)
        obs_logging.init_from_config(self.config, identity="client", staging_dir=staging_dir)
        # tracing (tony.trace.*): the submit span becomes the whole trace's
        # root — the AM links under it via TONY_TRACE_PARENT, executors under
        # the AM, training children under their executor
        tracer = obs_trace.init_from_config(
            self.config, identity="client", staging_dir=staging_dir, app_id=app_id
        )
        submit_span = submit_token = None
        if tracer is not None:
            submit_span, submit_token = tracer.start_span("client.submit", kind="client")
            submit_span.set(app_id=app_id)
            # later client spans (monitor polls) nest under the submit span
            tracer.root_parent = submit_span.span_id

        # launch the AM as a detached process (process boundary #1)
        env = dict(os.environ)
        env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
        if submit_span is not None:
            env[constants.ENV_TRACE_PARENT] = submit_span.span_id
        with open(os.path.join(staging_dir, "am.log"), "ab") as am_log:
            proc = subprocess.Popen(
                [
                    sys.executable,
                    "-u",
                    "-m",
                    "tony_tpu.cluster.appmaster",
                    "--app-id",
                    app_id,
                    "--staging-dir",
                    staging_dir,
                ],
                env=env,
                stdout=am_log,
                stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        if tracer is not None:
            tracer.end_span(submit_span, submit_token)
        self._notify("app_id", app_id)
        return ApplicationHandle(app_id, staging_dir, proc)

    # -- monitoring --------------------------------------------------------
    def monitor_application(self, handle: ApplicationHandle, quiet: bool = False) -> JobStatus:
        """Poll task transitions until a final status (reference monitor loop)."""
        last_state: dict[str, str] = {}
        tb_reported = False
        am_attempt_seen = 0
        rpc = handle.rpc()
        while True:
            status = handle.final_status()
            if status is not None:
                final = JobStatus(status["status"])
                if not quiet:
                    _print_final(handle, status)
                return final
            am_dead = handle.am_process is not None and handle.am_process.poll() is not None
            if rpc is None and not am_dead:
                # AM alive but not yet advertised (slow start) — keep waiting
                time.sleep(0.3)
                rpc = handle.rpc(timeout_s=5)
                continue
            if am_dead:
                # AM died without writing a final status → retry or fail
                time.sleep(0.2)  # let a just-written am_status.json land
                status = handle.final_status()
                if status is not None:
                    continue
                retried = self._maybe_retry_am(handle)
                if retried is None:
                    if not quiet:
                        obs_logging.error(f"[tony] AM for {handle.app_id} died without final status → FAILED")
                        _print_am_log_tail(handle)
                    return JobStatus.FAILED
                handle, rpc = retried
                continue
            try:
                infos = rpc.call("get_task_infos")
                app = rpc.call("get_application_status")
            except (RpcError, OSError):
                time.sleep(0.3)
                continue
            am_attempt = int(app.get("am_attempt") or 0)
            if am_attempt != am_attempt_seen:
                # a takeover must be visible to the submitter, not silent
                am_attempt_seen = am_attempt
                outcome = app.get("takeover")
                self._notify("am_attempt", {"am_attempt": am_attempt, "takeover": outcome})
                if not quiet:
                    obs_logging.info(
                        f"[tony] AM attempt {am_attempt} "
                        + ("adopted the running gang (work-preserving takeover)"
                           if outcome == "adopted"
                           else "restarted the gang (takeover degraded)"
                           if outcome == "degraded"
                           else "is serving"))
            for info in infos:
                tid = f"{info['name']}:{info['index']}"
                st = info["status"]
                if last_state.get(tid) != st:
                    last_state[tid] = st
                    self._notify("task_transition", info)
                    if not quiet:
                        loc = f" on {info['host']}:{info['port']}" if info.get("host") else ""
                        obs_logging.info(
                            f"[tony] task {tid} → {st}{loc}"
                            + (f" (logs: {info['log_dir']})"
                               if st in ("FAILED", "LOST") and info.get("log_dir") else ""))
            if app.get("tensorboard_url") and not tb_reported:
                tb_reported = True
                self._notify("tensorboard_url", app["tensorboard_url"])
                if not quiet:
                    obs_logging.info(f"[tony] tensorboard at {app['tensorboard_url']}")
            time.sleep(0.3)

    def _maybe_retry_am(self, handle: ApplicationHandle) -> tuple[ApplicationHandle, RpcClient | None] | None:
        """AM-retry path (SURVEY.md §3.5), now work-preserving: the new
        attempt launches in ``--takeover`` mode, replays ``am_journal.jsonl``
        and ADOPTS the live gang — executors re-resolve the refreshed
        ``am_info`` and resync, the training children never stop. Only a
        missing/corrupt journal degrades (loudly, `AM_TAKEOVER_DEGRADED`) to
        the old whole-gang restart."""
        retries = self.config.get_int(keys.AM_RETRY_COUNT, 0)
        attempt = getattr(handle, "_am_attempt", 0)
        if attempt >= retries:
            return None
        next_attempt = attempt + 1
        for stale in (handle.am_info_path,):
            try:
                os.remove(stale)
            except OSError:
                pass
        obs_logging.warning(
            f"[tony] AM for {handle.app_id} died (attempt {attempt}); "
            f"relaunching attempt {next_attempt} in takeover mode")
        env = dict(os.environ)
        env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
        with open(os.path.join(handle.staging_dir, f"am_attempt{next_attempt}.log"), "ab") as am_log:
            proc = subprocess.Popen(
                [sys.executable, "-u", "-m", "tony_tpu.cluster.appmaster",
                 "--app-id", handle.app_id, "--staging-dir", handle.staging_dir,
                 "--takeover", "--am-attempt", str(next_attempt)],
                env=env, stdout=am_log, stderr=subprocess.STDOUT, start_new_session=True,
            )
        new_handle = ApplicationHandle(handle.app_id, handle.staging_dir, proc)
        new_handle._am_attempt = next_attempt  # type: ignore[attr-defined]
        return new_handle, new_handle.rpc()

    def run(self, quiet: bool = False) -> int:
        """submit + monitor; exit code = job verdict (reference main flow)."""
        handle = self.submit()
        if not quiet:
            obs_logging.info(f"[tony] submitted {handle.app_id} (staging: {handle.staging_dir})")
        final = self.monitor_application(handle, quiet=quiet)
        return constants.EXIT_SUCCESS if final == JobStatus.SUCCEEDED else constants.EXIT_FAILURE

    @staticmethod
    def kill(handle: ApplicationHandle) -> bool:
        rpc = handle.rpc(timeout_s=5)
        if rpc is None:
            return False
        try:
            rpc.call("finish_application")
            return True
        except (RpcError, OSError):
            return False


def _print_am_log_tail(handle: ApplicationHandle, lines: int = 15) -> None:
    # error level like the "AM died" headline that precedes it, so the whole
    # forensic block lands on one stream (stderr) instead of splitting
    path = os.path.join(handle.staging_dir, "am.log")
    if os.path.exists(path):
        with open(path, errors="replace") as f:
            tail = f.readlines()[-lines:]
        if tail:
            obs_logging.error(f"[tony] last {len(tail)} lines of {path}:")
            for line in tail:
                obs_logging.error(f"[tony-am] {line.rstrip()}")


def _print_final(handle: ApplicationHandle, status: dict[str, Any]) -> None:
    obs_logging.info(f"[tony] application {handle.app_id} finished: {status['status']}")
    if status.get("reason"):
        obs_logging.info(f"[tony]   reason: {status['reason']}")
    # the finalized artifacts' story continues in the history tier — point
    # there instead of leaving the dead AM as the last address
    obs_logging.info(f"[tony]   history: tony history show {handle.app_id}")
    if status.get("am_attempt"):
        obs_logging.info(
            f"[tony]   served by AM attempt {status['am_attempt']}"
            + (f" ({status['takeover']} takeover)" if status.get("takeover") else ""))
    for t in status.get("tasks", []):
        obs_logging.info(
            f"[tony]   {t['name']}:{t['index']} {t['status']}"
            + (f" exit={t['exit_code']}" if t.get("exit_code") is not None else "")
        )


# -- CLI arg surface (reference Commons-CLI options, SURVEY.md §2.1) ---------
def build_config_from_args(argv: list[str]) -> TonyConfig:
    p = argparse.ArgumentParser(prog="tony submit", description="Submit a tony-tpu job")
    p.add_argument("--executes", help="command to run in each task container")
    p.add_argument("--task_params", help="args appended to the --executes command")
    p.add_argument("--conf_file", help="job config file (json/toml/hadoop-xml)")
    p.add_argument("--conf", action="append", default=[], help="key=value override (repeatable)")
    p.add_argument("--src_dir", help="directory staged into every container")
    p.add_argument("--python_venv", help="virtualenv root to activate in containers")
    p.add_argument("--python_binary_path", help="python interpreter for the user process")
    p.add_argument("--shell_env", action="append", default=[], help="extra k=v env (repeatable)")
    p.add_argument("--quiet", action="store_true")
    args = p.parse_args(argv)

    site = os.path.join(os.getcwd(), constants.TONY_SITE_CONF)
    config = TonyConfig.from_layers(
        site_file=site if os.path.exists(site) else None,
        conf_file=args.conf_file,
        conf_args=args.conf,
    )
    if args.executes:
        cmd = args.executes + (f" {args.task_params}" if args.task_params else "")
        config.set(keys.EXECUTES, cmd)
    if args.src_dir:
        config.set(keys.SRC_DIR, args.src_dir)
    if args.python_venv:
        config.set(keys.PYTHON_VENV, args.python_venv)
    if args.python_binary_path:
        config.set(keys.PYTHON_BINARY_PATH, args.python_binary_path)
    if args.shell_env:
        config.set(keys.SHELL_ENV, ",".join(args.shell_env))
    config._quiet = args.quiet  # type: ignore[attr-defined]
    return config


def main(argv: list[str] | None = None) -> int:
    config = build_config_from_args(argv if argv is not None else sys.argv[1:])
    return Client(config).run(quiet=getattr(config, "_quiet", False))


if __name__ == "__main__":
    sys.exit(main())
