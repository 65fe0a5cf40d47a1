"""Launching, reaping and reading the program's jobs (from chip_smoke.py).

The process that imports this never touches JAX: the job's child owns the chip.
Every process of a job inherits the job's TONY_ROOT, which is how they are found.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

from spec import ROOT

TONY = [sys.executable, "-m", "tony_tpu.cli.main"]


class JobFailed(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def tail(text: str, n: int = 30) -> str:
    return "\n".join(text.splitlines()[-n:])


def job_env(staging: str, extra: dict | None = None) -> dict:
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)  # the driver's own; nothing here may depend on it
    env["TONY_ROOT"] = staging
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


def job_procs(staging: str) -> dict[int, str]:
    """Every live process started under this staging dir: pid -> command line."""
    marker = ("TONY_ROOT=" + staging).encode()
    out = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        if int(pid) == os.getpid():
            continue
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                if marker not in f.read().split(b"\0"):
                    continue
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                out[int(pid)] = f.read().replace(b"\0", b" ").decode(errors="replace").strip()
        except OSError:
            continue
    return out


def chip_holders(staging: str) -> tuple[list[str], int]:
    """(command lines of processes with JAX or libtpu mapped, count of the rest)."""
    holders, others = [], 0
    for pid, cmd in job_procs(staging).items():
        try:
            with open(f"/proc/{pid}/maps") as f:
                maps = f.read()
        except OSError:
            continue
        if "libtpu" in maps or "jaxlib" in maps:
            holders.append(cmd)
        else:
            others += 1
    return holders, others


def kill_all(staging: str) -> None:
    """Stop every process of the job and wait until each has ended (an AM or
    executor that is being stopped may still start a child: hence the rounds)."""
    for sig in (signal.SIGTERM, signal.SIGKILL, signal.SIGKILL, signal.SIGKILL):
        procs = job_procs(staging)
        if not procs:
            return
        for pid in procs:
            try:
                os.kill(pid, sig)
            except OSError:
                pass
        deadline = time.time() + 5.0
        while job_procs(staging) and time.time() < deadline:
            time.sleep(0.2)


def reap(staging: str, what: str, wait_s: float = 30.0) -> None:
    """The chip-holding child of a finished job must be gone before anything
    else takes the chip, and before the run ends."""
    deadline = time.time() + wait_s
    while (left := job_procs(staging)) and time.time() < deadline:
        time.sleep(0.25)
    if left:
        kill_all(staging)
        raise JobFailed(f"{what}: processes outlived the job: {left}")


def stop_job(proc: subprocess.Popen, staging: str, what: str, wait_s: float = 90.0,
             app_dir: str | None = None) -> int:
    """Stop a running job the way its front end does. `tony serve` kills its
    job on the interrupt a user sends (the server drains first); `tony
    submit` would leave the AM running, so a training job is finished through
    the AM (`app_dir` given: Client.kill, the call `tony serve` makes itself).
    Returns the client's exit code."""
    if proc.poll() is None and app_dir is not None:
        from tony_tpu.cluster.client import ApplicationHandle, Client

        Client.kill(ApplicationHandle(os.path.basename(app_dir), app_dir))
    elif proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGINT)
        except OSError:
            pass
    try:
        rc = proc.wait(timeout=wait_s)
    except subprocess.TimeoutExpired:
        kill_all(staging)
        proc.kill()
        rc = proc.wait()
    reap(staging, what)
    return rc


def launch(cmd: list[str], staging: str, out_path: str, extra_env: dict | None = None) -> subprocess.Popen:
    os.makedirs(staging, exist_ok=True)
    with open(out_path, "w") as out_f:
        return subprocess.Popen(cmd, cwd=ROOT, env=job_env(staging, extra_env), stdout=out_f,
                                stderr=subprocess.STDOUT, start_new_session=True)


def app_dirs(staging: str) -> list[str]:
    if not os.path.isdir(staging):
        return []
    return sorted(os.path.join(staging, d) for d in os.listdir(staging)
                  if d != "history" and os.path.isdir(os.path.join(staging, d)))


def read_logs(app_dir: str, task: str) -> str:
    """stdout+stderr of one task (every restart attempt)."""
    text = []
    logs = os.path.join(app_dir, "logs")
    if not os.path.isdir(logs):
        return ""
    for d in sorted(os.listdir(logs)):
        if d == task or d.startswith(task + "_r"):
            for name in ("stdout.log", "stderr.log"):
                path = os.path.join(logs, d, name)
                if os.path.exists(path):
                    with open(path, errors="replace") as f:
                        text.append(f.read())
    return "\n".join(text)


def read_jsonl(path: str) -> list[dict]:
    """Whole lines of a JSONL file that parse (a torn tail is left out)."""
    out = []
    if not os.path.exists(path):
        return out
    with open(path, errors="replace") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict):
                out.append(rec)
    return out


def read_json(path: str) -> dict | None:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def find_files(top: str, suffix: str) -> list[str]:
    return sorted(os.path.join(r, fn) for r, _, fns in os.walk(top) for fn in fns if fn.endswith(suffix))


#: seconds to wait before the comparison's child is started again, once a try
#: of it has failed: a chip that the stopped job has only just let go of (four
#: chips reset together) can refuse the next process for a moment
CHILD_RETRY_WAITS = (5.0, 15.0, 30.0)


def compare_in_child(run, job: dict, what: str, budget_s: float = 900.0) -> dict:
    """The comparison with the reference (check.py) in a child of its own, now
    that the job is gone and the chip is free: outside the window and outside
    set-up. A child that exits with an error is started again after a wait
    (it computes from the seed alone, so a second try reads the same numbers),
    as long as the budget lasts; every failed try's last lines are printed.
    {} if none succeeded (the run is then not correct)."""
    from spec import HERE

    chk_in = os.path.join(run.work, "check_in.json")
    with open(chk_in, "w") as f:
        json.dump({"config": run.w["config"], "deployment": run.w["deployment"], "seed": run.seed,
                   "control": run.control, **job}, f)
    t0 = time.time()
    for attempt, wait_s in enumerate((0.0, *CHILD_RETRY_WAITS), 1):
        time.sleep(wait_s)
        left = budget_s - (time.time() - t0)
        if left <= 0:
            break
        try:
            proc = subprocess.run([sys.executable, os.path.join(HERE, "check.py"), chk_in], cwd=ROOT,
                                  env=job_env(run.staging), capture_output=True, text=True, timeout=left)
        except subprocess.TimeoutExpired as e:
            err = e.stderr.decode(errors="replace") if isinstance(e.stderr, bytes) else (e.stderr or "")
            say(f"[{what}] the comparison's child, try {attempt}, ran out of its {left:.0f}s: {tail(err, 12)}")
            break
        lines = proc.stdout.strip().splitlines()
        if proc.returncode == 0 and lines:
            return {**json.loads(lines[-1]), "seconds": round(time.time() - t0, 2), "tries": attempt}
        say(f"[{what}] the comparison's child, try {attempt}, failed (exit {proc.returncode}) after "
            f"{time.time() - t0:.0f}s: {tail(proc.stderr, 12)}")
    return {}
