"""Test harness configuration.

Multi-chip behavior is tested the way the reference tests multi-node behavior
(SURVEY.md §4): no real cluster — an in-process fake resource manager, local
subprocesses as "containers", and a virtual device mesh. Here the mesh is
8 virtual CPU devices via --xla_force_host_platform_device_count, set BEFORE
jax is first imported.
"""

import faulthandler
import os
import signal
import sys
import threading

# Force the CPU platform with 8 virtual devices. Both env and config are set
# so subprocesses spawned by E2E tests (AM/executors) inherit the CPU platform.
os.environ["JAX_PLATFORMS"] = "cpu"
# Run Pallas TPU kernels through the interpreter on CPU so kernel numerics
# (incl. the flash-attention backward) are covered without a chip.
os.environ["TONY_PALLAS_INTERPRET"] = "1"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Repo root on sys.path so `import tony_tpu` works without install.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture()
def tmp_tony_root(tmp_path, monkeypatch):
    """Isolated staging/history root per test."""
    root = tmp_path / ".tony"
    root.mkdir()
    monkeypatch.setenv("TONY_ROOT", str(root))
    return root


#: PR 55's per-layer metrics of a serving cell judged by tokens/s, in BENCHMARK.json's order
STARTUP_METRICS_SERVE = [
    "submit_to_am_s", "allocate_s", "register_s", "runtime_init_s", "weights_s", "replica_warmup_s.serve",
    "setup_compile_s.serve", "setup_cache_load_s.serve", "setup_trace_lower_s.serve", "compile_ms_per_pass.serve"]


@pytest.fixture()
def startup_account():
    """check(spec, workload): the start-up account of a rehearsal that has just
    run (benchmark/run.py --workload <tiny-*>.serve), read from what it left
    under .bench_work/ by the readers the listed cells use: every stage metric
    a number, the stages through `ready` no longer than the run, and the
    window's compile time printed (0 on a sound run). Starts no fleet or job."""
    import glob
    import importlib
    import json

    def check(spec, workload: str) -> dict:
        work = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".bench_work", workload)
        ctl = os.path.join(work, "out", "ctl")
        drive = {tag: json.load(open(os.path.join(ctl, f"snap.{name}.json")))
                 for tag, name in (("snap0", "open"), ("snap1", "close"))}
        drive["t_open"] = drive["snap0"]["t"]
        (app_dir,) = glob.glob(os.path.join(work, "staging", "application_*"))
        ctx = {"kind": "serve", "app_dir": app_dir, "drive": drive}
        got = {}
        for name in STARTUP_METRICS_SERVE:
            m = spec.metric(name)
            got[name] = importlib.import_module("readers." + m["reader"]).read(ctx, **m["args"])
            assert got[name] is not None and got[name] >= 0.0, (name, got)
        stages = sum(got[n] for n in STARTUP_METRICS_SERVE[:6])
        led = ctx["goodput_ledger"]
        assert 0.0 < got["runtime_init_s"] and 0.0 < got["weights_s"] and stages <= drive["t_open"] - led.t0_ms / 1000.0
        assert got["setup_compile_s.serve"] + got["setup_cache_load_s.serve"] > 0.0 and got["setup_trace_lower_s.serve"] > 0.0
        print(f"[startup] {workload}: " + ", ".join(f"{n}={got[n]:.3f}" for n in STARTUP_METRICS_SERVE))
        return got

    return check


# A hang costs one test, not the run: no pytest-timeout is installed, and a
# test that never returns holds its xdist worker until the whole run's own time
# limit cuts it (and every test still queued behind it goes uncounted).
WATCHDOG_S = 180
_real_stderr = None


def pytest_configure(config):
    """Capture is suspended while plugins are configured, so fd 2 is the run's
    own stderr here (each xdist worker's too): keep it for the watchdog."""
    global _real_stderr
    _real_stderr = os.fdopen(os.dup(2), "w")


@pytest.fixture(autouse=True)
def hang_watchdog(request):
    """Fail the test that is still running after WATCHDOG_S seconds and print
    every thread's stack to the run's stderr. SIGALRM reaches the main thread
    of this worker only; a test that sets an alarm of its own replaces this
    one, and is then left to it (its handler is not touched afterwards)."""
    if threading.current_thread() is not threading.main_thread() or _real_stderr is None:
        yield
        return

    def on_alarm(signum, frame):
        print(f"\n[watchdog] {request.node.nodeid} still running after {WATCHDOG_S}s; every thread's stack:",
              file=_real_stderr, flush=True)
        faulthandler.dump_traceback(file=_real_stderr, all_threads=True)
        pytest.fail(f"watchdog: still running after {WATCHDOG_S}s (stacks on stderr)", pytrace=True)

    # should the main thread be stuck where no Python handler can run, the
    # stacks still reach the log before the run's own limit
    faulthandler.dump_traceback_later(WATCHDOG_S + 20, file=_real_stderr)
    before = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(WATCHDOG_S)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        if signal.getsignal(signal.SIGALRM) is on_alarm:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, before)
