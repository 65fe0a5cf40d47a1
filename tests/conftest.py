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
import tempfile
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


# -- what the family files share: each is split by subject (the kernel's forms, the program against
# the reference, the family's files through the harness) so that `--dist loadfile` can place the parts apart
@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules, by name, with benchmark/ on the path for as
    long as the asking file's tests run."""
    before = list(sys.path)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"))
    import chipside
    import families
    import spec

    yield {"spec": spec, "families": families, "chipside": chipside}
    sys.path[:] = before


@pytest.fixture(scope="module")
def monkeypatch_module():
    mp = pytest.MonkeyPatch()
    yield mp
    mp.undo()


@pytest.fixture(scope="module")
def interpreted(monkeypatch_module):
    monkeypatch_module.setenv("TONY_PALLAS_INTERPRET", "1")


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
#: after the alarm, how long the main thread has to get back to the interpreter before its worker is ended
WATCHDOG_GRACE_S = 10
_real_stderr = None


def pytest_configure(config):
    """Capture is suspended while plugins are configured, so fd 2 is the run's
    own stderr here (each xdist worker's too): keep it for the watchdog."""
    global _real_stderr
    _real_stderr = os.fdopen(os.dup(2), "w")


def _hung_tests_file():
    """Where a worker that ends itself names the test it hung in, for the worker
    that xdist starts in its place (which is handed the same file again, that
    test included). xdist exports the run's id to all its workers."""
    run = os.environ.get("PYTEST_XDIST_TESTRUNUID")
    if not run or not os.environ.get("PYTEST_XDIST_WORKER"):
        return None
    return os.path.join(tempfile.gettempdir(), f"tony-hung-tests-{run}")


def _hung_earlier(hung, nodeid):
    try:
        with open(hung) as f:
            return nodeid in f.read().splitlines()
    except OSError:
        return False


def _stacks(nodeid, after):
    print(f"\n[watchdog] {nodeid} still running after {after}s; every thread's stack:", file=_real_stderr, flush=True)
    faulthandler.dump_traceback(file=_real_stderr, all_threads=True)


@pytest.fixture(autouse=True)
def hang_watchdog(request):
    """Fail the test that is still running after WATCHDOG_S seconds and print
    every thread's stack to the run's stderr. SIGALRM reaches the main thread
    of this worker only; a test that sets an alarm of its own replaces this
    one, and is then left to it (its handler is not touched afterwards).

    A main thread that sits in a C call never runs the alarm's handler. Under
    xdist a timer thread then names the test in `_hung_tests_file` and ends the
    worker: xdist reports the test as failed, and the worker it starts instead
    fails it again at once, here, and goes on with the rest of the file."""
    if threading.current_thread() is not threading.main_thread() or _real_stderr is None:
        yield
        return
    nodeid, hung = request.node.nodeid, _hung_tests_file()
    if hung and _hung_earlier(hung, nodeid):
        pytest.fail(f"watchdog: {nodeid} hung a worker of this run where no Python handler could run; not run again")

    def on_alarm(signum, frame):
        _stacks(nodeid, WATCHDOG_S)
        pytest.fail(f"watchdog: still running after {WATCHDOG_S}s (stacks on stderr)", pytrace=True)

    def end_the_worker():
        _stacks(nodeid, WATCHDOG_S + WATCHDOG_GRACE_S)
        with open(hung, "a") as f:
            f.write(nodeid + "\n")
        os._exit(1)

    timer = threading.Timer(WATCHDOG_S + WATCHDOG_GRACE_S, end_the_worker) if hung else None
    if timer:
        timer.daemon = True
        timer.start()
    # should the timer's thread not get to run either (a C call that keeps the
    # GIL), the stacks still reach the log before the run's own limit
    faulthandler.dump_traceback_later(WATCHDOG_S + 2 * WATCHDOG_GRACE_S, file=_real_stderr)
    before = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(WATCHDOG_S)
    try:
        yield
    finally:
        if timer:
            timer.cancel()
        faulthandler.cancel_dump_traceback_later()
        if signal.getsignal(signal.SIGALRM) is on_alarm:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, before)
