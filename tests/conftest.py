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
