"""tests/conftest.py's watchdog against a test that never returns to the interpreter: such a test cost the
driver a whole run at PR 60 (a C++ `close` that lost its wakeup; the SIGALRM handler never ran). Proven on a
three-test file in a run of its own, under the driver's `-p xdist -n N --dist loadfile`."""

import os
import re
import subprocess
import sys

_THREE_TESTS = '''
import ctypes


def test_before():
    pass


def test_stuck_in_a_c_call():
    # a mutex locked twice by one thread, as a join that nothing wakes: no signal ends the
    # call (`pause` and `sleep` return at the alarm), so no Python handler ever runs
    libc, mutex = ctypes.CDLL(None), ctypes.create_string_buffer(64)    # zeroes: PTHREAD_MUTEX_INITIALIZER
    libc.pthread_mutex_lock(mutex)
    libc.pthread_mutex_lock(mutex)


def test_behind():
    pass
'''

#: the repo's own conftest.py with its limits cut to seconds (module constants, no option)
_CONFTEST = '''
import importlib.util

spec = importlib.util.spec_from_file_location("repo_conftest", {conftest!r})
repo = importlib.util.module_from_spec(spec)
spec.loader.exec_module(repo)
repo.WATCHDOG_S, repo.WATCHDOG_GRACE_S = 2, 1
hang_watchdog, pytest_configure = repo.hang_watchdog, repo.pytest_configure
'''


def test_a_test_stuck_in_native_code_costs_itself_and_not_the_run(tmp_path):
    """Under the driver's `-n N --dist loadfile`: the worker of a test that never
    returns to the interpreter is ended, the test is reported failed, the worker
    started in its place does not run it again, and the tests of the same file
    before and behind it pass. The run ends by itself."""
    (tmp_path / "conftest.py").write_text(_CONFTEST.format(conftest=os.path.join(os.path.dirname(os.path.abspath(__file__)), "conftest.py")))
    (tmp_path / "test_three.py").write_text(_THREE_TESTS)
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "test_three.py", "-q", "-p", "no:cacheprovider", "-p", "xdist", "-n", "2",
         "--dist", "loadfile", "-p", "no:randomly"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    out = run.stdout + run.stderr
    assert run.returncode == 1, out
    assert re.search(r"\b2 passed\b", out) and "test_stuck_in_a_c_call" in out and "still running after 3s" in out, out
    failed = re.findall(r"^FAILED (\S+)", out, re.M)
    assert failed and all(f.endswith("test_stuck_in_a_c_call") for f in failed), out
