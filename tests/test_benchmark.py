"""The benchmark's own tests (benchmark/tests/test_benchmark.py: pure functions
and hand-built fixtures, ~12 s), run with the suite, so that the driver's run
guards the harness the per-layer readers plug into."""
import importlib.util
import os
import sys

_path = list(sys.path)
_spec = importlib.util.spec_from_file_location("benchmark_own_tests", os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark", "tests", "test_benchmark.py"))
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)
# that file puts benchmark/ FIRST on the path; its cases import readers by name
# as they run, so keep it, but last: the benchmark's top-level module names
# (run, check, spec, jobs, reduce ...) must not shadow a later test file's import
sys.path[:] = _path + [p for p in sys.path if p not in _path]
globals().update({k: v for k, v in vars(_module).items() if k.startswith("test_")})
