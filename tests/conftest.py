"""Test harness configuration.

Multi-chip behavior is tested the way the reference tests multi-node behavior
(SURVEY.md §4): no real cluster — an in-process fake resource manager, local
subprocesses as "containers", and a virtual device mesh. Here the mesh is
8 virtual CPU devices via --xla_force_host_platform_device_count, set BEFORE
jax is first imported.
"""

import os
import sys

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
