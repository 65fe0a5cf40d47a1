"""Profiler sidecar (SURVEY.md §5.1 rebuild): window state machine + env wiring."""

import os

from tony_tpu import constants
from tony_tpu.config import TonyConfig, keys
from tony_tpu.train import profiling
from tony_tpu.train.profiling import StepProfiler


class _FakeJaxProfiler:
    def __init__(self):
        self.calls = []

    def start_trace(self, d):
        self.calls.append(("start", d))

    def stop_trace(self):
        self.calls.append(("stop", None))


class TestStepProfiler:
    def test_disabled_without_env(self):
        p = StepProfiler(env={})
        assert not p.enabled
        p.step(0); p.step(100)  # must be a no-op (would import jax otherwise)
        assert not p.active

    def test_window_state_machine(self, tmp_path, monkeypatch):
        import jax

        fake = _FakeJaxProfiler()
        monkeypatch.setattr(jax, "profiler", fake)
        p = StepProfiler(env={
            profiling.ENV_PROFILE_DIR: str(tmp_path / "trace"),
            profiling.ENV_PROFILE_START_STEP: "2",
            profiling.ENV_PROFILE_NUM_STEPS: "3",
        })
        for step in range(8):
            p.step(step)
        assert fake.calls == [("start", str(tmp_path / "trace")), ("stop", None)]
        assert p.done
        p.step(20)  # one window only
        assert len(fake.calls) == 2

    def test_stop_flushes_open_window(self, tmp_path, monkeypatch):
        import jax

        fake = _FakeJaxProfiler()
        monkeypatch.setattr(jax, "profiler", fake)
        p = StepProfiler(env={profiling.ENV_PROFILE_DIR: str(tmp_path),
                              profiling.ENV_PROFILE_START_STEP: "0"})
        p.step(0)
        assert p.active
        p.stop()
        p.stop()  # idempotent
        assert fake.calls.count(("stop", None)) == 1

    def test_short_run_artifact_is_terminated_and_readable(self, tmp_path):
        """Training that finishes before start_step + num_steps used to leave
        the trace unterminated; the train-loop `finally` now stops it — with
        the REAL jax profiler, the capture directory must hold a complete,
        readable artifact after stop()."""
        import gzip

        import jax.numpy as jnp

        trace_dir = tmp_path / "trace"
        p = StepProfiler(env={
            profiling.ENV_PROFILE_DIR: str(trace_dir),
            profiling.ENV_PROFILE_START_STEP: "0",
            profiling.ENV_PROFILE_NUM_STEPS: "1000",  # run ends long before
        })
        p.step(0)
        assert p.active
        (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
        p.stop()  # what the loop's finally does
        assert p.done and not p.active
        artifacts = [
            os.path.join(root, f)
            for root, _, files in os.walk(trace_dir)
            for f in files
        ]
        xplanes = [a for a in artifacts if a.endswith(".xplane.pb")]
        assert xplanes and os.path.getsize(xplanes[0]) > 0, artifacts
        for gz in (a for a in artifacts if a.endswith(".trace.json.gz")):
            with gzip.open(gz) as f:  # terminated, not torn: gzip readable
                assert f.read(16)


class TestExecutorEnvWiring:
    def test_profile_env_injected(self, monkeypatch, tmp_path):
        """build_child_env exports the profile contract when enabled."""
        from tony_tpu.cluster.executor import TaskExecutor

        staging = tmp_path / "stage"
        staging.mkdir()
        cfg = TonyConfig({
            "tony.worker.instances": "1",
            keys.TASK_PROFILE: "true",
            keys.TASK_PROFILE_START_STEP: "7",
        })
        cfg.freeze()
        cfg.write_final(str(staging))
        env = {
            constants.ENV_APP_ID: "app",
            constants.ENV_STAGING_DIR: str(staging),
            constants.ENV_JOB_NAME: "worker",
            constants.ENV_TASK_INDEX: "0",
            constants.ENV_AM_PORT: "1",
        }
        ex = TaskExecutor(env=env)
        child_env = ex.build_child_env({"worker": ["h:1"]}, {})
        assert child_env[profiling.ENV_PROFILE_DIR].endswith(os.path.join("profile", "worker_0"))
        assert child_env[profiling.ENV_PROFILE_START_STEP] == "7"

    def test_introspection_env_injected(self, tmp_path):
        """The on-demand + logging contracts ride the same env channel: the
        control-file poll throttle and the structured-log sink/level."""
        from tony_tpu.cluster.executor import TaskExecutor

        staging = tmp_path / "stage"
        staging.mkdir()
        cfg = TonyConfig({
            "tony.worker.instances": "1",
            keys.PROFILE_POLL_INTERVAL_MS: "250",
            keys.LOG_LEVEL: "debug",
        })
        cfg.freeze()
        cfg.write_final(str(staging))
        env = {
            constants.ENV_APP_ID: "app",
            constants.ENV_STAGING_DIR: str(staging),
            constants.ENV_JOB_NAME: "worker",
            constants.ENV_TASK_INDEX: "0",
            constants.ENV_AM_PORT: "1",
        }
        ex = TaskExecutor(env=env)
        child_env = ex.build_child_env({"worker": ["h:1"]}, {})
        assert child_env[profiling.ENV_PROFILE_POLL_MS] == "250"
        assert child_env[constants.ENV_LOG_DIR] == os.path.join(str(staging), "logs")
        assert child_env[constants.ENV_LOG_LEVEL] == "debug"

    def test_log_level_off_skips_child_contract(self, tmp_path):
        from tony_tpu.cluster.executor import TaskExecutor

        staging = tmp_path / "stage"
        staging.mkdir()
        cfg = TonyConfig({
            "tony.worker.instances": "1",
            keys.LOG_LEVEL: "off",
        })
        cfg.freeze()
        cfg.write_final(str(staging))
        env = {
            constants.ENV_APP_ID: "app",
            constants.ENV_STAGING_DIR: str(staging),
            constants.ENV_JOB_NAME: "worker",
            constants.ENV_TASK_INDEX: "0",
            constants.ENV_AM_PORT: "1",
        }
        ex = TaskExecutor(env=env)
        child_env = ex.build_child_env({"worker": ["h:1"]}, {})
        assert constants.ENV_LOG_DIR not in child_env

    def test_no_kernel_lever_rides_the_env_channel(self, monkeypatch, tmp_path):
        """A kernel's block sizes are its module's: a config that still names
        the tuner's keys exports nothing to the task."""
        from tony_tpu.cluster.executor import TaskExecutor

        staging = tmp_path / "stage"
        staging.mkdir()
        cfg = TonyConfig({
            "tony.worker.instances": "1",
            "tony.tune.cache-file": str(tmp_path / "tune.json"),
            "tony.tune.enabled": "false",
        })
        cfg.freeze()
        cfg.write_final(str(staging))
        env = {
            constants.ENV_APP_ID: "app",
            constants.ENV_STAGING_DIR: str(staging),
            constants.ENV_JOB_NAME: "worker",
            constants.ENV_TASK_INDEX: "0",
            constants.ENV_AM_PORT: "1",
        }
        for name in [n for n in os.environ if n.startswith("TONY_TUNE")]:
            monkeypatch.delenv(name)
        child_env = TaskExecutor(env=env).build_child_env({"worker": ["h:1"]}, {})
        assert not [n for n in child_env if n.startswith("TONY_TUNE")]
