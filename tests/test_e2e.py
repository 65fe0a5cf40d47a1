"""End-to-end lifecycle tests: submit → AM → executors → user processes.

The TestTonyE2E analog (SURVEY.md §4): no real cluster — the
LocalResourceManager realizes containers as local subprocesses, and the
"training" workloads are the tiny fixture scripts in tests/fixtures/
asserting on the env contract, exactly the reference's strategy.
"""

import os
import sys
import time

import pytest

from tony_tpu import constants
from tony_tpu.config import TonyConfig, keys
from tony_tpu.cluster.client import Client
from tony_tpu.cluster.session import JobStatus
from tony_tpu.cluster import history

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")

FAST = {
    keys.AM_MONITOR_INTERVAL_MS: "50",
    keys.TASK_HEARTBEAT_INTERVAL_MS: "100",
    keys.AM_GANG_TIMEOUT_MS: "30000",
}


def fixture_cmd(name: str) -> str:
    return f"{sys.executable} {os.path.join(FIXTURES, name)}"


def run_job(tmp_tony_root, conf: dict) -> tuple[JobStatus, Client, object]:
    cfg = TonyConfig({**FAST, keys.STAGING_ROOT: str(tmp_tony_root), **conf})
    client = Client(cfg)
    handle = client.submit()
    final = client.monitor_application(handle, quiet=True)
    return final, client, handle


@pytest.mark.e2e
class TestLifecycle:
    def test_single_worker_success(self, tmp_tony_root):
        final, _, handle = run_job(
            tmp_tony_root,
            {"tony.worker.instances": "1", keys.EXECUTES: fixture_cmd("exit_0.py")},
        )
        assert final == JobStatus.SUCCEEDED
        status = handle.final_status()
        assert status["tasks"][0]["exit_code"] == 0

    def test_multi_worker_gang(self, tmp_tony_root):
        final, _, handle = run_job(
            tmp_tony_root,
            {"tony.worker.instances": "3", keys.EXECUTES: fixture_cmd("check_env.py"),
             keys.APPLICATION_FRAMEWORK: "tensorflow"},
        )
        assert final == JobStatus.SUCCEEDED, handle.final_status()

    def test_failure_fails_job(self, tmp_tony_root):
        final, _, handle = run_job(
            tmp_tony_root,
            {"tony.worker.instances": "1", keys.EXECUTES: fixture_cmd("exit_1.py")},
        )
        assert final == JobStatus.FAILED
        assert handle.final_status()["tasks"][0]["exit_code"] == 1

    def test_untracked_forever_task_killed_at_end(self, tmp_tony_root):
        # ps (untracked) sleeps forever; job ends when the tracked worker exits
        final, _, handle = run_job(
            tmp_tony_root,
            {
                "tony.worker.instances": "1",
                "tony.ps.instances": "1",
                keys.EXECUTES: fixture_cmd("exit_0.py"),
                "tony.ps.command": fixture_cmd("forever.py"),
            },
        )
        assert final == JobStatus.SUCCEEDED
        statuses = {f"{t['name']}": t["status"] for t in handle.final_status()["tasks"]}
        assert statuses["worker"] == "SUCCEEDED"
        assert statuses["ps"] in ("KILLED", "FAILED")

    def test_history_written(self, tmp_tony_root):
        final, _, handle = run_job(
            tmp_tony_root,
            {"tony.worker.instances": "1", keys.EXECUTES: fixture_cmd("exit_0.py")},
        )
        assert final == JobStatus.SUCCEEDED
        history_root = os.path.join(str(tmp_tony_root), "history")
        jobs = history.list_finished_jobs(history_root)
        assert [j.app_id for j in jobs] == [handle.app_id]
        assert jobs[0].status == "SUCCEEDED"
        types = [e.type.value for e in history.read_events(history_root, handle.app_id)]
        assert types[0] == "APPLICATION_INITED"
        assert "GANG_COMPLETE" in types
        assert types[-1] == "APPLICATION_FINISHED"
        # frozen config snapshot alongside (config.json)
        dest = history.finished_dir(history_root, handle.app_id, jobs[0].completed_ms)
        assert os.path.exists(os.path.join(dest, constants.CONFIG_SNAPSHOT_FILE))

    def test_task_logs_captured(self, tmp_tony_root):
        final, _, handle = run_job(
            tmp_tony_root,
            {"tony.worker.instances": "1", keys.EXECUTES: fixture_cmd("exit_0.py")},
        )
        assert final == JobStatus.SUCCEEDED
        log = os.path.join(handle.staging_dir, constants.TASK_LOG_DIRNAME, "worker_0", "stdout.log")
        assert "fixture: ok" in open(log).read()


@pytest.mark.e2e
class TestDistributedDataPlane:
    def test_gang_forms_jax_process_group_and_reduces(self, tmp_tony_root):
        """The distributed-backend proof: a tony-launched 2-worker gang joins
        one jax.distributed group from the injected env and a cross-process
        collective produces the right value on every rank."""
        final, _, handle = run_job(
            tmp_tony_root,
            {
                "tony.worker.instances": "2",
                keys.EXECUTES: fixture_cmd("jax_allreduce.py"),
                keys.APPLICATION_FRAMEWORK: "jax",
                # jax.distributed startup (gRPC coordination service) is slower
                # than the fixture scripts; give the gang room
                keys.AM_GANG_TIMEOUT_MS: "60000",
            },
        )
        assert final == JobStatus.SUCCEEDED, handle.final_status()


@pytest.mark.e2e
class TestMultiProcessSpmdTraining:
    def test_gang_trains_one_model_over_global_mesh(self, tmp_tony_root):
        """Full multi-host training proof: each of 2 workers owns 4 virtual
        devices; the sharded train step runs over the 8-device GLOBAL mesh
        with collectives crossing the process boundary."""
        final, _, handle = run_job(
            tmp_tony_root,
            {
                "tony.worker.instances": "2",
                keys.EXECUTES: fixture_cmd("spmd_train.py"),
                keys.APPLICATION_FRAMEWORK: "jax",
                keys.AM_GANG_TIMEOUT_MS: "120000",
                # jax compile + distributed init is slower than fixtures;
                # generous heartbeat budget
                keys.TASK_MAX_MISSED_HEARTBEATS: "100",
            },
        )
        assert final == JobStatus.SUCCEEDED, handle.final_status()


@pytest.mark.e2e
class TestTorchRuntimeDataPlane:
    def test_gang_forms_torch_process_group_and_reduces(self, tmp_tony_root):
        """TorchRuntime parity proof: workers read only the injected DDP env
        (MASTER_ADDR/PORT, RANK, WORLD_SIZE, INIT_METHOD), form a real gloo
        process group, and all-reduce across the gang."""
        pytest.importorskip("torch")
        final, _, handle = run_job(
            tmp_tony_root,
            {
                "tony.worker.instances": "2",
                keys.EXECUTES: fixture_cmd("torch_allreduce.py"),
                keys.APPLICATION_FRAMEWORK: "pytorch",
                keys.AM_GANG_TIMEOUT_MS: "60000",
            },
        )
        assert final == JobStatus.SUCCEEDED, handle.final_status()


@pytest.mark.e2e
class TestFailureDetection:
    def test_heartbeat_loss_marks_task_lost(self, tmp_tony_root):
        # chaos fault injection (tony.chaos.*): the hb-stall fault wedges the
        # executor — heartbeats stop while its process lives → AM declares LOST
        final, _, handle = run_job(
            tmp_tony_root,
            {
                "tony.worker.instances": "1",
                keys.EXECUTES: fixture_cmd("forever.py"),
                keys.TASK_MAX_MISSED_HEARTBEATS: "3",
                keys.CHAOS_SPEC: "hb-stall:worker:0",
                keys.CHAOS_SEED: "7",
            },
        )
        assert final == JobStatus.FAILED
        assert handle.final_status()["tasks"][0]["status"] == "LOST"

    def test_gang_restart_resumes_training_from_checkpoint(self, tmp_tony_root):
        """Reliability spine (SURVEY.md §5.3/§5.4): a training task dies
        mid-run, the gang restarts, and the relaunched task RESUMES from its
        checkpoint instead of step 0 — verified by the verdict and the
        'resumed from checkpoint' line in the task's stdout."""
        final, _, handle = run_job(
            tmp_tony_root,
            {
                "tony.worker.instances": "1",
                keys.EXECUTES: fixture_cmd("train_resume.py"),
                keys.TASK_RESTART_ON_FAILURE: "true",
            },
        )
        assert final == JobStatus.SUCCEEDED, handle.final_status()
        # the relaunched attempt logs under worker_0_r1 (restart suffix)
        log = os.path.join(
            str(tmp_tony_root), handle.app_id, "logs", "worker_0_r1", "stdout.log"
        )
        with open(log) as f:
            out = f.read()
        assert "resumed from checkpoint step" in out, out
        assert "resume run completed to step 8" in out, out

    def test_gang_restart_from_flaky_task(self, tmp_tony_root):
        # rebuild-only elasticity: whole-gang restart after a tracked failure
        final, _, handle = run_job(
            tmp_tony_root,
            {
                "tony.worker.instances": "1",
                keys.EXECUTES: fixture_cmd("flaky.py"),
                keys.TASK_RESTART_ON_FAILURE: "true",
                keys.TASK_MAX_TOTAL_INSTANCE_FAILURES: "2",
            },
        )
        assert final == JobStatus.SUCCEEDED
        assert handle.final_status()["app_id"] == handle.app_id

    def test_kill_application(self, tmp_tony_root):
        cfg = TonyConfig(
            {
                **FAST,
                keys.STAGING_ROOT: str(tmp_tony_root),
                "tony.worker.instances": "1",
                keys.EXECUTES: fixture_cmd("forever.py"),
            }
        )
        client = Client(cfg)
        handle = client.submit()
        rpc = handle.rpc()
        assert rpc is not None
        # wait until the worker is running, then kill
        deadline = time.time() + 20
        while time.time() < deadline:
            infos = rpc.call("get_task_infos")
            if infos and infos[0]["status"] in ("REGISTERED", "RUNNING"):
                break
            time.sleep(0.1)
        assert Client.kill(handle)
        final = client.monitor_application(handle, quiet=True)
        assert final == JobStatus.KILLED


@pytest.mark.e2e
class TestSchedulingE2E:
    def test_dependency_ordering_ps_before_worker(self, tmp_tony_root):
        final, _, handle = run_job(
            tmp_tony_root,
            {
                "tony.ps.instances": "1",
                "tony.worker.instances": "1",
                "tony.ps.command": fixture_cmd("forever.py"),
                keys.EXECUTES: fixture_cmd("exit_0.py"),
                keys.dependency_key("worker", "ps"): "20s",
            },
        )
        assert final == JobStatus.SUCCEEDED
        # event order: ps TASK_STARTED strictly before worker TASK_STARTED
        history_root = os.path.join(str(tmp_tony_root), "history")
        evs = history.read_events(history_root, handle.app_id)
        started = [e.payload["task"] for e in evs if e.type.value == "TASK_STARTED"]
        assert started.index("ps:0") < started.index("worker:0")

    def test_allocation_failure_fails_job(self, tmp_tony_root):
        final, _, handle = run_job(
            tmp_tony_root,
            {
                "tony.worker.instances": "2",
                "tony.worker.memory": "48g",   # 2x48g > 64g host
                keys.EXECUTES: fixture_cmd("exit_0.py"),
            },
        )
        assert final == JobStatus.FAILED
        assert "memory" in (handle.final_status().get("reason") or "")


@pytest.mark.e2e
class TestMultiSlicePool:
    def test_gang_spans_slices_with_placement_env(self, tmp_tony_root):
        # 4 workers x 4 chips on a pool of two v5e-8 slices: the gang MUST
        # spill onto the second slice, and every task sees the slice contract
        final, _, handle = run_job(
            tmp_tony_root,
            {
                "tony.worker.instances": "4",
                "tony.worker.chips": "4",
                keys.TPU_POOL_SPEC: "pool:v5e-8x2",
                keys.EXECUTES: fixture_cmd("check_slice_env.py"),
            },
        )
        assert final == JobStatus.SUCCEEDED, handle.final_status()
        app_dir = os.path.join(str(tmp_tony_root), handle.app_id)
        placements = set()
        for root, _, files in os.walk(app_dir):
            for f in files:
                if f == "stdout.log":
                    with open(os.path.join(root, f)) as fh:
                        for line in fh:
                            if line.startswith("SLICE_PLACEMENT"):
                                placements.add(line.strip().split(" -> ")[1])
        assert placements == {"0", "1"}, placements

    def test_pool_too_small_fails_cleanly(self, tmp_tony_root):
        # a 16-chip task cannot fit an 8-chip slice: allocation must fail the
        # job (DCN-spanning single tasks are rejected), not hang the gang
        final, _, handle = run_job(
            tmp_tony_root,
            {
                "tony.worker.instances": "1",
                "tony.worker.chips": "16",
                keys.TPU_POOL_SPEC: "pool:v5e-8x2",
                keys.EXECUTES: fixture_cmd("exit_0.py"),
            },
        )
        assert final == JobStatus.FAILED


@pytest.mark.e2e
class TestVenvArchive:
    def test_venv_zip_staged_and_activated(self, tmp_tony_root, tmp_path):
        # build a fake venv archive: bin/activate marker + bin/ on PATH
        import zipfile

        venv_src = tmp_path / "venv" / "bin"
        venv_src.mkdir(parents=True)
        probe = venv_src / "tony-venv-probe"
        probe.write_text("#!/bin/sh\necho venv-probe-ran\n")
        probe.chmod(0o755)
        archive = tmp_path / "venv.zip"
        with zipfile.ZipFile(archive, "w") as z:
            # z.write records each file's on-disk mode in external_attr
            # (the probe is 0755), which the unpacker must restore
            for p in (tmp_path / "venv").rglob("*"):
                z.write(p, p.relative_to(tmp_path))

        out_file = tmp_path / "which.txt"
        final, _, handle = run_job(
            tmp_tony_root,
            {
                "tony.worker.instances": "1",
                keys.PYTHON_VENV: str(archive),
                keys.EXECUTES: (
                    # EXECUTE the probe (not just resolve it): catches zip
                    # extraction dropping the executable bit
                    f"bash -c 'tony-venv-probe > {out_file} && "
                    f"command -v tony-venv-probe >> {out_file} && "
                    f"echo VIRTUAL_ENV=$VIRTUAL_ENV >> {out_file}'"
                ),
            },
        )
        assert final == JobStatus.SUCCEEDED, handle.final_status()
        text = out_file.read_text()
        # the probe RAN from the unpacked archive inside staging, and
        # VIRTUAL_ENV points there too
        assert "venv-probe-ran" in text
        assert "/venv/worker_0" in text and "tony-venv-probe" in text
        assert "VIRTUAL_ENV=" in text and str(tmp_tony_root) in text
