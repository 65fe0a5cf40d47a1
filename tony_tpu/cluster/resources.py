"""TPU-slice resource model and resource managers.

The reference asks YARN for containers with ``{memory, vcores, gpus}``
(``TonyApplicationMaster`` container requests — SURVEY.md §2.1). The
TPU-native rebuild makes the **slice** the first-class resource
(BASELINE.json north star): a pool is a 2D chip grid with ICI links
(v5e meshes are 2D), and an allocation is an **axis-aligned contiguous
sub-rectangle** of that grid — contiguity is what keeps a job's collectives
on ICI instead of DCN (SURVEY.md §2.6, §5.8).

``ResourceManager`` is the interface the AM schedules against; the
``LocalResourceManager`` realizes containers as local subprocesses (the
MiniYARNCluster analog, SURVEY.md §4) so the same AM code path runs under
tests, on one TPU VM, or (later rounds) against a multi-host pool service.
"""

from __future__ import annotations

import itertools
import os
import signal
import subprocess
import threading
import time
import uuid
from abc import ABC, abstractmethod
from dataclasses import dataclass

from tony_tpu import constants
from tony_tpu.config import parse_memory_string

# chips per accelerator host VM (v5e: 4 chips per VM is typical; v4/v5p: 4)
DEFAULT_CHIPS_PER_HOST = 4

# Known slice sizes → canonical 2D topologies (v5e/v6e pod slices).
_KNOWN_TOPOLOGIES: dict[int, tuple[int, int]] = {
    1: (1, 1), 2: (1, 2), 4: (2, 2), 8: (2, 4), 16: (4, 4),
    32: (4, 8), 64: (8, 8), 128: (8, 16), 256: (16, 16),
}


def squarish_topology(chips: int) -> tuple[int, int]:
    """Most-square 2D factorization for a chip count (ICI-friendly)."""
    if chips in _KNOWN_TOPOLOGIES:
        return _KNOWN_TOPOLOGIES[chips]
    best = (1, chips)
    for r in range(1, int(chips**0.5) + 1):
        if chips % r == 0:
            best = (r, chips // r)
    return best


@dataclass(frozen=True)
class SliceSpec:
    """An accelerator slice shape, e.g. v5e-64 = ('v5e', (8, 8))."""

    accelerator: str           # v5e | v5p | v4 | cpu
    topology: tuple[int, int]  # chip grid (rows, cols); (0, 0) for cpu

    @property
    def chips(self) -> int:
        return self.topology[0] * self.topology[1]

    @property
    def name(self) -> str:
        return f"{self.accelerator}-{self.chips}" if self.chips else self.accelerator

    @classmethod
    def parse(cls, spec: str) -> "SliceSpec":
        """Accepts 'v5e-64', 'v5e,8x8', or 'cpu'."""
        spec = spec.strip()
        if "," in spec:
            accel, topo = spec.split(",", 1)
            r, c = topo.lower().split("x")
            return cls(accel.strip(), (int(r), int(c)))
        if "-" in spec:
            accel, _, n = spec.rpartition("-")
            return cls(accel, squarish_topology(int(n)))
        return cls(spec, (0, 0))


@dataclass
class Resources:
    """Per-task resource ask (reference: memory/vcores/gpus → chips)."""

    memory_bytes: int = 2 * 1024**3
    vcores: int = 1
    chips: int = 0

    @classmethod
    def from_config_strings(cls, memory: str | None, vcores: str | None, chips: str | None) -> "Resources":
        return cls(
            memory_bytes=parse_memory_string(memory) if memory else 2 * 1024**3,
            vcores=int(vcores) if vcores else 1,
            chips=int(chips) if chips else 0,
        )


@dataclass
class Container:
    """An allocated execution slot (YARN Container analog), with TPU coords."""

    id: str
    host: str
    resources: Resources
    chip_coords: tuple[tuple[int, int], ...] = ()   # coords within the pool grid
    slice_name: str = ""                            # e.g. "v5e-64"
    slice_topology: tuple[int, int] = (0, 0)        # the job gang's slice shape
    job_type: str = ""
    task_index: int = -1

    def device_env(self) -> dict[str, str]:
        """TPU placement env injected into the executor (replaces the
        reference's GPU device plumbing via nvidia-smi/YARN GPU isolation).

        Besides the orchestrator's own description of the placement, this
        exports what the TPU runtime itself reads to bound a process to its
        chips (the names are libtpu's, as jax's own multi-process test
        launcher sets them): ``TPU_VISIBLE_CHIPS`` — the chips' indices on
        their host, row-major in the slice grid — and the process bounds
        that say "one process, this rectangle of chips". Without them two
        one-chip containers on one host would each claim every chip. The
        indices hold where the slice grid is one host's chips (local pools);
        a pool of several hosts has to translate them per host (ROADMAP R0)."""
        env = {
            constants.ENV_CONTAINER_ID: self.id,
            constants.ENV_TPU_CHIPS_PER_TASK: str(len(self.chip_coords)),
        }
        if self.chip_coords:
            env[constants.ENV_TPU_SLICE_NAME] = self.slice_name
            env[constants.ENV_TPU_SLICE_TOPOLOGY] = f"{self.slice_topology[0]}x{self.slice_topology[1]}"
            env[constants.ENV_TPU_CHIP_COORDS] = ";".join(f"{r},{c}" for r, c in self.chip_coords)
            cols = self.slice_topology[1]
            rows_used = {r for r, _ in self.chip_coords}
            cols_used = {c for _, c in self.chip_coords}
            env["TPU_VISIBLE_CHIPS"] = ",".join(
                str(i) for i in sorted(r * cols + c for r, c in self.chip_coords))
            env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = f"{len(cols_used)},{len(rows_used)},1"
            env["TPU_PROCESS_BOUNDS"] = "1,1,1"
        return env


def container_to_record(container: "Container") -> dict:
    """JSON-serializable form of a Container for the AM's takeover journal
    (rebuilt by :func:`container_from_record` in the successor AM)."""
    return {
        "id": container.id,
        "host": container.host,
        "resources": {
            "memory_bytes": container.resources.memory_bytes,
            "vcores": container.resources.vcores,
            "chips": container.resources.chips,
        },
        "chip_coords": [list(c) for c in container.chip_coords],
        "slice_name": container.slice_name,
        "slice_topology": list(container.slice_topology),
        "job_type": container.job_type,
        "task_index": container.task_index,
    }


def container_from_record(record: dict) -> "Container":
    res = record.get("resources") or {}
    return Container(
        id=record["id"],
        host=record.get("host", ""),
        resources=Resources(
            memory_bytes=int(res.get("memory_bytes", 0)),
            vcores=int(res.get("vcores", 0)),
            chips=int(res.get("chips", 0)),
        ),
        chip_coords=tuple((int(r), int(c)) for r, c in record.get("chip_coords", [])),
        slice_name=record.get("slice_name", ""),
        slice_topology=tuple(record.get("slice_topology") or (0, 0)),  # type: ignore[arg-type]
        job_type=record.get("job_type", ""),
        task_index=int(record.get("task_index", -1)),
    )


class AllocationError(RuntimeError):
    """The ask can NEVER be satisfied by this pool (or the pool has no
    nodes): the job fails. Transient shortage raises AllocationPending."""


class AllocationPending(RuntimeError):
    """Capacity is short NOW but the ask is feasible: the app waits in its
    queue (YARN capacity-queue analog). The caller releases any partial gang
    and retries on its next scheduling tick."""


class ChipGrid:
    """Occupancy tracking + contiguous-rectangle allocation on a 2D chip mesh.

    The ICI-affinity invariant (tony.tpu.ici-strict): an allocation is always
    an axis-aligned contiguous rectangle, so every chip in it reaches every
    other over ICI hops inside the rectangle — a mesh axis never silently
    spans DCN.
    """

    def __init__(self, topology: tuple[int, int]):
        self.rows, self.cols = topology
        self._used: set[tuple[int, int]] = set()
        self._lock = threading.Lock()

    @property
    def total(self) -> int:
        return self.rows * self.cols

    @property
    def free(self) -> int:
        return self.total - len(self._used)

    def allocate_rect(self, shape: tuple[int, int]) -> tuple[tuple[int, int], ...] | None:
        """First-fit scan for a free shape=(r,c) rectangle; tries both
        orientations. Returns row-major chip coords or None."""
        with self._lock:
            for r, c in dict.fromkeys([shape, shape[::-1]]):
                if r > self.rows or c > self.cols:
                    continue
                for r0 in range(self.rows - r + 1):
                    for c0 in range(self.cols - c + 1):
                        coords = tuple(
                            (r0 + i, c0 + j) for i, j in itertools.product(range(r), range(c))
                        )
                        if not self._used.intersection(coords):
                            self._used.update(coords)
                            return coords
            return None

    def allocate_chips(self, n: int) -> tuple[tuple[int, int], ...] | None:
        """Allocate n chips as the most-square rectangle that fits."""
        if n <= 0:
            return ()
        for r in sorted(
            {r for r in range(1, n + 1) if n % r == 0},
            key=lambda r: abs(r - n // r),
        ):
            got = self.allocate_rect((r, n // r))
            if got is not None:
                return got
        return None

    def occupy(self, coords: tuple[tuple[int, int], ...]) -> bool:
        """Mark SPECIFIC coords used — re-accounting a container ADOPTED from
        a dead AM's journal, whose placement already exists in the world.
        False (nothing marked) when any coord is already taken: the journal
        disagrees with this grid, so the adoption must fail."""
        coords = tuple((int(r), int(c)) for r, c in coords)
        with self._lock:
            if any(not (0 <= r < self.rows and 0 <= c < self.cols) for r, c in coords):
                return False
            if self._used.intersection(coords):
                return False
            self._used.update(coords)
            return True

    def release(self, coords: tuple[tuple[int, int], ...]) -> None:
        with self._lock:
            self._used.difference_update(coords)


# Env keys forwarded into docker containers: the executor/user contract, not
# the host's whole environment (reference: YARN forwards a whitelist).
_DOCKER_ENV_PREFIXES = (
    "TONY_", "JOB_", "TASK_", "JAX_", "TPU_", "PYTHON", "TF_", "DMLC_",
    "HOROVOD_", "RANK", "WORLD_SIZE", "LOCAL_RANK", "MASTER_", "CLUSTER_SPEC",
)


# Env values that must never appear on a command line (visible in /proc):
# passed as bare `-e KEY` so docker inherits them from the client process env.
_DOCKER_SECRET_KEYS = (constants.ENV_AM_SECRET,)


def _docker_wrap(command: list[str], env: dict[str, str]) -> list[str]:
    """Rewrite a container launch into ``docker run`` (YARN docker-runtime
    analog). Host networking keeps the executor's registered host:port valid;
    the staging dir and any TONY_CONTAINER_MOUNTS paths are bind-mounted so
    the frozen config, logs, and framework code resolve inside the image."""
    binary = env.get(constants.ENV_CONTAINER_RUNTIME_BINARY) or "docker"
    image = env.get(constants.ENV_CONTAINER_RUNTIME_IMAGE)
    if not image:
        raise ValueError(f"docker runtime requested but no image set "
                         f"({constants.ENV_CONTAINER_RUNTIME_IMAGE} empty)")
    cmd = [binary, "run", "--rm", "--network=host", "--ipc=host"]
    mounts = [env.get(constants.ENV_STAGING_DIR)]
    mounts += (env.get(constants.ENV_CONTAINER_MOUNTS) or "").split(",")
    for m in mounts:
        if m:
            src = m.split(":", 1)[0]
            cmd += ["-v", f"{src}:{m}" if ":" in m else f"{m}:{m}"]
    for k, v in env.items():
        if k in _DOCKER_SECRET_KEYS:
            cmd += ["-e", k]  # value inherited from the docker client's env
        elif any(k.startswith(p) for p in _DOCKER_ENV_PREFIXES):
            cmd += ["-e", f"{k}={v}"]
    return cmd + [image] + command


@dataclass(eq=False)  # identity hash: hosts are accounting objects, keyed by identity
class _Host:
    name: str
    memory_bytes: int
    vcores: int
    used_memory: int = 0
    used_vcores: int = 0


class ResourceManager(ABC):
    """What the AM's scheduler talks to (YARN RM + NM analog, collapsed).

    Separated so the loopback-emulated pool and a real multi-host pool are
    interchangeable (SURVEY.md §7 hard part (a)).
    """

    #: optional fault-injection context (tony.chaos.*), assigned by the AM;
    #: container faults (node-loss, preempt) apply at the poll_exited seam
    chaos = None

    def register_app(
        self, queue: str, priority: int, demand: "Resources",
        elastic_unit: "Resources | None" = None, elastic_slack: int = 0,
    ) -> None:
        """Announce the app's queue, priority, and TOTAL gang demand to the
        pool (ApplicationSubmissionContext analog), plus the elastic
        partial-reclaim contract (resources one shed worker frees, and how
        many workers the app may shed — zero when not elastic). In-process
        pools are single-tenant — only the remote pool service consumes
        this."""

    def poll_preemption(self) -> "dict | None":
        """The pool's cooperative-preemption notice for this app (drain /
        shrink request, or a cancellation), observed on the most recent
        ``poll_exited``. None for single-tenant in-process pools — only the
        remote pool service preempts cooperatively."""
        return None

    @abstractmethod
    def allocate(self, job_type: str, task_index: int, resources: Resources) -> Container:
        """Allocate a container, raise AllocationError (never fits), or raise
        AllocationPending (queued behind other tenants — retry later)."""

    def total_capacity(self) -> "Resources | None":
        """TOTAL resources of the pool's currently-alive universe (ignoring
        occupancy), or None when unknown. The AM's elastic-downsize decision
        compares this against the configured gang demand: a gang that no
        longer FITS the pool (node permanently lost) can re-plan smaller
        instead of queuing forever."""
        return None

    def node_capacities(self) -> "list[Resources] | None":
        """Per-alive-node capacities (same universe as ``total_capacity``),
        or None when unknown. Lets the downsize decision check a real
        PLACEMENT, not just totals — a 4x3g gang does not fit three 4g
        hosts even though the sums agree."""
        return None

    def journal_info(self, container: Container) -> dict | None:
        """Serializable adoption record the AM writes to its takeover journal
        so a SUCCESSOR AM process can re-adopt this live container without
        restarting it (``adopt_container``). None → this RM cannot support
        adoption and a takeover attempt must degrade to a full gang restart."""
        return None

    def adopt_container(self, record: dict) -> Container | None:
        """Re-track a container a PREVIOUS AM process allocated (from its
        journal's ``journal_info`` record): rebuild accounting and liveness
        tracking without launching anything. None → unadoptable (takeover
        degrades)."""
        return None

    def reclaim_orphans(self) -> None:
        """Degraded-takeover backstop: kill/release everything the pool still
        holds for this app. Remote pools implement it (release_all); for
        in-process RMs the dead AM's local children are reaped by the
        caller's /proc sweep — nothing to do here."""

    @abstractmethod
    def release(self, container: Container) -> None: ...

    @abstractmethod
    def start_container(
        self, container: Container, command: list[str], env: dict[str, str], log_dir: str
    ) -> None: ...

    @abstractmethod
    def poll_exited(self) -> dict[str, int]:
        """container_id → exit code, for containers that exited since last poll
        (the NMClient container-completed callback analog)."""

    @abstractmethod
    def kill_container(self, container: Container) -> None: ...

    @abstractmethod
    def shutdown(self) -> None: ...


class ContainerLauncher:
    """Agent-side container runtime (NM ``ContainerExecutor`` analog): one
    local subprocess per container id, own process group, per-container stdio
    capture, docker rewrite when requested.

    This is the single implementation of the *launch half* of the host-agent
    protocol: the in-process resource managers drive it directly, and the
    ``NodeAgent`` daemon (cluster/agent.py) drives the same object on a remote
    host on behalf of AM launch RPCs — local and distributed pools differ only
    in who calls it (SURVEY.md §3.1 process boundary #2).
    """

    def __init__(self) -> None:
        self._procs: dict[str, subprocess.Popen] = {}
        # containers ADOPTED from a dead AM's journal: tracked by bare pid —
        # they are init's children now, so exit codes are unknowable and
        # liveness is a kill(pid, 0) probe, not a wait(). None = known dead
        # at adoption (pid vanished or was recycled during the outage).
        self._adopted: dict[str, int | None] = {}
        self._grace_s: dict[str, float] = {}
        self._reported: set[str] = set()
        self._lock = threading.Lock()

    def start(
        self, container_id: str, command: list[str], env: dict[str, str], log_dir: str
    ) -> None:
        os.makedirs(log_dir, exist_ok=True)
        if env.get(constants.ENV_CONTAINER_RUNTIME_TYPE) == "docker":
            command = _docker_wrap(command, env)
        # SIGTERM→SIGKILL grace, from the job's env contract (the AM sets it
        # from tony.task.kill-grace-ms): long-draining tasks — a serving
        # endpoint finishing in-flight requests — need more than the 3 s
        # default before escalation
        try:
            grace_s = float(env.get(constants.ENV_KILL_GRACE_MS, "3000")) / 1000
        except ValueError:
            grace_s = 3.0
        with open(os.path.join(log_dir, "stdout.log"), "ab") as stdout, open(
            os.path.join(log_dir, "stderr.log"), "ab"
        ) as stderr:
            proc = subprocess.Popen(
                command,
                env=env,
                stdout=stdout,
                stderr=stderr,
                start_new_session=True,  # own process group → clean kill of user subtree
            )
        with self._lock:
            self._procs[container_id] = proc
            self._grace_s[container_id] = grace_s

    def adopt(
        self, container_id: str, pid: int, grace_s: float = 3.0,
        start_ticks: int | None = None,
    ) -> None:
        """Track a container launched by a DEAD predecessor process (AM
        takeover): the subprocess was re-parented to init, so this launcher
        can only probe/kill it by pid. The pid may already be gone — the
        first ``poll_exited`` then reports it with the unknowable-exit code
        and the AM's normal failure machinery takes over.

        ``start_ticks`` (the journaled /proc start time) guards against pid
        reuse during the AM outage: a recycled pid would otherwise make this
        launcher probe — and eventually SIGKILL — a stranger process."""
        tracked: int | None = int(pid)
        if start_ticks is not None:
            actual = _pid_start_ticks(tracked)
            if actual is not None and actual != int(start_ticks):
                tracked = None  # pid recycled: the real container is gone
        with self._lock:
            self._adopted[container_id] = tracked
            self._grace_s[container_id] = grace_s

    def pid_of(self, container_id: str) -> int | None:
        with self._lock:
            proc = self._procs.get(container_id)
            if proc is not None:
                return proc.pid
            return self._adopted.get(container_id)

    def poll_exited(self) -> dict[str, int]:
        out: dict[str, int] = {}
        with self._lock:
            for cid, proc in self._procs.items():
                if cid in self._reported:
                    continue
                rc = proc.poll()
                if rc is not None:
                    out[cid] = rc
                    self._reported.add(cid)
            for cid, pid in self._adopted.items():
                if cid in self._reported or (pid is not None and _pid_alive(pid)):  # lint: disable=blocking-under-lock — procfs read: memory-backed, never blocks on storage
                    continue
                # init reaped the real exit status with the dead AM; the
                # executor's RPC result report (which rides out the takeover)
                # is the authoritative record — this code is only the
                # silent-death backstop
                out[cid] = constants.EXIT_ADOPTED_UNKNOWN
                self._reported.add(cid)
        return out

    def kill(self, container_id: str, wait: bool = True, force: bool = False) -> None:
        """SIGTERM the container's process group, escalating to SIGKILL after
        the container's grace window (tony.task.kill-grace-ms; default 3 s).
        ``wait=False`` runs the grace/escalation in a background thread — the
        node agent's heartbeat loop must never block on a container's
        teardown (a synchronous multi-second wait exceeds the liveness
        window and gets the whole NODE declared dead). ``force=True`` skips
        the drain entirely (immediate SIGKILL): pool preemption and node
        death give no grace, and the chaos faults that simulate them must
        not either."""
        with self._lock:
            proc = self._procs.get(container_id)
            adopted_pid = self._adopted.get(container_id)
            grace_s = self._grace_s.get(container_id, 3.0)
        if proc is None:
            if adopted_pid is not None:
                _kill_adopted(adopted_pid, grace_s, wait=wait, force=force)
            return
        if proc.poll() is not None:
            return
        if force:
            # the cgroup-kill analog: cross setsid boundaries (the executor
            # starts the user child in its own session, so a plain killpg
            # would orphan it — the graceful path relies on the executor's
            # SIGTERM handler to reap the child, which SIGKILL never runs)
            _kill_process_tree(proc.pid)
            return
        try:
            pgid = os.getpgid(proc.pid)
            os.killpg(pgid, signal.SIGTERM)
        except ProcessLookupError:
            return

        def escalate() -> None:
            try:
                proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(pgid, signal.SIGKILL)
                except ProcessLookupError:
                    pass

        if wait:
            escalate()
        else:
            threading.Thread(target=escalate, daemon=True).start()

    def live_ids(self) -> list[str]:
        with self._lock:
            live = [cid for cid, p in self._procs.items() if p.poll() is None]
            live += [
                cid for cid, pid in self._adopted.items()
                if pid is not None and _pid_alive(pid)  # lint: disable=blocking-under-lock — procfs read: memory-backed, never blocks on storage
            ]
            return live

    def kill_all(self, wait: bool = True) -> None:
        for cid in self.live_ids():
            self.kill(cid, wait=wait)


def _pid_start_ticks(pid: int) -> int | None:
    """The process's start time in clock ticks (/proc stat field 22) — the
    (pid, start_ticks) pair is a unique process identity on this boot, which
    is what makes adopting a bare pid across an AM swap safe against pid
    reuse. None where /proc is unavailable (the guard degrades to pid-only)."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return int(f.read().rsplit(")", 1)[1].split()[19])
    except (OSError, IndexError, ValueError):
        return None


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    try:
        # a zombie answers kill(pid, 0) but is dead — it just awaits a reap
        # by whoever inherited it (init for adopted containers)
        with open(f"/proc/{pid}/stat") as f:
            if f.read().rsplit(")", 1)[1].split()[0] == "Z":
                return False
    except (OSError, IndexError):
        pass
    return True


def _kill_adopted(pid: int, grace_s: float, wait: bool, force: bool) -> None:
    """Kill an adopted (non-child) container by pid: same SIGTERM → grace →
    SIGKILL contract as the Popen path, with liveness probed via kill(pid, 0)
    since there is no child handle to wait() on."""
    if not _pid_alive(pid):
        return
    if force:
        _kill_process_tree(pid)
        return
    try:
        pgid = os.getpgid(pid)
        os.killpg(pgid, signal.SIGTERM)
    except ProcessLookupError:
        return

    def escalate() -> None:
        deadline = time.monotonic() + grace_s
        while time.monotonic() < deadline:
            if not _pid_alive(pid):
                return
            time.sleep(0.05)
        try:
            os.killpg(pgid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    if wait:
        escalate()
    else:
        threading.Thread(target=escalate, daemon=True).start()


def _kill_process_tree(pid: int) -> None:
    """SIGKILL ``pid`` and every descendant, crossing process-group/session
    boundaries — what a container-runtime cgroup kill (pool preemption, node
    death) does to the whole container subtree. /proc walk; on hosts without
    /proc only the root's process group is killed."""
    pgids = set()
    try:
        pgids.add(os.getpgid(pid))
    except ProcessLookupError:
        pass
    try:
        children: dict[int, list[tuple[int, int]]] = {}
        for name in os.listdir("/proc"):
            if not name.isdigit():
                continue
            try:
                with open(f"/proc/{name}/stat") as f:
                    # field 2 (comm) may contain spaces/parens: split after it
                    rest = f.read().rsplit(")", 1)[1].split()
                ppid, pgid = int(rest[1]), int(rest[2])
            except (OSError, IndexError, ValueError):
                continue
            children.setdefault(ppid, []).append((int(name), pgid))
        stack = [pid]
        while stack:
            for cpid, pgid in children.get(stack.pop(), ()):
                pgids.add(pgid)
                stack.append(cpid)
    except OSError:
        pass
    for pgid in pgids:
        try:
            os.killpg(pgid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass


class ProcessContainerMixin:
    """RM-facing adapter over a local ``ContainerLauncher``: the in-process
    deployments (single-host RM, multi-slice pool emulation) launch through
    the very same runtime object the NodeAgent daemon uses, so swapping in a
    distributed pool changes the transport, never the container semantics."""

    launcher: ContainerLauncher

    def start_container(
        self, container: Container, command: list[str], env: dict[str, str], log_dir: str
    ) -> None:
        self.launcher.start(container.id, command, env, log_dir)

    def poll_exited(self) -> dict[str, int]:
        exits = self.launcher.poll_exited()
        if self.chaos is not None:
            # chaos node-loss / preempt: victims die through the real kill
            # path and surface here as synthetic cluster exit codes
            exits = self.chaos.perturb_container_exits(self, exits)
        return exits

    def kill_container(self, container: Container) -> None:
        self.launcher.kill(container.id)

    def kill_container_abrupt(self, container: Container) -> None:
        """Chaos node-loss/preempt fidelity: a preempted container or a dead
        node never drains politely — SIGKILL the process group outright
        (the graceful path would also block the caller for the full grace
        window per victim, letting survivors run seconds past the fault)."""
        self.launcher.kill(container.id, force=True)

    def _live_containers(self) -> list[Container]:
        raise NotImplementedError

    def shutdown(self) -> None:
        for c in self._live_containers():
            self.kill_container(c)
            self.release(c)


class LocalResourceManager(ProcessContainerMixin, ResourceManager):
    """Process-per-container RM on one host (MiniCluster analog, SURVEY.md §4).

    Models a single TPU VM pool (or a pure-CPU pool for tests): one logical
    host with a chip grid; containers are local subprocesses in their own
    process groups with stdout/stderr captured per-container.
    """

    def __init__(
        self,
        pool_spec: str = "local:cpu",
        host_memory: str = "64g",
        host_vcores: int = 64,
    ):
        name, _, accel = pool_spec.partition(":")
        self.slice = SliceSpec.parse(accel or "cpu")
        self.grid = ChipGrid(self.slice.topology)
        self.host = _Host(name or "localhost", parse_memory_string(host_memory), host_vcores)
        self.launcher = ContainerLauncher()
        self._containers: dict[str, Container] = {}
        self._lock = threading.Lock()

    def allocate(self, job_type: str, task_index: int, resources: Resources) -> Container:
        with self._lock:
            if self.host.used_memory + resources.memory_bytes > self.host.memory_bytes:
                raise AllocationError(f"host out of memory for {job_type}:{task_index}")
            if self.host.used_vcores + resources.vcores > self.host.vcores:
                raise AllocationError(f"host out of vcores for {job_type}:{task_index}")
            coords = self.grid.allocate_chips(resources.chips)
            if coords is None:
                raise AllocationError(
                    f"no contiguous {resources.chips}-chip rectangle free "
                    f"({self.grid.free}/{self.grid.total} chips free)"
                )
            self.host.used_memory += resources.memory_bytes
            self.host.used_vcores += resources.vcores
            c = Container(
                id=f"container_{uuid.uuid4().hex[:12]}",
                host=self.host.name,
                resources=resources,
                chip_coords=coords,
                slice_name=self.slice.name,
                slice_topology=self.slice.topology,
                job_type=job_type,
                task_index=task_index,
            )
            self._containers[c.id] = c
            return c

    def release(self, container: Container) -> None:
        with self._lock:
            if self._containers.pop(container.id, None) is None:
                return
            self.grid.release(container.chip_coords)
            self.host.used_memory -= container.resources.memory_bytes
            self.host.used_vcores -= container.resources.vcores

    def total_capacity(self) -> Resources:
        return Resources(
            memory_bytes=self.host.memory_bytes,
            vcores=self.host.vcores,
            chips=self.grid.total,
        )

    def node_capacities(self) -> list[Resources]:
        return [self.total_capacity()]

    def journal_info(self, container: Container) -> dict | None:
        pid = self.launcher.pid_of(container.id)
        if pid is None:
            return None  # allocated but never started: nothing to adopt
        with self._lock:
            grace_s = self.launcher._grace_s.get(container.id, 3.0)
        return {
            **container_to_record(container), "pid": pid, "grace_s": grace_s,
            # (pid, start_ticks) is the unique identity the adopting AM
            # verifies — a pid recycled during the outage must not be probed
            "pid_start": _pid_start_ticks(pid),
        }

    def adopt_container(self, record: dict) -> Container | None:
        pid = record.get("pid")
        if not pid:
            return None
        c = container_from_record(record)
        with self._lock:
            if self.host.used_memory + c.resources.memory_bytes > self.host.memory_bytes:
                return None
            if self.host.used_vcores + c.resources.vcores > self.host.vcores:
                return None
            if c.chip_coords and not self.grid.occupy(c.chip_coords):
                return None
            self.host.used_memory += c.resources.memory_bytes
            self.host.used_vcores += c.resources.vcores
            self._containers[c.id] = c
        # liveness by pid probe: a pid that already died (or was recycled —
        # start_ticks mismatch) surfaces on the first poll_exited as
        # EXIT_ADOPTED_UNKNOWN — adoption still succeeds so the normal
        # failure machinery (not a degraded takeover) handles it
        self.launcher.adopt(c.id, int(pid), float(record.get("grace_s", 3.0)),
                            start_ticks=record.get("pid_start"))
        return c

    def _live_containers(self) -> list[Container]:
        with self._lock:
            return list(self._containers.values())


@dataclass
class _PoolSlice:
    """One ICI island in a multi-slice pool."""

    slice_id: int
    spec: SliceSpec
    grid: ChipGrid
    hosts: list[_Host]

    def host_of(self, coords: tuple[tuple[int, int], ...]) -> _Host:
        """The host owning a rect's first chip (chips are tiled onto hosts
        row-major, DEFAULT_CHIPS_PER_HOST per host)."""
        if not coords:
            return self.hosts[0]
        r, c = coords[0]
        linear = r * self.spec.topology[1] + c
        return self.hosts[min(linear // DEFAULT_CHIPS_PER_HOST, len(self.hosts) - 1)]

    def hosts_of(self, coords: tuple[tuple[int, int], ...]) -> dict[int, int]:
        """host index → chip count for every host a rect touches (a multi-host
        allocation charges memory/vcores on every host it lands on, not just
        the first chip's)."""
        if not coords:
            return {self.hosts.index(self.host_of(coords)): 0}
        counts: dict[int, int] = {}
        for r, c in coords:
            linear = r * self.spec.topology[1] + c
            h = min(linear // DEFAULT_CHIPS_PER_HOST, len(self.hosts) - 1)
            counts[h] = counts.get(h, 0) + 1
        return counts


class MultiSliceResourceManager(ProcessContainerMixin, ResourceManager):
    """A pool of SEVERAL ICI slices joined by DCN (the multi-slice analog of
    a YARN cluster with several racks). Spec: ``pool:v5e-64x4`` = four
    v5e-64 slices.

    Placement policy:
    - a chip ask is always satisfied INSIDE one slice as a contiguous
      rectangle (the ICI invariant — `tony.tpu.ici-strict`); asks larger
      than a slice are rejected with a clear error,
    - best-fit across slices: the fullest slice that still fits takes the
      task, so gangs pack into as few slices as possible and data-parallel
      replicas spill onto the next slice only when one fills — exactly the
      DP-over-DCN / TP-CP-EP-over-ICI split the mesh layer assumes,
    - every container env carries its slice id and the pool's slice count
      (``TPU_SLICE_ID`` / ``TPU_NUM_SLICES``) so runtimes can build
      ``MeshSpec(num_slices=...)`` with DCN-safe axis placement.

    Containers are realized as local subprocesses (the pool *scheduling*
    model is the thing under test without multi-host hardware); a real
    deployment overrides the launch methods with its fabric.
    """

    def __init__(
        self,
        pool_spec: str = "pool:v5e-8x2",
        host_memory: str = "64g",
        host_vcores: int = 64,
    ):
        _, _, spec = pool_spec.partition(":")
        base, _, count = spec.rpartition("x")
        if not base or not count.isdigit():
            raise ValueError(
                f"multi-slice pool spec must look like 'pool:v5e-64x4', got {pool_spec!r}"
            )
        self.num_slices = int(count)
        slice_spec = SliceSpec.parse(base)
        if self.num_slices < 1 or slice_spec.chips < 1:
            raise ValueError(f"degenerate pool spec {pool_spec!r}")
        self.slices = []
        for s in range(self.num_slices):
            n_hosts = max(1, slice_spec.chips // DEFAULT_CHIPS_PER_HOST)
            hosts = [
                _Host(f"slice{s}-host{h}", parse_memory_string(host_memory), host_vcores)
                for h in range(n_hosts)
            ]
            self.slices.append(
                _PoolSlice(s, slice_spec, ChipGrid(slice_spec.topology), hosts)
            )
        self.launcher = ContainerLauncher()
        self._containers: dict[str, tuple[Container, int, dict[_Host, tuple[int, int]]]] = {}
        self._span: list[int] | None = None  # gang DCN span, snapshotted at first launch
        self._lock = threading.Lock()

    @staticmethod
    def _host_charges(
        sl: _PoolSlice, coords: tuple[tuple[int, int], ...], resources: Resources
    ) -> dict[_Host, tuple[int, int]]:
        """Split a container's memory/vcores across every host its chip rect
        touches, pro-rata by chip count (remainder on the first host). A
        chipless ask charges wholly on the rect's nominal host."""
        counts = sl.hosts_of(coords)
        total = sum(counts.values())
        if total == 0:
            only = next(iter(counts))
            return {sl.hosts[only]: (resources.memory_bytes, resources.vcores)}
        charges: dict[_Host, tuple[int, int]] = {}
        for h, n in sorted(counts.items()):
            charges[sl.hosts[h]] = (
                resources.memory_bytes * n // total,
                resources.vcores * n // total,
            )
        # integer remainders land on the first touched host
        mem_used = sum(m for m, _ in charges.values())
        vc_used = sum(v for _, v in charges.values())
        h0 = sl.hosts[min(counts)]
        charges[h0] = (
            charges[h0][0] + resources.memory_bytes - mem_used,
            charges[h0][1] + resources.vcores - vc_used,
        )
        return charges

    def allocate(self, job_type: str, task_index: int, resources: Resources) -> Container:
        chips = resources.chips
        per_slice = self.slices[0].spec.chips
        if chips > per_slice:
            raise AllocationError(
                f"{job_type}:{task_index} asks {chips} chips but a slice has "
                f"{per_slice}: a task may not span DCN (shard the job into "
                f"per-slice tasks and let data/pipeline axes cross slices)"
            )
        with self._lock:
            # best-fit: fullest slice that still fits → gangs pack tightly
            order = sorted(self.slices, key=lambda s: s.grid.free)
            for sl in order:
                if chips and sl.grid.free < chips:
                    continue
                coords = sl.grid.allocate_chips(chips)
                if coords is None and chips:
                    continue
                charges = self._host_charges(sl, coords or (), resources)
                if any(
                    h.used_memory + mem > h.memory_bytes or h.used_vcores + vc > h.vcores
                    for h, (mem, vc) in charges.items()
                ):
                    if coords:
                        sl.grid.release(coords)
                    continue
                for h, (mem, vc) in charges.items():
                    h.used_memory += mem
                    h.used_vcores += vc
                c = Container(
                    id=f"container_{uuid.uuid4().hex[:12]}",
                    host=sl.host_of(coords or ()).name,
                    resources=resources,
                    chip_coords=coords or (),
                    slice_name=sl.spec.name,
                    slice_topology=sl.spec.topology,
                    job_type=job_type,
                    task_index=task_index,
                )
                self._containers[c.id] = (c, sl.slice_id, charges)
                return c
            raise AllocationError(
                f"no slice can host {job_type}:{task_index} "
                f"({chips} chips; free per slice: "
                f"{[s.grid.free for s in self.slices]})"
            )

    def slice_of(self, container: Container) -> int:
        with self._lock:
            return self._containers[container.id][1]

    def release(self, container: Container) -> None:
        with self._lock:
            entry = self._containers.pop(container.id, None)
            if entry is None:
                return
            c, slice_id, charges = entry
            self.slices[slice_id].grid.release(c.chip_coords)
            for h, (mem, vc) in charges.items():
                h.used_memory -= mem
                h.used_vcores -= vc
            if not self._containers:
                # gang fully released (restart path): next gang spans anew
                self._span = None

    def total_capacity(self) -> Resources:
        return Resources(
            memory_bytes=sum(h.memory_bytes for sl in self.slices for h in sl.hosts),
            vcores=sum(h.vcores for sl in self.slices for h in sl.hosts),
            chips=sum(sl.grid.total for sl in self.slices),
        )

    def node_capacities(self) -> list[Resources]:
        out = []
        for sl in self.slices:
            n = max(len(sl.hosts), 1)
            base, rem = divmod(sl.grid.total, n)
            for i, h in enumerate(sl.hosts):
                # remainder chips land on the first hosts so the node list
                # SUMS to the true pool total — an undercount here would
                # trigger spurious elastic downsizing
                out.append(Resources(
                    memory_bytes=h.memory_bytes,
                    vcores=h.vcores,
                    chips=base + (1 if i < rem else 0),
                ))
        return out

    def journal_info(self, container: Container) -> dict | None:
        pid = self.launcher.pid_of(container.id)
        with self._lock:
            entry = self._containers.get(container.id)
            grace_s = self.launcher._grace_s.get(container.id, 3.0)
        if pid is None or entry is None:
            return None
        _, slice_id, charges = entry
        sl = self.slices[slice_id]
        return {
            **container_to_record(container),
            "pid": pid,
            "pid_start": _pid_start_ticks(pid),
            "grace_s": grace_s,
            "slice_id": slice_id,
            "charges": [
                [sl.hosts.index(h), mem, vc] for h, (mem, vc) in charges.items()
            ],
        }

    def adopt_container(self, record: dict) -> Container | None:
        pid = record.get("pid")
        sid = record.get("slice_id")
        if not pid or sid is None or not 0 <= int(sid) < len(self.slices):
            return None
        c = container_from_record(record)
        sl = self.slices[int(sid)]
        with self._lock:
            charges: dict[_Host, tuple[int, int]] = {}
            for hidx, mem, vc in record.get("charges", []):
                if not 0 <= int(hidx) < len(sl.hosts):
                    return None
                charges[sl.hosts[int(hidx)]] = (int(mem), int(vc))
            if any(
                h.used_memory + mem > h.memory_bytes or h.used_vcores + vc > h.vcores
                for h, (mem, vc) in charges.items()
            ):
                return None
            if c.chip_coords and not sl.grid.occupy(c.chip_coords):
                return None
            for h, (mem, vc) in charges.items():
                h.used_memory += mem
                h.used_vcores += vc
            self._containers[c.id] = (c, int(sid), charges)
        self.launcher.adopt(c.id, int(pid), float(record.get("grace_s", 3.0)),
                            start_ticks=record.get("pid_start"))
        return c

    def gang_slice_span(self) -> list[int]:
        """Slice ids the gang's allocations occupy — the job's DCN span.

        Append-only across launch waves: the scheduler allocates a whole job
        type before starting any of its containers, so every task in one wave
        sees the identical span; a dependency-gated later type that lands on
        a new slice *appends* it, keeping earlier tasks' TPU_SLICE_ID indices
        stable (tasks in different waves never form one mesh). Reset only
        when the gang is fully released (whole-gang restart)."""
        with self._lock:
            current = {sid for _, sid, _ in self._containers.values()}
            if self._span is None:
                self._span = sorted(current)
            else:
                self._span.extend(sorted(current - set(self._span)))
            return self._span

    def start_container(
        self, container: Container, command: list[str], env: dict[str, str], log_dir: str
    ) -> None:
        # the env carries the GANG's slice layout, not the pool's: a gang
        # packed into one slice of a 4-slice pool is all-ICI and must build
        # a plain (non-hybrid) mesh — slice ids are densified over the span
        span = self.gang_slice_span()
        env = dict(env)
        env[constants.ENV_TPU_SLICE_ID] = str(span.index(self.slice_of(container)))
        env[constants.ENV_TPU_NUM_SLICES] = str(len(span))
        super().start_container(container, command, env, log_dir)

    def _live_containers(self) -> list[Container]:
        with self._lock:
            return [c for c, _, _ in self._containers.values()]
