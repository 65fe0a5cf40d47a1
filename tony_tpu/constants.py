"""Well-known names shared across the framework.

Analog of the reference's ``tony-core/.../tony/Constants.java`` (SURVEY.md §2.1):
frozen-config artifact name, staging-dir layout, env-var names forming the
executor↔user-process contract, and TPU-specific additions (slice coordinates,
jax.distributed rendezvous) that replace the reference's GPU/YARN names.
"""

from __future__ import annotations

import os

# ---------------------------------------------------------------------------
# Artifact / directory names (analog: Constants.TONY_FINAL_XML, ".tony/" staging)
# ---------------------------------------------------------------------------
TONY_FINAL_CONF = "tony-final.json"     # frozen job conf shipped to AM/executors
TONY_DEFAULT_CONF = "tony-default.json"  # packaged defaults (tony-default.xml analog)
TONY_SITE_CONF = "tony-site.json"       # cluster-level overrides
TONY_STAGING_DIRNAME = ".tony"          # per-app staging root
AM_INFO_FILE = "am_info.json"           # AM host/port/secret advertisement (YARN report analog)
SUBMIT_INFO_FILE = "submit_info.json"   # the client's submit stamp, staged beside the frozen conf (goodput `submit` phase)
AM_JOURNAL_FILE = "am_journal.jsonl"    # AM recoverable-state journal (work-preserving takeover)
POOL_INFO_FILE = "pool_info.json"       # pool-service host/port advertisement (RM address analog)
CONFIG_SNAPSHOT_FILE = "config.json"    # job conf written alongside history (HistoryFileUtils)
HISTORY_SUFFIX = ".jhist"               # history event file suffix (Avro .jhist analog → JSONL)
HISTORY_INTERMEDIATE_DIR = "intermediate"
HISTORY_FINISHED_DIR = "finished"
TASK_LOG_DIRNAME = "logs"

# ---------------------------------------------------------------------------
# Env-var contract: AM/executor plumbing
# (analog: Constants.java env names CLUSTER_SPEC, JOB_NAME, TASK_INDEX, ...)
# ---------------------------------------------------------------------------
ENV_APP_ID = "TONY_APP_ID"
ENV_AM_HOST = "TONY_AM_HOST"
ENV_AM_PORT = "TONY_AM_PORT"
ENV_AM_SECRET = "TONY_AM_SECRET"
ENV_STAGING_DIR = "TONY_STAGING_DIR"
ENV_CONTAINER_ID = "TONY_CONTAINER_ID"
ENV_NODE_NAME = "TONY_NODE_NAME"        # host-agent name that launched this container
ENV_POOL_SECRET = "TONY_POOL_SECRET"    # pool-service shared secret (daemons only)

# Container-runtime passthrough (analog: YARN_CONTAINER_RUNTIME_TYPE /
# YARN_CONTAINER_RUNTIME_DOCKER_IMAGE set by TonY when tony.docker.enabled).
# The AM sets these; the ResourceManager (NM analog) interprets them at launch.
ENV_CONTAINER_RUNTIME_TYPE = "TONY_CONTAINER_RUNTIME_TYPE"
ENV_CONTAINER_RUNTIME_IMAGE = "TONY_CONTAINER_RUNTIME_DOCKER_IMAGE"
ENV_CONTAINER_RUNTIME_BINARY = "TONY_CONTAINER_RUNTIME_DOCKER_BINARY"
ENV_CONTAINER_MOUNTS = "TONY_CONTAINER_MOUNTS"  # csv "path[:ro]" extra binds

ENV_JOB_NAME = "JOB_NAME"               # task type, e.g. "worker"
ENV_TASK_INDEX = "TASK_INDEX"           # index within the type
ENV_TASK_NUM = "TASK_NUM"               # instances of this type
ENV_DISTRIBUTED_MODE = "DISTRIBUTED_MODE"  # GANG | SINGLE_NODE
ENV_CLUSTER_SPEC = "CLUSTER_SPEC"       # full cluster spec JSON (legacy TF contract)
ENV_TB_PORT = "TB_PORT"                 # tensorboard task port
# train loop drops step metrics here; the executor push loop picks them up
ENV_TRAIN_METRICS_FILE = "TONY_TRAIN_METRICS_FILE"
ENV_LOCKTRACE = "TONY_LOCKTRACE"        # "1"/"true": traced control-plane locks (tony.debug.locktrace)
ENV_KILL_GRACE_MS = "TONY_KILL_GRACE_MS"  # SIGTERM→SIGKILL window for this container (tony.task.kill-grace-ms)
ENV_CHECKPOINT_DIR = "TONY_CHECKPOINT_DIR"            # from tony.checkpoint.dir
ENV_CHECKPOINT_INTERVAL = "TONY_CHECKPOINT_INTERVAL"  # from tony.checkpoint.interval-steps
ENV_CHAOS_SPEC = "TONY_CHAOS_SPEC"    # from tony.chaos.spec (child-process chaos contract)
ENV_CHAOS_SEED = "TONY_CHAOS_SEED"    # from tony.chaos.seed
# Tracing contract across process spawns (tony.trace.*, docs/observability.md):
# parents export these so the child's root span links under theirs
ENV_TRACE_ENABLED = "TONY_TRACE_ENABLED"  # "1" → tracing on in this process tree
ENV_TRACE_DIR = "TONY_TRACE_DIR"          # span JSONL sink dir (<staging>/trace)
ENV_TRACE_PARENT = "TONY_TRACE_PARENT"    # parent span id for this process's root span
# Start-up account (obs/startup.py, always on): the executor stamps the child's
# Popen on the .jhist's clock (epoch ms) so the child's own stamps start there
ENV_CHILD_SPAWNED_MS = "TONY_CHILD_SPAWNED_MS"
ENV_METRICS_ENABLED = "TONY_METRICS_ENABLED"  # "0" → child metrics recording off (tony.metrics.enabled)
# SLO contract (tony.slo.*): serve children align a TTFT histogram bucket
# edge to this threshold so good/bad request counts are exact, not
# interpolated (obs/slo.py)
ENV_SLO_TTFT_MS = "TONY_SLO_TTFT_MS"
# Structured-logging contract across process spawns (tony.log.*): the
# executor exports these so the training child's JSONL records land in the
# same <staging>/logs/ aggregate `tony logs` merges
ENV_LOG_DIR = "TONY_LOG_DIR"            # log JSONL sink dir (<staging>/logs)
ENV_LOG_LEVEL = "TONY_LOG_LEVEL"        # debug|info|warning|error|off
# Profiling contract across process spawns (tony.profile.* / tony.task.
# profile): the executor exports these for the training child's StepProfiler.
# They live here — not train/profiling.py — so the executor supervisor can
# export them without importing the train package (whose init pulls the
# trainer, and with it jax).
ENV_PROFILE_DIR = "TONY_PROFILE_DIR"                  # static-window artifact dir
ENV_PROFILE_START_STEP = "TONY_PROFILE_START_STEP"    # static window start
ENV_PROFILE_NUM_STEPS = "TONY_PROFILE_NUM_STEPS"      # static window length
# how often (at most) the on-demand control file is stat'ed, ms
ENV_PROFILE_POLL_MS = "TONY_PROFILE_POLL_MS"
# Input-pipeline contract (tony.train.*, docs/performance.md): lookahead
# depth for the overlapped batch assembly (0 = synchronous) and the minimum
# blocked-on-input stall that emits a train.input_wait span for the goodput
# ledger's input_wait phase.
ENV_PREFETCH_DEPTH = "TONY_PREFETCH_DEPTH"            # from tony.train.prefetch-depth
ENV_INPUT_WAIT_SPAN_MS = "TONY_INPUT_WAIT_SPAN_MS"    # from tony.train.input-wait-span-ms
ENV_NOTEBOOK_PORT = "NOTEBOOK_PORT"     # notebook task port (proxied by submitter)
# Hot-spare contract (tony.elastic.spares): set → this executor parks after
# register_spare and polls for a gang-slot assignment instead of joining as
# the (JOB_NAME, TASK_INDEX) identity it was nominally launched with
ENV_SPARE_ID = "TONY_SPARE_ID"

# ---------------------------------------------------------------------------
# Env-var contract: framework rendezvous (runtime adapters, SURVEY.md §2.2)
# ---------------------------------------------------------------------------
ENV_TF_CONFIG = "TF_CONFIG"
ENV_RANK = "RANK"
ENV_WORLD_SIZE = "WORLD_SIZE"
ENV_LOCAL_RANK = "LOCAL_RANK"
ENV_MASTER_ADDR = "MASTER_ADDR"
ENV_MASTER_PORT = "MASTER_PORT"
ENV_INIT_METHOD = "INIT_METHOD"
ENV_DMLC_ROLE = "DMLC_ROLE"
ENV_DMLC_PS_ROOT_URI = "DMLC_PS_ROOT_URI"
ENV_DMLC_PS_ROOT_PORT = "DMLC_PS_ROOT_PORT"
ENV_DMLC_NUM_SERVER = "DMLC_NUM_SERVER"
ENV_DMLC_NUM_WORKER = "DMLC_NUM_WORKER"
ENV_HOROVOD_CONTROLLER = "HOROVOD_CONTROLLER"
ENV_HOROVOD_CPU_OPERATIONS = "HOROVOD_CPU_OPERATIONS"
ENV_HOROVOD_GLOO_RENDEZVOUS_ADDR = "HOROVOD_GLOO_RENDEZVOUS_ADDR"
ENV_HOROVOD_GLOO_RENDEZVOUS_PORT = "HOROVOD_GLOO_RENDEZVOUS_PORT"
ENV_HOROVOD_RANK = "HOROVOD_RANK"
ENV_HOROVOD_SIZE = "HOROVOD_SIZE"
ENV_HOROVOD_LOCAL_RANK = "HOROVOD_LOCAL_RANK"
ENV_HOROVOD_LOCAL_SIZE = "HOROVOD_LOCAL_SIZE"
ENV_HOROVOD_CROSS_RANK = "HOROVOD_CROSS_RANK"
ENV_HOROVOD_CROSS_SIZE = "HOROVOD_CROSS_SIZE"

# ---------------------------------------------------------------------------
# Env-var contract: TPU-native additions (replace nvidia-smi / CUDA_VISIBLE_DEVICES)
# ---------------------------------------------------------------------------
ENV_JAX_COORDINATOR = "JAX_COORDINATOR_ADDRESS"   # host:port for jax.distributed
ENV_JAX_PROCESS_ID = "JAX_PROCESS_ID"
ENV_JAX_NUM_PROCESSES = "JAX_NUM_PROCESSES"
ENV_TPU_SLICE_NAME = "TPU_SLICE_NAME"             # e.g. "v5e-64"
ENV_TPU_SLICE_TOPOLOGY = "TPU_SLICE_TOPOLOGY"     # e.g. "8x8"
ENV_TPU_CHIP_COORDS = "TPU_CHIP_COORDS"           # this task's chip coords within slice, JSON
ENV_TPU_CHIPS_PER_TASK = "TPU_CHIPS_PER_TASK"
ENV_TPU_SLICE_ID = "TPU_SLICE_ID"                 # which pool slice this task landed on (0-based)
ENV_TPU_NUM_SLICES = "TPU_NUM_SLICES"             # slices in the pool (DCN groups for MeshSpec)

# ---------------------------------------------------------------------------
# Task types with built-in behavior (analog: Constants.java well-known job names)
# ---------------------------------------------------------------------------
CHIEF_JOB_NAME = "chief"
WORKER_JOB_NAME = "worker"
PS_JOB_NAME = "ps"
EVALUATOR_JOB_NAME = "evaluator"
TENSORBOARD_JOB_NAME = "tensorboard"
NOTEBOOK_JOB_NAME = "notebook"
SERVE_JOB_NAME = "serve"
# Disaggregated serving (docs/serving.md "Disaggregated serving"): the
# prefill tier runs as a SECOND jobtype of the same application — prompt
# processing there, token decode on the ``serve`` tier, KV pages handed off
# between them (serve/disagg.py).
PREFILL_JOB_NAME = "prefill"
DRIVER_JOB_NAME = "driver"

# Exit codes (analog of TonY's exit-code conventions)
EXIT_SUCCESS = 0
EXIT_FAILURE = 1
EXIT_AM_ERROR = 10
EXIT_EXECUTOR_REGISTRATION_FAILED = 11
EXIT_HEARTBEAT_LOST = 12
# the executor killed the user process at tony.task.execution-timeout-ms:
# distinct from EXIT_FAILURE so .jhist separates timeouts from user-code crashes
EXIT_EXECUTION_TIMEOUT = 13
EXIT_KILLED = 137
EXIT_NODE_LOST = -100   # container's host agent died (YARN ContainerExitStatus.ABORTED analog)
# pool preempted the container for a higher-priority app (the YARN
# ContainerExitStatus.PREEMPTED analog; not a job failure — excluded
# from restart budgets)
EXIT_PREEMPTED = -102
# a container ADOPTED across a work-preserving AM takeover died while the
# AM was away: it re-parented to init when the old AM was SIGKILLed, so its
# real exit status was reaped and is unknowable. Only the silent-death
# backstop — the executor's RPC result report (which rides out the takeover)
# is the authoritative record and lands first on every healthy exit.
EXIT_ADOPTED_UNKNOWN = -103

# Distributed-mode values
DISTRIBUTED_MODE_GANG = "GANG"
DISTRIBUTED_MODE_SINGLE_NODE = "SINGLE_NODE"


def default_tony_root() -> str:
    """Root directory for staging + history when not configured.

    (The reference stages to ``hdfs://.../.tony``; with no HDFS in a TPU-VM
    world we stage to a local/shared filesystem path.)
    """
    return os.environ.get("TONY_ROOT", os.path.join(os.path.expanduser("~"), TONY_STAGING_DIRNAME))
