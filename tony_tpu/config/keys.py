"""The ``tony.*`` configuration-key namespace, with defaults.

Analog of the reference's ``TonyConfigurationKeys.java`` plus
``tony-core/src/main/resources/tony-default.xml`` (SURVEY.md §2.1, §5.6):
every knob the framework reads is declared here, with its default, so the
config-completeness unit test (mirroring TestTonyConfigurationFields) can
assert the registry and the defaults artifact never drift apart.

Naming keeps the reference's dotted namespace (``tony.application.*``,
``tony.am.*``, ``tony.task.*``, per-job-type ``tony.<jobtype>.*``) so configs
look familiar; TPU-specific keys replace GPU/YARN ones (``tony.<type>.gpus`` →
``tony.<type>.chips`` / ``tony.<type>.slice``).
"""

from __future__ import annotations

# ---------------------------------------------------------------------------
# tony.application.* — job-level
# ---------------------------------------------------------------------------
APPLICATION_NAME = "tony.application.name"
APPLICATION_QUEUE = "tony.application.queue"
APPLICATION_PRIORITY = "tony.application.priority"  # int; higher runs first within a queue
# Elastic-downsize hysteresis: the pool's capacity must stay short for this
# long (continuously) before the AM applies a min-instances shrink — a node
# heartbeat blip coinciding with an unrelated restart must not permanently
# halve the gang. While waiting, the gang queues at full size and retries.
APPLICATION_DOWNSIZE_GRACE_MS = "tony.application.downsize-grace-ms"
APPLICATION_FRAMEWORK = "tony.application.framework"      # jax|tensorflow|pytorch|horovod|mxnet|generic
APPLICATION_UNTRACKED_TYPES = "tony.application.untracked.jobtypes"  # csv; don't gate job verdict
APPLICATION_NODE_LABEL = "tony.application.node-label"
APPLICATION_SECURITY_ENABLED = "tony.application.security.enabled"
APPLICATION_PREPARE_STAGE_TIMEOUT_MS = "tony.application.prepare-timeout-ms"
# dependency ordering: tony.application.dependency.<A>.timeout.after.<B> = ms
DEPENDENCY_PREFIX = "tony.application.dependency."
APPLICATION_TAGS = "tony.application.tags"

# ---------------------------------------------------------------------------
# tony.am.* — application master
# ---------------------------------------------------------------------------
AM_RETRY_COUNT = "tony.am.retry-count"
# Work-preserving AM restart (docs/fault-tolerance.md "Control-plane
# failures"): the AM journals its recoverable state (gang epoch, per-task
# registrations, container map, pending resizes, chaos progress) to
# <staging>/am_journal.jsonl, and a retried AM attempt replays it to ADOPT
# the live gang — executors ride out the outage on their missed-heartbeat
# budget and re-sync, the training children never stop. false restores the
# pre-takeover behavior: every AM retry is a full gang restart.
AM_TAKEOVER_ENABLED = "tony.am.takeover.enabled"
# Takeover-journal compaction, same contract as tony.pool.journal.compact-every:
# after this many appends the monitor loop folds the recoverable state into a
# snapshot record and rotates am_journal.jsonl. 0 (default) never compacts.
AM_JOURNAL_COMPACT_EVERY = "tony.am.journal.compact-every"
AM_RPC_PORT = "tony.am.rpc.port"                  # 0 = ephemeral
AM_GANG_TIMEOUT_MS = "tony.am.gang-timeout-ms"    # max wait for full gang registration
AM_MONITOR_INTERVAL_MS = "tony.am.monitor-interval-ms"
AM_MEMORY = "tony.am.memory"
AM_VCORES = "tony.am.vcores"

# ---------------------------------------------------------------------------
# tony.task.* — executor / liveness contract
# ---------------------------------------------------------------------------
TASK_HEARTBEAT_INTERVAL_MS = "tony.task.heartbeat-interval-ms"
TASK_MAX_MISSED_HEARTBEATS = "tony.task.max-missed-heartbeats"
TASK_METRICS_INTERVAL_MS = "tony.task.metrics-interval-ms"
TASK_EXECUTOR_REGISTRATION_TIMEOUT_MS = "tony.task.registration-timeout-ms"
TASK_EXECUTOR_EXECUTION_TIMEOUT_MS = "tony.task.execution-timeout-ms"  # 0 = unlimited
TASK_KILL_GRACE_MS = "tony.task.kill-grace-ms"     # SIGTERM→SIGKILL window (serve tasks drain here)
TASK_RESTART_ON_FAILURE = "tony.task.restart-on-failure"  # gang-restart-from-checkpoint
TASK_MAX_TOTAL_INSTANCE_FAILURES = "tony.task.max-total-instance-failures"
TASK_PROFILE = "tony.task.profile"                 # capture jax.profiler traces per worker
TASK_PROFILE_START_STEP = "tony.task.profile.start-step"
TASK_PROFILE_NUM_STEPS = "tony.task.profile.num-steps"

# ---------------------------------------------------------------------------
# Per-job-type parameterized keys: tony.<jobtype>.<suffix>
# (analog: tony.<jobtype>.{instances,memory,vcores,gpus}; gpus→chips/slice)
# ---------------------------------------------------------------------------
INSTANCES_SUFFIX = "instances"
MEMORY_SUFFIX = "memory"
VCORES_SUFFIX = "vcores"
CHIPS_SUFFIX = "chips"          # TPU chips per task (reference: gpus)
SLICE_SUFFIX = "slice"          # TPU slice spec per task gang, e.g. "v5e-8" or "2x4"
COMMAND_SUFFIX = "command"      # per-type command override (reference: tony.<type>.command)
# Elastic floor: on gang restart, if the pool's ALIVE capacity can no longer
# fit the configured gang (node permanently lost), the AM may re-plan this
# type down to min-instances and the workers restore the checkpoint onto the
# smaller mesh (data/fsdp-axis jobs — the global-order data replay keeps the
# sample stream exact). Absent/0 → the type never shrinks (default).
MIN_INSTANCES_SUFFIX = "min-instances"


def jobtype_key(jobtype: str, suffix: str) -> str:
    """`tony.<jobtype>.<suffix>` — per-type parameterized key."""
    return f"tony.{jobtype}.{suffix}"


def dependency_key(depender: str, dependee: str) -> str:
    """`tony.application.dependency.<A>.timeout.after.<B>` — A starts after B."""
    return f"{DEPENDENCY_PREFIX}{depender}.timeout.after.{dependee}"


# ---------------------------------------------------------------------------
# tony.docker.* — container image passthrough (reference parity)
# ---------------------------------------------------------------------------
DOCKER_ENABLED = "tony.docker.enabled"
DOCKER_IMAGE = "tony.docker.containers.image"
DOCKER_BINARY = "tony.docker.binary"  # docker CLI (tests substitute a fake)

# ---------------------------------------------------------------------------
# tony.keytab.* — security analog (no Kerberos here; shared-secret auth)
# ---------------------------------------------------------------------------
KEYTAB_USER = "tony.keytab.user"
KEYTAB_LOCATION = "tony.keytab.location"

# ---------------------------------------------------------------------------
# tony.tpu.* — TPU-native resource model (replaces GPU-on-YARN)
# ---------------------------------------------------------------------------
TPU_POOL_SPEC = "tony.tpu.pool"                 # RM inventory, e.g. "v5e-64" or "host:v5e,8x8"
TPU_POOL_SECRET = "tony.tpu.pool.secret"        # shared secret for a remote (rm:) pool service
TPU_ACCELERATOR_TYPE = "tony.tpu.accelerator-type"  # v5e | v5p | v4 | cpu
TPU_ICI_STRICT = "tony.tpu.ici-strict"          # never split a slice across DCN
TPU_CHIPS_PER_HOST = "tony.tpu.chips-per-host"

# ---------------------------------------------------------------------------
# tony.heartbeat.* — executor → AM heartbeat shaping (docs/performance.md
# "Control-plane scalability"): a thousand-executor gang whose supervisors
# all beat on the same whole-second boundary knocks the AM in lockstep;
# per-beat jitter spreads the fan-in. A stretched gap can span up to
# (1 + pct) of the AM's missed-heartbeat intervals, so the false-positive
# margin shrinks by up to pct intervals — keep pct well under
# tony.task.max-missed-heartbeats (trivial at the defaults: 0.25 vs 25).
# ---------------------------------------------------------------------------
HEARTBEAT_BACKOFF_ENABLED = "tony.heartbeat.backoff-enabled"
# Each beat waits interval * (1 + U[0, pct]) from a per-task seeded RNG —
# deterministic per identity, decorrelated across the gang.
HEARTBEAT_BACKOFF_JITTER_PCT = "tony.heartbeat.backoff-jitter-pct"

# ---------------------------------------------------------------------------
# tony.node.* — host-agent liveness (pool-service ↔ NodeAgent contract)
# ---------------------------------------------------------------------------
NODE_HEARTBEAT_INTERVAL_MS = "tony.node.heartbeat-interval-ms"
NODE_MAX_MISSED_HEARTBEATS = "tony.node.max-missed-heartbeats"

# ---------------------------------------------------------------------------
# tony.pool.* — pool-service multi-tenancy (capacity-queue analog, SURVEY §3.1)
# ---------------------------------------------------------------------------
POOL_QUEUES = "tony.pool.queues"                # "name=share,..." e.g. "prod=0.7,dev=0.3"
POOL_PREEMPTION_ENABLED = "tony.pool.preemption.enabled"
# Cross-queue reclaim grace: a waiting under-share head must wait this long
# before the scheduler evicts over-share borrowers from OTHER queues
# (same-queue priority preemption has no grace — it is an explicit ranking).
POOL_PREEMPTION_GRACE_MS = "tony.pool.preemption.grace-ms"
# Cooperative drain window (docs/scheduling.md): eviction becomes two-phase —
# the victim AM learns it is DRAINING through its poll path, triggers an
# urgent checkpoint, and yields; the pool escalates to the kill path only at
# this deadline. 0 (the default) keeps the classic immediate kill.
POOL_PREEMPTION_DRAIN_MS = "tony.pool.preemption.drain-ms"
# Anti-thrash guard: a just-admitted app is not evictable (or shrinkable)
# until it has run this long — evict→admit→evict ping-pong is structurally
# impossible. 0 disables the protection.
POOL_PREEMPTION_MIN_RUNTIME_MS = "tony.pool.preemption.min-runtime-ms"
# Anti-thrash guard: a queue may CAUSE at most this many evictions/shrinks
# per budget window; an exhausted aggressor's heads wait for free capacity
# like anyone else. 0 = unlimited.
POOL_PREEMPTION_BUDGET = "tony.pool.preemption.budget"
POOL_PREEMPTION_BUDGET_WINDOW_MS = "tony.pool.preemption.budget-window-ms"
# Pool-service recovery journal (docs/fault-tolerance.md "Control-plane
# failures"): app registrations/admissions/allocations are journaled here so
# a restarted pool rebuilds its queue state (admitted apps stay admitted,
# waiting apps keep their place) and re-adopts live containers from agent
# re-registration instead of forgetting every admitted app. Empty (the
# default) disables journaling — a restarted pool starts empty and agents
# kill the orphaned containers, the pre-journal behavior.
POOL_JOURNAL_FILE = "tony.pool.journal.file"
# Incremental journal compaction (docs/performance.md "Control-plane
# scalability"): after this many appended records the pool folds its live
# state into one durable snapshot record and rotates the file, so restart
# replay is O(live apps + containers), not O(everything that ever happened).
# 0 (the default) never compacts — the pre-compaction behavior exactly.
POOL_JOURNAL_COMPACT_EVERY = "tony.pool.journal.compact-every"
# Indexed scheduler pass (docs/performance.md "Scheduler pass"): the pool
# evaluates admission/preemption over an incrementally-maintained WorldIndex
# (heap heads, O(1) waiting counters, delta-fed claim aggregates) instead of
# rebuilding every view each pass — ~100x faster at 10k queued apps, with
# decision-trace equality to the reference pass property-tested and
# replayable via `tony sim --parity`. false restores the reference
# (full-rescan) implementation verbatim — the kill switch, not a semantic
# choice: both produce byte-identical decisions.
POOL_SCHEDULER_INDEXED = "tony.pool.scheduler.indexed"
# Scheduler flight recorder (docs/scheduling.md "Explaining decisions"): the
# pool keeps a bounded in-memory ring of DecisionRecords — every committed
# admit/evict/shrink plus each blocked queue head's binding rule — served by
# the `pool_explain` RPC and rendered by `tony explain <app_id|--queue Q>`.
# Per-queue telemetry (used/share/demand/wait-age/disruption counters) is
# sampled on the liveness tick into `tony_pool_queue_*` gauges and
# fixed-width windows. Provenance needs the indexed scheduler pass (the
# default); under the reference kill switch only pool-side records appear.
POOL_RECORDER_ENABLED = "tony.pool.recorder.enabled"
POOL_RECORDER_CAPACITY = "tony.pool.recorder.capacity"      # ring size, records
# telemetry aggregation window; each finalized window is one cluster_series row
POOL_RECORDER_WINDOW_MS = "tony.pool.recorder.window-ms"
# finalized windows append here as JSONL; the history server sweeps this file
# into the store's cluster_series table (empty disables the flush — the
# in-memory ring and gauges still work)
POOL_RECORDER_SERIES_FILE = "tony.pool.recorder.series-file"
# The capacity market (docs/scheduling.md "Capacity market"): admitted apps
# may publish unmet demand via the update_demand RPC; with preemption on,
# the pool funds it by shrinking over-share elastic borrowers (recorder
# rule demand-spike) and grows them back once demand ebbs (rule grow-back).
POOL_DEMAND_ENABLED = "tony.pool.demand.enabled"
# A published deficit whose publisher goes quiet expires after this long —
# a crashed spike must not keep taxing borrowers. 0 = never expire.
POOL_DEMAND_TTL_MS = "tony.pool.demand.ttl-ms"
# Grow-back hysteresis: ALL published demand must have been clear for this
# long before shed workers are offered back (spike→ebb→spike cannot thrash).
POOL_DEMAND_GROWBACK_EBB_MS = "tony.pool.demand.growback-ebb-ms"
# Max workers offered back per borrower per grow-back pass; 0 = all owed.
POOL_DEMAND_GROWBACK_STEP = "tony.pool.demand.growback-step"

# ---------------------------------------------------------------------------
# tony.history.* / tony.portal.* — events, history, portal, history server
# ---------------------------------------------------------------------------
HISTORY_LOCATION = "tony.history.location"
HISTORY_MOVE_INTERVAL_MS = "tony.history.move-interval-ms"
# Persistent history tier (docs/history.md): the `tony history-server`
# daemon ingests finalized jobs' artifacts into a SQLite store and serves a
# query API; `tony history ingest` is the inline one-shot path.
HISTORY_STORE = "tony.history.store"                # sqlite path; empty → <history>/history.sqlite
HISTORY_SERVER_PORT = "tony.history.server.port"    # daemon HTTP port (0 = ephemeral)
HISTORY_SCAN_INTERVAL_MS = "tony.history.scan-interval-ms"  # ingestion sweep cadence
# Retention window, days: store rows past it are purged each sweep, and
# `tony history gc` (or the daemon with gc enabled) removes ingested jobs'
# raw staging dirs past it. 0 (the default) keeps everything forever.
HISTORY_RETENTION_DAYS = "tony.history.retention-days"
# Series compaction: at most this many evenly-strided points are stored per
# (job, metric) series — bounds the store however long a job ran.
HISTORY_MAX_SERIES_POINTS = "tony.history.max-series-points"
# Let the DAEMON's sweep also GC raw staging dirs past retention (the CLI
# `tony history gc` works regardless). Never touches live/un-ingested jobs.
HISTORY_GC_ENABLED = "tony.history.gc.enabled"
# Cluster-series sources: comma-separated JSONL paths the sweep ingests into
# the store's cluster_series table (each line = one finalized per-queue
# telemetry window the pool wrote via tony.pool.recorder.series-file). The
# portal's /history capacity dashboards chart these across runs.
HISTORY_CLUSTER_SERIES = "tony.history.cluster-series"
PORTAL_PORT = "tony.portal.port"
# O(changed) portal scrape (docs/performance.md "Control-plane scalability"):
# a running AM's get_metrics result is cached and re-served for up to this
# long, re-scraped early only when the AM's am_info.json moved (takeover).
# Stale entries are exported with a `tony_portal_scrape_age_seconds` label so
# dashboards can see they are cached. 0 (the default) scrapes every AM on
# every exposition — the pre-cache behavior exactly.
PORTAL_SCRAPE_TTL_MS = "tony.portal.scrape-ttl-ms"

# ---------------------------------------------------------------------------
# tony.elastic.* — elastic training (docs/fault-tolerance.md)
# ---------------------------------------------------------------------------
# Which jobtype is the data-parallel axis the AM may resize live (shrink on
# preemption/capacity loss, grow/shrink on resize_jobtype). The workers of
# this type restore the checkpoint onto the resized mesh and the loader's
# global-order draw keeps the sample stream exact (keep the GLOBAL batch
# constant across sizes).
ELASTIC_JOBTYPE = "tony.elastic.jobtype"
# Shrink floor for the elastic jobtype; 0 (the default) disables elastic
# shrinking entirely (equivalent to leaving tony.<type>.min-instances unset).
ELASTIC_MIN_WORKERS = "tony.elastic.min-workers"
# Grow ceiling for resize_jobtype on the elastic jobtype; 0 = no ceiling
# beyond what the pool can place.
ELASTIC_MAX_WORKERS = "tony.elastic.max-workers"
# Preemption response: instead of re-queuing the FULL gang and waiting for
# the pool to give the capacity back, shrink the elastic jobtype to the
# largest divisor count the surviving workers can form (>= min-workers) and
# resume from the latest checkpoint immediately.
ELASTIC_SHRINK_ON_PREEMPT = "tony.elastic.shrink-on-preempt"
# Hot spares: keep this many pre-registered spare executors of the elastic
# jobtype parked next to the gang. A grow or preemption-replacement promotes
# a spare — skipping container allocation and executor startup — cutting the
# restart epoch from a full relaunch to a spec re-fence.
ELASTIC_SPARES = "tony.elastic.spares"

# ---------------------------------------------------------------------------
# tony.serve.* — replicated serving control plane (docs/serving.md)
# ---------------------------------------------------------------------------
# Replica autoscaling bounds for the ``serve`` jobtype. max-replicas > 0
# enables the autoscaler (runs next to the fleet router in the submitting
# `tony serve` process); min-replicas is its floor. Scaling drives the AM's
# elastic-resize path (``resize_jobtype`` RPC → session/scheduler rebuild),
# never a re-submission.
SERVE_MIN_REPLICAS = "tony.serve.min-replicas"
SERVE_MAX_REPLICAS = "tony.serve.max-replicas"
SERVE_AUTOSCALE_INTERVAL_MS = "tony.serve.autoscale-interval-ms"
# Scale-up triggers: mean engine admission-queue depth per healthy replica,
# or fleet slot utilization above the high watermark (whichever fires first,
# sustained for the up-hysteresis ticks).
SERVE_SCALE_UP_QUEUE_DEPTH = "tony.serve.scale-up-queue-depth"
SERVE_SCALE_UP_UTILIZATION = "tony.serve.scale-up-utilization"
# Scale-down trigger: empty queues AND fleet slot utilization below the low
# watermark, sustained for the down-hysteresis ticks (longer than up: adding
# capacity is cheap, a restart to remove it is not).
SERVE_SCALE_DOWN_UTILIZATION = "tony.serve.scale-down-utilization"
SERVE_SCALE_UP_TICKS = "tony.serve.scale-up-ticks"
SERVE_SCALE_DOWN_TICKS = "tony.serve.scale-down-ticks"
# Fleet router (the HTTP front door the submitter runs).
SERVE_ROUTER_PORT = "tony.serve.router.port"          # 0 = ephemeral
SERVE_ROUTER_RETRIES = "tony.serve.router.retries"    # failover attempts before waiting
# How long the router keeps retrying/waiting for a healthy replica before a
# request is answered 503 — sized to cover a whole-gang restart (replica
# relaunch + engine compile), so a replica crash is not client-visible.
SERVE_FAILOVER_DEADLINE_MS = "tony.serve.failover-deadline-ms"
# Hedging (non-streaming requests only): p>0 duplicates a request to a second
# replica once it outlives the p-th percentile of recent router latencies
# (floored at hedge-min-ms); first response wins. 0 disables.
SERVE_HEDGE_PERCENTILE = "tony.serve.hedge-percentile"
SERVE_HEDGE_MIN_MS = "tony.serve.hedge-min-ms"
# Active health checks against each replica's /stats endpoint.
SERVE_HEALTH_INTERVAL_MS = "tony.serve.health-interval-ms"
SERVE_HEALTH_FAIL_THRESHOLD = "tony.serve.health-fail-threshold"
# Session affinity (X-Tony-Session → replica pins, serve/sessions.py):
# idle pins expire after ttl-ms; the table is LRU-capped at max-sessions;
# prefix-span is how many leading prompt tokens the cross-session prefix
# hint fingerprints (match the engine's page_len so a hint implies at least
# one warm cache page; 0 disables hints).
SERVE_SESSION_TTL_MS = "tony.serve.session.ttl-ms"
SERVE_SESSION_MAX_SESSIONS = "tony.serve.session.max-sessions"
SERVE_SESSION_PREFIX_SPAN = "tony.serve.session.prefix-span"
# Drain-aware scale-down: before resize_jobtype removes the victim replica,
# the autoscaler asks it to drain (request_task_drain → DrainCourier) and
# waits up to this long for the ack before shrinking anyway.
SERVE_SCALE_DOWN_DRAIN_MS = "tony.serve.scale-down-drain-ms"
# ``tony loadtest`` defaults (serve/loadgen.py): open-loop session arrival
# rate (sessions/s), session count, turns per session, prompt-length mix
# ("len:weight,len:weight"), and generated tokens per turn.
SERVE_LOADTEST_RATE = "tony.serve.loadtest.rate"
SERVE_LOADTEST_SESSIONS = "tony.serve.loadtest.sessions"
SERVE_LOADTEST_TURNS = "tony.serve.loadtest.turns"
SERVE_LOADTEST_PROMPT_MIX = "tony.serve.loadtest.prompt-mix"
SERVE_LOADTEST_MAX_TOKENS = "tony.serve.loadtest.max-tokens"
SERVE_LOADTEST_STREAM = "tony.serve.loadtest.stream"
# Capacity market (serve side): when enabled, a serve AM whose allocation
# request sits pending (the autoscaler asked for replicas the pool cannot
# place) publishes the deficit to the pool via ``update_demand``; the pool's
# preemption policy may fund it by partially shrinking elastic training
# borrowers (see ``tony.pool.demand.*``). slo-ttft-ms is the serve-side p99
# time-to-first-token objective the live market e2e/loadtest verdict checks.
SERVE_MARKET_ENABLED = "tony.serve.market.enabled"
SERVE_MARKET_SLO_TTFT_MS = "tony.serve.market.slo-ttft-ms"
# Router tier sharding (serve/disagg.py RouterShardFront): N FleetRouter
# workers, each owning a consistent-hash shard of the session-pin space,
# behind one front (``tony serve --routers N``); prefix hints replicate
# between shards every gossip tick.
SERVE_ROUTERS = "tony.serve.routers"
SERVE_ROUTER_GOSSIP_INTERVAL_MS = "tony.serve.router.gossip-interval-ms"
# Disaggregated prefill/decode serving (serve/disagg.py): a second jobtype
# (``prefill``) runs the prompt phase and ships finished KV pages to the
# decode tier over the paged-KV handoff contract. prefill-replicas sizes the
# tier at submit; prefill-min/max-replicas bound its own autoscaler (max 0 =
# no autoscaling); handoff-timeout-ms bounds one prefill leg end-to-end.
SERVE_DISAGG_ENABLED = "tony.serve.disagg.enabled"
SERVE_DISAGG_PREFILL_REPLICAS = "tony.serve.disagg.prefill-replicas"
SERVE_DISAGG_PREFILL_MIN_REPLICAS = "tony.serve.disagg.prefill-min-replicas"
SERVE_DISAGG_PREFILL_MAX_REPLICAS = "tony.serve.disagg.prefill-max-replicas"
SERVE_DISAGG_HANDOFF_TIMEOUT_MS = "tony.serve.disagg.handoff-timeout-ms"
# Decode-tier memory-bound scaling: paged-KV occupancy (live/total pages)
# above which the autoscaler counts up-pressure even with idle slots. 0
# disables (dense fleets report occupancy 0).
SERVE_SCALE_UP_KV_OCCUPANCY = "tony.serve.scale-up-kv-occupancy"

# ---------------------------------------------------------------------------
# tony.cbench.* — control-plane benchmark sizes (`tony cbench`,
# docs/performance.md "Control-plane scalability"). These parameterize the
# five seeded in-process microbenchmarks; the checked-in CBENCH_r<N>.json
# rounds are produced at the full-scale defaults, tier-1 runs scaled down.
# ---------------------------------------------------------------------------
CBENCH_APPS = "tony.cbench.apps"                    # queued apps in the scheduler bench
CBENCH_QUEUES = "tony.cbench.queues"                # queues the apps spread over
CBENCH_EXECUTORS = "tony.cbench.executors"          # simulated executors in the heartbeat fan-in
CBENCH_HEARTBEAT_SECONDS = "tony.cbench.heartbeat-seconds"  # sustained-knock window per phase
CBENCH_JOURNAL_RECORDS = "tony.cbench.journal-records"      # pool-journal history length
CBENCH_JOURNAL_LIVE_APPS = "tony.cbench.journal-live-apps"  # live apps the replay must rebuild
CBENCH_HISTORY_JOBS = "tony.cbench.history-jobs"    # finalized fixture jobs the sweep ingests
CBENCH_PORTAL_AMS = "tony.cbench.portal-ams"        # registered AMs the portal scrapes
CBENCH_SEED = "tony.cbench.seed"                    # every benchmark draw is seeded from this

# ---- tony sim --from-history (cluster/replay.py, docs/scheduling.md
# "What-if capacity planning"): trace-driven replay of recorded history
SIM_REPLAY_DEFAULT_WORK_S = "tony.sim.replay.default-work-s"    # work for apps recorded waiting-only
SIM_REPLAY_HORIZON_S = "tony.sim.replay.horizon-s"              # virtual-seconds cap per replay
SIM_REPLAY_COOP_YIELD_S = "tony.sim.replay.coop-yield-s"        # cooperative victim yield latency
SIM_REPLAY_SHRINK_REBUILD_S = "tony.sim.replay.shrink-rebuild-s"  # elastic shed/rebuild latency

# ---------------------------------------------------------------------------
# tony.profile.* — ON-DEMAND profiler capture (docs/observability.md)
# ---------------------------------------------------------------------------
# `tony profile <app_id>` asks a RUNNING job's workers to capture a
# jax.profiler trace at the next step boundary — no resubmit, unlike the
# submit-time `tony.task.profile` window. These keys set the defaults the
# AM applies when the CLI omits the flags, and the contract knobs.
PROFILE_STEPS = "tony.profile.steps"            # default capture window (steps)
PROFILE_MEMORY = "tony.profile.memory"          # also save a device memory profile
# How often (at most) the training child stats the control file for a new
# capture request — the only recurring cost of the on-demand plane when idle.
PROFILE_POLL_INTERVAL_MS = "tony.profile.poll-interval-ms"

# ---------------------------------------------------------------------------
# tony.log.* — aggregated structured logging (docs/observability.md)
# ---------------------------------------------------------------------------
# Every job process (client, AM, executors, training children) appends JSONL
# records to <staging>/logs/<identity>.log.jsonl; `tony logs <app_id>` merges
# and tails them in timestamp order. Records below the level are never built.
LOG_LEVEL = "tony.log.level"                    # debug|info|warning|error|off
LOG_DIR = "tony.log.dir"                        # sink override; empty → <staging>/logs

# ---------------------------------------------------------------------------
# tony.chaos.* — deterministic fault injection (docs/fault-tolerance.md)
# ---------------------------------------------------------------------------
# Fault schedule, e.g. "rpc-drop:p=0.05;exec-crash:worker:1@gang_complete";
# empty (the default) disables every injection point. Grammar in
# tony_tpu/chaos/schedule.py.
CHAOS_SPEC = "tony.chaos.spec"
# Seed for the injection PRNGs: the same (spec, seed) pair reproduces the
# same injected-fault sequence exactly.
CHAOS_SEED = "tony.chaos.seed"

# ---------------------------------------------------------------------------
# tony.trace.* / tony.metrics.* — observability (docs/observability.md)
# ---------------------------------------------------------------------------
# Distributed tracing: one trace per job (trace_id = app_id), spans appended
# to <staging>/trace/<identity>.spans.jsonl per process, context propagated
# in-band through RPC frames and via TONY_TRACE_PARENT across process spawns.
# Disabled (the default) costs one None check per hook and allocates nothing.
TRACE_ENABLED = "tony.trace.enabled"
# Span sink directory override; empty → <staging>/trace
TRACE_DIR = "tony.trace.dir"
# Process-wide metrics registry (RPC latency histograms, retry/backoff
# counters, heartbeat RTT, queue wait, checkpoint durations, sampled train
# step time) — exposed at the portal's /metrics (Prometheus text) and the
# AM's get_metrics RPC. false turns every recording call into a no-op.
METRICS_ENABLED = "tony.metrics.enabled"
# Traced control-plane locks (obs/locktrace.py): record real acquisition
# order, hold times (tony_lock_hold_seconds), and contention for every lock
# the static lock-order graph models. Debug/test-only — false (the default)
# hands out plain threading locks, zero overhead and byte-identical
# behavior. Also settable via TONY_LOCKTRACE=1 before process start.
DEBUG_LOCKTRACE = "tony.debug.locktrace"

# ---------------------------------------------------------------------------
# tony.goodput.* — goodput accounting + straggler detection (docs/observability.md)
# ---------------------------------------------------------------------------
# The AM's goodput tick: classifies wall-time into phases (obs/goodput.py),
# feeds the straggler detector from the piggybacked per-task step-time
# histograms, and evaluates the tony.alerts.* rules. false turns the whole
# plane off (no tick, no events, no gauges).
GOODPUT_ENABLED = "tony.goodput.enabled"
GOODPUT_INTERVAL_MS = "tony.goodput.interval-ms"      # tick cadence
# Trailing window the LIVE goodput value (alert input, tony top header) is
# computed over — cumulative goodput can never recover from one early stall;
# a windowed value resolves once the job is productive again.
GOODPUT_WINDOW_MS = "tony.goodput.window-ms"
# A rank is a straggler when its step time stays >= factor × the gang median
# for `checks` consecutive goodput ticks (needs >= 3 reporting ranks).
GOODPUT_STRAGGLER_FACTOR = "tony.goodput.straggler-factor"
GOODPUT_STRAGGLER_CHECKS = "tony.goodput.straggler-checks"

# ---------------------------------------------------------------------------
# tony.alerts.* — declarative alert rules (obs/alerts.py; empty = disabled)
# ---------------------------------------------------------------------------
ALERTS_GOODPUT_FLOOR = "tony.alerts.goodput-floor"        # fires while windowed goodput < this
ALERTS_STEP_TIME_P99_MS = "tony.alerts.step-time-p99-ms"  # fires while step-time p99 > this
ALERTS_HEARTBEAT_AGE_MS = "tony.alerts.heartbeat-age-ms"  # fires while any task heartbeat older
ALERTS_QUEUE_DEPTH = "tony.alerts.queue-depth"            # fires while any serve queue deeper
ALERTS_SINK = "tony.alerts.sink"        # transition JSONL; empty → <staging>/alerts.jsonl
ALERTS_WEBHOOK = "tony.alerts.webhook"  # optional URL POSTed each transition

# ---------------------------------------------------------------------------
# tony.slo.* — declarative SLO objectives + error budgets (obs/slo.py,
# docs/observability.md "SLOs & error budgets"). An objective is active when
# its target is non-empty (mirrors tony.alerts.*); the AM's goodput tick
# feeds the budget ledgers and compiles the burn-rate rules into the alert
# engine (SLO_BURN_ALERT/SLO_BURN_RESOLVED events, tony_slo_* gauges).
# ---------------------------------------------------------------------------
SLO_WINDOW_MS = "tony.slo.window-ms"    # compliance window the budget spans
SLO_BUCKET_MS = "tony.slo.bucket-ms"    # ledger bucket width (accounting grain)
# serve-ttft: fraction of requests whose TTFT lands under threshold-ms.
# Empty threshold inherits tony.serve.market.slo-ttft-ms so the market's
# defended number and the measured objective can't drift apart.
SLO_SERVE_TTFT_TARGET = "tony.slo.serve-ttft-target"
SLO_SERVE_TTFT_THRESHOLD_MS = "tony.slo.serve-ttft-threshold-ms"
# serve-availability: fraction of requests answered without server error.
SLO_SERVE_AVAILABILITY_TARGET = "tony.slo.serve-availability-target"
# train-goodput: windowed goodput fraction floor (per queue, from the ledger).
SLO_TRAIN_GOODPUT_TARGET = "tony.slo.train-goodput-target"
# Multi-window multi-burn-rate alerting (SRE workbook shape): the fast rule
# pages when the short-window burn rate exceeds fast-burn (budget gone in
# hours), the slow rule warns on sustained slow leaks.
SLO_FAST_BURN = "tony.slo.fast-burn"
SLO_FAST_WINDOW_MS = "tony.slo.fast-window-ms"
SLO_SLOW_BURN = "tony.slo.slow-burn"
SLO_SLOW_WINDOW_MS = "tony.slo.slow-window-ms"
SLO_SINK = "tony.slo.sink"  # budget-window JSONL; empty → <staging>/<app>/slo.jsonl

# ---------------------------------------------------------------------------
# tony.train.* — step-path knobs of the framework train loop (docs/performance.md)
# ---------------------------------------------------------------------------
# Input-pipeline lookahead: batch N+1 is assembled (loader read / synthetic
# draw + host-to-device transfer) on a background thread while the device
# runs step N (train/input_pipeline.py). 0 restores synchronous per-step
# assembly; >2 rarely helps (the queue only hides assembly jitter).
TRAIN_PREFETCH_DEPTH = "tony.train.prefetch-depth"
# A step-loop stall on the input pipeline at or above this emits a
# train.input_wait span, so the goodput ledger's input_wait phase charges it
# precisely; sub-floor waits stay inside productive (they are noise).
TRAIN_INPUT_WAIT_SPAN_MS = "tony.train.input-wait-span-ms"

# ---------------------------------------------------------------------------
# tony.checkpoint.* — gang-restart-from-checkpoint (rebuild-only; SURVEY §5.3/5.4)
# ---------------------------------------------------------------------------
CHECKPOINT_DIR = "tony.checkpoint.dir"
CHECKPOINT_INTERVAL_STEPS = "tony.checkpoint.interval-steps"
CHECKPOINT_MAX_TO_KEEP = "tony.checkpoint.max-to-keep"
CHECKPOINT_ASYNC = "tony.checkpoint.async"

# ---------------------------------------------------------------------------
# Submission-time keys filled by client (paths, venv, shell env)
# ---------------------------------------------------------------------------
EXECUTES = "tony.submit.executes"               # user training command
SRC_DIR = "tony.submit.src-dir"
PYTHON_BINARY_PATH = "tony.submit.python-binary-path"
PYTHON_VENV = "tony.submit.python-venv"
SHELL_ENV = "tony.submit.shell-env"             # csv k=v extra env
STAGING_ROOT = "tony.submit.staging-root"

# ---------------------------------------------------------------------------
# Defaults — the tony-default.xml analog. Single source of truth.
# ---------------------------------------------------------------------------
DEFAULTS: dict[str, str] = {
    APPLICATION_NAME: "tony-tpu-app",
    APPLICATION_QUEUE: "default",
    APPLICATION_PRIORITY: "0",
    APPLICATION_DOWNSIZE_GRACE_MS: "10s",
    APPLICATION_FRAMEWORK: "jax",
    APPLICATION_UNTRACKED_TYPES: "ps,tensorboard,notebook",
    APPLICATION_NODE_LABEL: "",
    APPLICATION_SECURITY_ENABLED: "true",
    APPLICATION_PREPARE_STAGE_TIMEOUT_MS: "60000",
    APPLICATION_TAGS: "",

    AM_RETRY_COUNT: "0",
    AM_TAKEOVER_ENABLED: "true",
    AM_JOURNAL_COMPACT_EVERY: "0",
    AM_RPC_PORT: "0",
    AM_GANG_TIMEOUT_MS: "300000",
    AM_MONITOR_INTERVAL_MS: "200",
    AM_MEMORY: "2g",
    AM_VCORES: "1",

    TASK_HEARTBEAT_INTERVAL_MS: "1000",
    TASK_MAX_MISSED_HEARTBEATS: "25",
    TASK_METRICS_INTERVAL_MS: "5000",
    TASK_EXECUTOR_REGISTRATION_TIMEOUT_MS: "60000",
    TASK_EXECUTOR_EXECUTION_TIMEOUT_MS: "0",
    TASK_KILL_GRACE_MS: "3000",
    TASK_RESTART_ON_FAILURE: "false",
    TASK_MAX_TOTAL_INSTANCE_FAILURES: "3",  # only consulted when restart-on-failure
    TASK_PROFILE: "false",
    TASK_PROFILE_START_STEP: "3",
    TASK_PROFILE_NUM_STEPS: "5",

    DOCKER_ENABLED: "false",
    DOCKER_IMAGE: "",
    DOCKER_BINARY: "docker",

    KEYTAB_USER: "",
    KEYTAB_LOCATION: "",

    TPU_POOL_SPEC: "local:cpu,1x1",
    TPU_POOL_SECRET: "",
    TPU_ACCELERATOR_TYPE: "cpu",
    TPU_ICI_STRICT: "true",
    TPU_CHIPS_PER_HOST: "4",

    HEARTBEAT_BACKOFF_ENABLED: "false",
    HEARTBEAT_BACKOFF_JITTER_PCT: "0.25",

    NODE_HEARTBEAT_INTERVAL_MS: "1000",
    NODE_MAX_MISSED_HEARTBEATS: "10",

    POOL_QUEUES: "default=1.0",
    POOL_PREEMPTION_ENABLED: "false",
    POOL_PREEMPTION_GRACE_MS: "0",
    POOL_PREEMPTION_DRAIN_MS: "0",
    POOL_PREEMPTION_MIN_RUNTIME_MS: "0",
    POOL_PREEMPTION_BUDGET: "0",
    POOL_PREEMPTION_BUDGET_WINDOW_MS: "60s",
    POOL_JOURNAL_FILE: "",
    POOL_JOURNAL_COMPACT_EVERY: "0",
    POOL_SCHEDULER_INDEXED: "true",
    POOL_RECORDER_ENABLED: "true",
    POOL_RECORDER_CAPACITY: "2048",
    POOL_RECORDER_WINDOW_MS: "60s",
    POOL_RECORDER_SERIES_FILE: "",
    POOL_DEMAND_ENABLED: "true",
    POOL_DEMAND_TTL_MS: "60s",
    POOL_DEMAND_GROWBACK_EBB_MS: "30s",
    POOL_DEMAND_GROWBACK_STEP: "0",

    HISTORY_LOCATION: "",            # empty → <staging-root>/history
    HISTORY_MOVE_INTERVAL_MS: "1000",
    HISTORY_STORE: "",               # empty → <history>/history.sqlite
    HISTORY_SERVER_PORT: "28081",
    HISTORY_SCAN_INTERVAL_MS: "2000",
    HISTORY_RETENTION_DAYS: "0",
    HISTORY_MAX_SERIES_POINTS: "512",
    HISTORY_GC_ENABLED: "false",
    HISTORY_CLUSTER_SERIES: "",
    PORTAL_PORT: "28080",
    PORTAL_SCRAPE_TTL_MS: "0",

    ELASTIC_JOBTYPE: "worker",
    ELASTIC_MIN_WORKERS: "0",
    ELASTIC_MAX_WORKERS: "0",
    ELASTIC_SHRINK_ON_PREEMPT: "false",
    ELASTIC_SPARES: "0",

    SERVE_MIN_REPLICAS: "0",
    SERVE_MAX_REPLICAS: "0",
    SERVE_AUTOSCALE_INTERVAL_MS: "5000",
    SERVE_SCALE_UP_QUEUE_DEPTH: "4",
    SERVE_SCALE_UP_UTILIZATION: "0.85",
    SERVE_SCALE_DOWN_UTILIZATION: "0.25",
    SERVE_SCALE_UP_TICKS: "2",
    SERVE_SCALE_DOWN_TICKS: "6",
    SERVE_ROUTER_PORT: "0",
    SERVE_ROUTER_RETRIES: "3",
    SERVE_FAILOVER_DEADLINE_MS: "120000",
    SERVE_HEDGE_PERCENTILE: "0",
    SERVE_HEDGE_MIN_MS: "50",
    SERVE_HEALTH_INTERVAL_MS: "1000",
    SERVE_HEALTH_FAIL_THRESHOLD: "3",
    SERVE_SESSION_TTL_MS: "600000",
    SERVE_SESSION_MAX_SESSIONS: "10000",
    SERVE_SESSION_PREFIX_SPAN: "256",
    SERVE_SCALE_DOWN_DRAIN_MS: "10000",
    SERVE_LOADTEST_RATE: "4",
    SERVE_LOADTEST_SESSIONS: "16",
    SERVE_LOADTEST_TURNS: "3",
    SERVE_LOADTEST_PROMPT_MIX: "16:0.5,64:0.3,256:0.2",
    SERVE_LOADTEST_MAX_TOKENS: "16",
    SERVE_LOADTEST_STREAM: "true",
    SERVE_MARKET_ENABLED: "false",
    SERVE_MARKET_SLO_TTFT_MS: "2000",
    SERVE_ROUTERS: "1",
    SERVE_ROUTER_GOSSIP_INTERVAL_MS: "2000",
    SERVE_DISAGG_ENABLED: "false",
    SERVE_DISAGG_PREFILL_REPLICAS: "1",
    SERVE_DISAGG_PREFILL_MIN_REPLICAS: "0",
    SERVE_DISAGG_PREFILL_MAX_REPLICAS: "0",
    SERVE_DISAGG_HANDOFF_TIMEOUT_MS: "30000",
    SERVE_SCALE_UP_KV_OCCUPANCY: "0",

    CBENCH_APPS: "10000",
    CBENCH_QUEUES: "8",
    CBENCH_EXECUTORS: "1000",
    CBENCH_HEARTBEAT_SECONDS: "5",
    CBENCH_JOURNAL_RECORDS: "100000",
    CBENCH_JOURNAL_LIVE_APPS: "200",
    CBENCH_HISTORY_JOBS: "10000",
    CBENCH_PORTAL_AMS: "500",
    CBENCH_SEED: "0",
    SIM_REPLAY_DEFAULT_WORK_S: "30",
    SIM_REPLAY_HORIZON_S: "10000000",
    SIM_REPLAY_COOP_YIELD_S: "1.0",
    SIM_REPLAY_SHRINK_REBUILD_S: "2.0",

    PROFILE_STEPS: "5",
    PROFILE_MEMORY: "false",
    PROFILE_POLL_INTERVAL_MS: "500",

    LOG_LEVEL: "info",
    LOG_DIR: "",                     # empty → <staging>/logs

    CHAOS_SPEC: "",
    CHAOS_SEED: "0",

    TRACE_ENABLED: "false",
    TRACE_DIR: "",                   # empty → <staging>/trace
    METRICS_ENABLED: "true",
    DEBUG_LOCKTRACE: "false",

    GOODPUT_ENABLED: "true",
    GOODPUT_INTERVAL_MS: "5000",
    GOODPUT_WINDOW_MS: "60000",
    GOODPUT_STRAGGLER_FACTOR: "1.5",
    GOODPUT_STRAGGLER_CHECKS: "3",

    ALERTS_GOODPUT_FLOOR: "",
    ALERTS_STEP_TIME_P99_MS: "",
    ALERTS_HEARTBEAT_AGE_MS: "",
    ALERTS_QUEUE_DEPTH: "",
    ALERTS_SINK: "",
    ALERTS_WEBHOOK: "",

    SLO_WINDOW_MS: "3600000",
    SLO_BUCKET_MS: "5000",
    SLO_SERVE_TTFT_TARGET: "",
    SLO_SERVE_TTFT_THRESHOLD_MS: "",  # empty → tony.serve.market.slo-ttft-ms
    SLO_SERVE_AVAILABILITY_TARGET: "",
    SLO_TRAIN_GOODPUT_TARGET: "",
    SLO_FAST_BURN: "14.4",
    SLO_FAST_WINDOW_MS: "300000",
    SLO_SLOW_BURN: "6.0",
    SLO_SLOW_WINDOW_MS: "1800000",
    SLO_SINK: "",

    TRAIN_PREFETCH_DEPTH: "2",
    TRAIN_INPUT_WAIT_SPAN_MS: "25",

    CHECKPOINT_DIR: "",
    CHECKPOINT_INTERVAL_STEPS: "0",
    CHECKPOINT_MAX_TO_KEEP: "3",
    CHECKPOINT_ASYNC: "true",

    EXECUTES: "",
    SRC_DIR: "",
    PYTHON_BINARY_PATH: "",
    PYTHON_VENV: "",
    SHELL_ENV: "",
    STAGING_ROOT: "",                # empty → constants.default_tony_root()
}

# Known per-jobtype suffixes, for validation + docs.
JOBTYPE_SUFFIXES = (
    INSTANCES_SUFFIX,
    MEMORY_SUFFIX,
    VCORES_SUFFIX,
    CHIPS_SUFFIX,
    SLICE_SUFFIX,
    COMMAND_SUFFIX,
    MIN_INSTANCES_SUFFIX,
)


def all_known_keys() -> frozenset[str]:
    """Every fixed (non-parameterized) key declared in this module."""
    return frozenset(
        v
        for k, v in globals().items()
        if isinstance(v, str)
        and k.isupper()
        and v.startswith("tony.")
        and not k.endswith("_PREFIX")  # key-family prefixes are parameterized, not fixed keys
    )
