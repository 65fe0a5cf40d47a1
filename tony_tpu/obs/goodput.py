"""Goodput/badput accounting: classify every second of a job's wall-time.

The operator's first question about a job on a shared cluster is not "did it
succeed" but "what fraction of its wall-clock was *productive*, and where did
the rest go?" (PAPER.md §0 — accountability is TonY's whole premise; ROADMAP
item 2 needs the answer to aim the MFU work). This module turns the artifacts
the repo already emits — the ``.jhist`` event stream (cluster/events.py) and
the span JSONL trace (obs/trace.py), both resolved through obs/artifacts.py —
into an **exact partition** of ``[t0, t1]`` into phases:

==================  =========================================================
``queue_wait``      queued behind other tenants (QUEUE_WAIT episodes)
``submit``          the client's hop: ``submitted`` (Client.submit, before
                    staging; carried in APPLICATION_INITED's payload) → the
                    AM's APPLICATION_INITED. ``t0`` is ``submitted`` when the
                    payload has it
``startup``         container allocation + executor launch, per gang epoch
``registration``    the gang registration barrier (first TASK_REGISTERED →
                    GANG_COMPLETE)
``runtime_init``    GANG_COMPLETE → the child's ``devices_ready``: the
                    executor's barrier poll and spawn, the interpreter, the
                    imports, the PJRT client and the TPU's initialisation
``weights``         ``devices_ready`` → ``weights_ready``: weights drawn or
                    restored (a server's caches and page pool too), blocked on
``compile``         a training task's ``weights_ready`` → ``first_step_done``:
                    tracing, lowering, the backend's compile or the cache's
                    read, and the first step's execution. Only a ``.jhist``
                    whose gang epoch carries no stamps falls back to the
                    estimate [GANG_COMPLETE, train.first_step span's end when
                    traced, else the first METRICS_SNAPSHOT with a step >= 1]
``warmup``          a serving task's ``weights_ready`` → ``ready`` (the AM's
                    TASK_URL_REGISTERED): the HTTP server bound and the URL
                    registered. ``productive`` starts at ``ready`` for a
                    serving gang, not at GANG_COMPLETE
``productive``      steps actually advancing the job — THE goodput
``checkpoint``      checkpoint save work on the step path (ckpt.save spans)
``input_wait``      step loop blocked on the input pipeline
                    (train.input_wait spans, train/input_pipeline.py)
``restart_rework``  work the job had already done and lost to a restart:
                    the time between the last checkpointed step and the
                    failure, re-derived from the step reports of adjacent
                    gang epochs (the resumed epoch's first step says where
                    the checkpoint was)
``resize``          elastic-resize episodes (GANG_RESIZED → the resized
                    gang's GANG_COMPLETE)
``takeover``        AM journal replay + gang adoption (am.takeover spans)
``drain``           teardown after the last task finished
``other``           anything unattributable (history gaps, torn streams)
==================  =========================================================

Exactness is by construction: claims derived from events/spans are laid over
the integer-millisecond timeline, each elementary interval is assigned to the
single highest-priority covering claim (``productive`` is the filler inside a
live gang window, ``other`` outside), and the phase totals therefore sum to
``t1 - t0`` to the millisecond — property-tested over randomized histories in
tests/test_goodput.py.

The stages ``runtime_init`` / ``weights`` / ``compile`` / ``warmup`` are
claimed from stamps the chip-holding child takes itself (obs/startup.py), which
reach the ``.jhist`` as TASK_STARTUP_STAMPS events whether or not tracing is
on, a gang epoch and task at a time. A stage whose closing stamp was never
taken runs to the end of its epoch (the child is still in it, or died in it); a
stage whose own edge is missing while a later one is there is nobody's.

Also here: :class:`StragglerDetector` — per-task step-time skew from the
piggybacked ``tony_train_step_seconds`` histograms, flagging ranks whose step
time persistently exceeds the gang median — used by the AM's goodput tick
(cluster/appmaster.py) and fed to ``tony top`` / the portal. The alert-rule
engine that consumes both lives in obs/alerts.py.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Iterable, Mapping

#: phase names in display order; ``productive`` is the goodput, the rest is
#: the badput breakdown
PHASE_ORDER = (
    "productive", "queue_wait", "submit", "startup", "registration",
    "runtime_init", "weights", "compile", "warmup",
    "checkpoint", "input_wait", "restart_rework", "preempt_drain", "resize",
    "takeover", "drain", "other",
)

#: claim priorities: when claims overlap, the highest wins for that instant.
#: takeover/checkpoint/rework are narrow and precise; startup/productive are
#: wide fillers that yield to everything more specific.
_PRIORITY = {
    "takeover": 90,
    "checkpoint": 80,
    # step loop blocked on the input pipeline (train.input_wait spans,
    # train/input_pipeline.py): narrow precise claims like checkpoint —
    # inside a live gang window, badput the operator tunes with
    # tony.train.prefetch-depth rather than "productive" dilution
    "input_wait": 75,
    "restart_rework": 70,
    # cooperative-preemption drain window (PREEMPTION_REQUESTED → YIELDED/
    # ESCALATED): wider than the urgent ckpt.save inside it (which wins),
    # narrower than rework — the window is real badput the operator tunes
    # with tony.pool.preemption.drain-ms, not "other"
    "preempt_drain": 65,
    "queue_wait": 60,
    # the start-up stages, stamped by the processes that do the work: narrow
    # and precise, so above the wide startup / registration / productive
    # claims they lie in. `submit` lies before the AM's first event, where
    # only startup's claim from t0 reaches. Among a gang's tasks the earlier
    # stage wins: the gang is as far as its slowest member
    "submit": 58,
    "runtime_init": 56,
    "weights": 54,
    "warmup": 52,
    "compile": 50,
    "registration": 45,
    "resize": 40,
    "startup": 30,
    "drain": 20,
    "productive": 10,
}


@dataclass
class Ledger:
    """The exact phase partition of one job's wall-time (all times int ms)."""

    app_id: str
    t0_ms: int
    t1_ms: int
    live: bool                                   # t1 is "now", not a verdict
    phases_ms: dict[str, int]                    # phase → total milliseconds
    episodes: list[tuple[str, int, int]]         # merged (phase, start, end)
    restarts: int = 0
    resizes: int = 0
    takeovers: int = 0
    step_time_by_task_ms: dict[str, float] = field(default_factory=dict)
    #: the stamps the start-up stages were claimed from: ``client`` →
    #: ``{submitted}``, ``<task>@<epoch's GANG_COMPLETE ms>`` → the child's
    #: stamps (+ ``ready``). Empty for a ``.jhist`` that carries none
    stamps: dict[str, dict[str, int]] = field(default_factory=dict)

    @property
    def wall_ms(self) -> int:
        return max(self.t1_ms - self.t0_ms, 0)

    @property
    def goodput_fraction(self) -> float:
        """productive / wall — THE goodput number."""
        return (self.phases_ms.get("productive", 0) / self.wall_ms
                if self.wall_ms > 0 else 0.0)

    def badput_ms(self) -> dict[str, int]:
        """Non-productive phases with non-zero time, largest first."""
        items = [(p, ms) for p, ms in self.phases_ms.items()
                 if p != "productive" and ms > 0]
        return dict(sorted(items, key=lambda kv: -kv[1]))

    def disruption_fraction(self, phases: tuple[str, ...] = (
            "restart_rework", "preempt_drain", "resize")) -> float:
        """Fraction of wall-time lost to the named disruption phases — the
        capacity-market verdict number: a borrower repeatedly shed and
        regrown pays exactly these (drain windows, restart/resize rebuilds,
        replayed work), so the market e2e bounds this fraction to prove the
        spike's funding did not churn the training gang to death."""
        if self.wall_ms <= 0:
            return 0.0
        return sum(self.phases_ms.get(p, 0) for p in phases) / self.wall_ms

    def window_fraction(self, window_ms: int) -> float:
        """Goodput over the trailing ``window_ms`` (clipped to the job) —
        the value live alert rules evaluate: a cumulative fraction can never
        resolve after one early stall, a windowed one recovers."""
        lo = max(self.t1_ms - int(window_ms), self.t0_ms)
        span = self.t1_ms - lo
        if span <= 0:
            return 0.0
        good = sum(
            min(e, self.t1_ms) - max(s, lo)
            for ph, s, e in self.episodes
            if ph == "productive" and e > lo and s < self.t1_ms
        )
        return max(good, 0) / span

    def skew_by_task(self) -> dict[str, float]:
        """Per-task step-time / gang-median ratio (finalized-job analog of
        the live :class:`StragglerDetector` view)."""
        times = self.step_time_by_task_ms
        if not times:
            return {}
        med = _median(list(times.values()))
        if med <= 0:
            return {}
        return {t: v / med for t, v in sorted(times.items())}

    def to_dict(self) -> dict[str, Any]:
        return {
            "app_id": self.app_id,
            "t0_ms": self.t0_ms,
            "t1_ms": self.t1_ms,
            "live": self.live,
            "wall_ms": self.wall_ms,
            "goodput_fraction": self.goodput_fraction,
            "phases_ms": dict(self.phases_ms),
            "restarts": self.restarts,
            "resizes": self.resizes,
            "takeovers": self.takeovers,
            "step_time_by_task_ms": dict(self.step_time_by_task_ms),
            "skew_by_task": self.skew_by_task(),
            "stamps": {k: dict(v) for k, v in self.stamps.items()},
        }


def _median(vals: list[float]) -> float:
    if not vals:
        return 0.0
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2.0


def _ev_type(ev: Any) -> str:
    return ev.type.value


def _is_restart_marker(ev: Any) -> bool:
    """HEARTBEAT_LOST doubles as the task-lost event and the gang-restart
    announcement; the restart spelling carries reason='gang restart: …'."""
    return (_ev_type(ev) == "HEARTBEAT_LOST"
            and str(ev.payload.get("reason", "")).startswith("gang restart"))


def _snapshot_steps(ev: Any) -> dict[str, int]:
    """task → train step from one METRICS_SNAPSHOT event."""
    out: dict[str, int] = {}
    for entry in ev.payload.get("tasks", []):
        step = ((entry.get("metrics") or {}).get("train") or {}).get("step")
        if isinstance(step, (int, float)) and math.isfinite(step):
            out[str(entry.get("task", "?"))] = int(step)
    return out


def _stamp_ms(v: Any) -> int | None:
    """A stamp as whole epoch milliseconds, or None for anything else (a torn
    or hand-edited payload must not break the partition)."""
    if isinstance(v, bool) or not isinstance(v, (int, float)) or not math.isfinite(v):
        return None
    return int(v)


def _span_ms(s: Mapping[str, Any]) -> tuple[int, int]:
    start = int(round(float(s.get("start_ms", 0.0))))
    end = int(round(float(s.get("end_ms", start))))
    return start, max(end, start)


def flagged_stragglers(events: Iterable[Any]) -> list[str]:
    """Ranks whose LAST straggler transition in the event stream is
    ``STRAGGLER_DETECTED`` — the finalized-job answer to "who was dragging
    the gang at the end". Order matters: a rank can resolve across a gang
    restart (its stats vanish) and be re-detected afterwards."""
    state: dict[str, bool] = {}
    for ev in events:
        t = _ev_type(ev)
        if t == "STRAGGLER_DETECTED":
            state[str(ev.payload.get("task"))] = True
        elif t == "STRAGGLER_RESOLVED":
            state[str(ev.payload.get("task"))] = False
    return sorted(task for task, flagged in state.items() if flagged)


def step_time_by_task(events: Iterable[Any]) -> dict[str, float]:
    """Mean per-task step wall time (ms) from METRICS_SNAPSHOT deltas — the
    finalized-job source for per-rank skew (`tony goodput`), mirroring the
    derived ``step_time_ms`` series the history ingester distills."""
    last: dict[str, tuple[int, int]] = {}            # task → (step, ts)
    total: dict[str, float] = {}
    count: dict[str, int] = {}
    for ev in events:
        if _ev_type(ev) == "GANG_COMPLETE":
            # epoch boundary: a delta straddling a restart/resize would
            # charge the whole outage gap to whichever ranks' step counts
            # happened to increase across it
            last.clear()
            continue
        if _ev_type(ev) != "METRICS_SNAPSHOT":
            continue
        for task, step in _snapshot_steps(ev).items():
            prev = last.get(task)
            if prev is not None and step > prev[0] and ev.timestamp_ms > prev[1]:
                total[task] = total.get(task, 0.0) + (ev.timestamp_ms - prev[1])
                count[task] = count.get(task, 0) + (step - prev[0])
            last[task] = (step, ev.timestamp_ms)
    return {t: total[t] / count[t] for t in total if count.get(t)}


def build_ledger(
    app_id: str,
    events: list[Any],
    spans: list[Mapping[str, Any]] | None = None,
    now_ms: int | None = None,
) -> Ledger:
    """The exact phase partition for one job from its event stream (+ spans
    when the job was traced). The start-up stages come from the stamps the
    stream carries (module docstring); the untraced ``compile`` estimate (the
    first METRICS_SNAPSHOT with a step >= 1) is only the fallback for a gang
    epoch without stamps.

    ``events`` is the (possibly torn-truncated) ``.jhist`` stream in file
    order; ``spans`` the merged span dicts (obs/artifacts.load_spans). A job
    without an APPLICATION_FINISHED event is treated as live and accounted
    up to ``now_ms`` (required then).
    """
    spans = spans or []
    if not events:
        now = int(now_ms or 0)
        return Ledger(app_id, now, now, live=True, phases_ms={}, episodes=[])

    t0 = min(ev.timestamp_ms for ev in events)
    stamps: dict[str, dict[str, int]] = {}
    inited = next((ev for ev in events if _ev_type(ev) == "APPLICATION_INITED"), None)
    submitted = _stamp_ms((inited.payload if inited else {}).get("submitted_ms"))
    if submitted is not None:
        stamps["client"] = {"submitted": submitted}
        t0 = min(t0, submitted)
    finished = [ev for ev in events if _ev_type(ev) == "APPLICATION_FINISHED"]
    if finished:
        t1, live = finished[-1].timestamp_ms, False
    else:
        if now_ms is None:
            raise ValueError("live job: pass now_ms to account up to the present")
        t1, live = int(now_ms), True
    t1 = max(t1, t0)

    claims: list[tuple[int, int, int, str]] = []     # (start, end, prio, phase)

    def claim(phase: str, start: int, end: int) -> None:
        start, end = max(int(start), t0), min(int(end), t1)
        if end > start:
            claims.append((start, end, _PRIORITY[phase], phase))

    # ---- submit: the client's hop, from its own stamp to the AM's first event
    if submitted is not None:
        claim("submit", submitted, inited.timestamp_ms)

    # ---- queue wait: waiting → admitted pairs (unterminated waits run to t1)
    wait_start: int | None = None
    for ev in events:
        if _ev_type(ev) != "QUEUE_WAIT":
            continue
        if ev.payload.get("state") == "waiting" and wait_start is None:
            wait_start = ev.timestamp_ms
        elif ev.payload.get("state") == "admitted" and wait_start is not None:
            claim("queue_wait", wait_start, ev.timestamp_ms)
            wait_start = None
    if wait_start is not None:
        claim("queue_wait", wait_start, t1)

    # ---- gang epochs: boundaries are GANG_COMPLETE (epoch start) and the
    # next restart marker / t1 (epoch end); epoch starts are restart markers
    completes = [ev.timestamp_ms for ev in events if _ev_type(ev) == "GANG_COMPLETE"]
    restarts = [ev.timestamp_ms for ev in events if _is_restart_marker(ev)]
    resize_marks = [
        ev.timestamp_ms for ev in events
        if _ev_type(ev) == "GANG_RESIZED" and not ev.payload.get("rejected")
    ]
    takeover_events = [
        ev for ev in events
        if _ev_type(ev) in ("AM_TAKEOVER", "AM_TAKEOVER_DEGRADED")
    ]

    def next_at_or_after(ts_list: list[int], t: int, default: int) -> int:
        """First timestamp >= t (inclusive: an epoch's GANG_COMPLETE can
        land in the same millisecond as the epoch start — the claim must
        then be empty, not span the rest of the job)."""
        later = [x for x in ts_list if x >= t]
        return min(later) if later else default

    # startup: [epoch start, its GANG_COMPLETE] — epoch starts are t0 and
    # every restart marker; a gang that never completes claims to epoch end
    for start in [t0] + restarts:
        claim("startup", start, next_at_or_after(completes, start, t1))

    # registration barrier: first TASK_REGISTERED of the epoch → GANG_COMPLETE
    regs = [ev.timestamp_ms for ev in events if _ev_type(ev) == "TASK_REGISTERED"]
    for start in [t0] + restarts:
        gc = next_at_or_after(completes, start, t1)
        first_reg = next_at_or_after(regs, start, gc)
        if first_reg < gc:
            claim("registration", first_reg, gc)

    # productive filler: [GANG_COMPLETE, next restart marker / t1]; the
    # marker search starts just past gc so the restart that CAUSED this
    # epoch (always <= gc) is never taken as its end
    for gc in completes:
        claim("productive", gc, next_at_or_after(restarts, gc + 1, t1))

    # resize episodes: the resize announcement through the resized gang's
    # completion — wins over generic startup, yields to registration/compile
    for rm in resize_marks:
        claim("resize", rm, next_at_or_after(completes, rm + 1, t1))

    # ---- the child's start-up stages, from its own stamps: the latest report
    # of each task in each gang epoch (an event belongs to the epoch of the
    # last GANG_COMPLETE before it: the AM fences pushes of older epochs)
    reports: dict[tuple[int, str], Any] = {}
    for ev in events:
        if _ev_type(ev) != "TASK_STARTUP_STAMPS":
            continue
        gcs = [gc for gc in completes if gc <= ev.timestamp_ms]
        if gcs:
            reports[(max(gcs), str(ev.payload.get("task")))] = ev
    urls = [(ev.timestamp_ms, str(ev.payload.get("task"))) for ev in events
            if _ev_type(ev) == "TASK_URL_REGISTERED"]
    for (gc, task), ev in reports.items():
        epoch_end = next_at_or_after(restarts, gc + 1, t1)
        raw = ev.payload.get("stamps")
        st = {k: ms for k, v in (raw if isinstance(raw, dict) else {}).items()
              if (ms := _stamp_ms(v)) is not None}
        kind = ev.payload.get("kind")
        if kind == "serve":
            ready = min((ts for ts, t in urls if t == task and gc <= ts < epoch_end), default=None)
            if ready is not None:
                st["ready"] = ready
        stamps[f"{task}@{gc}"] = st
        last = {"train": ("compile", "first_step_done"), "serve": ("warmup", "ready")}.get(kind)
        edges = [gc, st.get("devices_ready"), st.get("weights_ready"), st.get(last[1]) if last else None]
        for i, phase in enumerate(("runtime_init", "weights", last[0] if last else None)):
            lo, hi = edges[i], edges[i + 1]
            if phase is None or lo is None:
                continue
            if hi is None:
                if any(e is not None for e in edges[i + 2:]):
                    continue  # an edge never stamped though the child got past it: nobody's stage
                hi = epoch_end  # the child is still in this stage, or died in it
            claim(phase, lo, hi)

    # ---- compile, for a gang epoch whose .jhist carries no stamps (written
    # by an older build, or by a child that takes none): traced first-step
    # spans, else the first step evidence, a resolution of the AM's snapshot
    # period. An epoch with stamps never takes this estimate
    first_steps = [s for s in spans if s.get("name") == "train.first_step"]
    snapshots = [ev for ev in events if _ev_type(ev) == "METRICS_SNAPSHOT"]
    stamped_epochs = {gc for gc, _task in reports}
    for gc in completes:
        if gc in stamped_epochs:
            continue
        epoch_end = next_at_or_after(restarts, gc + 1, t1)
        ends = [
            _span_ms(s)[1] for s in first_steps
            if gc <= _span_ms(s)[0] < epoch_end
        ]
        if ends:
            claim("compile", gc, min(max(ends), epoch_end))
            continue
        for ev in snapshots:
            if ev.timestamp_ms <= gc or ev.timestamp_ms >= epoch_end:
                continue
            if any(v >= 1 for v in _snapshot_steps(ev).values()):
                claim("compile", gc, ev.timestamp_ms)
                break

    # ---- checkpoint: save spans (the restore cost after a restart is
    # already inside startup/resize; double-claiming it would shrink them)
    for s in spans:
        if s.get("name") == "ckpt.save":
            start, end = _span_ms(s)
            claim("checkpoint", start, end)

    # ---- input wait: step-loop stalls on the input pipeline (backdated
    # spans the prefetcher emits for waits past its span floor; sub-floor
    # waits stay inside productive — they are noise, not a phase)
    for s in spans:
        if s.get("name") == "train.input_wait":
            start, end = _span_ms(s)
            claim("input_wait", start, end)

    # ---- takeover: journal replay + adoption (traced); without a span the
    # event is an instant and contributes no width
    for s in spans:
        if s.get("name") == "am.takeover":
            start, end = _span_ms(s)
            claim("takeover", start, end)

    # ---- restart rework: for each restart, the resumed epoch's first step
    # report says where the checkpoint was; everything the previous epoch
    # ran past that step was lost and re-done
    epoch_steps: list[list[tuple[int, int]]] = [[] for _ in range(len(completes) + 1)]
    for ev in snapshots:
        # snapshot belongs to the epoch of the last GANG_COMPLETE before it
        epoch = sum(1 for gc in completes if gc <= ev.timestamp_ms)
        steps = _snapshot_steps(ev)
        if steps:
            epoch_steps[epoch].append((ev.timestamp_ms, max(steps.values())))
    for rt in restarts:
        prev_epoch = sum(1 for gc in completes if gc <= rt)
        next_epoch = prev_epoch + 1
        if prev_epoch < 1 or next_epoch >= len(epoch_steps) or not epoch_steps[next_epoch]:
            continue
        resume_step = epoch_steps[next_epoch][0][1]
        lost_from = next(
            (ts for ts, step in epoch_steps[prev_epoch] if step >= resume_step),
            None,
        )
        if lost_from is not None and lost_from < rt:
            claim("restart_rework", lost_from, rt)

    # ---- cooperative-preemption drain windows: request → yield/escalate
    # (an unterminated window ends at the next restart marker — the yield IS
    # the restart — or t1 for a live job mid-drain)
    drain_resolutions = [
        ev.timestamp_ms for ev in events
        if _ev_type(ev) in (
            "PREEMPTION_YIELDED", "PREEMPTION_ESCALATED", "PREEMPTION_CANCELLED")
    ]
    for ev in events:
        if _ev_type(ev) != "PREEMPTION_REQUESTED":
            continue
        end = next_at_or_after(
            drain_resolutions, ev.timestamp_ms,
            next_at_or_after(restarts, ev.timestamp_ms, t1),
        )
        claim("preempt_drain", ev.timestamp_ms, end)

    # ---- drain: after the last evidence of work — the last task finish, or
    # the last metrics snapshot when one outlives it (the final task's
    # finish event can be lost to the shutdown race / a torn tail, and its
    # last productive stretch must not be misread as teardown)
    finishes = [ev.timestamp_ms for ev in events if _ev_type(ev) == "TASK_FINISHED"]
    if not live and finishes:
        claim("drain", max(finishes + [ev.timestamp_ms for ev in snapshots]), t1)

    phases_ms, episodes = _partition(t0, t1, claims)
    return Ledger(
        app_id=app_id,
        t0_ms=t0,
        t1_ms=t1,
        live=live,
        phases_ms=phases_ms,
        episodes=episodes,
        restarts=len(restarts),
        resizes=len(resize_marks),
        takeovers=len(takeover_events),
        step_time_by_task_ms=step_time_by_task(events),
        stamps=stamps,
    )


def _partition(
    t0: int, t1: int, claims: list[tuple[int, int, int, str]]
) -> tuple[dict[str, int], list[tuple[str, int, int]]]:
    """Sweep the claim edges: each elementary interval goes to the single
    highest-priority covering claim (ties broken by later claim — irrelevant,
    same phase priorities are unique), else ``other``. Integer milliseconds
    throughout, so the phase totals sum to ``t1 - t0`` EXACTLY."""
    bounds = sorted({t0, t1, *(c[0] for c in claims), *(c[1] for c in claims)})
    bounds = [b for b in bounds if t0 <= b <= t1]
    phases: dict[str, int] = {}
    episodes: list[tuple[str, int, int]] = []
    for lo, hi in zip(bounds, bounds[1:]):
        if hi <= lo:
            continue
        best = None
        for start, end, prio, phase in claims:
            if start <= lo and end >= hi and (best is None or prio > best[0]):
                best = (prio, phase)
        phase = best[1] if best else "other"
        phases[phase] = phases.get(phase, 0) + (hi - lo)
        if episodes and episodes[-1][0] == phase and episodes[-1][2] == lo:
            episodes[-1] = (phase, episodes[-1][1], hi)
        else:
            episodes.append((phase, lo, hi))
    return phases, episodes


class JhistFollower:
    """Incremental reader of one append-only ``.jhist``: each :meth:`poll`
    parses only the bytes appended since the last call (complete lines
    only — a torn tail waits for its newline) and returns the accumulated
    event list. The AM's goodput tick and ``get_goodput`` RPC share one
    instance, so a long job pays O(new events) per tick for file I/O + JSON
    instead of re-reading its whole history every few seconds. Thread-safe:
    RPC handler threads race the monitor loop on it."""

    def __init__(self, path: str):
        self.path = path
        self._pos = 0
        self._events: list[Any] = []
        import threading

        self._lock = threading.Lock()

    def poll(self) -> list[Any]:
        from tony_tpu.cluster.events import Event

        with self._lock:
            try:
                with open(self.path, "rb") as f:  # lint: disable=blocking-under-lock — leaf lock serializing the follower's (pos, tail-buffer) against concurrent polls; local jhist read
                    f.seek(self._pos)
                    chunk = f.read()
            except OSError:
                return list(self._events)
            end = chunk.rfind(b"\n")
            if end >= 0:
                for line in chunk[:end].split(b"\n"):
                    if not line.strip():
                        continue
                    try:
                        self._events.append(
                            Event.from_json(line.decode("utf-8", "replace")))
                    except (ValueError, AttributeError, TypeError):
                        continue  # garbled line: live accounting skips it
                self._pos += end + 1
            return list(self._events)


def build_ledger_from_artifacts(art, now_ms: int | None = None) -> Ledger:
    """Ledger straight off the artifact index (finalized or live job):
    events with torn tolerance + spans when traced. The single resolution
    `tony goodput`, the portal, the history ingester, and the AM's live
    tick all share."""
    from tony_tpu.obs import artifacts as obs_artifacts

    events, _complete = art.read_events()
    spans = obs_artifacts.load_spans(art.trace_dir)
    return build_ledger(art.app_id, events, spans, now_ms=now_ms)


# ---------------------------------------------------------------------------
# straggler detection: per-task step-time skew off the piggybacked histograms
# ---------------------------------------------------------------------------
def histogram_percentile(
    snapshots: Iterable[Any], name: str, q: float
) -> float | None:
    """Upper-bound percentile estimate over the merged bucket counts of one
    histogram across many registry snapshots (the per-task groups of the
    AM's ``get_metrics``): the q-quantile's bucket upper edge, in the
    histogram's native unit. None without samples."""
    buckets: list[float] | None = None
    counts: list[int] | None = None
    total = 0
    for snap in snapshots:
        for m in snap or []:
            if m.get("name") != name or m.get("type") != "histogram":
                continue
            bs = list(m.get("buckets") or [])
            for sample in m.get("samples", []):
                cs = list(sample.get("counts") or [])
                if buckets is None:
                    buckets, counts = bs, [0] * len(cs)
                if bs != buckets or len(cs) != len(counts):
                    continue  # shape drift between processes: skip, not lie
                counts = [a + b for a, b in zip(counts, cs)]
                total += int(sample.get("count", 0))
    if not total or buckets is None or counts is None:
        return None
    target = q * total
    cum = 0
    for i, n in enumerate(counts[:-1]):
        cum += n
        if cum >= target:
            return float(buckets[i])
    return float(buckets[-1])  # overflow bucket: report the largest edge


class StragglerDetector:
    """Flags ranks whose step time persistently exceeds the gang median.

    Fed once per goodput tick with the per-task cumulative ``(count, sum)``
    of ``tony_train_step_seconds`` (obs_introspect.step_stats_by_task); the
    delta between ticks is the task's live step time. A task whose
    time >= ``factor`` × the gang median for ``min_checks`` consecutive
    *evaluated* ticks is a straggler until it drops back under — the
    transitions come back as ``("detected"|"resolved", task, ratio,
    median_s)`` tuples for the caller to turn into events/gauges. A rank
    that stops advancing entirely — the worst straggler — is judged by the
    time since its last completed step (a LOWER bound on its in-flight step
    time) once that bound alone crosses the factor. Needs 3+ participating
    tasks: with two, "the median" is the midpoint of the pair and a slow
    rank drags it.
    """

    def __init__(self, factor: float = 1.5, min_checks: int = 3):
        self.factor = max(float(factor), 1.0)
        self.min_checks = max(int(min_checks), 1)
        self._prev: dict[str, tuple[int, float]] = {}
        self._last_advance: dict[str, float] = {}   # task → monotonic seconds
        self._streak: dict[str, int] = {}
        self.flagged: set[str] = set()
        self.skew: dict[str, float] = {}
        self.median_s: float = 0.0

    def observe(
        self, stats: Mapping[str, tuple[int, float]], now_s: float | None = None
    ) -> list[tuple[str, str, float, float]]:
        """One tick. Returns state transitions (see class docstring)."""
        import time as _time

        now = _time.monotonic() if now_s is None else now_s
        times: dict[str, float] = {}
        stalled: dict[str, float] = {}   # no new steps → lower-bound step time
        for task, (count, total) in stats.items():
            prev = self._prev.get(task)
            self._prev[task] = (count, total)
            if prev is None:
                self._last_advance[task] = now
            elif count > prev[0] and total > prev[1]:
                times[task] = (total - prev[1]) / (count - prev[0])
                self._last_advance[task] = now
            else:
                stalled[task] = now - self._last_advance.get(task, now)
        # tasks that vanished (resized away, finished) resolve silently
        gone = set(self._prev) - set(stats)
        out: list[tuple[str, str, float, float]] = []
        for task in sorted(gone):
            self._prev.pop(task, None)
            self._last_advance.pop(task, None)
            self._streak.pop(task, None)
            self.skew.pop(task, None)
            if task in self.flagged:
                self.flagged.discard(task)
                out.append(("resolved", task, 0.0, self.median_s))
        if len(times) < 2 or len(times) + len(stalled) < 3:
            return out
        med = _median(list(times.values()))
        if med <= 0:
            return out
        self.median_s = med
        # a stalled rank joins the evaluation once its silence ALONE exceeds
        # the factor (its true step time can only be longer); a rank merely
        # mid-step (bound under the factor) holds its streak/skew unchanged
        judged = dict(times)
        for task, bound in stalled.items():
            if bound / med >= self.factor:
                judged[task] = bound
        for task, t in sorted(judged.items()):
            ratio = t / med
            self.skew[task] = ratio
            if ratio >= self.factor:
                self._streak[task] = self._streak.get(task, 0) + 1
                if self._streak[task] >= self.min_checks and task not in self.flagged:
                    self.flagged.add(task)
                    out.append(("detected", task, ratio, med))
            else:
                self._streak[task] = 0
                if task in self.flagged:
                    self.flagged.discard(task)
                    out.append(("resolved", task, ratio, med))
        return out
