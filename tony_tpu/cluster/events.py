"""Job event stream and history writing.

Analog of the reference's ``tony-core/.../tony/events/`` (Avro ``Event{type,
payload, timestamp}`` records drained by an ``EventHandler`` thread into a
``.jhist`` file in an HDFS intermediate dir, moved to
``finished/yyyy/MM/dd/<appId>/`` on completion — SURVEY.md §2.1, §5.5).

TPU-native carrier: JSONL instead of Avro (self-describing, zero schema
tooling, portal/CLI-greppable), local/shared filesystem instead of HDFS.
"""

from __future__ import annotations

import enum
import json
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any

from tony_tpu import constants


class EventType(enum.Enum):
    APPLICATION_INITED = "APPLICATION_INITED"
    TASK_SCHEDULED = "TASK_SCHEDULED"
    TASK_STARTED = "TASK_STARTED"
    TASK_REGISTERED = "TASK_REGISTERED"
    TASK_FINISHED = "TASK_FINISHED"
    HEARTBEAT_LOST = "HEARTBEAT_LOST"
    AM_TAKEOVER = "AM_TAKEOVER"                    # relaunched AM adopted the live gang (work-preserving restart)
    AM_TAKEOVER_DEGRADED = "AM_TAKEOVER_DEGRADED"  # journal missing/corrupt → full gang restart fallback
    TASK_RESYNCED = "TASK_RESYNCED"                # executor re-attached to a takeover AM's refreshed endpoint
    QUEUE_WAIT = "QUEUE_WAIT"
    # cooperative preemption (docs/scheduling.md): the pool asked this job to
    # drain (checkpoint-then-yield) or shrink; YIELDED records the urgent
    # checkpoint + voluntary teardown, ESCALATED records the pool killing a
    # victim that missed the drain deadline, CANCELLED records the pool
    # withdrawing the request (victim re-admitted before yielding)
    PREEMPTION_REQUESTED = "PREEMPTION_REQUESTED"
    PREEMPTION_YIELDED = "PREEMPTION_YIELDED"
    PREEMPTION_ESCALATED = "PREEMPTION_ESCALATED"
    PREEMPTION_CANCELLED = "PREEMPTION_CANCELLED"
    GANG_COMPLETE = "GANG_COMPLETE"
    GANG_RESIZED = "GANG_RESIZED"
    SPARE_READY = "SPARE_READY"        # hot-spare executor pre-registered with the AM
    SPARE_PROMOTED = "SPARE_PROMOTED"  # spare bound to a gang slot (skipped allocation)
    TASK_URL_REGISTERED = "TASK_URL_REGISTERED"
    # the chip-holding child's start-up stamps (obs/startup.py), as the task's
    # metrics push carried them: written when a task's stamps are first seen
    # in a gang epoch and again only when one more has been taken
    TASK_STARTUP_STAMPS = "TASK_STARTUP_STAMPS"
    METRICS_SNAPSHOT = "METRICS_SNAPSHOT"
    PROFILE_REQUESTED = "PROFILE_REQUESTED"    # on-demand capture fan-out began
    PROFILE_FINISHED = "PROFILE_FINISHED"      # every targeted task reported
    STRAGGLER_DETECTED = "STRAGGLER_DETECTED"  # rank's step time persistently over the gang median
    STRAGGLER_RESOLVED = "STRAGGLER_RESOLVED"  # flagged rank back under the skew factor (or gone)
    ALERT_FIRED = "ALERT_FIRED"                # a tony.alerts.* rule crossed its threshold
    ALERT_RESOLVED = "ALERT_RESOLVED"          # the rule's signal recovered (or the job finalized)
    SLO_BURN_ALERT = "SLO_BURN_ALERT"          # an SLO burn-rate rule (tony.slo.*) started firing
    SLO_BURN_RESOLVED = "SLO_BURN_RESOLVED"    # the burn rate dropped back under the rule threshold
    APPLICATION_FINISHED = "APPLICATION_FINISHED"


class UnknownEventType:
    """Forward-compat stand-in for an event type this build doesn't declare.

    A ``.jhist`` written by a NEWER tony (e.g. carrying trace/metrics
    snapshot events) must stay readable by older portals and ``tony
    history`` — refusing the whole file over one unrecognized type would
    break every rolling upgrade. Mirrors the ``EventType`` surface readers
    touch (``.value``/``.name``, equality, hashing) so event consumers work
    unchanged.
    """

    __slots__ = ("value",)

    def __init__(self, value: str):
        self.value = value

    @property
    def name(self) -> str:
        return self.value

    def __eq__(self, other: object) -> bool:
        return getattr(other, "value", None) == self.value

    def __hash__(self) -> int:
        return hash(self.value)

    def __repr__(self) -> str:
        return f"UnknownEventType({self.value!r})"


@dataclass
class Event:
    type: "EventType | UnknownEventType"
    payload: dict[str, Any] = field(default_factory=dict)
    timestamp_ms: int = 0

    def __post_init__(self) -> None:
        if not self.timestamp_ms:
            self.timestamp_ms = int(time.time() * 1000)

    def to_json(self) -> str:
        return json.dumps(
            {"type": self.type.value, "timestamp_ms": self.timestamp_ms, "payload": self.payload}
        )

    @classmethod
    def from_json(cls, line: str) -> "Event":
        d = json.loads(line)
        raw = d.get("type", "")
        try:
            etype: "EventType | UnknownEventType" = EventType(raw)
        except ValueError:
            etype = UnknownEventType(raw)  # tolerate newer writers
        return cls(etype, d.get("payload", {}), d.get("timestamp_ms", 0))


class EventHandler:
    """Queue-draining writer thread (reference EventHandler analog).

    Events are appended (line-buffered JSONL) to
    ``<history>/intermediate/<app_id>.jhist``; ``finalize()`` moves the file to
    ``<history>/finished/yyyy/MM/dd/<app_id>/`` with the status-encoding
    filename (history.py codec) and writes ``config.json`` alongside.
    """

    def __init__(self, history_root: str, app_id: str):
        self.history_root = history_root
        self.app_id = app_id
        self._q: "queue.Queue[Event | None]" = queue.Queue()
        self._path = os.path.join(history_root, constants.HISTORY_INTERMEDIATE_DIR, app_id + constants.HISTORY_SUFFIX)
        os.makedirs(os.path.dirname(self._path), exist_ok=True)
        self._file = open(self._path, "a", buffering=1)
        self._thread = threading.Thread(target=self._drain, name="event-handler", daemon=True)
        self._started = False

    def start(self) -> None:
        self._thread.start()
        self._started = True

    def emit(self, type_: EventType, **payload: Any) -> None:
        self._q.put(Event(type_, payload))

    def _drain(self) -> None:
        while True:
            ev = self._q.get()
            if ev is None:
                return
            self._file.write(ev.to_json() + "\n")

    def stop(self) -> None:
        if self._started:
            self._q.put(None)
            self._thread.join(timeout=10)
        self._file.close()

    @property
    def intermediate_path(self) -> str:
        return self._path
