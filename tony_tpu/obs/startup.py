"""Start-up stamps of the chip-holding child: who took how long before the
first step or the first answer.

The goodput ledger (obs/goodput.py) splits a gang epoch's start-up into
stages whose edges are stamped here, by the process that does the work, on
the clock the ``.jhist`` and the spans use (``time.time()``, epoch ms):

==================  =========================================================
``child_spawned``   the executor's ``Popen`` (read from ENV_CHILD_SPAWNED_MS)
``main_entered``    the entry's first line (:func:`begin`): interpreter start
                    and the entry's imports lie before it
``devices_ready``   the first ``jax.devices()`` / mesh construction returned
``weights_ready``   weights (and a server's caches and page pool) on the device
``first_step_done`` train: the first executed step, compile included
``registered``      serve: the AM acknowledged the replica's URL (the ledger's
                    ``ready`` is the AM's own TASK_URL_REGISTERED; this is the
                    child's view of it, for the ``startup.warmup`` span)
==================  =========================================================

Always on: a stamp is one clock read and one small atomic write next to the
step report (``<train-metrics-file>.startup``), which the executor's metrics
push carries to the AM; the AM writes it to the ``.jhist`` when it changes
(TASK_STARTUP_STAMPS). Outside a tony container nothing is written. With a
tracer on, each stage whose two edges are known also closes one backdated span
``startup.<stage>`` under the process's root span, for ``tony trace``; no
metric reads those.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any

from tony_tpu import constants
from tony_tpu.obs import trace as obs_trace

#: the report's file, next to the step report the executor advertised
FILE_SUFFIX = ".startup"

#: span ``startup.<stage>`` runs from the first stamp to the second
STAGES = (
    ("interpreter", "child_spawned", "main_entered"),
    ("runtime_init", "main_entered", "devices_ready"),
    ("weights", "devices_ready", "weights_ready"),
    ("compile", "weights_ready", "first_step_done"),
    ("warmup", "weights_ready", "registered"),
)

_lock = threading.Lock()
_kind = ""
_stamps: dict[str, int] = {}
_spans_written: set[str] = set()


def begin(kind: str) -> None:
    """First line of a chip-holding entry (``train`` | ``serve``): takes
    ``main_entered`` and the executor's spawn stamp. A second call in one
    process (a loop entered again) starts the account anew."""
    global _kind
    now_ms = int(time.time() * 1000)
    with _lock:
        _kind = kind
        _stamps.clear()
        _spans_written.clear()
        try:
            _stamps["child_spawned"] = int(os.environ[constants.ENV_CHILD_SPAWNED_MS])
        except (KeyError, ValueError):
            pass
        _stamps["main_entered"] = now_ms
    _publish()


def stamp(name: str) -> None:
    """Take ``name`` now (the first taking stands) and publish the report."""
    now_ms = int(time.time() * 1000)
    with _lock:
        if not _kind or name in _stamps:
            return
        _stamps[name] = now_ms
    _publish()


def report() -> dict[str, Any]:
    with _lock:
        return {"kind": _kind, "stamps": dict(_stamps)}


def _publish() -> None:
    rep = report()
    path = os.environ.get(constants.ENV_TRAIN_METRICS_FILE)
    if path:
        try:
            tmp = path + FILE_SUFFIX + ".tmp"
            with open(tmp, "w") as f:
                json.dump(rep, f)
            os.replace(tmp, path + FILE_SUFFIX)
        except OSError:
            pass  # the account is best-effort, like the step report
    if obs_trace.get() is None:
        return
    stamps = rep["stamps"]
    for stage, opens, closes in STAGES:
        if opens in stamps and closes in stamps and stage not in _spans_written:
            _spans_written.add(stage)
            obs_trace.end_manual(
                obs_trace.start_manual("startup." + stage, start_s=stamps[opens] / 1000.0),
                end_s=stamps[closes] / 1000.0)


def read_report(metrics_path: str | None) -> dict[str, Any] | None:
    """The executor's side: the child's latest report, or None."""
    if not metrics_path:
        return None
    try:
        with open(metrics_path + FILE_SUFFIX) as f:
            rep = json.load(f)
        return rep if isinstance(rep, dict) else None
    except (OSError, ValueError):
        return None
