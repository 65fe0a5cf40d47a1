"""Dapper-style distributed tracing for the control plane.

One job = one trace (``trace_id`` is the application id). Every process in
the job — submitting client, AM, each executor supervisor, each training
child — owns a process-global :class:`Tracer` (``init_*`` factories below)
that appends finished spans to ``<staging>/trace/<identity>.spans.jsonl``;
``tony trace <app_id>`` merges those files into a Chrome trace-event timeline
(cli/trace.py). Causality crosses process boundaries two ways:

- **in-band through RPC frames**: ``RpcClient`` injects ``{"t": trace_id,
  "s": span_id}`` into every request and ``RpcServer`` parents its handler
  span on it (cluster/rpc.py);
- **through the spawn env**: a parent process exports its root span id as
  ``TONY_TRACE_PARENT`` so the child's root span links under it
  (client → AM → executor → training child).

The current span travels in a :data:`contextvars.ContextVar`, so nested
``with tracer.span(...)`` blocks parent naturally and each thread gets its
own stack; spans opened on a thread with no current span fall back to the
tracer's ``root_parent`` (the process root span).

Disabled is the default and MUST stay free: ``get()`` returns ``None``, every
injection point guards on that single check, and :func:`maybe_span` hands out
a shared no-op context manager — no Span allocation, no I/O, nothing
(asserted by tests/test_obs.py).
"""

from __future__ import annotations

import contextvars
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Iterator, Mapping

from tony_tpu import constants

_CURRENT: contextvars.ContextVar["Span | None"] = contextvars.ContextVar("tony_span", default=None)
_tracer: "Tracer | None" = None


def get() -> "Tracer | None":
    """The process-global tracer, or None (tracing disabled — the default)."""
    return _tracer


def current_span() -> "Span | None":
    """The span currently open on this thread, or None."""
    return _CURRENT.get() if _tracer is not None else None


def add_event(name: str, **attrs: Any) -> None:
    """Annotate the current span with a point-in-time event.

    Safe to call from anywhere (chaos injection points, retry loops): a no-op
    when tracing is off or no span is open on this thread.
    """
    if _tracer is None:
        return
    span = _CURRENT.get()
    if span is not None:
        span.add_event(name, **attrs)


class _NoopCtx:
    """Shared do-nothing context manager for the disabled fast path."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc: Any) -> bool:
        return False


_NOOP = _NoopCtx()


def maybe_span(name: str, kind: str = "internal", **attrs: Any):
    """A real span when tracing is on, else the shared no-op context."""
    tr = _tracer
    if tr is None:
        return _NOOP
    return tr.span(name, kind=kind, **attrs)


def start_manual(name: str, kind: str = "internal", parent_id: str | None = None,
                 start_s: float | None = None, **attrs: Any) -> "Span | None":
    """A span NOT bound to the thread's context — for lifecycles that cross
    event-loop iterations (one serve request's queue → prefill → decode
    chain lives across many engine steps). Returns None when tracing is off:
    the disabled hot path stays one None check, no Span allocation (same
    contract as :func:`maybe_span`). Pair with :func:`end_manual`.
    ``start_s`` (and ``end_manual``'s ``end_s``), in ``time.time()`` seconds,
    place a span whose boundaries were stamped before it was written."""
    tr = _tracer
    if tr is None:
        return None
    if parent_id is None:
        cur = _CURRENT.get()
        parent_id = cur.span_id if cur is not None else tr.root_parent
    span = Span(name, tr.trace_id, _new_span_id(), parent_id, kind, tr.identity)
    if start_s is not None:
        span.start_ms = start_s * 1000.0
    if attrs:
        span.attrs.update(attrs)
    return span


def end_manual(span: "Span | None", status: str = "ok", end_s: float | None = None,
               **attrs: Any) -> None:
    """Finish and sink a :func:`start_manual` span (no-op on None)."""
    tr = _tracer
    if tr is None or span is None:
        return
    if attrs:
        span.attrs.update(attrs)
    span.end_ms = (time.time() if end_s is None else end_s) * 1000.0
    span.status = status
    tr._write(span)


def _new_span_id() -> str:
    return os.urandom(8).hex()


def _safe_identity(identity: str) -> str:
    return identity.replace(":", "_").replace(os.sep, "_")


class Span:
    """One timed operation: name, causal links, attributes, point events."""

    __slots__ = (
        "name", "trace_id", "span_id", "parent_id", "kind", "identity",
        "thread_id", "start_ms", "end_ms", "status", "attrs", "events",
    )

    def __init__(self, name: str, trace_id: str, span_id: str,
                 parent_id: str | None, kind: str, identity: str):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.kind = kind
        self.identity = identity
        self.thread_id = threading.get_ident()
        self.start_ms = time.time() * 1000.0
        self.end_ms = 0.0
        self.status = "ok"
        self.attrs: dict[str, Any] = {}
        self.events: list[dict[str, Any]] = []

    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self

    def add_event(self, name: str, **attrs: Any) -> None:
        ev: dict[str, Any] = {"name": name, "ts_ms": time.time() * 1000.0}
        if attrs:
            ev["attrs"] = attrs
        self.events.append(ev)

    def to_dict(self) -> dict[str, Any]:
        d: dict[str, Any] = {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "kind": self.kind,
            "identity": self.identity,
            "thread": self.thread_id,
            "start_ms": round(self.start_ms, 3),
            "end_ms": round(self.end_ms, 3),
            "status": self.status,
        }
        if self.attrs:
            d["attrs"] = self.attrs
        if self.events:
            d["events"] = self.events
        return d


class Tracer:
    """Per-process span factory + JSONL sink (one file per process identity).

    The sink is line-buffered append — finished spans hit disk immediately,
    so an ``os._exit`` (heartbeat-lost executor) or SIGKILL loses at most the
    spans still open. Restart attempts of the same identity append to the
    same file; the restart epoch rides in span attrs.
    """

    def __init__(self, trace_id: str, identity: str, trace_dir: str,
                 parent_id: str | None = None):
        self.trace_id = trace_id
        self.identity = identity
        self.trace_dir = trace_dir
        #: fallback parent for spans opened with no current span on the
        #: thread — processes point this at their root span so background
        #: threads (heartbeat, metrics push) still nest under it
        self.root_parent = parent_id
        self._lock = threading.Lock()
        os.makedirs(trace_dir, exist_ok=True)
        path = os.path.join(trace_dir, _safe_identity(identity) + ".spans.jsonl")
        self._file = open(path, "a", buffering=1)

    # ------------------------------------------------------------ span API
    def start_span(
        self, name: str, kind: str = "internal", parent_id: str | None = None,
    ) -> tuple[Span, contextvars.Token]:
        """Open a span and make it current on this thread; pair with
        :meth:`end_span`. Prefer the :meth:`span` context manager unless the
        span must outlive a lexical scope (process root spans)."""
        if parent_id is None:
            cur = _CURRENT.get()
            parent_id = cur.span_id if cur is not None else self.root_parent
        span = Span(name, self.trace_id, _new_span_id(), parent_id, kind, self.identity)
        token = _CURRENT.set(span)
        return span, token

    def end_span(self, span: Span, token: contextvars.Token, status: str = "ok") -> None:
        span.end_ms = time.time() * 1000.0
        span.status = status
        try:
            _CURRENT.reset(token)
        except ValueError:
            pass  # ended from a different context than it started in
        self._write(span)

    def discard_span(self, span: Span, token: contextvars.Token) -> None:
        """Close a span WITHOUT writing it — for expected control-flow
        aborts (e.g. a queued allocation retried every monitor tick) that
        would otherwise flood the sink with identical error spans."""
        try:
            _CURRENT.reset(token)
        except ValueError:
            pass

    @contextmanager
    def span(self, name: str, kind: str = "internal",
             parent_id: str | None = None, **attrs: Any) -> Iterator[Span]:
        sp, token = self.start_span(name, kind=kind, parent_id=parent_id)
        if attrs:
            sp.attrs.update(attrs)
        try:
            yield sp
        except BaseException:
            self.end_span(sp, token, status="error")
            raise
        self.end_span(sp, token)

    # (the RPC wire context {"t": trace_id, "s": span_id} is built by
    # RpcClient.call from the span it just opened — cluster/rpc.py)

    # ---------------------------------------------------------------- sink
    def _write(self, span: Span) -> None:
        line = json.dumps(span.to_dict())
        with self._lock:
            try:
                self._file.write(line + "\n")  # lint: disable=blocking-under-lock — the tracer lock IS the span-line serializer (leaf; span serialized outside it)
            except ValueError:
                pass  # closed mid-teardown: spans are best-effort by contract

    def close(self) -> None:
        with self._lock:
            try:
                self._file.close()
            except OSError:
                pass


# ---------------------------------------------------------------- factories
def init_tracing(trace_id: str, identity: str, trace_dir: str,
                 parent_id: str | None = None) -> Tracer:
    """Install the process-global tracer (replacing any previous one)."""
    global _tracer
    if _tracer is not None:
        _tracer.close()
    _tracer = Tracer(trace_id, identity, trace_dir, parent_id=parent_id)
    return _tracer


def init_from_config(config, identity: str, staging_dir: str, app_id: str,
                     parent_id: str | None = None) -> "Tracer | None":
    """Control-plane processes (client, AM, executor): enable from the frozen
    job config. None — and zero ongoing cost — unless ``tony.trace.enabled``."""
    from tony_tpu.config import keys

    if not config.get_bool(keys.TRACE_ENABLED):
        return None
    trace_dir = config.get(keys.TRACE_DIR) or os.path.join(staging_dir, "trace")
    return init_tracing(app_id, identity, trace_dir, parent_id=parent_id)


def init_from_env(env: Mapping[str, str] | None = None) -> "Tracer | None":
    """The training child's contract: the executor exports TONY_TRACE_ENABLED
    / TONY_TRACE_DIR / TONY_TRACE_PARENT when tracing is on. None otherwise
    (also the no-op path for library use outside a tony container)."""
    env = os.environ if env is None else env
    if env.get(constants.ENV_TRACE_ENABLED) != "1":
        return None
    trace_dir = env.get(constants.ENV_TRACE_DIR, "")
    if not trace_dir:
        return None
    job = env.get(constants.ENV_JOB_NAME)
    idx = env.get(constants.ENV_TASK_INDEX)
    identity = f"{job}:{idx}:train" if job and idx is not None else "proc"
    return init_tracing(
        env.get(constants.ENV_APP_ID, "trace"),
        identity,
        trace_dir,
        parent_id=env.get(constants.ENV_TRACE_PARENT) or None,
    )


def shutdown() -> None:
    """Close and uninstall the process-global tracer (idempotent)."""
    global _tracer
    if _tracer is not None:
        _tracer.close()
        _tracer = None
