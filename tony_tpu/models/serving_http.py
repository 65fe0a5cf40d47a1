"""HTTP front end for the continuous-batching engine: the ``serve`` jobtype.

The reference runs training jobs and interactive notebooks under the AM
(SURVEY.md §3.4: the notebook jobtype registers its URL so the submitter can
proxy it); serving is new TPU-era capability built the same way — a
long-running, AM-supervised task that:

- boots a ``ContinuousBatcher`` (models/serving.py) over a model preset,
  HF checkpoint, or random-init weights (bench/test mode), optionally int8;
- serves a streaming completions API (stdlib ThreadingHTTPServer — one
  user-facing control path, no framework dependency):
    POST /v1/completions   {"prompt_tokens": [...], "max_tokens": N,
                            "stream": true|false, "temperature": ..,
                            "top_k": ..}  → JSON or SSE token stream
    GET  /healthz           liveness
    GET  /stats             engine counters (slots, queue depth, tok/s)
- when launched inside a tony container (TONY_AM_* env present), registers
  its URL over the AM RPC (``register_task_url`` — the §3.4 path) and drops
  engine throughput into ENV_TRAIN_METRICS_FILE so the executor's existing
  metrics loop feeds the portal;
- drains on SIGTERM: stops admitting, finishes the in-flight decode chunk,
  answers in-flight streams, exits 0;
- drains on a **cooperative-preemption notice** the same way: a watcher
  thread polls ``<TONY_TRAIN_METRICS_FILE>.drain`` — the control file the
  executor's DrainCourier drops when the pool asks this gang to drain —
  exactly like the training loop's UrgentSaveSignal. On a notice the server
  flips ``draining`` (the fleet HealthMonitor sheds it from routing and the
  SessionTable re-pins its sessions), finishes in-flight streams, publishes
  ``.drain.done`` (the courier reports ``report_drain_saved``), and exits
  clean inside the pool's deadline — serving survives preemption as
  gracefully as training does.

Threading model: three kinds of thread, each with one job.

- HTTP handler threads (``ThreadingHTTPServer``, one a connection) parse the
  request, ``submit`` it and park. One that does not stream waits on its
  request's queue and writes the one reply. One that streams waits there for
  the FIRST event only (an error before the first byte is still a plain
  429 / 504 / 503 / 400), sends the headers, hands its connection to the
  stream writer and sleeps until the writer says the stream has ended: it
  wakes once a request, not once a chunk.
- ONE engine thread owns the batcher (submit → step → drain_stream), so the
  engine itself needs no locks — the same host/device split the engine's
  docstring promises stays intact. It ends a pass by handing the stream
  writer ONE list of that pass's stream events, each stamped where it was
  appended: one ``put`` and one wake-up, whatever the number of live streams.
- ONE stream-writer thread (:class:`StreamWriter`) owns every streaming
  response's socket from the headers on. It encodes each event to its SSE
  bytes and sends it without blocking; what a socket will not take stays as
  that stream's pending bytes while the writer goes on to the next stream, so
  one stalled reader holds up nobody else. The drain waits for it.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import queue
import selectors
import signal
import socket
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any

import jax

from tony_tpu import constants
from tony_tpu.models import registry
from tony_tpu.models.serving import ContinuousBatcher
from tony_tpu.obs import logging as obs_logging
from tony_tpu.obs import metrics as obs_metrics
from tony_tpu.obs import startup as obs_startup
from tony_tpu.obs import trace as obs_trace
from tony_tpu.ops.interpret import interpret
from tony_tpu.runtime import device_facts, enable_compile_cache

# Serving instruments (obs registry, satellite of the training child's:
# snapshots drop at <train-metrics-file>.obs and ride the executor's
# push_metrics piggyback to the AM's get_metrics → the portal's /metrics).
_QUEUE_DEPTH = obs_metrics.gauge(
    "tony_serve_queue_depth",
    "engine admission + staging queue depth (requests waiting for a slot)")
_TTFT = obs_metrics.histogram(
    "tony_serve_ttft_seconds",
    "time from request submission to its first generated-token fanout")
_STAGE = obs_metrics.histogram(
    "tony_serve_stage_seconds",
    "one request's TTFT by stage, stamped where the state changes: queue "
    "(submit → staged), prefill (staged → in a slot), emit (in a slot → first "
    "fanout); the three add up to its tony_serve_ttft_seconds sample",
    labelnames=("stage",))
_TOKEN_LATENCY = obs_metrics.histogram(
    "tony_serve_token_latency_seconds",
    "per-token decode latency (chunk interval / tokens in the chunk)")
_DELIVERED = obs_metrics.counter(
    "tony_serve_tokens_delivered_total", "tokens actually written to client sockets")
_REQUESTS_DONE = obs_metrics.counter(
    "tony_serve_requests_total", "finished engine requests by outcome",
    labelnames=("outcome",))
_PREFIX_HITS = obs_metrics.counter(
    "tony_serve_prefix_hit_tokens_total",
    "prompt tokens whose prefill was skipped via paged prefix-cache hits")
_KV_HANDOFF = obs_metrics.counter(
    "tony_serve_kv_handoff_total",
    "KV pages moved through the disaggregated prefill→decode handoff "
    "(exported by the prefill tier / adopted into the decode tier's pool)",
    labelnames=("side",))
_HANDOFF_LATENCY = obs_metrics.histogram(
    "tony_serve_kv_handoff_seconds",
    "disaggregated handoff wall time on the prefill replica: prompt done → "
    "pages exported, shipped, and acked by the decode replica")
# The stream writer's account (docs/observability.md "Where a pass's host time
# goes"): one observation of each an SSE event, on the ONE writer thread that
# sends every stream's events (StreamWriter._send), both under one lock (a
# snapshot on another thread reads them).
# Its annotation in a profiler capture is NOT under ``tony.serve.``: those are
# the engine thread's phases, which tile that thread's time, and the writer's
# events lie on another thread's line, over and between them.
_WRITE_ANNOTATION = "tony.stream.write"  # lint: disable=config-keys — an annotation's name, not a config key
# an event written alone takes about 50 us
_EVENT_BUCKETS = (5e-5, 1e-4, 2.5e-4, 5e-4, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 1.0)
_STREAM_WRITE = obs_metrics.histogram(
    "tony_serve_stream_write_seconds",
    "the stream writer's wall time for one SSE event: encode and send (of an event its socket did not take "
    "whole at once, the attempt that sent its last byte)", buckets=_EVENT_BUCKETS)
_FANOUT_DELAY = obs_metrics.histogram(
    "tony_serve_fanout_delay_seconds",
    "from the engine thread's stamp on a stream event (where it joins the pass's hand-over) to the return of "
    "the send that put its last byte on the socket: how long a chunk's tokens lie between the engine and the "
    "socket (the rest of the fan-out, the writer's wake-up, its place in the pass's line, the send)",
    buckets=_EVENT_BUCKETS, lock_of=_STREAM_WRITE)
_HANDOVERS = obs_metrics.counter(
    "tony_serve_stream_handovers_total",
    "lists of stream events the engine thread handed the stream writer: one a pass that had any, whatever the "
    "number of live streams (tony_serve_stream_write_seconds' count over this: the events a hand-over)")
_DEFERRED = obs_metrics.counter(
    "tony_serve_stream_writes_deferred_total",
    "sends a streaming client's socket did not take whole at once: the rest waits as that stream's pending "
    "bytes while the writer goes on to the next stream")


class RequestStream:
    """The per-request event channel ``submit()`` returns. Quacks like the
    plain Queue it used to be (``get`` the events), plus ``cancel()`` —
    the client-disconnect/deadline path: the engine thread picks the flag
    up within one decode chunk and frees the slot/pages."""

    __slots__ = ("q", "cancelled", "submitted_s", "last_fanout_s", "handed_s",
                 "request_id", "span", "stage", "defer_finish", "req", "response", "started")

    def __init__(self, maxsize: int = 0, request_id: str = ""):
        self.q: queue.Queue = queue.Queue(maxsize)
        self.cancelled = threading.Event()
        # instrument timestamps (engine-thread only): TTFT measures from
        # SUBMISSION, so admission-queue wait is included — the number a
        # client actually experiences
        self.submitted_s = time.time()
        self.last_fanout_s = 0.0
        #: when the event ``get`` last returned was handed over
        #: (``time.perf_counter``). The stamp rides in the queue with its
        #: event, so a later chunk cannot overwrite it; only the consumer's
        #: thread reads or writes this field
        self.handed_s = 0.0
        #: router-propagated id (X-Tony-Request-Id) — exemplar + span key
        self.request_id = request_id
        #: disagg handoff: True → on "done" the engine opens a serve.handoff
        #: stage instead of closing the span; the /v1/prefill handler owns
        #: finish_trace after the pages ship (safe: the engine thread never
        #: touches the stream again after its terminal event)
        self.defer_finish = False
        # per-request span chain (queue → prefill → emit → decode) under one
        # serve.request umbrella; both stay None with tracing disabled, so
        # every hot-path hook below is a single attribute check
        self.span = None
        self.stage = None
        #: the engine's record of this request (its stage stamps), set by the
        #: engine thread once ``engine.submit`` accepted it
        self.req = None
        #: a STREAMED request's record with the stream writer (``submit`` makes
        #: it). Only its first event comes through ``q``; the engine thread
        #: hands every later one to the writer (``EngineServer._deliver``)
        self.response: _Response | None = None
        #: the engine thread has delivered an event (engine thread only)
        self.started = False

    def get(self, timeout: float | None = None):
        kind, payload, self.handed_s = self.q.get(timeout=timeout)
        return kind, payload

    def put(self, item) -> None:
        self.q.put((*item, time.perf_counter()))

    def cancel(self) -> None:
        self.cancelled.set()

    # ------------------------------------------------------ request spans
    def open_trace(self) -> None:
        """Start the serve.request umbrella (no-op — and allocation-free —
        when tracing is disabled). Its TTFT stages are written by
        :meth:`stages` once the stamps exist, not opened as they pass."""
        self.span = obs_trace.start_manual("serve.request", rid=self.request_id)

    def stages(self, end_s: float, status: str = "ok") -> list[tuple[str, float]]:
        """The request's TTFT stages as (label, seconds), from the engine's
        stamps: queue, prefill, emit, contiguous from ``submitted_s``; the one
        it is in ends at ``end_s`` (the first fanout, or where it died). With
        tracing on, each is also written as a ``serve.<label>`` span."""
        req = self.req
        marks = [self.submitted_s, 0.0, 0.0, 0.0]
        attrs = {"rid": self.request_id}
        if req is not None:  # None: it died in the inbox, before the engine saw it
            marks[1:3] = req.staged_s, req.slot_s
            attrs.update(prompt_tokens=len(req.prompt), prefix_tokens=req.prefix_tokens,
                         chunks=req.prefill_chunks, slot=req.slot)
        out = []
        for i, label in enumerate(("queue", "prefill", "emit")):
            current = not marks[i + 1]  # no later stamp: the stage it is in
            end = end_s if current else marks[i + 1]
            out.append((label, end - marks[i]))
            if self.span is not None:
                obs_trace.end_manual(obs_trace.start_manual(
                    "serve." + label, parent_id=self.span.span_id, start_s=marks[i], **attrs,
                ), status if current else "ok", end_s=end)
            if current:
                break
        return out

    def begin_stage(self, name: str, **attrs: Any) -> None:
        """End the current stage span and open the next one in the chain."""
        if self.span is not None:
            obs_trace.end_manual(self.stage)
            self.stage = obs_trace.start_manual(
                name, parent_id=self.span.span_id, **attrs)

    def finish_trace(self, status: str = "ok") -> None:
        if self.span is not None:
            if self.stage is None:  # died before its first token: say where
                self.stages(time.time(), status)
            obs_trace.end_manual(self.stage, status)
            obs_trace.end_manual(self.span, status)
            self.span = self.stage = None


def _sse(kind: str, payload: Any) -> bytes:
    """One engine event as the one SSE event a streaming client reads."""
    if kind == "tokens":
        obj = {"tokens": payload}
    elif kind == "done":
        obj = {"finished": True, "tokens": list(payload)}
    else:
        obj = {"error": payload}
    return b"data: " + json.dumps(obj).encode() + b"\n\n"


class _Response:
    """The stream writer's record of one streaming response. The handler
    thread parks on ``ended``; every other field is the writer thread's."""

    __slots__ = ("out", "sock", "unsent", "rest", "delivered", "waiting", "ended")

    def __init__(self, out: RequestStream):
        self.out = out
        self.sock: socket.socket | None = None  # the client's, once its handler attached it
        #: (kind, payload, stamp) not yet whole on the socket, in the engine's order
        self.unsent: collections.deque = collections.deque()
        self.rest: bytes | None = None  # what the socket has not taken of ``unsent[0]``
        self.delivered = 0  # tokens its "tokens" events carried to the socket
        self.waiting = False  # with the selector, until the socket takes bytes again
        self.ended = threading.Event()


class StreamWriter:
    """The ONE thread that writes every streaming response (module docstring,
    "Threading model").

    Other threads only enqueue a call for it (``open``, ``attach``, ``drop``,
    ``hand``, ``close``) and wake it; it runs them in the order they came, so
    no event passes another of its stream: a response is ``open`` from the
    moment its request is admitted, its handler ``attach``es the connection
    with the first event (or ``drop``s it: an error reply, a failed header),
    and what the engine ``hand``ed before that waits behind the first event.

    It never waits on one client. Sockets are non-blocking; what a send leaves
    over is that stream's pending bytes, the selector says when the socket
    takes more, and meanwhile the stream's later events queue behind them. A
    stream with ``bound`` events not yet on its socket is cancelled like a
    disconnect (the slow-consumer bound, ``EngineServer.STREAM_QUEUE_CHUNKS``),
    and so is one whose send fails: the engine frees the slot within a chunk.

    After ``close`` it runs until every open response has ended (its terminal
    event written, or failed), then sets ``flushed`` and exits."""

    def __init__(self, bound: int, delivered):
        self._bound = bound
        self._delivered = delivered  # tokens written to a socket -> the server's count
        self._calls: collections.deque = collections.deque()
        self._open: set[_Response] = set()
        self._closing = False
        self._sel: selectors.BaseSelector | None = None
        self._wake_r = self._wake_w = None
        self.deferred = 0  # sends not taken whole (/stats; writer thread only)
        self.flushed = threading.Event()
        self._thread = threading.Thread(target=self._run, name="stream-writer", daemon=True)

    def start(self) -> None:
        self._sel = selectors.DefaultSelector()
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._wake_w.setblocking(False)
        self._sel.register(self._wake_r, selectors.EVENT_READ)
        self._thread.start()

    # ------------------------------------------------- any thread: enqueue
    def _call(self, fn, *args, wake: bool = True) -> None:
        self._calls.append((fn, args))
        if wake:
            try:
                self._wake_w.send(b"\0")
            except OSError:
                pass  # full: a wake-up is already on its way; closed: the writer has exited

    def open(self, resp: _Response) -> None:
        """A streamed request was admitted: the drain waits for its response
        from here on. Nothing to do yet, so nobody is woken."""
        self._call(self._open.add, resp, wake=False)

    def attach(self, resp: _Response, sock: socket.socket, first: tuple) -> None:
        """The headers are out: the connection is the writer's from here, and
        ``first`` (kind, payload, stamp) goes out before anything handed since."""
        self._call(self._attach, resp, sock, first)

    def drop(self, resp: _Response) -> None:
        """Its handler answered without a stream (an error before the first byte)."""
        self._call(self._end, resp)

    def hand(self, events: list[tuple]) -> None:
        """A pass's events, ``(response, kind, payload, stamp)`` each, in one list."""
        self._call(self._take, events)

    def close(self) -> None:
        """No request will be admitted any more: flush and exit."""
        self._call(self._close)

    def open_count(self) -> int:
        return len(self._open)

    # ------------------------------------------------- the writer thread
    def _run(self) -> None:
        while not (self._closing and not self._open):
            for key, _ in self._sel.select():
                if key.data is None:
                    try:
                        self._wake_r.recv(4096)
                    except BlockingIOError:
                        pass
                else:
                    self._send(key.data)  # a socket that takes bytes again
            while self._calls:
                fn, args = self._calls.popleft()
                fn(*args)
        self._sel.close()
        self._wake_r.close()
        self._wake_w.close()
        self.flushed.set()

    def _close(self) -> None:
        self._closing = True

    def _attach(self, resp: _Response, sock: socket.socket, first: tuple) -> None:
        if resp.ended.is_set():
            return  # over the bound before its handler came back
        sock.setblocking(False)
        resp.sock = sock
        resp.unsent.appendleft(first)
        self._send(resp)

    def _take(self, events: list[tuple]) -> None:
        for resp, *event in events:
            if resp.ended.is_set():
                continue  # failed or cancelled: what the engine still had for it goes nowhere
            resp.unsent.append(event)
            if resp.sock is not None and not resp.waiting:
                self._send(resp)
            if len(resp.unsent) >= self._bound:
                # dead-slow consumer: cap host memory by treating it as a
                # disconnect (the engine's next sweep frees the slot)
                resp.out.cancel()
                self._end(resp)

    def _send(self, resp: _Response) -> None:
        """Put the stream's events on its socket, in order, as far as it takes them."""
        while resp.unsent:
            kind, payload, handed_s = resp.unsent[0]
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(_WRITE_ANNOTATION):
                if resp.rest is None:
                    resp.rest = _sse(kind, payload)
                try:
                    sent = resp.sock.send(resp.rest)
                except BlockingIOError:
                    sent = 0
                except OSError:
                    resp.out.cancel()  # dropped client: free the slot mid-decode
                    self._end(resp)
                    return
            t1 = time.perf_counter()
            if sent < len(resp.rest):
                resp.rest = resp.rest[sent:]
                self.deferred += 1
                _DEFERRED.inc()
                if not resp.waiting:
                    self._sel.register(resp.sock, selectors.EVENT_WRITE, resp)
                    resp.waiting = True
                return
            resp.rest = None
            resp.unsent.popleft()
            obs_metrics.observe_pair(_STREAM_WRITE, t1 - t0, _FANOUT_DELAY, t1 - handed_s)
            if kind == "tokens":
                resp.delivered += len(payload)
                self._delivered(len(payload))
            else:
                if kind == "done":  # chunks already written; only the remainder is new bytes
                    self._delivered(max(len(payload) - resp.delivered, 0))
                self._end(resp)
                return
        self._unwatch(resp)

    def _unwatch(self, resp: _Response) -> None:
        if resp.waiting:
            self._sel.unregister(resp.sock)
            resp.waiting = False

    def _end(self, resp: _Response) -> None:
        self._unwatch(resp)
        resp.unsent.clear()
        resp.rest = None
        self._open.discard(resp)
        resp.ended.set()  # its handler thread returns, and the server closes the connection


class EngineServer:
    """Thread-safe facade over one ContinuousBatcher.

    HTTP threads call ``submit()`` (enqueue + wait on a per-request stream);
    the engine thread drains the inbox, steps the batcher, fans tokens out,
    and processes cancellations/deadlines between chunks. ``stop()``
    initiates the drain.

    Load shedding: the admission inbox is BOUNDED (``max_queue``) — when
    it is full, submit() refuses with an "overloaded" error the HTTP layer
    maps to 429, so overload surfaces as fast rejection, not unbounded
    latency. What waits for one client is bounded too, on a request's queue
    and with the stream writer alike: a consumer that stops draining
    (dead-slow SSE client) trips the bound and is cancelled like a
    disconnect instead of growing host memory without limit."""

    STREAM_QUEUE_CHUNKS = 1024  # per-request bound on events not yet with the client (chunks, not tokens)

    def __init__(self, engine: ContinuousBatcher, on_fatal=None,
                 max_queue: int = 256, request_timeout_s: float = 0.0,
                 role: str = "serve"):
        self.engine = engine
        #: tier this replica serves in ("serve" = decode-capable default,
        #: "prefill" = disagg prompt tier) — advisory: /stats carries it so
        #: the per-tier health monitors and the docs' tier diagram line up
        self.role = role
        self._inbox: "queue.Queue[tuple]" = queue.Queue(maxsize=max_queue)
        #: engine-thread control channel (disagg KV export/adopt): closures
        #: that must run where the allocator + cache live. Drained at the
        #: top of every loop iteration, answered (ok, value) on a per-op box.
        self._control: "queue.Queue[tuple]" = queue.Queue()
        self._streams: dict[int, RequestStream] = {}
        #: the stream writer's share of this pass's events, handed over once (engine thread only)
        self._events: list[tuple] = []
        self.writer = StreamWriter(self.STREAM_QUEUE_CHUNKS, self.add_delivered)
        self._deadlines: dict[int, float] = {}
        self.request_timeout_s = request_timeout_s
        self._draining = threading.Event()
        self._stopped = threading.Event()
        # serializes the draining-check+enqueue in submit() against the
        # loop's final refuse-sweep: without it a request slipping between
        # the sweep and _stopped would sit in an inbox nobody reads
        self._admit_lock = threading.Lock()
        self._thread = threading.Thread(target=self._loop, name="engine", daemon=True)
        self.error: BaseException | None = None  # fatal engine failure, if any
        self._on_fatal = on_fatal
        # engine counters (read by /stats without locking: ints are atomic)
        self.started_s = time.time()
        self.tokens_out = 0         # GENERATED by the engine (fanout time)
        self.tokens_delivered = 0   # actually written to a client socket
        self.requests_done = 0
        self.requests_cancelled = 0
        self.stream_handovers = 0   # lists of events handed to the stream writer
        self._prefix_hits_exported = 0  # engine-thread watermark → registry delta
        # disagg handoff accounting (engine-thread only: export/adopt both
        # run as control ops, so plain ints need no lock)
        self.kv_handoff_exported = 0    # pages shipped toward decode replicas
        self.kv_handoff_adopted = 0     # pages adopted into this pool
        # delivered is the ONE counter with multiple writers (the stream
        # writer, and every handler thread that answers a request whole);
        # unsynchronized += would lose updates
        self._delivered_lock = threading.Lock()

    def add_delivered(self, n: int) -> None:
        with self._delivered_lock:
            self.tokens_delivered += n
        _DELIVERED.inc(n)

    def run_on_engine(self, fn, timeout_s: float = 30.0):
        """Run ``fn()`` ON the engine thread (between decode chunks) and
        return its result. The disagg KV export/adopt path: the page
        allocator and the cache arrays have exactly one owner, and a handler
        thread mutating them mid-step would race the loop's functional
        cache updates. Raises what ``fn`` raised; TimeoutError when the
        engine never picked the op up (draining / wedged)."""
        box: "queue.Queue[tuple]" = queue.Queue(1)
        self._control.put((fn, box))
        try:
            ok, val = box.get(timeout=timeout_s)
        except queue.Empty:
            raise TimeoutError("engine did not service the control op "
                               f"within {timeout_s:.0f}s") from None
        if not ok:
            raise val
        return val

    def _drain_control(self) -> None:
        """Service queued control ops (engine thread only). A failing op
        answers its caller and never takes the loop down — export/adopt
        problems are per-request errors, not engine fatals."""
        while True:
            try:
                fn, box = self._control.get_nowait()
            except queue.Empty:
                return
            try:
                box.put((True, fn()))
            except Exception as e:  # noqa: BLE001 — answered to the caller
                box.put((False, e))

    def start(self) -> "EngineServer":
        self.writer.start()
        self._thread.start()
        return self

    def submit(
        self, prompt_tokens: list[int], max_tokens: int,
        sampling: dict | None = None, timeout_s: float | None = None,
        request_id: str = "", streamed: bool = False,
    ) -> RequestStream:
        """Enqueue a request; returns the stream its events arrive on:
        ("tokens", [..]) zero or more times, then ("done", all_tokens) —
        or ("error", message). ``sampling``: per-request temperature /
        top_k / top_p overrides. ``timeout_s`` overrides the server's
        default per-request deadline (0/None → no deadline).
        ``request_id``: router-propagated id for spans/exemplars.
        ``streamed``: only the FIRST event arrives on the stream; the caller
        then owes the stream writer an ``attach`` of the client's connection
        or a ``drop`` (``out.response``), and the rest go out there."""
        out = RequestStream(self.STREAM_QUEUE_CHUNKS, request_id=request_id)
        if streamed:
            out.response = _Response(out)
        # span chain opens BEFORE the inbox put: once the engine thread can
        # see the stream, only it touches the spans
        out.open_trace()
        with self._admit_lock:
            if self._draining.is_set() or self.error is not None:
                out.put(("error", "server is draining" if self.error is None
                         else f"engine failed: {self.error}"))
                out.finish_trace("error")
                return out
            timeout = timeout_s if timeout_s is not None else self.request_timeout_s
            # the deadline clock starts at SUBMISSION, so time spent queued
            # in the admission inbox counts — exactly the overload case a
            # deadline exists for
            deadline_abs = time.time() + timeout if timeout and timeout > 0 else 0.0
            try:
                self._inbox.put_nowait((prompt_tokens, max_tokens, sampling or {},
                                        deadline_abs, out))
            except queue.Full:
                out.put(("error", "overloaded: admission queue full"))
                out.finish_trace("error")
            else:
                if streamed:  # under the lock: before the loop's last sweep closes the writer
                    self.writer.open(out.response)
        return out

    def _queue_depth(self) -> int:
        """Requests waiting for a slot: engine pending + staged prefills +
        the admission inbox. THE definition of queue depth — /stats (what
        the fleet health monitor and autoscaler consume) and the
        tony_serve_queue_depth gauge must never diverge."""
        eng = self.engine
        return len(eng.pending) + len(eng._staged) + self._inbox.qsize()

    def stats(self) -> dict[str, Any]:
        eng = self.engine
        up = max(time.time() - self.started_s, 1e-9)
        return {
            "slots_total": eng.S,
            "slots_active": eng.slots_active,
            "queue_depth": self._queue_depth(),
            "requests_done": self.requests_done,
            "requests_cancelled": self.requests_cancelled,
            "tokens_out": self.tokens_out,
            "tokens_delivered": self.tokens_delivered,
            "stream_handovers": self.stream_handovers,
            "stream_writes_deferred": self.writer.deferred,
            "tokens_per_s": round(self.tokens_out / up, 2),
            "uptime_s": round(up, 1),
            "draining": self._draining.is_set(),
            "healthy": self.error is None,
            "role": self.role,
            "kv": getattr(eng, "kv", "dense"),
            "device": device_facts(),
            **(
                {
                    "pages_live": eng.allocator.live_pages(),
                    "pages_total": eng.num_pages - 1,
                    "prefix_hit_tokens": eng.prefix_hit_tokens,
                    "kv_handoff_exported": self.kv_handoff_exported,
                    "kv_handoff_adopted": self.kv_handoff_adopted,
                }
                if getattr(eng, "kv", "dense") == "paged"
                else {}
            ),
        }

    def stop(self, timeout_s: float = 10.0) -> bool:
        """Drain: no new admissions; in-flight requests finish, and the stream
        writer puts every terminal event handed to it on its socket (or sees
        that stream fail). Returns True if both completed inside ``timeout_s``
        (False → truncated)."""
        self._draining.set()
        deadline = time.monotonic() + timeout_s
        return (self._stopped.wait(timeout_s)
                and self.writer.flushed.wait(max(deadline - time.monotonic(), 0.0)))

    def _loop(self) -> None:
        try:
            self._loop_inner()
        except BaseException as e:  # noqa: BLE001 — a dead silent engine thread
            # is the worst failure mode: every in-flight stream would block
            # forever while /healthz keeps answering ok. Record, error out
            # every stream, and tell the process (the AM supervises restarts).
            import traceback

            self.error = e
            traceback.print_exc()
            if self._streams:
                _REQUESTS_DONE.inc(len(self._streams), outcome="error")
            for out in self._streams.values():
                self._deliver(out, ("error", f"engine failed: {e}"))
                out.finish_trace("error")
            self._streams.clear()
            self._hand_over()
            if self._on_fatal is not None:
                self._on_fatal()
        finally:
            self.engine.phase.to(None)  # a pass that raised left its phase open
            # refuse anything still queued (or enqueued mid-teardown)
            with self._admit_lock:
                self._draining.set()
                while True:
                    try:
                        self._inbox.get_nowait()[-1].put(("error", "server is draining"))
                    except queue.Empty:
                        break
                while True:  # control ops must not leave their caller hanging
                    try:
                        _, box = self._control.get_nowait()
                        box.put((False, RuntimeError("engine stopped")))
                    except queue.Empty:
                        break
                self.writer.close()  # after the last hand-over and the last admission
                self._stopped.set()

    @staticmethod
    def _finish_stream(stream: RequestStream, event: tuple) -> None:
        """Deliver a TERMINAL event without ever blocking the engine thread:
        if the stream's bounded queue is full (slow consumer), evict one
        buffered chunk to make room — the handler always sees an end-of-
        stream event instead of blocking forever on a silently-dead queue."""
        event = (*event, time.perf_counter())  # the hand-over's stamp (RequestStream.handed_s)
        try:
            stream.q.put_nowait(event)
        except queue.Full:
            try:
                stream.q.get_nowait()
            except queue.Empty:
                pass
            try:
                stream.q.put_nowait(event)
            except queue.Full:
                pass  # racing consumer refilled it: it is draining, fine

    def _deliver(self, out: RequestStream, event: tuple) -> None:
        """One event of an admitted request toward its client, without ever
        blocking the engine thread. A streamed request's FIRST event goes on
        its queue like anyone's, where its handler thread waits to choose
        between an error reply and the headers; every later one joins this
        pass's hand-over to the stream writer, stamped here."""
        if out.response is not None and out.started:
            self._events.append((out.response, *event, time.perf_counter()))
        elif event[0] != "tokens":
            self._finish_stream(out, event)
        else:
            try:
                out.q.put_nowait((*event, time.perf_counter()))
            except queue.Full:
                # dead-slow consumer: cap host memory by treating it
                # as a disconnect (picked up by the next sweep)
                out.cancel()
        out.started = True

    def _hand_over(self) -> None:
        """The stream writer gets what ``_deliver`` gathered: one list, one
        wake-up, whatever the number of live streams."""
        if self._events:
            self.writer.hand(self._events)
            self._events = []
            self.stream_handovers += 1
            _HANDOVERS.inc()

    def _sweep_cancellations(self) -> None:
        """Between chunks: propagate client cancellations (disconnect, slow
        consumer) and expired deadlines into the engine — the slot/pages
        free at the next retirement flush, within one decode chunk."""
        eng = self.engine
        now = time.time()
        for rid, stream in list(self._streams.items()):
            expired = (
                rid in self._deadlines and now > self._deadlines[rid]
            )
            if stream.cancelled.is_set() or expired:
                eng.cancel(rid)
                # ALWAYS terminate the stream (the handler may still be
                # attached — slow-consumer cancels have a live socket)
                self._deliver(
                    stream,
                    ("error", "deadline exceeded" if expired
                     else "cancelled: consumer stopped draining"),
                )
                self.requests_cancelled += 1
                _REQUESTS_DONE.inc(outcome="cancelled")
                stream.finish_trace("error")
                del self._streams[rid]
                self._deadlines.pop(rid, None)
        self._hand_over()  # now, not a pass later (nothing to hand unless something was cancelled)

    def _loop_inner(self) -> None:
        eng = self.engine
        carry = None  # item pulled by the idle wait — admitted FIRST (FIFO)
        while True:
            eng.phase.to("intake")
            while True:
                if carry is not None:
                    prompt, max_tokens, sampling, deadline, out = carry
                    carry = None
                else:
                    try:
                        prompt, max_tokens, sampling, deadline, out = (
                            self._inbox.get_nowait()
                        )
                    except queue.Empty:
                        break
                if out.cancelled.is_set():
                    out.finish_trace("error")
                    continue  # client gone before the engine ever saw it
                if deadline and time.time() > deadline:
                    out.put(("error", "deadline exceeded"))
                    self.requests_cancelled += 1
                    _REQUESTS_DONE.inc(outcome="cancelled")
                    out.finish_trace("error")
                    continue  # expired while queued in the inbox
                try:
                    rid = eng.submit(prompt, max_tokens, **sampling)
                except (ValueError, TypeError) as e:
                    out.put(("error", str(e)))
                    out.finish_trace("error")
                    continue
                self._streams[rid] = out
                out.req = eng.request(rid)
                if deadline:
                    self._deadlines[rid] = deadline
            self._sweep_cancellations()
            self._drain_control()
            _QUEUE_DEPTH.set(self._queue_depth())
            # one pass: admit in the shadow of the chunk in flight (what intake
            # just submitted is prefilled and inserted BEHIND it), dispatch the
            # next chunk behind that, then decode_wait and emit the chunk that
            # was in flight (with the first tokens of the requests whose first
            # chunk it was). From here to the next dispatch (fan-out below,
            # intake above) the device has the chunk just dispatched to run
            had_work = eng.step()
            eng.phase.to("emit")
            # export the engine's prefix-reuse win as a REAL instrument, not
            # a /stats-payload-only field: the loadtest harness and the
            # portal read the registry, and "reuse happened" must be
            # observable wherever tony_serve_* metrics flow
            hits = getattr(eng, "prefix_hit_tokens", 0)
            if hits > self._prefix_hits_exported:
                _PREFIX_HITS.inc(hits - self._prefix_hits_exported)
                self._prefix_hits_exported = hits
            now_s = time.time()
            for rid, (toks, done) in eng.drain_stream().items():
                out = self._streams.get(rid)
                final = eng.done.pop(rid, None) if done else None
                if out is None:
                    continue
                if toks:
                    if out.last_fanout_s:
                        _TOKEN_LATENCY.observe((now_s - out.last_fanout_s) / len(toks))
                    else:
                        ttft = now_s - out.submitted_s
                        # worst-offender exemplars: id-carrying requests link
                        # a burning TTFT SLO straight to their trace
                        _TTFT.observe(ttft, exemplar=out.request_id or None)
                        for label, seconds in out.stages(now_s):
                            _STAGE.observe(seconds, stage=label)
                        out.begin_stage("serve.decode", ttft_s=round(ttft, 6))
                    out.last_fanout_s = now_s
                self.tokens_out += len(toks)
                if done:
                    self.requests_done += 1
                    _REQUESTS_DONE.inc(outcome="done")
                    self._deliver(out, ("done", final if final is not None else toks))
                    if out.defer_finish:
                        # disagg: the span stays open through the KV handoff;
                        # the /v1/prefill handler closes it after the ship
                        out.begin_stage("serve.handoff")
                    else:
                        out.finish_trace("ok")
                    del self._streams[rid]
                    self._deadlines.pop(rid, None)
                else:
                    self._deliver(out, ("tokens", toks))
            self._hand_over()
            if not had_work:
                if self._draining.is_set():
                    eng.phase.to(None)
                    return
                eng.phase.to("idle")
                # idle: block until the next request (or drain) arrives; the
                # pulled item is carried to the admission pass directly —
                # re-queuing it would reorder it behind later arrivals
                try:
                    carry = self._inbox.get(timeout=0.2)
                except queue.Empty:
                    pass


def _json_body(handler: BaseHTTPRequestHandler) -> dict:
    n = int(handler.headers.get("Content-Length") or 0)
    return json.loads(handler.rfile.read(n) or b"{}")


class _Handler(BaseHTTPRequestHandler):
    server_ref: EngineServer = None  # set by serve()
    tokenizer = None

    def log_message(self, *a) -> None:  # quiet
        pass

    def _reply(self, code: int, obj: Any) -> None:
        body = json.dumps(obj).encode()
        try:
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            # the caller gave up before the answer was ready (a poller's timeout while the process was
            # stalled): its loss, not a fault of the server's, and no traceback for the log
            self.close_connection = True

    def do_GET(self) -> None:  # noqa: N802
        if self.path == "/healthz":
            err = self.server_ref.error
            if err is None:
                self._reply(200, {"ok": True})
            else:
                self._reply(503, {"ok": False, "error": str(err)})
        elif self.path == "/stats":
            self._reply(200, self.server_ref.stats())
        else:
            self._reply(404, {"error": "not found"})

    def do_POST(self) -> None:  # noqa: N802
        if self.path == "/v1/prefill":
            self._handle_prefill()
            return
        if self.path == "/v1/kv/adopt":
            self._handle_adopt()
            return
        if self.path != "/v1/completions":
            self._reply(404, {"error": "not found"})
            return
        try:
            req = _json_body(self)
            if not isinstance(req, dict):
                raise ValueError("request body must be a JSON object")
            prompt = req.get("prompt_tokens")
            if prompt is None and "prompt" in req:
                if self.tokenizer is None:
                    raise ValueError("text prompts need --tokenizer; send prompt_tokens")
                prompt = self.tokenizer.encode(req["prompt"])
            if not prompt:
                raise ValueError("empty prompt")
            max_tokens = int(req.get("max_tokens", 16))
            stream = bool(req.get("stream", False))
            sampling = {
                k: (float(req[k]) if k != "top_k" else int(req[k]))
                for k in ("temperature", "top_k", "top_p")
                if req.get(k) is not None
            }
            timeout_s = (
                float(req["timeout_s"]) if req.get("timeout_s") is not None else None
            )
            if timeout_s is not None and timeout_s <= 0:
                raise ValueError(f"timeout_s must be > 0, got {timeout_s}")
            prompt = [int(t) for t in prompt]
        except (TypeError, ValueError, json.JSONDecodeError) as e:
            self._reply(400, {"error": str(e)})
            return
        request_id = (self.headers.get("X-Tony-Request-Id") or "").strip()
        out = self.server_ref.submit(prompt, max_tokens, sampling, timeout_s=timeout_s,
                                     request_id=request_id, streamed=stream)
        if stream:
            self._stream_response(out)
        else:
            self._block_response(out)

    def _handle_prefill(self) -> None:
        """Disagg prefill leg (serve/disagg.py contract): run the prompt
        through this engine for exactly ONE generated token (the prefill +
        first sample), export the finished full-prompt KV pages, POST them
        to the assigned decode replica's ``/v1/kv/adopt``, and reply with
        the first token + handoff accounting. The handoff is best-effort
        past the first token: a failed ship degrades to a decode-side
        recompute, never to a client-visible error."""
        from tony_tpu.serve import disagg

        srv = self.server_ref
        try:
            req = _json_body(self)
            if not isinstance(req, dict):
                raise ValueError("request body must be a JSON object")
            prompt = [int(t) for t in (req.get("prompt_tokens") or [])]
            if not prompt:
                raise ValueError("empty prompt")
            decode_url = str(req.get("decode_url") or "").rstrip("/")
            sampling = {
                k: (float(req[k]) if k != "top_k" else int(req[k]))
                for k in ("temperature", "top_k", "top_p")
                if req.get(k) is not None
            }
        except (TypeError, ValueError, json.JSONDecodeError) as e:
            self._reply(400, {"error": str(e)})
            return
        if getattr(srv.engine, "kv", "dense") != "paged":
            self._reply(409, {"error": "kv handoff needs a paged engine "
                                       "(--kv paged)"})
            return
        request_id = (self.headers.get("X-Tony-Request-Id") or "").strip()
        t0 = time.perf_counter()
        out = srv.submit(prompt, 1, sampling, request_id=request_id)
        out.defer_finish = True
        while True:
            kind, payload = out.get()
            if kind in ("done", "error"):
                break
        if kind == "error":
            self._error_reply(payload)
            return
        first = list(payload)
        shipped = have = pages = 0
        ship_error = ""
        try:
            exported = srv.run_on_engine(
                lambda: disagg.export_prefix_pages(srv, prompt))
            if exported is not None:
                pages = len(exported["keys"])
                if decode_url:
                    shipped, have = disagg.ship_pages(
                        decode_url, exported,
                        timeout_s=float(req.get("timeout_s") or 30.0))
        except Exception as e:  # noqa: BLE001 — degrade to decode recompute
            ship_error = str(e)[:200]
        took = time.perf_counter() - t0
        _HANDOFF_LATENCY.observe(took, exemplar=request_id or None)
        out.finish_trace("ok" if not ship_error else "error")
        resp = {
            "first_token": first[-1] if first else None,
            "pages": pages,
            "adopted": shipped,
            "already_resident": have,
            "handoff_ms": round(took * 1000, 3),
        }
        if ship_error:
            resp["ship_error"] = ship_error
        self._reply(200, resp)

    def _handle_adopt(self) -> None:
        """Adopt shipped KV pages into this replica's paged pool (the decode
        half of the handoff): alloc → scatter → register → park in the reuse
        pool, where the next matching prompt's prefix match picks them up
        instead of recomputing the prefill."""
        from tony_tpu.serve import disagg

        srv = self.server_ref
        if getattr(srv.engine, "kv", "dense") != "paged":
            self._reply(409, {"error": "kv adopt needs a paged engine"})
            return
        try:
            payload = _json_body(self)
            if not isinstance(payload, dict):
                raise ValueError("adopt body must be a JSON object")
            adopted, have = srv.run_on_engine(
                lambda: disagg.adopt_pages(srv, payload))
        except (TypeError, ValueError, KeyError, json.JSONDecodeError) as e:
            self._reply(400, {"error": str(e)})
            return
        except (TimeoutError, RuntimeError) as e:
            self._reply(503, {"error": str(e)})
            return
        self._reply(200, {"adopted": adopted, "already_resident": have})

    def _error_reply(self, payload: str) -> None:
        if "overloaded" in payload:
            # fast rejection, not unbounded latency: tell the client when
            # to come back instead of letting it camp on the socket
            body = json.dumps({"error": payload}).encode()
            self.send_response(429)
            self.send_header("Content-Type", "application/json")
            self.send_header("Retry-After", "1")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        if "deadline" in payload:
            self._reply(504, {"error": payload})
            return
        self._reply(503 if "draining" in payload else 400, {"error": payload})

    def _block_response(self, out) -> None:
        toks: list[int] = []
        while True:
            kind, payload = out.get()
            if kind == "error":
                self._error_reply(payload)
                return
            if kind == "tokens":
                toks.extend(payload)
            else:  # done → payload is the authoritative full list
                self._reply(200, {"tokens": list(payload), "finished": True})
                self.server_ref.add_delivered(len(payload))
                return

    def _stream_response(self, out) -> None:
        """SSE: one ``data: {"tokens": [...]}`` event per decode chunk, then
        ``data: {"finished": true, ...}``. This thread waits for the first
        event, answers an error that comes before the first byte as any
        request's, else sends the headers, hands the connection to the stream
        writer with that event and parks until the stream has ended: the
        writer sends every event. A send that fails there (client went away)
        CANCELS the engine request — the slot frees within one decode chunk
        instead of decoding to max_tokens for nobody."""
        writer, attached = self.server_ref.writer, False
        try:
            kind, payload = out.get()
            if kind == "error":
                self._error_reply(payload)
                return
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.end_headers()
            writer.attach(out.response, self.connection, (kind, payload, out.handed_s))
            attached = True
        finally:
            if not attached:
                out.cancel()  # nobody to decode for (a no-op after an error: that request is over)
                writer.drop(out.response)
        out.response.ended.wait()


def _register_with_am(url: str) -> None:
    """Inside a tony container, publish the endpoint through the AM
    (SURVEY.md §3.4 register_task_url path). No-op standalone."""
    host = os.environ.get(constants.ENV_AM_HOST)
    if not host:
        return
    from tony_tpu.cluster.rpc import RpcClient, RpcError

    try:
        cli = RpcClient(
            host,
            int(os.environ[constants.ENV_AM_PORT]),
            secret=os.environ.get(constants.ENV_AM_SECRET, ""),
        )
        cli.call(
            "register_task_url",
            job_name=os.environ.get(constants.ENV_JOB_NAME, "serve"),
            index=int(os.environ.get(constants.ENV_TASK_INDEX, "0")),
            url=url,
            attempt=int(os.environ.get("TONY_RESTART_ATTEMPT", "0")),
        )
        cli.close()
    except (RpcError, OSError, ValueError):
        pass  # AM unreachable: serving still works, just unadvertised


def _metrics_pump(srv: EngineServer, stop: threading.Event, interval_s: float = 2.0) -> None:
    """Drop engine stats into ENV_TRAIN_METRICS_FILE (atomic rename) — the
    executor's metrics loop ships them to the AM, so the portal charts
    serving throughput with the machinery training already uses. The obs
    metrics-registry snapshot (queue-depth gauge, TTFT / per-token-latency
    histograms, delivered-tokens counter) drops next to it at
    ``<train-metrics-file>.obs`` — the same contract as the training child's
    loop.py — so serving instruments reach the executor's push_metrics
    piggyback and the portal's /metrics."""
    path = os.environ.get(constants.ENV_TRAIN_METRICS_FILE)
    if not path:
        return
    step = 0
    last_tokens = 0
    last_t = time.time()
    while not stop.wait(interval_s):
        step += 1
        now, toks = time.time(), srv.tokens_out
        rate = (toks - last_tokens) / max(now - last_t, 1e-9)
        last_tokens, last_t = toks, now
        st = srv.stats()
        line = {
            "step": step,
            "tokens_per_s": round(rate, 2),
            "slots_active": st["slots_active"],
            "queue_depth": st["queue_depth"],
            "requests_done": st["requests_done"],
        }
        try:
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(line, f)
            os.replace(tmp, path)
        except OSError:
            pass
        snap = [m for m in obs_metrics.REGISTRY.snapshot() if m["samples"]]
        if snap:
            try:
                tmp = path + ".obs.tmp"
                with open(tmp, "w") as f:
                    json.dump(snap, f)
                os.replace(tmp, path + ".obs")
            except OSError:
                pass


def _drain_watch(srv: EngineServer, stop: threading.Event,
                 budget_s: float = 10.0) -> None:
    """Replica half of the cooperative-preemption drain contract
    (docs/scheduling.md): poll ``<TONY_TRAIN_METRICS_FILE>.drain`` — the
    control file the executor's DrainCourier drops when the AM's heartbeat
    fan-out reaches this task — at the same cadence UrgentSaveSignal uses.

    On a notice: stop admitting (``/stats`` flips ``draining`` so the fleet
    HealthMonitor sheds this replica and the SessionTable re-pins its
    sessions), finish in-flight streams (``EngineServer.stop``), then ack
    via :func:`_ack_drain` so the courier reports ``report_drain_saved``
    and the AM can yield without burning its margin. Like the training
    loop after UrgentSaveSignal, the process then PARKS — yielding is the
    AM's move; its SIGTERM finds an already-drained server and the exit is
    immediate and clean, well inside the deadline."""
    from tony_tpu.obs import introspect

    path = os.environ.get(constants.ENV_TRAIN_METRICS_FILE)
    if not path:
        return
    try:
        poll_ms = int(os.environ.get(constants.ENV_PROFILE_POLL_MS, "500") or 500)
    except ValueError:
        poll_ms = 500
    interval_s = max(poll_ms, 50) / 1000.0
    acked: set[str] = set()
    while not stop.wait(interval_s):
        ctl = introspect.read_json(path + introspect.DRAIN_CONTROL_SUFFIX)
        req_id = str((ctl or {}).get("req_id") or "")
        if not req_id or req_id in acked:
            continue
        if not acked:
            obs_logging.warning(
                f"[tony-serve] drain notice {req_id} (cooperative preemption) "
                "— refusing new admissions, finishing in-flight streams")
            if not srv.stop(timeout_s=budget_s):
                obs_logging.warning(
                    f"[tony-serve] drain {req_id} timed out with "
                    f"{len(srv._streams)} request(s) in flight, "
                    f"{srv.writer.open_count()} stream(s) unwritten — truncating")
        # later requests against an already-drained server (a gang-wide
        # preemption following a scale-down drain) ack instantly — stop()
        # is idempotent and the AM must not burn its margin waiting
        _ack_drain(req_id, step=srv.requests_done)
        acked.add(req_id)
        obs_logging.info(
            f"[tony-serve] drain {req_id} acknowledged "
            f"({srv.requests_done} request(s) completed) — parked, "
            "awaiting the AM's yield")


def _ack_drain(req_id: str, step: int) -> None:
    """Publish the drain done-file (atomic) the courier reports back. For a
    serving replica the 'saved step' is the completed-request count — there
    is no checkpoint to land, the state that matters (in-flight streams) is
    already drained by the time this is called."""
    from tony_tpu.obs import introspect

    path = os.environ.get(constants.ENV_TRAIN_METRICS_FILE)
    if not path:
        return
    try:
        introspect.write_json_atomic(
            path + introspect.DRAIN_DONE_SUFFIX,
            {"req_id": req_id, "step": int(step)})
    except OSError:
        pass  # best-effort: the AM's yield margin covers a lost ack


def _resolve_kv(args) -> str:
    """Resolve ``--kv`` when unset. Defaults to paged (shared-prefix wins,
    3x slot capacity at equal HBM, decode at parity in the builders' r5 run, older than this code) but
    only where paged can actually run, which the CLI cannot see and this
    process can: dense under TP (per-device page indirection), on CPU
    backends without Pallas interpret mode (the paged kernel has no XLA
    fallback), and when --max_len doesn't fit the page geometry (dense
    accepts any multiple of 128; a defaulted paged would turn that into a
    startup error). An EXPLICIT --kv paged keeps the hard errors."""
    if args.kv is not None:
        return args.kv
    if getattr(args, "tp", 1) > 1:
        return "dense"
    if jax.default_backend() != "tpu" and not interpret():
        return "dense"  # the paged decode kernel is TPU-only (or interpreted)
    if args.page_len <= 0 or args.max_len % args.page_len:
        obs_logging.warning(
            f"[tony-serve] kv defaulting to dense: max_len {args.max_len} "
            f"is not a positive multiple of page_len {args.page_len} "
            f"(pass --kv paged --page_len <divisor> for paged)")
        return "dense"
    return "paged"


def init(key, cfg) -> dict:
    """Random weights for a preset: its own module's ``init``."""
    return registry.module_of(cfg).init(key, cfg)


def build_engine(args) -> ContinuousBatcher:
    args.kv = _resolve_kv(args)
    cfg = registry.presets()[args.preset]
    if args.hf:
        from tony_tpu.models.convert import from_hf

        params, cfg = from_hf(args.hf)
    else:
        params = init(jax.random.PRNGKey(args.seed), cfg)
    if args.int8:
        from tony_tpu.ops.quant import quantize_tree

        params, _, _ = quantize_tree(params)
    mesh = None
    if getattr(args, "tp", 1) > 1:
        from tony_tpu.parallel import MeshSpec

        # model-axis TP decode over the FIRST tp visible devices: the host
        # may expose more chips than the mesh uses (MeshSpec.build requires
        # an exact count, so hand it the slice explicitly)
        if len(jax.devices()) < args.tp:
            raise ValueError(
                f"--tp {args.tp} needs {args.tp} devices but only "
                f"{len(jax.devices())} are visible"
            )
        mesh = MeshSpec(model=args.tp).build(devices=jax.devices()[:args.tp])
    return ContinuousBatcher(
        params, cfg,
        num_slots=args.slots, max_len=args.max_len, eos_id=args.eos_id,
        temperature=args.temperature, top_k=args.top_k,
        decode_chunk=args.decode_chunk, attn=args.attn,
        prefill_chunk=args.prefill_chunk,
        kv=args.kv, page_len=args.page_len,
        num_pages=args.num_pages if args.num_pages > 0 else None,
        mesh=mesh,
    )


def main(argv: list[str] | None = None) -> int:
    obs_startup.begin("serve")  # main_entered: the start-up account's first stamp of this process
    # under a tony container the executor exports the structured-logging
    # contract; outside it the helpers echo to the console only
    obs_logging.init_from_env(role="serve")
    p = argparse.ArgumentParser(
        prog="tony-serve", description="continuous-batching HTTP inference server"
    )
    p.add_argument("--preset", default="tiny", choices=sorted(registry.presets()),
                   help="model preset (random init unless --hf)")
    p.add_argument("--hf", default="", help="HuggingFace checkpoint dir to load")
    p.add_argument("--tokenizer", default="", help="tokenizer dir for text prompts")
    p.add_argument("--int8", action="store_true", help="int8 weight-only quantization")
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--max-len", type=int, default=512)
    p.add_argument("--decode-chunk", type=int, default=8)
    p.add_argument("--prefill-chunk", type=int, default=0)
    p.add_argument("--attn", default="auto", choices=["auto", "ragged", "bucketed"])
    p.add_argument("--kv", default=None, choices=["dense", "paged"],
                   help="paged: block-paged KV pool + shared-prefix reuse. "
                        "Default: paged where it can run (TPU, tp=1, "
                        "page-aligned max_len), else dense — see _resolve_kv")
    p.add_argument("--page-len", type=int, default=256)
    p.add_argument("--num-pages", type=int, default=0,
                   help="page pool size (0 = dense-equivalent: slots x max_len)")
    p.add_argument("--tp", type=int, default=1,
                   help="model-axis tensor parallelism for the decode step "
                        "(shards projections + KV heads over the mesh; dense kv only)")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--eos-id", type=int, default=-1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--host", default="",
                   help="bind AND advertise this host; default: bind all "
                        "interfaces, advertise the container's reachable "
                        "address (loopback deployments stay on loopback)")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--url-file", default="", help="write the bound URL here once serving")
    p.add_argument("--admission-queue", type=int, default=256,
                   help="bounded admission inbox; a full inbox returns 429")
    p.add_argument("--request-timeout-s", type=float, default=0.0,
                   help="default per-request deadline (0 = none); requests "
                        "may override via the timeout_s body field")
    p.add_argument("--role", default="serve", choices=["serve", "prefill"],
                   help="disagg tier this replica serves in: 'prefill' "
                        "replicas take /v1/prefill legs and ship KV pages; "
                        "'serve' replicas decode (and adopt shipped pages). "
                        "Both answer the full API — the role is advisory "
                        "(stats/logs), routing is the router's job")
    p.add_argument("--slo-ttft-ms", type=float,
                   default=float(os.environ.get(constants.ENV_SLO_TTFT_MS, "0") or 0),
                   help="align a TTFT histogram bucket edge to this SLO "
                        "threshold (exact good/bad counts; default from "
                        "TONY_SLO_TTFT_MS, 0 = off)")
    args = p.parse_args(argv)

    if os.environ.get(constants.ENV_METRICS_ENABLED) == "0":
        obs_metrics.set_enabled(False)  # job opted out (tony.metrics.enabled)
    if args.slo_ttft_ms > 0:
        _TTFT.ensure_bucket(args.slo_ttft_ms / 1000.0)
    # per-request span chain sink (no-op unless the executor exported the
    # tracing contract — the training child's init_from_env, reused)
    obs_trace.init_from_env()
    cache_dir = enable_compile_cache()
    jax.devices()
    obs_startup.stamp("devices_ready")  # JAX imported, PJRT client up
    done = threading.Event()
    engine = build_engine(args)
    # the stage ends when weights, caches and page pool are on the device, not when they were asked for
    jax.block_until_ready((engine.params, engine.cache))
    srv = EngineServer(
        engine, on_fatal=done.set,
        max_queue=args.admission_queue, request_timeout_s=args.request_timeout_s,
        role=args.role,
    ).start()
    obs_startup.stamp("weights_ready")  # and the engine's threads up
    tokenizer = None
    if args.tokenizer:
        from transformers import AutoTokenizer

        tokenizer = AutoTokenizer.from_pretrained(args.tokenizer)
    handler = type("Handler", (_Handler,), {"server_ref": srv, "tokenizer": tokenizer})
    if args.host:
        bind_host, adv_host = args.host, args.host
    else:
        # same reachability rule as the executor's URL registration: a
        # remote pool needs a routable address, a loopback deployment must
        # NOT advertise a hostname other containers can't resolve
        from tony_tpu.cluster.executor import _own_host

        bind_host = "0.0.0.0"
        adv_host = _own_host(os.environ.get(constants.ENV_AM_HOST, "127.0.0.1"))
    httpd = ThreadingHTTPServer((bind_host, args.port), handler)
    url = f"http://{adv_host}:{httpd.server_address[1]}"
    if args.url_file:
        tmp = args.url_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(url)
        os.replace(tmp, args.url_file)
    _register_with_am(url)
    obs_startup.stamp("registered")
    stop_metrics = threading.Event()
    threading.Thread(
        target=_metrics_pump, args=(srv, stop_metrics), daemon=True
    ).start()

    def _drain(*_):
        done.set()

    signal.signal(signal.SIGTERM, _drain)
    signal.signal(signal.SIGINT, _drain)
    # drain budget for SIGTERM and preemption notices alike: the container's
    # SIGTERM→SIGKILL window (tony.task.kill-grace-ms) minus teardown margin
    grace_ms = float(os.environ.get(constants.ENV_KILL_GRACE_MS, "0") or 0)
    budget_s = max(grace_ms / 1000 - 1.0, 2.0) if grace_ms else 10.0
    # cooperative-preemption watcher: DrainCourier notice → drain, ack, park
    stop_drain_watch = threading.Event()
    threading.Thread(
        target=_drain_watch, args=(srv, stop_drain_watch, budget_s), daemon=True
    ).start()
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    obs_logging.info(f"[tony-serve] {url} role={args.role} preset={args.preset} "
                     f"slots={args.slots} max_len={args.max_len} kv={args.kv} "
                     f"compile_cache={cache_dir}")
    # poll rather than block forever: a process-directed SIGTERM may be
    # delivered to a busy worker thread, in which case CPython only runs the
    # Python-level handler once the MAIN thread executes bytecode again — a
    # main thread parked in an untimed Event.wait() never does, and the
    # signal (and the whole drain) would be swallowed. Waking twice a second
    # bounds drain-start latency without relying on who the kernel picked.
    while not done.wait(0.5):
        pass
    if srv.error is not None:
        obs_logging.error(f"[tony-serve] engine failed: {srv.error}")
        srv.stop(timeout_s=budget_s)  # the loop is over: only the open streams' error events are waited for
        httpd.shutdown()
        return 1
    # graceful drain: refuse new work, finish in-flight, then exit 0.
    obs_logging.info(f"[tony-serve] draining (budget {budget_s:.0f}s)")
    if not srv.stop(timeout_s=budget_s):
        obs_logging.warning(f"[tony-serve] drain timed out with {len(srv._streams)} "
                            f"request(s) in flight, {srv.writer.open_count()} stream(s) "
                            f"unwritten — truncating")
    stop_drain_watch.set()
    stop_metrics.set()
    httpd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
