"""Control-plane RPC: length-framed JSON over TCP.

Analog of the reference's ``tony-core/.../tony/rpc/`` (``ApplicationRpc`` over
Hadoop protobuf RPC + ``MetricsRpc``; SURVEY.md §2.1, §2.6). The traffic is
low-rate control-plane only — register/heartbeat/spec/result — so a tiny
threaded server with a shared-secret auth token is the idiomatic analog; the
data plane never touches this path (it rides ICI/DCN inside XLA).

Wire format: 4-byte big-endian length, then a UTF-8 JSON object.
Request:  {"method": str, "params": {...}, "auth": str[, "trace": {"t","s"}]}
Response: {"ok": true, "result": ...} | {"ok": false, "error": str}

Observability (docs/observability.md): when tracing is enabled the client
injects its span context as the optional ``trace`` field and the server
parents its handler span on it — causal links cross the RPC boundary in-band.
Old servers ignore the extra field; when tracing is off (the default) the
request is byte-identical to before and no span is allocated. Latency
histograms and retry counters record into the process metrics registry
unconditionally (control-plane rate).
"""

from __future__ import annotations

import json
import random
import socket
import socketserver
import struct
import threading
import time
from typing import TYPE_CHECKING, Any, Callable

from tony_tpu.obs import metrics as _metrics
from tony_tpu.obs import trace as _trace

if TYPE_CHECKING:
    from tony_tpu.chaos import ChaosContext

_LEN = struct.Struct(">I")
MAX_FRAME = 64 * 1024 * 1024

_CLIENT_LATENCY = _metrics.histogram(
    "tony_rpc_client_latency_seconds",
    "RPC client round-trip latency (successful calls)", labelnames=("method",))
_CLIENT_ERRORS = _metrics.counter(
    "tony_rpc_client_errors_total",
    "RPC client calls that raised (connect/transport/remote error)", labelnames=("method",))
_SERVER_LATENCY = _metrics.histogram(
    "tony_rpc_server_latency_seconds",
    "RPC server dispatch latency (auth + handler)", labelnames=("method",))
_SERVER_ERRORS = _metrics.counter(
    "tony_rpc_server_errors_total",
    "RPC dispatches answered with an error frame", labelnames=("method",))
_RETRY_ATTEMPTS = _metrics.counter(
    "tony_rpc_retry_attempts_total",
    "failed attempts inside call_with_retry", labelnames=("method",))
_RETRY_BACKOFF = _metrics.counter(
    "tony_rpc_retry_backoff_seconds_total",
    "total backoff sleep inside call_with_retry", labelnames=("method",))
_RECONNECTS = _metrics.counter(
    "tony_rpc_reconnects_total",
    "client sockets re-established transparently after a broken/stale "
    "persistent connection (each is a fresh TCP handshake the server pays)",
    labelnames=("method",))


class RpcError(RuntimeError):
    """Remote method raised, or protocol violation."""


def _send_frame(sock: socket.socket, obj: Any) -> None:
    payload = json.dumps(obj).encode("utf-8")
    if len(payload) > MAX_FRAME:
        raise RpcError(f"frame too large: {len(payload)}")
    sock.sendall(_LEN.pack(len(payload)) + payload)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed connection")
        buf.extend(chunk)
    return bytes(buf)


def _recv_frame(sock: socket.socket) -> Any:
    (length,) = _LEN.unpack(_recv_exact(sock, _LEN.size))
    if length > MAX_FRAME:
        raise RpcError(f"frame too large: {length}")
    return json.loads(_recv_exact(sock, length))


class RpcServer:
    """Threaded RPC server dispatching to registered methods.

    The AM (ApplicationRpcServer analog) registers its handlers and runs this
    next to its event loop; handlers must be thread-safe (session lock).
    """

    def __init__(self, host: str = "127.0.0.1", port: int = 0, secret: str = ""):
        self._methods: dict[str, Callable[..., Any]] = {}
        self._secret = secret
        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self) -> None:  # one connection may issue many calls
                sock = self.request
                try:
                    while True:
                        req = _recv_frame(sock)
                        _send_frame(sock, outer._dispatch(req))
                except (ConnectionError, OSError):
                    return

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server((host, port), Handler)
        self._thread = threading.Thread(target=self._server.serve_forever, name="rpc-server", daemon=True)

    def _dispatch(self, req: Any) -> dict[str, Any]:
        t0 = time.perf_counter()
        name = ""
        try:
            if not isinstance(req, dict):
                raise RpcError("malformed request")
            if self._secret and req.get("auth") != self._secret:
                raise RpcError("authentication failed")
            name = req.get("method", "")
            method = self._methods.get(name)
            if method is None:
                raise RpcError(f"unknown method: {name!r}")
            params = req.get("params") or {}
            tr = _trace.get()
            if tr is None:  # disabled: the incoming trace field (if any) is ignored
                result = method(**params)
            else:
                ctx = req.get("trace") or {}
                with tr.span(f"rpc.server:{name}", kind="server",
                             parent_id=ctx.get("s")):
                    result = method(**params)
            _SERVER_LATENCY.observe(time.perf_counter() - t0, method=name)
            return {"ok": True, "result": result}
        except Exception as e:  # noqa: BLE001 — fault isolation at the RPC boundary
            _SERVER_ERRORS.inc(method=name or "?")
            _SERVER_LATENCY.observe(time.perf_counter() - t0, method=name or "?")
            return {"ok": False, "error": f"{type(e).__name__}: {e}"}

    def register(self, name: str, fn: Callable[..., Any]) -> None:
        self._methods[name] = fn

    def register_object(self, obj: Any, names: list[str]) -> None:
        for n in names:
            self.register(n, getattr(obj, n))

    def start(self) -> None:
        self._thread.start()

    @property
    def address(self) -> tuple[str, int]:
        return self._server.server_address[:2]

    def stop(self) -> None:
        if self._thread.is_alive():
            # shutdown() blocks on the serve_forever loop acknowledging; only
            # safe when that loop is actually running
            self._server.shutdown()
        self._server.server_close()


class RpcClient:
    """Blocking client over ONE persistent connection, with transparent
    broken-pipe reconnect and retry helpers.

    (ApplicationRpcClient analog; executors and the monitoring client use
    it.) The socket opened by the first call is reused for every subsequent
    call — the server's handler loop serves many calls per connection — so
    the per-second heartbeat path costs one TCP handshake per executor
    LIFETIME, not per beat. A call that finds the cached socket dead (AM
    restarted, idle timeout, connection reset) reconnects once and retries
    transparently, counted in ``tony_rpc_reconnects_total``.
    """

    def __init__(
        self,
        host: str,
        port: int,
        secret: str = "",
        timeout_s: float = 10.0,
        chaos: "ChaosContext | None" = None,
    ):
        self.host, self.port, self.secret = host, port, secret
        self.timeout_s = timeout_s
        #: optional fault-injection context (tony.chaos.*); None on the
        #: production path — every injection is guarded on it
        self.chaos = chaos
        self._sock: socket.socket | None = None
        self._lock = threading.Lock()

    def _connect(self) -> socket.socket:
        if self._sock is None:
            s = socket.create_connection((self.host, self.port), timeout=self.timeout_s)  # lint: disable=blocking-under-lock — the client lock deliberately serializes the ONE socket (request/response framing); a connect races nothing else
            s.settimeout(self.timeout_s)
            self._sock = s
        return self._sock

    def close(self) -> None:
        with self._lock:
            if self._sock is not None:
                try:
                    self._sock.close()
                finally:
                    self._sock = None

    def retarget(self, host: str, port: int, secret: str | None = None) -> None:
        """Re-point this client at a MOVED server (work-preserving AM
        takeover republishes ``am_info`` with a fresh port + secret). The
        stale socket is dropped; the next call reconnects to the new
        address. Thread-safe against in-flight calls (same lock)."""
        with self._lock:
            if self._sock is not None:
                try:
                    self._sock.close()
                finally:
                    self._sock = None
            self.host, self.port = host, int(port)
            if secret is not None:
                self.secret = secret

    def call(self, method: str, **params: Any) -> Any:
        tr = _trace.get()
        if tr is None:  # disabled fast path: no span objects, no trace field
            return self._observed_call(method, params, None)
        with tr.span(f"rpc.client:{method}", kind="client") as sp:
            return self._observed_call(
                method, params, {"t": sp.trace_id, "s": sp.span_id}
            )

    def _observed_call(
        self, method: str, params: dict[str, Any], trace_ctx: dict[str, str] | None
    ) -> Any:
        t0 = time.perf_counter()
        try:
            with self._lock:
                reconnecting = False
                for attempt in (0, 1):  # one transparent reconnect on a stale socket
                    had_cached = self._sock is not None
                    try:
                        if self.chaos is not None:
                            # may sleep (rpc-delay) or raise (rpc-drop/blackhole)
                            self.chaos.rpc_before_send(method, self.timeout_s)
                        sock = self._connect()
                        req: dict[str, Any] = {"method": method, "params": params, "auth": self.secret}
                        if trace_ctx is not None:
                            req["trace"] = trace_ctx
                        _send_frame(sock, req)
                        if self.chaos is not None and self.chaos.rpc_sever_after_send(method):
                            sock.close()  # response lost mid-call (server may have executed)
                        resp = _recv_frame(sock)
                        if reconnecting:
                            # only now was a broken PERSISTENT connection
                            # actually re-established — initial-connect
                            # failures and failed retries are not handshakes
                            # the server paid
                            _RECONNECTS.inc(method=method)
                        break
                    except (ConnectionError, OSError):
                        self._sock = None
                        if attempt:
                            raise
                        reconnecting = had_cached
                    except BaseException:
                        # a user's interrupt (or a frame that does not parse)
                        # between request and response leaves the answer
                        # unread on the persistent connection, where the NEXT
                        # call would take it for its own: `tony serve`'s
                        # monitor, interrupted mid-poll, read the kill's
                        # answer as task infos and died with exit 1 after a
                        # clean drain (ROADMAP D8)
                        if self._sock is not None:
                            self._sock.close()
                            self._sock = None
                        raise
                if not resp.get("ok"):
                    raise RpcError(resp.get("error", "unknown remote error"))
                result = resp.get("result")
        except Exception:
            _CLIENT_ERRORS.inc(method=method)
            raise
        _CLIENT_LATENCY.observe(time.perf_counter() - t0, method=method)
        return result

    def call_with_retry(
        self,
        method: str,
        *,
        retries: int = 30,
        delay_s: float = 0.2,
        max_delay_s: float = 2.0,
        deadline_s: float | None = None,
        **params: Any,
    ) -> Any:
        """Retry through AM startup races / transient connect failures.

        Exponential backoff with FULL jitter (sleep ~ U[0, min(max_delay_s,
        delay_s * 2^attempt)]) so a restarted gang's executors don't hammer a
        recovering AM in lockstep, bounded by ``deadline_s`` of overall wall
        time when given — a caller with a contract timeout (registration,
        final-result report) fails crisply instead of retrying past it.
        """
        start = time.monotonic()
        last: Exception | None = None
        for attempt in range(retries):
            try:
                return self.call(method, **params)
            except (ConnectionError, OSError, RpcError) as e:
                last = e
                _RETRY_ATTEMPTS.inc(method=method)
                _trace.add_event("rpc.retry", method=method, attempt=attempt, error=str(e)[:200])
                if attempt + 1 >= retries:
                    break
                cap = min(max_delay_s, delay_s * (2 ** min(attempt, 32)))
                sleep = random.uniform(0, cap)
                if deadline_s is not None:
                    remaining = deadline_s - (time.monotonic() - start)
                    if remaining <= 0:
                        raise RpcError(
                            f"{method} deadline {deadline_s:.1f}s exceeded "
                            f"after {attempt + 1} attempts: {last}"
                        ) from last
                    sleep = min(sleep, remaining)
                _RETRY_BACKOFF.inc(sleep, method=method)
                time.sleep(sleep)
        raise RpcError(f"{method} failed after {retries} retries: {last}")


# Canonical ApplicationRpc method names (reference iface, SURVEY.md §2.1)
APPLICATION_RPC_METHODS = [
    "register_worker_spec",
    "get_cluster_spec",
    "register_execution_result",
    "resync_task",           # post-takeover re-attach (idempotent, epoch-fenced)
    "register_tensorboard_url",
    "register_task_url",
    "task_executor_heartbeat",
    "get_task_infos",
    "get_application_status",
    "finish_application",
    "push_metrics",          # MetricsRpc analog
    "get_metrics",           # process metrics-registry snapshot (obs/metrics.py)
    "push_client_metrics",   # submitter-side registry (fleet router) re-exported by get_metrics
    "resize_jobtype",        # elastic retarget of tony.<type>.instances (autoscaler / tony resize)
    "register_spare",        # hot-spare executor announces itself (tony.elastic.spares)
    "poll_spare_assignment", # parked spare polls for a gang-slot promotion
    "start_profile",         # arm an on-demand profiler capture (tony profile)
    "get_profile_status",    # per-task capture status for the in-flight request
    "report_profile_status", # executors report delivery/capture back to the AM
    "report_drain_saved",    # executors report the child's urgent pre-preemption checkpoint
    "request_task_drain",    # drain ONE task (autoscaler pre-scale-down lever); idempotent poll
    "get_goodput",           # live goodput ledger + straggler skew + active alerts
    "get_slo",               # SLO objectives: budgets, burn rates, exemplars (obs/slo.py)
]
