"""``tony serve`` jobtype tests: the AM-supervised inference endpoint.

VERDICT r3 #2's done-when: a job submission stands up the serving engine
behind a streaming HTTP endpoint, the URL registers through the AM
(SURVEY.md §3.4 register_task_url path), a client streams completions
mid-run, engine metrics reach the AM task info (the portal's data source),
and kill drains gracefully.
"""

import json
import os
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import pytest

from tony_tpu import constants
from tony_tpu.config import keys
from tony_tpu.cluster.client import Client
from tony_tpu.cluster.session import JobStatus
from tony_tpu.cli.notebook import wait_for_task_url
from tony_tpu.cli.serve import build_serve_config
from tony_tpu.models.llama import LLAMA_TINY, init
from tony_tpu.models.serving import ContinuousBatcher
from tony_tpu.models.serving_http import EngineServer


def tiny_engine(**kw):
    params = init(jax.random.PRNGKey(0), LLAMA_TINY)
    defaults = dict(num_slots=2, max_len=64, decode_chunk=4)
    defaults.update(kw)
    return ContinuousBatcher(params, LLAMA_TINY, **defaults)


def http_server(srv, sndbuf=None):
    """A bare ThreadingHTTPServer around an EngineServer — the HTTP layer
    without the tony job spine (for handler-level tests). ``sndbuf``: shrink
    every connection's send buffer, so that a client that stops reading
    fills it within a few KB."""
    from http.server import ThreadingHTTPServer

    from tony_tpu.models.serving_http import _Handler

    def setup(self):
        if sndbuf:
            self.request.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, sndbuf)
        _Handler.setup(self)

    handler = type("Handler", (_Handler,), {"server_ref": srv, "tokenizer": None, "setup": setup})
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, f"http://127.0.0.1:{httpd.server_address[1]}"


def post_raw(url, obj, timeout=120):
    """POST returning (status, parsed-json) — does NOT raise on 4xx/5xx."""
    req = urllib.request.Request(
        url, json.dumps(obj).encode(), {"Content-Type": "application/json"}
    )
    try:
        resp = urllib.request.urlopen(req, timeout=timeout)
        return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def post(url, obj, timeout=120):
    return post_raw(url, obj, timeout)[1]


# ---------------------------------------------------------------------------
# Unit: the thread-safe engine facade
# ---------------------------------------------------------------------------
class TestEngineServer:
    def test_concurrent_requests_match_direct_engine(self):
        # direct engine (same seed/params) is the parity reference
        ref = tiny_engine()
        rids = [ref.submit([1 + i, 2, 3], max_new_tokens=5) for i in range(3)]
        expect = ref.run()

        srv = EngineServer(tiny_engine()).start()
        outs = [srv.submit([1 + i, 2, 3], max_tokens=5) for i in range(3)]
        got = []
        for out in outs:
            toks = []
            while True:
                kind, payload = out.get(timeout=120)
                assert kind != "error", payload
                if kind == "done":
                    got.append(list(payload))
                    break
                toks.extend(payload)
        assert got == [expect[r] for r in rids]
        srv.stop()

    def test_drain_refuses_new_work(self):
        srv = EngineServer(tiny_engine()).start()
        out = srv.submit([1, 2], max_tokens=4)
        kind = None
        while kind != "done":
            kind, payload = out.get(timeout=120)
        srv.stop()
        refused = srv.submit([1], max_tokens=1)
        kind, payload = refused.get(timeout=10)
        assert kind == "error" and "draining" in payload

    def test_invalid_request_surfaces_error(self):
        srv = EngineServer(tiny_engine(max_len=16)).start()
        out = srv.submit([1] * 20, max_tokens=10)  # exceeds max_len
        kind, payload = out.get(timeout=60)
        assert kind == "error" and "max_len" in payload
        srv.stop()

    def test_engine_failure_errors_streams_and_marks_unhealthy(self):
        """A dead-silent engine thread is the worst failure mode: streams
        must error out, health must flip, and the fatal hook must fire."""
        srv = EngineServer(tiny_engine())
        fired = threading.Event()
        srv._on_fatal = fired.set
        srv.engine.step = lambda: (_ for _ in ()).throw(RuntimeError("device lost"))
        srv.start()
        out = srv.submit([1, 2], max_tokens=4)
        kind, payload = out.get(timeout=60)
        assert kind == "error" and "device lost" in payload
        assert fired.wait(timeout=10)
        assert srv.error is not None and not srv.stats()["healthy"]
        # post-failure submissions are refused immediately
        kind, payload = srv.submit([1], max_tokens=1).get(timeout=10)
        assert kind == "error"

    def test_malformed_prompt_tokens_is_400_not_dropped_connection(self):
        """Non-integer prompt_tokens must map to a 400 JSON error, not an
        uncaught ValueError in the handler thread (ADVICE r4)."""
        srv = EngineServer(tiny_engine()).start()
        httpd, url = http_server(srv)
        try:
            for bad in (["x", "y"], "abc", [[1]], [None]):
                code, body = post_raw(
                    url + "/v1/completions",
                    {"prompt_tokens": bad, "max_tokens": 2}, timeout=30,
                )
                assert code == 400 and "error" in body, (bad, code, body)
            # a valid-JSON NON-OBJECT body must also be a 400, not a crash
            for bad_body in ([1, 2, 3], "abc", 7):
                code, body = post_raw(url + "/v1/completions", bad_body, timeout=30)
                assert code == 400 and "error" in body, (bad_body, code, body)
            # a valid request on the same server still works
            code, body = post_raw(
                url + "/v1/completions", {"prompt_tokens": [1, 2], "max_tokens": 2},
                timeout=120,
            )
            assert code == 200 and body["finished"]
        finally:
            httpd.shutdown()
            srv.stop()

    def test_overload_returns_429_not_unbounded_latency(self):
        """VERDICT r4 #4: the admission inbox is bounded; a full inbox is a
        fast 429 with Retry-After, not a silently growing queue."""
        srv = EngineServer(tiny_engine(), max_queue=1)  # loop NOT started
        first = srv.submit([1, 2], max_tokens=2)   # occupies the inbox
        second = srv.submit([3, 4], max_tokens=2)  # refused immediately
        kind, payload = second.get(timeout=5)
        assert kind == "error" and "overloaded" in payload
        # HTTP layer maps it to 429 + Retry-After
        httpd, url = http_server(srv)
        try:
            req = urllib.request.Request(
                url + "/v1/completions",
                json.dumps({"prompt_tokens": [5], "max_tokens": 1}).encode(),
                {"Content-Type": "application/json"},
            )
            try:
                urllib.request.urlopen(req, timeout=10)
                raise AssertionError("expected 429")
            except urllib.error.HTTPError as e:
                assert e.code == 429
                assert e.headers.get("Retry-After") == "1"
                assert "overloaded" in json.loads(e.read())["error"]
        finally:
            httpd.shutdown()
        assert first  # silence unused warning

    def test_request_deadline_cancels_and_frees_slot(self):
        """A per-request deadline errors the stream AND cancels the engine
        request (slot freed), instead of decoding to max_tokens."""
        srv = EngineServer(tiny_engine(num_slots=1, max_len=512)).start()
        out = srv.submit([1, 2, 3], max_tokens=400, timeout_s=0.5)
        kind, payload = None, None
        deadline = time.time() + 60
        while time.time() < deadline:
            kind, payload = out.get(timeout=60)
            if kind != "tokens":
                break
        assert kind == "error" and "deadline" in payload, (kind, payload)
        # the slot frees: a fresh request completes promptly
        out2 = srv.submit([4, 5], max_tokens=3)
        kind2 = None
        while kind2 != "done":
            kind2, payload2 = out2.get(timeout=120)
            assert kind2 != "error", payload2
        st = srv.stats()
        assert st["requests_cancelled"] >= 1
        srv.stop()

    def test_dropped_sse_client_frees_slot_and_stats_split(self):
        """A disconnected SSE client is detected at the next chunk write;
        the engine request is CANCELLED (slot freed long before max_tokens)
        and /stats separates generated from delivered tokens."""
        import socket

        srv = EngineServer(tiny_engine(num_slots=1, max_len=512)).start()
        httpd, url = http_server(srv)
        port = httpd.server_address[1]
        try:
            body = json.dumps({"prompt_tokens": [1, 2, 3], "max_tokens": 400,
                               "stream": True}).encode()
            sock = socket.create_connection(("127.0.0.1", port), timeout=30)
            sock.sendall(
                b"POST /v1/completions HTTP/1.1\r\nHost: x\r\n"
                b"Content-Type: application/json\r\n"
                + f"Content-Length: {len(body)}\r\n\r\n".encode() + body
            )
            first = sock.recv(256)  # status line + first bytes of the stream
            assert b"200" in first
            sock.close()            # vanish mid-stream
            deadline = time.time() + 90
            while time.time() < deadline:
                if not srv.engine.running and srv.stats()["requests_cancelled"] >= 1:
                    break
                time.sleep(0.1)
            st = srv.stats()
            assert st["requests_cancelled"] >= 1, st
            assert not srv.engine.running
            # far fewer than max_tokens were generated, and fewer delivered
            assert st["tokens_out"] < 400, st
            assert 0 < st["tokens_delivered"] < st["tokens_out"], st
        finally:
            httpd.shutdown()
            srv.stop()

    def test_drain_stream_reports_each_request_once(self):
        eng = tiny_engine()
        r1 = eng.submit([1, 2], max_new_tokens=3)
        seen: dict[int, list[int]] = {}
        finished: set[int] = set()
        while eng.step():
            for rid, (toks, done) in eng.drain_stream().items():
                seen.setdefault(rid, []).extend(toks)
                if done:
                    assert rid not in finished
                    finished.add(rid)
        for rid, (toks, done) in eng.drain_stream().items():
            seen.setdefault(rid, []).extend(toks)
            if done:
                assert rid not in finished
                finished.add(rid)
        assert finished == {r1}
        assert seen[r1] == eng.done[r1]


class TestEngineServerDrain:
    """The drain contract (the CLI docstring's promise, now asserted):
    SIGTERM → in-flight streaming requests FINISH, new admissions are
    refused, exit code 0."""

    def test_facade_drain_finishes_in_flight_work(self):
        srv = EngineServer(tiny_engine(num_slots=2, max_len=128)).start()
        out = srv.submit([1, 2, 3], max_tokens=20)
        # wait until the request is actually decoding (first tokens flowed)
        kind, payload = out.get(timeout=120)
        assert kind == "tokens", payload
        got = list(payload)
        done = {}
        stopper = threading.Thread(
            target=lambda: done.update(clean=srv.stop(timeout_s=60)), daemon=True)
        stopper.start()
        # the in-flight stream must run to completion THROUGH the drain
        while True:
            kind, payload = out.get(timeout=120)
            assert kind != "error", payload
            if kind == "done":
                assert len(payload) == 20
                break
            got.extend(payload)
        stopper.join(timeout=90)
        assert done.get("clean") is True  # drain completed inside its budget
        refused = srv.submit([4], max_tokens=1)
        kind, payload = refused.get(timeout=10)
        assert kind == "error" and "draining" in payload

    @pytest.mark.e2e
    def test_sigterm_drains_streaming_request_and_exits_zero(self, tmp_path):
        """The real process contract: run serving_http standalone, SIGTERM it
        mid-stream, read the stream to completion, and take exit code 0."""
        import signal
        import subprocess

        url_file = tmp_path / "url"
        proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "tony_tpu.models.serving_http",
             "--preset", "tiny", "--slots", "2", "--max-len", "256",
             "--decode-chunk", "4", "--host", "127.0.0.1",
             "--url-file", str(url_file)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            # generous SIGTERM→SIGKILL window: the drain must finish the
            # 200-token stream even on a loaded CI box
            env={**os.environ, constants.ENV_KILL_GRACE_MS: "60000"},
        )
        try:
            deadline = time.time() + 180
            while time.time() < deadline and not url_file.exists():
                assert proc.poll() is None, proc.stdout.read().decode()
                time.sleep(0.2)
            assert url_file.exists(), "server never wrote its URL"
            url = url_file.read_text().strip()

            req = urllib.request.Request(
                url + "/v1/completions",
                json.dumps({"prompt_tokens": [1, 2], "max_tokens": 200,
                            "stream": True}).encode(),
                {"Content-Type": "application/json"},
            )
            resp = urllib.request.urlopen(req, timeout=120)
            events = []
            # after the first chunk arrives, the request is in flight: drain
            line = resp.readline().decode().strip()
            while line == "":
                line = resp.readline().decode().strip()
            assert line.startswith("data: ")
            events.append(json.loads(line[6:]))
            proc.send_signal(signal.SIGTERM)

            # new admissions are refused while the stream is still live
            code = None
            refuse_deadline = time.time() + 30
            while time.time() < refuse_deadline:
                try:
                    status, body = post_raw(url + "/v1/completions",
                                            {"prompt_tokens": [9], "max_tokens": 1},
                                            timeout=30)
                except Exception:  # noqa: BLE001 — server may already be gone
                    break
                if status == 503 and "draining" in body["error"]:
                    code = status
                    break
                time.sleep(0.05)
            assert code == 503, "drain never started refusing admissions"

            # ... and the in-flight stream runs to completion
            for line in resp:
                line = line.decode().strip()
                if line.startswith("data: "):
                    events.append(json.loads(line[6:]))
                    if events[-1].get("finished"):
                        break
            assert events[-1].get("finished") and len(events[-1]["tokens"]) == 200
            assert proc.wait(timeout=60) == 0  # graceful drain exits clean
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)


# ---------------------------------------------------------------------------
# The stream writer: one hand-over a pass, one thread on every SSE socket
# ---------------------------------------------------------------------------
def open_stream(port, obj, rcvbuf=None, timeout=90):
    """POST a streamed completion on a raw socket: what the client reads back
    is the server's bytes, nothing parsed or buffered in between. ``rcvbuf``:
    a receive buffer so small that a client that does not read stalls the
    server's sends after a few KB."""
    sock = socket.socket()
    if rcvbuf:
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    sock.settimeout(timeout)
    sock.connect(("127.0.0.1", port))
    body = json.dumps({**obj, "stream": True}).encode()
    sock.sendall(b"POST /v1/completions HTTP/1.1\r\nHost: x\r\nContent-Type: application/json\r\n"
                 + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    return sock


def read_to_end(sock, raw=b""):
    """(status line, body) of a response the server ends by closing (``raw``:
    what was read of it already)."""
    while chunk := sock.recv(65536):
        raw += chunk
    sock.close()
    head, _, body = raw.partition(b"\r\n\r\n")
    return head.split(b"\r\n")[0], body


def sse_events(body):
    assert body.endswith(b"\n\n")
    frames = body[:-2].split(b"\n\n")
    assert all(f.startswith(b"data: ") for f in frames)
    return [json.loads(f[6:]) for f in frames]


def wait_for(cond, seconds=60):
    deadline = time.time() + seconds
    while not cond():
        assert time.time() < deadline, "condition not met in time"
        time.sleep(0.01)


def counter_value(name):
    from tony_tpu.obs import metrics as obs_metrics

    for m in obs_metrics.REGISTRY.snapshot():
        if m["name"] == name:
            return sum(x.get("value", x.get("count", 0)) for x in m["samples"])
    raise AssertionError(f"{name} is not registered")


class TestStreamWriter:
    def streamed_bodies(self, n, max_tokens):
        """``n`` identical greedy streamed requests, all in the inbox before
        the engine's first pass (so all are admitted in it and decode in
        lockstep): each one's body, and the counters' change."""
        srv = EngineServer(tiny_engine(num_slots=4))
        httpd, url = http_server(srv)
        before = [counter_value("tony_serve_stream_handovers_total"),
                  counter_value("tony_serve_stream_write_seconds")]
        try:
            socks = [open_stream(httpd.server_address[1], {"prompt_tokens": [4, 5, 6], "max_tokens": max_tokens})
                     for _ in range(n)]
            wait_for(lambda: srv._inbox.qsize() == n)
            srv.start()
            got = [read_to_end(sock) for sock in socks]
        finally:
            httpd.shutdown()
            httpd.server_close()
            assert srv.stop()
        assert all(status == b"HTTP/1.0 200 OK" for status, _ in got)
        assert srv.stats()["stream_handovers"] == counter_value("tony_serve_stream_handovers_total") - before[0]
        return [body for _, body in got], srv.stats()["stream_handovers"], (
            counter_value("tony_serve_stream_write_seconds") - before[1])

    def test_concurrent_streams_read_a_lone_streams_bytes_and_a_pass_is_one_handover(self):
        (alone,), handovers_alone, writes_alone = self.streamed_bodies(1, 13)
        events = sse_events(alone)
        # one event a chunk in the engine's order (the first token, then chunks of 4), then the whole answer
        assert [len(e["tokens"]) for e in events] == [1, 4, 4, 13] and events[-1]["finished"] is True
        assert [list(e) for e in events[:-1]] == [["tokens"]] * 3
        assert sum((e["tokens"] for e in events[:-1]), []) == events[-1]["tokens"][:9]
        assert alone == b"".join(b"data: " + json.dumps(e).encode() + b"\n\n" for e in events)
        # the first event goes through the request's own queue, every later one through a hand-over
        assert (handovers_alone, writes_alone) == (3, 4)
        bodies, handovers, writes = self.streamed_bodies(4, 13)
        assert bodies == [alone] * 4  # byte for byte
        assert (handovers, writes) == (3, 16)  # one a pass, not one a stream

    def test_a_client_that_stops_reading_is_cancelled_at_the_bound_and_delays_nobody(self, monkeypatch):
        """Over HTTP: one client never reads, with buffers so small that the
        server's sends soon stop being taken; a second one reads its whole
        answer meanwhile. The stalled one is cancelled when ``STREAM_QUEUE_CHUNKS``
        events wait for its socket, and its slot frees."""
        monkeypatch.setattr(EngineServer, "STREAM_QUEUE_CHUNKS", 8)
        deferred0 = counter_value("tony_serve_stream_writes_deferred_total")
        srv = EngineServer(tiny_engine(num_slots=2, max_len=1024)).start()
        httpd, url = http_server(srv, sndbuf=1)
        port = httpd.server_address[1]
        try:
            stalled = open_stream(port, {"prompt_tokens": [1, 2, 3], "max_tokens": 1000}, rcvbuf=1)
            wait_for(lambda: srv.stats()["slots_active"] == 1)
            status, body = read_to_end(open_stream(port, {"prompt_tokens": [7, 8], "max_tokens": 200}))
            events = sse_events(body)
            assert status == b"HTTP/1.0 200 OK" and events[-1]["finished"] and len(events[-1]["tokens"]) == 200
            wait_for(lambda: srv.stats()["requests_cancelled"] == 1 and not srv.engine.running, 120)
            st = srv.stats()
            assert st["tokens_out"] < 1200, st  # far short of the stalled request's 1000 tokens
            assert st["stream_writes_deferred"] >= 1
            assert counter_value("tony_serve_stream_writes_deferred_total") - deferred0 == st["stream_writes_deferred"]
            # its connection is closed on it: what it reads now is what the socket had taken, and the end
            status, body = read_to_end(stalled)
            assert status == b"HTTP/1.0 200 OK" and b'"finished"' not in body
        finally:
            httpd.shutdown()
            httpd.server_close()
            assert srv.stop()

    def test_a_stalled_socket_holds_up_no_other_stream_for_even_one_handover(self):
        """The writer alone, on socket pairs, one step at a time: a peer that
        never reads takes part of a large event and no more; every event of the
        other stream is on its socket before the next list is handed, and the
        stalled stream ends when ``bound`` events wait for it, not before."""
        from tony_tpu.models.serving_http import RequestStream, StreamWriter, _Response

        delivered = []
        writer = StreamWriter(6, delivered.append)
        writer.start()
        pairs = [socket.socketpair() for _ in range(2)]
        for ours, peer in pairs:
            ours.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1)
            peer.settimeout(30)
        (slow_sock, _), (fast_sock, fast_peer) = pairs
        slow, fast = _Response(RequestStream()), _Response(RequestStream())
        big = list(range(20000))  # some 130 KB as an event: more than the pair's buffers hold
        for resp, sock, first in ((slow, slow_sock, big), (fast, fast_sock, [0])):
            writer.open(resp)
            writer.attach(resp, sock, ("tokens", first, time.perf_counter()))
        buf = b""

        def fast_reads(event):
            nonlocal buf
            want = b"data: " + json.dumps(event).encode() + b"\n\n"
            while len(buf) < len(want):
                buf += fast_peer.recv(65536)
            assert buf[:len(want)] == want
            buf = buf[len(want):]

        fast_reads({"tokens": [0]})
        for i in range(1, 5):  # the stalled stream holds its first event and these four: 5 of a bound of 6
            writer.hand([(slow, "tokens", [i], time.perf_counter()), (fast, "tokens", [i], time.perf_counter())])
            fast_reads({"tokens": [i]})
            assert not slow.ended.is_set() and not slow.out.cancelled.is_set()
        assert writer.deferred >= 1 and writer.open_count() == 2
        writer.hand([(slow, "tokens", [5], time.perf_counter()), (fast, "done", [0, 1, 2, 3, 4, 5], time.perf_counter())])
        fast_reads({"finished": True, "tokens": [0, 1, 2, 3, 4, 5]})
        assert slow.ended.wait(30) and slow.out.cancelled.is_set()  # like a disconnect
        assert fast.ended.wait(30) and not fast.out.cancelled.is_set()
        assert delivered == [1, 1, 1, 1, 1, 1]  # the fast stream's tokens: five events and the answer's remainder
        writer.close()
        assert writer.flushed.wait(30) and writer.open_count() == 0
        for ours, peer in pairs:
            ours.close()
            peer.close()

    def test_an_error_before_the_first_event_is_a_plain_reply_with_its_status(self):
        """A streamed request that fails before its first byte gets what any
        request gets: 400, 504, 429, 503, a JSON body, no SSE."""
        srv = EngineServer(tiny_engine(max_len=16)).start()
        httpd, url = http_server(srv)
        port = httpd.server_address[1]

        def answer(obj):
            status, body = read_to_end(open_stream(port, obj))
            return int(status.split()[1]), json.loads(body)["error"]

        try:
            code, err = answer({"prompt_tokens": [1] * 20, "max_tokens": 10})  # longer than max_len
            assert code == 400 and "max_len" in err
            code, err = answer({"prompt_tokens": [1, 2], "max_tokens": 4, "timeout_s": 1e-6})
            assert code == 504 and "deadline" in err
            # and a stream on the same server still works afterwards
            status, body = read_to_end(open_stream(port, {"prompt_tokens": [1, 2], "max_tokens": 3}))
            assert sse_events(body)[-1]["finished"]
            assert srv.stop()
            code, err = answer({"prompt_tokens": [1, 2], "max_tokens": 4})
            assert code == 503 and "draining" in err
        finally:
            httpd.shutdown()
            httpd.server_close()
        full = EngineServer(tiny_engine(), max_queue=1)  # loop NOT started: the inbox stays full
        full.submit([1, 2], max_tokens=2)
        httpd, url = http_server(full)
        try:
            status, body = read_to_end(open_stream(httpd.server_address[1], {"prompt_tokens": [5], "max_tokens": 1}))
            assert status == b"HTTP/1.0 429 Too Many Requests" and "overloaded" in json.loads(body)["error"]
        finally:
            httpd.shutdown()
            httpd.server_close()

    def test_an_engine_failure_mid_stream_ends_every_open_stream_with_an_error_event(self):
        srv = EngineServer(tiny_engine(num_slots=2, max_len=512))
        fired = threading.Event()
        srv._on_fatal = fired.set
        srv.start()
        httpd, url = http_server(srv)
        try:
            socks = [open_stream(httpd.server_address[1], {"prompt_tokens": [1 + i, 2], "max_tokens": 400})
                     for i in range(2)]
            heads = [sock.recv(4096) for sock in socks]  # headers (and first bytes): both are streaming
            assert all(h.startswith(b"HTTP/1.0 200 OK") for h in heads)
            srv.engine.step = lambda: (_ for _ in ()).throw(RuntimeError("device lost"))
            for head, sock in zip(heads, socks):
                events = sse_events(read_to_end(sock, head)[1])
                assert "device lost" in events[-1]["error"] and all(list(e) == ["tokens"] for e in events[:-1])
            assert fired.wait(10) and srv.error is not None
            assert srv.stop()  # the loop is over and the writer has nothing left
        finally:
            httpd.shutdown()
            httpd.server_close()

    def test_the_drain_waits_for_a_terminal_event_still_with_the_writer(self):
        """A client that reads nothing until its request is finished: the
        answer's last events cannot all be on its socket, so the drain is not
        over when the engine is; it is once the client has read them."""
        srv = EngineServer(tiny_engine(num_slots=1, max_len=1024)).start()
        httpd, url = http_server(srv, sndbuf=1)
        try:
            sock = open_stream(httpd.server_address[1], {"prompt_tokens": [1, 2, 3], "max_tokens": 900}, rcvbuf=1)
            wait_for(lambda: srv.stats()["requests_done"] == 1, 150)
            done = {}
            stopper = threading.Thread(target=lambda: done.update(clean=srv.stop(timeout_s=120)), daemon=True)
            stopper.start()
            assert srv._stopped.wait(30)  # the engine's part of the drain is over ...
            stopper.join(0.3)
            assert stopper.is_alive() and srv.writer.open_count() == 1  # ... the writer's is not
            assert srv.stats()["stream_writes_deferred"] >= 1 and srv.stats()["requests_cancelled"] == 0
            status, body = read_to_end(sock)
            events = sse_events(body)
            assert events[-1]["finished"] and len(events[-1]["tokens"]) == 900
            chunks = sum((e["tokens"] for e in events[:-1]), [])
            assert chunks == events[-1]["tokens"][:len(chunks)] and len(chunks) >= 896  # nothing dropped
            stopper.join(60)
            assert done.get("clean") is True and srv.writer.open_count() == 0
        finally:
            httpd.shutdown()
            httpd.server_close()


def test_a_caller_that_hung_up_before_its_reply_leaves_no_traceback(capsys):
    """A poller whose timeout passed while the process was stalled (a profiler's
    export holds the interpreter for seconds) has closed its socket when the
    reply is written: the handler ends that connection and prints nothing (a
    harness that reads the replica's log for "Traceback" must not find one)."""
    from tony_tpu.models.serving_http import _Handler

    class Gone:
        def write(self, _):
            raise BrokenPipeError(32, "Broken pipe")

        def flush(self):
            pass

    handler = _Handler.__new__(_Handler)
    handler.request_version, handler.requestline, handler.client_address = "HTTP/1.1", "GET /stats HTTP/1.1", ("127.0.0.1", 1)
    handler.wfile, handler.close_connection = Gone(), False
    handler._reply(200, {"slots_active": 3})
    assert handler.close_connection is True
    out, err = capsys.readouterr()
    assert "Traceback" not in out + err


class TestServingInstruments:
    """Satellite of PR 3's obs wiring: EngineServer records queue depth,
    TTFT, per-token latency, and delivered tokens into the process metrics
    registry (the same registry the .obs drop ships to /metrics)."""

    @staticmethod
    def _snap(name):
        from tony_tpu.obs import metrics as obs_metrics

        for m in obs_metrics.REGISTRY.snapshot():
            if m["name"] == name:
                return m["samples"]
        return []

    @classmethod
    def _hist_count(cls, name):
        return sum(s["count"] for s in cls._snap(name))

    @classmethod
    def _counter(cls, name, **labels):
        for s in cls._snap(name):
            if all(s["labels"].get(k) == str(v) for k, v in labels.items()):
                return s["value"]
        return 0.0

    def test_request_lifecycle_reaches_registry(self):
        ttft0 = self._hist_count("tony_serve_ttft_seconds")
        tok0 = self._hist_count("tony_serve_token_latency_seconds")
        done0 = self._counter("tony_serve_requests_total", outcome="done")
        delivered0 = self._counter("tony_serve_tokens_delivered_total")

        srv = EngineServer(tiny_engine()).start()
        httpd, url = http_server(srv)
        try:
            # 2 chunks (8 tokens / decode_chunk 4): TTFT once, token-latency
            # at least once, delivered counts the client-visible bytes
            r = post(url + "/v1/completions",
                     {"prompt_tokens": [1, 2, 3], "max_tokens": 8})
            assert r["finished"] and len(r["tokens"]) == 8
        finally:
            httpd.shutdown()
            srv.stop()
        assert self._hist_count("tony_serve_ttft_seconds") == ttft0 + 1
        assert self._hist_count("tony_serve_token_latency_seconds") >= tok0 + 1
        assert self._counter("tony_serve_requests_total", outcome="done") == done0 + 1
        assert self._counter("tony_serve_tokens_delivered_total") == delivered0 + 8
        # the queue-depth gauge exists (set every engine tick)
        assert self._snap("tony_serve_queue_depth"), "queue-depth gauge never set"


# ---------------------------------------------------------------------------
# E2E: serve jobtype through the client → AM → executor spine
# ---------------------------------------------------------------------------
@pytest.mark.e2e
class TestServeE2E:
    def test_serve_job_end_to_end(self, tmp_tony_root):
        config, _ = build_serve_config([
            "--preset", "tiny", "--slots", "2", "--max_len", "64",
            "--decode_chunk", "4",
        ])
        config.set(keys.STAGING_ROOT, str(tmp_tony_root))
        config.set(keys.AM_MONITOR_INTERVAL_MS, "50")
        config.set(keys.TASK_METRICS_INTERVAL_MS, "500")
        client = Client(config)
        handle = client.submit()
        result: dict = {}
        mon = threading.Thread(
            target=lambda: result.update(final=client.monitor_application(handle, quiet=True)),
            daemon=True,
        )
        mon.start()
        try:
            # 1. the endpoint registers its URL through the AM (§3.4 path)
            target = wait_for_task_url(
                handle, constants.SERVE_JOB_NAME, timeout_s=120
            )
            assert target is not None, "serve task never registered a URL"
            url = f"http://{target[0]}:{target[1]}"

            # 2. blocking completion + greedy determinism
            r = post(url + "/v1/completions",
                     {"prompt_tokens": [1, 2, 3], "max_tokens": 6})
            assert r["finished"] and len(r["tokens"]) == 6
            r2 = post(url + "/v1/completions",
                      {"prompt_tokens": [1, 2, 3], "max_tokens": 6})
            assert r2["tokens"] == r["tokens"]

            # 3. streaming completion mid-run
            req = urllib.request.Request(
                url + "/v1/completions",
                json.dumps({"prompt_tokens": [4, 5], "max_tokens": 8,
                            "stream": True}).encode(),
                {"Content-Type": "application/json"},
            )
            events = []
            with urllib.request.urlopen(req, timeout=120) as resp:
                assert resp.headers["Content-Type"].startswith("text/event-stream")
                for line in resp:
                    line = line.decode().strip()
                    if line.startswith("data: "):
                        events.append(json.loads(line[6:]))
                        if events[-1].get("finished"):
                            break
            assert events[-1]["finished"] and len(events[-1]["tokens"]) == 8

            # 4. engine metrics flow into the AM task info (portal's source)
            rpc = handle.rpc(timeout_s=10)
            assert rpc is not None
            deadline = time.time() + 30
            metrics = {}
            while time.time() < deadline:
                infos = rpc.call("get_task_infos")
                m = next(
                    (i.get("metrics") for i in infos
                     if i["name"] == constants.SERVE_JOB_NAME), None
                ) or {}
                metrics = m.get("train") or {}
                if metrics.get("requests_done", 0) >= 3:
                    break
                time.sleep(0.2)
            assert metrics.get("requests_done", 0) >= 3, metrics
            assert "tokens_per_s" in metrics and "slots_active" in metrics
        finally:
            # 5. kill → graceful drain → KILLED verdict
            Client.kill(handle)
            mon.join(timeout=60)
        assert result.get("final") == JobStatus.KILLED, handle.final_status()


# ---------------------------------------------------------------------------
# Capstone: the two halves compose — a high-priority serving job PREEMPTS a
# training job through the multi-tenant pool, serves, and hands capacity back
# ---------------------------------------------------------------------------
from tests.test_pool_queue import small_pool  # noqa: F401, E402 — fixture reuse


@pytest.mark.e2e
class TestServeComposesWithPool:
    @pytest.mark.slow
    def test_high_priority_serve_preempts_training(
        self, tmp_tony_root, small_pool, tmp_path  # noqa: F811
    ):
        from tests.test_pool import pool_conf
        from tests.test_pool_queue import marker_script, submit_async

        svc = small_pool  # one 4 GB agent + preemption on (shared fixture)
        h1 = h2 = None
        try:
            # low-priority "training" job: first incarnation parks forever;
            # the post-preemption restart (marker present) exits clean
            script, marker = marker_script(tmp_path, "trainee.py")
            h1, t1, r1 = submit_async(tmp_tony_root, pool_conf(svc, {
                "tony.worker.instances": "1", "tony.worker.memory": "3g",
                keys.APPLICATION_PRIORITY: "0",
                keys.EXECUTES: f"{sys.executable} {script}",
            }))
            deadline = time.time() + 30
            while time.time() < deadline and not marker.exists():
                time.sleep(0.05)
            assert marker.exists(), "training job never started"

            # high-priority serving job into the SAME full pool
            serve_conf, _ = build_serve_config([
                "--preset", "tiny", "--slots", "2", "--max_len", "64",
            ])
            serve_conf.set(keys.STAGING_ROOT, str(tmp_tony_root))
            for k, v in pool_conf(svc, {}).items():
                serve_conf.set(k, v)
            serve_conf.set(keys.APPLICATION_PRIORITY, "5")
            serve_conf.set(keys.jobtype_key(constants.SERVE_JOB_NAME, keys.MEMORY_SUFFIX), "3g")
            c2 = Client(serve_conf)
            h2 = c2.submit()
            r2: dict = {}
            t2 = threading.Thread(
                target=lambda: r2.update(final=c2.monitor_application(h2, quiet=True)),
                daemon=True,
            )
            t2.start()

            # the serve job preempts the trainee, comes up, and serves
            target = wait_for_task_url(h2, constants.SERVE_JOB_NAME, timeout_s=180)
            assert target is not None, "serve endpoint never registered (preemption failed?)"
            url = f"http://{target[0]}:{target[1]}"
            r = post(url + "/v1/completions",
                     {"prompt_tokens": [1, 2, 3], "max_tokens": 4})
            assert r["finished"] and len(r["tokens"]) == 4

            # hand capacity back: kill the serve job; the preempted training
            # job re-queues, restarts from the top, and completes clean
            Client.kill(h2)
            t2.join(timeout=90)
            assert r2.get("final") == JobStatus.KILLED
            h2 = None  # terminal: no cleanup kill needed
            t1.join(timeout=120)
            assert r1.get("final") == JobStatus.SUCCEEDED
            h1 = None
        finally:
            # a failed assertion must not leak detached AMs (and their
            # sleeping executors) into the rest of the pytest session
            for h in (h1, h2):
                if h is not None:
                    try:
                        Client.kill(h)
                    except Exception:  # noqa: BLE001 — best-effort teardown
                        pass


class TestKvDefaultResolution:
    """--kv unset resolves in the SERVER process (where the backend is
    visible), to paged only where paged can actually run (r5 review
    findings: the old CLI-side paged default broke CPU pools without
    interpret mode and turned page-misaligned --max_len into startup
    errors)."""

    @staticmethod
    def _args(**kw):
        import types

        d = dict(kv=None, tp=1, max_len=512, page_len=256)
        d.update(kw)
        return types.SimpleNamespace(**d)

    def test_resolution_matrix(self, monkeypatch):
        from tony_tpu.models.serving_http import _resolve_kv

        # the harness backend is cpu + interpret (conftest) → paged
        assert _resolve_kv(self._args()) == "paged"
        assert _resolve_kv(self._args(tp=2)) == "dense"
        assert _resolve_kv(self._args(max_len=640)) == "dense"
        assert _resolve_kv(self._args(kv="dense")) == "dense"
        # explicit paged is passed through even where the default
        # would decline it (the engine then raises its own hard error)
        assert _resolve_kv(self._args(kv="paged", tp=2)) == "paged"
        # cpu WITHOUT interpret mode: the paged kernel cannot run
        monkeypatch.delenv("TONY_PALLAS_INTERPRET", raising=False)
        assert _resolve_kv(self._args()) == "dense"

    def test_cli_forwards_only_explicit_kv(self):
        import shlex

        from tony_tpu.cli.serve import build_serve_config

        cfg, _ = build_serve_config([])
        assert "--kv" not in cfg.get("tony.serve.command")
        cfg, _ = build_serve_config(["--kv", "paged"])
        cmd = shlex.split(cfg.get("tony.serve.command"))
        assert cmd[cmd.index("--kv") + 1] == "paged"
