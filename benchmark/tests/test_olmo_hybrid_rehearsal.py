"""The olmo_hybrid family's rehearsal: `tiny-olmo-hybrid.serve` end to end on the
CPU through run.py (sessions whose later turns hit their pages and a restored
snapshot), the comparison's control at the tiny size, and the traffic
generator's `sessions` path against a stub endpoint. Run with the suite through
tests/test_olmo_hybrid.py, or alone:
`JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_olmo_hybrid_rehearsal.py -q`.
"""

import http.server
import json
import os
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TINY = "tiny-olmo-hybrid"


def test_the_rehearsal_cell_runs_end_to_end_on_the_cpu(tmp_path):
    """`tiny-olmo-hybrid.serve` through run.py: the `tony serve` path, the router,
    the replica registered through the family's hook (its prefill shapes warmed
    before the server starts), chunked prefill cut at page edges, decode through
    the paged pool and the carried state under the interpreter, three sessions of
    three turns in the traffic, and the harness's own comparison with the
    reference: `correct`, with snapshots taken and restored inside the window and a
    share of the window's prompt tokens hit. Its own time limit: 300 s."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT, "HOME": str(tmp_path), "TMPDIR": str(tmp_path)}
    proc = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", TINY + ".serve",
                           "--seed", str(2 ** 31 + 50), "--seconds", "3", "--trace", "0"],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 3, proc.stdout[-3000:]
    assert last["device"]["platform"] == "cpu" and "serve_out_tok_s" in last["metrics"]
    assert "repeated greedy request identical: True" in proc.stdout
    ctl = os.path.join(ROOT, ".bench_work", TINY + ".serve", "out", "ctl")
    snaps = []
    for tag in ("open", "close"):
        with open(os.path.join(ctl, f"snap.{tag}.json")) as f:
            snaps.append({m["name"]: m["samples"] for m in json.load(f)["metrics"]})
    opened, closed = snaps
    events = {s["labels"]["event"]: s["value"] for s in closed["tony_serve_state_snapshots_total"]}
    assert events["taken"] >= 3 and events["restored"] >= 2, events
    # prefix_hit_pct.serve over the window, as readers/registry_share reads it: above zero
    total = lambda snap, name: sum(s["value"] for s in snap.get(name, []))
    hit = total(closed, "tony_serve_prefix_hit_tokens_total") - total(opened, "tony_serve_prefix_hit_tokens_total")
    filled = total(closed, "tony_serve_prefill_tokens_total") - total(opened, "tony_serve_prefill_tokens_total")
    assert hit > 0 and 100.0 * hit / (hit + filled) > 20.0, (hit, filled)


def test_the_control_lies_far_from_the_reference_and_the_program_on_it():
    """The float8 control teacher-forced through the float32 reference reads a
    gap where the float32 program reads none: what the cell's limit has to tell apart."""
    sys.path.insert(0, BENCH)
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np

        import check
        import spec
        from chipside import seed_weights

        sizes = spec.model_sizes(spec.config(TINY), "serve-1chip")
        params = seed_weights(sizes, 11)
        R = __import__("families").reference(sizes)
        prompt = np.random.default_rng(5).integers(1, sizes["vocab"], 40).tolist()
        forward = jax.jit(lambda p, seq: R.forward(p, seq, sizes, "f32", 32))
        seq, toks = list(prompt), []
        for _ in range(8):                                                             # the reference's own greedy answer
            logits = np.asarray(forward(params, jnp.asarray(seq + [0] * (96 - len(seq)), jnp.int32)))[len(seq) - 1]
            toks.append(int(np.argmax(logits)))
            seq.append(toks[-1])
        result = check.check_serve(params, sizes, [{"prompt": prompt, "tokens": toks}], control=True, pad_seq=32, pad_answer=16)
        assert result["worst_gap"] < 1e-4 and result["argmax_agree"] == 8
        assert result["control_worst_gap"] > 0.05, result
    finally:
        sys.path.remove(BENCH)


# -- the generator's sessions, against a stub endpoint --------------------------------------------------

class _Stub(http.server.BaseHTTPRequestHandler):
    """Streams `max_tokens` tokens back: the prompt's length, then 1, 2, ...; logs what it was sent."""

    log: list = []
    fail_after: int | None = None

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        type(self).log.append({"t": time.time(), "session": self.headers["X-Tony-Session"], "prompt": body["prompt_tokens"],
                               "max_tokens": body["max_tokens"]})
        if type(self).fail_after is not None and len(type(self).log) > type(self).fail_after:
            self.send_response(500)
            self.end_headers()
            return
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.end_headers()
        toks = [len(body["prompt_tokens"])] + list(range(1, body["max_tokens"]))
        for part in (toks[:2], toks[2:]):
            self.wfile.write(b"data: " + json.dumps({"tokens": part}).encode() + b"\n\n")
            self.wfile.flush()
        self.wfile.write(b"data: " + json.dumps({"finished": True, "tokens": toks}).encode() + b"\n\n")

    def log_message(self, *args):
        pass


def _stub(fail_after=None):
    handler = type("Handler", (_Stub,), {"log": [], "fail_after": fail_after})
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server, handler, f"http://127.0.0.1:{server.server_address[1]}"


def _traffic_module():
    sys.path.insert(0, BENCH)
    try:
        import traffic
    finally:
        sys.path.remove(BENCH)
    return traffic


def test_a_sessions_later_turns_resend_the_conversation():
    """Two callers, sessions of three turns: turn k + 1 sends turn k's prompt, the
    answer that came back and `turn_tokens` new ids, `think_s` after the reply
    and not before; a session stops after `turns`; a caller then starts the
    next planned session."""
    T = _traffic_module()
    server, handler, url = _stub()
    try:
        mix = {"draw_seed": 3, "arrivals": {"process": "closed", "clients": 2, "ramp_s": 0.1},
               "sessions": {"turns": 3, "turn_tokens": 5, "think_s": 0.15},
               "prompt_len": {"dist": "fixed", "value": 11}, "answer_len": {"dist": "fixed", "value": 4}}
        planned = T.plan(mix, 9, 1.0, 50)
        client = T.Client(url, mix, 50, 9)
        client.run_closed(planned, 2, 0.1)
        time.sleep(1.2)
        client.stop.set()
        assert client.join(10) == 0
    finally:
        server.shutdown()
    by_session = {}
    for rec in client.records:
        assert not rec.error and rec.done_t is not None
        by_session.setdefault(rec.planned.session, []).append(rec)
    whole = [recs for recs in by_session.values() if len(recs) == 3]
    assert len(whole) >= 2 and all(len(recs) <= 3 for recs in by_session.values())    # stops after `turns`
    for recs in whole:
        assert [r.planned.turn for r in recs] == [0, 1, 2] and len(recs[0].planned.prompt) == 11
        for prev, nxt in zip(recs, recs[1:]):
            sent = nxt.planned.prompt
            assert sent[:len(prev.planned.prompt) + 4] == prev.planned.prompt + prev.tokens      # the prompt and the answer again
            assert len(sent) == len(prev.planned.prompt) + 4 + 5 and all(1 <= t < 50 for t in sent[-5:])
            assert nxt.sent_t >= prev.done_t + 0.15 - 1e-3 and nxt.due_t == prev.done_t + 0.15   # think_s from the reply
            assert nxt.planned.max_tokens == 4 and nxt.planned.session == prev.planned.session
    # the stub saw what the records say was sent, a session's turns under one session header
    seen = {}
    for entry in handler.log:
        seen.setdefault(entry["session"], []).append(entry["prompt"])
    assert all(seen[s][:len(recs)] == [r.planned.prompt for r in recs] for s, recs in by_session.items())
    assert len(by_session) > 2                                                          # a caller went on to its next session


def test_a_session_stops_at_stop_and_after_an_error():
    """`stop` is looked at when a reply arrives: set while a session thinks, the
    turn it was about to send is its last (the window's closing waits for one
    request a caller, not for a session). A turn that fails ends its session:
    nothing is resent on top of an answer that never came."""
    T = _traffic_module()
    mix = {"draw_seed": 3, "arrivals": {"process": "closed", "clients": 1, "ramp_s": 0.0},
           "sessions": {"turns": 4, "turn_tokens": 3, "think_s": 0.4},
           "prompt_len": {"dist": "fixed", "value": 7}, "answer_len": {"dist": "fixed", "value": 3}}
    server, handler, url = _stub()
    try:
        client = T.Client(url, mix, 50, 4)
        client.run_closed(T.plan(mix, 4, 1.0, 50)[:1], 1, 0.0)
        time.sleep(0.2)                                                                # the first turn is done, the session thinks
        client.stop.set()
        assert client.join(10) == 0 and [r.planned.turn for r in client.records] == [0, 1] and len(handler.log) == 2
    finally:
        server.shutdown()
    server, handler, url = _stub(fail_after=2)
    try:
        mix["sessions"]["think_s"] = 0.0
        client = T.Client(url, mix, 50, 4)
        client.run_closed(T.plan(mix, 4, 1.0, 50)[:1], 1, 0.0)
        assert client.join(10) == 0
    finally:
        server.shutdown()
    assert [bool(r.error) for r in client.records] == [False, False, True] and len(handler.log) == 3
