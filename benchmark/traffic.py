"""Traffic: one generator that reads a cell's parameters, and the client that
sends what it planned. (Open loop, sessions and streamed TTFT are loadgen.py's;
arrivals, lengths, due-time stamps and the lateness report are corrected here.)

A traffic mix is data (the `traffic` object of workloads/<cell>.json):

  arrivals  {"process": "poisson", "rate": r}                        open loop
            {"process": "bursty", "rate": r, "burst_factor": k,
             "burst_s": b, "period_s": p}         open loop, k x rate for b s in every p
            {"process": "closed", "clients": n, "ramp_s": r}         closed loop; the callers
                                          start r/n apart, so they never move in step
  prompt_len / answer_len
            {"dist": "fixed", "value": v}
            {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
  sessions  {"turns": t, "turn_tokens": n, "think_s": s}   later turns resend the
            conversation so far (prompt + answer + n new tokens), s after the reply
  prefix    {"groups": g, "tokens": n}   each first turn starts with one of g shared prefixes

The schedule (arrival times, prompt and answer lengths, in order) is drawn
from `draw_seed`, a constant of the file; --seed draws the token ids and the
choice among shared prefixes. So every seed offers the same work at the same
times and runs differ by the system's noise, not by the luck of the draw. The
same lengths and gaps in another order were tried first (my chip runs, PR 24):
one order of six put the TTFT p95 at 1.8 s where the others read 1.1 s, twice.
So a cell replays ONE draw of its process, and what helps only other
clusterings cannot show in it: `schedule_stats` says in every run what that
draw offers, and PERF.md sets it beside other draws of the same process.

Every request is timed from when it was due, not from when it was sent: a
stall shows in the requests behind it. How late the generator itself ran is
reported beside the results.
"""

from __future__ import annotations

import http.client
import json
import math
import threading
import time
from dataclasses import dataclass, field
from urllib.parse import urlsplit

import numpy as np


def draw_lengths(rng: np.random.Generator, p: dict, n: int) -> np.ndarray:
    if p["dist"] == "fixed":
        return np.full(n, int(p["value"]), np.int64)
    if p["dist"] == "lognormal":
        x = rng.lognormal(math.log(p["median"]), p["sigma"], n)
        return np.clip(np.rint(x), p["min"], p["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {p['dist']!r}")


def draw_gaps(rng: np.random.Generator, a: dict, horizon_s: float) -> np.ndarray:
    """Inter-arrival gaps of an open loop that fill `horizon_s` and a little more."""
    if a["process"] == "poisson":
        n = int(a["rate"] * horizon_s * 1.25) + 16
        return rng.exponential(1.0 / a["rate"], n)
    if a["process"] == "bursty":
        # piecewise-constant rate: burst_factor x rate inside bursts, the rest
        # spread so that the mean stays `rate`; thinning of a fast Poisson stream
        hi = a["rate"] * a["burst_factor"]
        share = a["burst_s"] / a["period_s"]
        lo = max(a["rate"] * (1 - a["burst_factor"] * share) / (1 - share), 0.0)
        t, times = 0.0, []
        while t < horizon_s * 1.25 + 2:
            t += rng.exponential(1.0 / hi)
            in_burst = (t % a["period_s"]) < a["burst_s"]
            if in_burst or rng.random() < lo / hi:
                times.append(t)
        return np.diff(np.asarray([0.0] + times))
    raise ValueError(f"unknown open-loop arrival process {a['process']!r}")


@dataclass
class Planned:
    index: int
    due_s: float | None          # offset from the window's start; None: sent when its client is free
    prompt: list[int]
    max_tokens: int
    session: str
    turn: int = 0


def plan(traffic: dict, seed: int, horizon_s: float, vocab: int) -> list[Planned]:
    """The requests of one run's first turns, in sending order."""
    base = np.random.default_rng(traffic.get("draw_seed", 0))
    rng = np.random.default_rng(seed)
    a = traffic["arrivals"]
    if a["process"] == "closed":
        n = a["clients"] * 64  # more than any window lets a caller finish
        due = [None] * n
    else:
        t = np.cumsum(draw_gaps(base, a, horizon_s))
        due = [float(x) for x in t[t < horizon_s]]
        n = len(due)
    plen = draw_lengths(base, traffic["prompt_len"], n)
    alen = draw_lengths(base, traffic["answer_len"], n)
    prefix = traffic.get("prefix") or {}
    prefixes = [rng.integers(1, vocab, prefix["tokens"]).tolist() for _ in range(prefix.get("groups", 0))]
    out = []
    for i in range(n):
        pl = int(plen[i])
        head = prefixes[int(rng.integers(len(prefixes)))] if prefixes else []
        body = rng.integers(1, vocab, max(pl - len(head), 1)).tolist()
        out.append(Planned(i, due[i], (head + body)[:max(pl, 1)], int(alen[i]), f"s{seed}-{i}"))
    return out


def schedule_stats(planned: list[Planned]) -> dict:
    """What one schedule offers, whatever the system makes of it: requests,
    tokens asked for, and its two heaviest moments."""
    due = np.asarray([p.due_s for p in planned if p.due_s is not None])
    plen = np.asarray([len(p.prompt) for p in planned])
    out = {"requests": len(planned), "prompt_tokens": int(plen.sum()),
           "answer_tokens": int(sum(p.max_tokens for p in planned)), "prompts_2048_up": int((plen >= 2048).sum())}
    if len(due) == len(planned) and len(due):
        out["most_arrivals_in_1s"] = int(max(((due >= t) & (due < t + 1)).sum() for t in due))
        out["most_prompt_tokens_in_2s"] = int(max(plen[(due >= t) & (due < t + 2)].sum() for t in due))
    return out


@dataclass
class Sent:
    planned: Planned
    due_t: float = 0.0            # absolute, host clock
    sent_t: float = 0.0
    arrivals: list[tuple[float, int]] = field(default_factory=list)   # (time, tokens in the event)
    tokens: list[int] = field(default_factory=list)
    done_t: float | None = None
    error: str = ""

    @property
    def ttft_s(self) -> float | None:
        return self.arrivals[0][0] - self.due_t if self.arrivals else None

    def gaps_s(self) -> list[float]:
        """Gap before each token after the first: tokens of one event arrive
        together (gap 0), the first of an event waits for the event."""
        out, prev = [], None
        for t, n in self.arrivals:
            if prev is not None:
                out.append(t - prev)
                out.extend([0.0] * (n - 1))
            else:
                out.extend([0.0] * (n - 1))
            prev = t
        return out


class Client:
    """Sends planned requests to one endpoint and records what came back."""

    def __init__(self, url: str, traffic: dict, vocab: int, seed: int, timeout_s: float = 120.0):
        self.url, self.traffic, self.vocab, self.timeout_s = url, traffic, vocab, timeout_s
        self.rng = np.random.default_rng(seed + 1)
        self.records: list[Sent] = []
        self._lock = threading.Lock()
        self._threads: list[threading.Thread] = []
        self.stop = threading.Event()

    def post(self, prompt: list[int], max_tokens: int, session: str, rec: Sent | None = None) -> list[int]:
        """One streamed completion; fills `rec` as events arrive. Returns the tokens."""
        parts = urlsplit(self.url)
        conn = http.client.HTTPConnection(parts.hostname, parts.port, timeout=self.timeout_s)
        try:
            body = json.dumps({"prompt_tokens": prompt, "max_tokens": max_tokens, "stream": True})
            conn.request("POST", "/v1/completions", body.encode(),
                         {"Content-Type": "application/json", "X-Tony-Session": session})
            resp = conn.getresponse()
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}: {resp.read(200)!r}")
            buf, streamed = b"", []
            while True:
                chunk = resp.read1(65536)
                if not chunk:
                    raise RuntimeError("stream ended with no finished event")
                now = time.time()
                buf += chunk
                while b"\n\n" in buf:
                    event, buf = buf.split(b"\n\n", 1)
                    line = event.strip()
                    if not line.startswith(b"data:"):
                        continue
                    obj = json.loads(line[5:])
                    if obj.get("error"):
                        raise RuntimeError(str(obj["error"]))
                    if obj.get("finished"):
                        final = list(obj.get("tokens") or streamed)
                        if rec is not None and len(final) > len(streamed):
                            rec.arrivals.append((now, len(final) - len(streamed)))
                        return final
                    new = obj.get("tokens") or []
                    if new:
                        streamed += new
                        if rec is not None:
                            rec.arrivals.append((now, len(new)))
        finally:
            conn.close()

    def _one(self, p: Planned, due_t: float) -> Sent:
        rec = Sent(p, due_t=due_t, sent_t=time.time())
        with self._lock:
            self.records.append(rec)
        try:
            rec.tokens = self.post(p.prompt, p.max_tokens, p.session, rec)
            rec.done_t = time.time()
        except Exception as e:  # noqa: BLE001 - a failed request is a result, counted as failed
            rec.error = f"{type(e).__name__}: {e}"
        return rec

    def _conversation(self, p: Planned, due_t: float) -> None:
        """A first turn and, where the mix has sessions, its later turns."""
        s = self.traffic.get("sessions") or {}
        while True:
            rec = self._one(p, due_t)
            if rec.error or p.turn + 1 >= s.get("turns", 1) or self.stop.is_set():
                return
            with self._lock:
                more = self.rng.integers(1, self.vocab, s.get("turn_tokens", 32)).tolist()
            due_t = rec.done_t + s.get("think_s", 0.0)
            time.sleep(max(0.0, due_t - time.time()))
            p = Planned(p.index, None, p.prompt + rec.tokens + more, p.max_tokens, p.session, p.turn + 1)

    def run_open(self, planned: list[Planned], t0: float) -> None:
        """Send each request at t0 + due_s (a thread a conversation)."""
        for p in planned:
            due_t = t0 + p.due_s
            delay = due_t - time.time()
            if delay > 0 and self.stop.wait(delay):
                return
            th = threading.Thread(target=self._conversation, args=(p, due_t), daemon=True)
            th.start()
            self._threads.append(th)

    def run_closed(self, planned: list[Planned], clients: int, ramp_s: float = 0.0) -> None:
        """`clients` callers, each sending its next request when the last
        returned, started `ramp_s / clients` apart (blocks for `ramp_s`)."""
        it = iter(planned)
        it_lock = threading.Lock()

        def caller() -> None:
            while not self.stop.is_set():
                with it_lock:
                    p = next(it, None)
                if p is None:
                    return
                self._conversation(p, time.time())

        for _ in range(clients):
            th = threading.Thread(target=caller, daemon=True)
            th.start()
            self._threads.append(th)
            if ramp_s > 0 and self.stop.wait(ramp_s / clients):
                return

    def join(self, timeout_s: float) -> int:
        """Wait for what is in flight; returns how many threads are still alive."""
        deadline = time.time() + timeout_s
        for th in list(self._threads):
            th.join(max(0.0, deadline - time.time()))
        return sum(th.is_alive() for th in self._threads)


def percentile(xs: list[float], p: float) -> float:
    """Nearest-rank percentile (p in 0..100) of a non-empty list."""
    s = sorted(xs)
    return s[min(len(s) - 1, max(0, math.ceil(p / 100.0 * len(s)) - 1))]


def lateness(records: list[Sent]) -> dict:
    late = [r.sent_t - r.due_t for r in records if r.planned.due_s is not None] or [0.0]
    return {"mean_ms": 1000 * sum(late) / len(late), "max_ms": 1000 * max(late)}
