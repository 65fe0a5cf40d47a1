"""A serving cell: a fleet through the `tony serve` path, load from this process.

Set-up is everything up to the window: the fleet submitted, weights drawn,
every prompt bucket of the cell's traffic and the decode chunk warmed by one
request each (and the greedy request repeated, which must return the same
tokens). The window lasts --seconds: an open loop sends what was due in it
and then waits for those requests; a closed loop keeps its callers busy and
counts what arrived inside it. Afterwards the fleet is interrupted (it drains
and exits 0) and a child of its own, which then has the chip, teacher-forces
the float32 reference over a seeded sample of finished requests.
"""

from __future__ import annotations

import json
import os
import re
import sys
import threading
import time
import urllib.request

import numpy as np

import jobs
import reduce as trace_reduce
import spec
import traffic as T
from chipside import write_json


#: how long the replica may take over a registry snapshot or a /stats poll: a capture's export holds much
#: of its interpreter for 24-28 s at 64 slots and longer at 256 (PERF.md section 7), and `run` gives the
#: same export 60 s to end
SNAPSHOT_WAIT_S = 120.0


def get_json(url: str, timeout: float = 10.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read())


def buckets(lo: int, hi: int, floor: int = 16) -> list[int]:
    """The engine pads a prompt to a power of two (from 16): one length for
    each bucket the traffic's prompts can fall in, the largest cut to `hi`."""
    out, b = [], floor
    while True:
        if b >= lo:
            out.append(min(b, hi))
        if b >= hi:
            return out
        b *= 2


class Fleet:
    """One `tony serve` fleet of this cell's deployment, up until stop()."""

    def __init__(self, run):
        self.run, self.w = run, run.w
        self.eng = self.w["engine"]
        self.proc = None
        self.endpoint = self.replica = self.app = None
        self.launch_s = None

    def start(self) -> None:
        run, eng = self.run, self.eng
        spec_path = os.path.join(run.work, "bench_spec.json")
        with open(spec_path, "w") as f:
            json.dump({"config": self.w["config"], "deployment": self.w["deployment"], "seed": run.seed,
                       "out_dir": run.out_dir, "engine": eng}, f)
        cmd = [sys.executable, os.path.join(spec.HERE, "entry", "serve_launch.py"),
               "--preset", self.w["config"], "--replicas", "1", "--slots", str(eng["slots"]),
               "--max_len", str(eng["max_len"]), "--page_len", str(eng["page_len"]),
               "--num_pages", str(eng["num_pages"]), "--seed", str(run.seed % 2 ** 31),
               "--url_timeout_s", "1000",
               "--conf", f"tony.tpu.pool={run.pool}",
               "--conf", f"tony.serve.chips={0 if run.cpu_rehearsal else 1}",
               "--conf", "tony.serve.memory=16g"]
        extra = {"BENCH_SPEC": spec_path}
        if run.cpu_rehearsal:
            extra["TONY_PALLAS_INTERPRET"] = "1"  # the paged kernel has no XLA path
        self.out_path = os.path.join(run.work, "serve.out")
        t0 = time.time()
        self.proc = jobs.launch(cmd, run.staging, self.out_path, extra)
        device = None
        while self.endpoint is None:
            if self.proc.poll() is not None or time.time() - t0 > 1050:
                raise self.fail("no fleet router endpoint")
            if device is None:
                device = jobs.read_json(os.path.join(run.out_dir, "device.json"))
                if device is not None:
                    run.check_device(device)
            with open(self.out_path) as f:
                m = re.search(r"fleet router (http://\S+)", f.read())
            self.endpoint = m.group(1) if m else None
            time.sleep(0.2)
        self.launch_s = time.time() - t0
        self.app = jobs.app_dirs(run.staging)[0]
        m = re.search(r"\[tony-serve\] (http://\S+) role=", jobs.read_logs(self.app, "serve_0"))
        if not m:
            raise self.fail("the replica logged no endpoint")
        self.replica = m.group(1)

    def fail(self, msg: str) -> jobs.JobFailed:
        with open(self.out_path) as f:
            out = f.read()
        apps = jobs.app_dirs(self.run.staging)
        log = jobs.read_logs(apps[0], "serve_0") if apps else ""
        return jobs.JobFailed(f"serve: {msg}\n--- serve_launch\n{jobs.tail(out, 15)}\n--- replica\n{jobs.tail(log, 40)}")

    def warm(self) -> bool:
        """One request for each prompt bucket, two decode chunks each; the
        first is sent twice and must come back the same. Returns that."""
        p = self.w["traffic"]["prompt_len"]
        lo, hi = (p["value"], p["value"]) if p["dist"] == "fixed" else (p["min"], p["max"])
        rng = np.random.default_rng(self.run.seed + 2)
        client = T.Client(self.endpoint, {}, self.run.sizes["vocab"], self.run.seed, timeout_s=900)
        n_new = 2 * self.eng.get("decode_chunk", 8) + 1
        first = None
        for i, length in enumerate(buckets(lo, hi)):
            prompt = rng.integers(1, self.run.sizes["vocab"], length).tolist()
            toks = client.post(prompt, n_new, f"warm{i}")
            if first is None:
                first = (prompt, toks)
        again = client.post(first[0], n_new, "warm-again")
        return again == first[1] and len(again) == n_new

    def ask_snapshot(self, tag: str) -> None:
        """Ask the replica for its registry: entry/serve_replica.py answers on a thread of its own."""
        with open(os.path.join(self.run.out_dir, "ctl", f"snap.{tag}.req"), "w") as f:
            f.write("{}")

    def snapshot(self, tag: str, asked: float | None = None, wait_s: float = SNAPSHOT_WAIT_S) -> dict:
        """The registry the replica wrote for `tag`, asked for here unless it was at `asked`. An untraced
        run's comes in milliseconds; a traced run's may come once the capture's export lets the replica's
        threads run, which is why the wait is as long as an export lasts. None within `wait_s` of the
        asking fails the RUN: a run whose counters cannot be read is made again, and no metric of it
        reads nothing for the harness's reason."""
        if asked is None:
            asked = time.time()
            self.ask_snapshot(tag)
        path = os.path.join(self.run.out_dir, "ctl", f"snap.{tag}.json")
        while True:
            got = jobs.read_json(path)
            if got is not None:
                return got
            if time.time() - asked > wait_s:
                raise self.fail(f"the replica gave no registry snapshot {tag!r} within {wait_s:.0f} s")
            time.sleep(0.02)

    def stop(self) -> tuple[int, bool]:
        rc = jobs.stop_job(self.proc, self.run.staging, "serve", wait_s=150)
        log = jobs.read_logs(self.app, "serve_0") if self.app else ""
        drained = "[tony-serve] draining" in log and "drain timed out" not in log and "Traceback" not in log
        return rc, drained


def drive(fleet: Fleet, run, seconds: float, traffic: dict, seed: int, trace: bool = False) -> dict:
    """One measured window against a warm fleet. A closed loop's callers are
    started before the window opens (their ramp is set-up)."""
    planned = T.plan(traffic, seed, seconds, run.sizes["vocab"])
    client = T.Client(fleet.endpoint, traffic, run.sizes["vocab"], seed)
    a = traffic["arrivals"]
    if a["process"] == "closed":
        client.run_closed(planned, a["clients"], a.get("ramp_s", 0.0))
    stats, poll_errors, stop_poll = [], [], threading.Event()

    def poll() -> None:
        while not stop_poll.wait(0.5):
            try:
                stats.append({"t": time.time(), **get_json(fleet.replica + "/stats", SNAPSHOT_WAIT_S)})
            except OSError as e:
                poll_errors.append(f"{type(e).__name__}: {e}")

    snap0 = fleet.snapshot("open")
    t_open = time.time()
    threading.Thread(target=poll, daemon=True).start()
    if a["process"] == "closed":
        sender = None
    else:
        sender = threading.Thread(target=client.run_open, args=(planned, t_open), daemon=True)
        sender.start()
    if trace:
        time.sleep(min(2.0, seconds / 4))
        write_json(os.path.join(run.out_dir, "ctl", "trace.req"), {"seconds": min(3.0, seconds / 2)})
    time.sleep(max(0.0, t_open + seconds - time.time()))
    # the window closes HERE: the registry is asked for, the clients and the poll are stopped at once, and
    # only then is the answer waited for, so an answer that a capture's export delays lengthens no window
    t_close = time.time()
    fleet.ask_snapshot("close")
    client.stop.set()
    stop_poll.set()
    snap1 = fleet.snapshot("close", asked=t_close)
    if seconds >= 1.0 and not any(t_open <= s["t"] <= t_close for s in stats):  # the poll's period is 0.5 s
        raise fleet.fail(f"the replica answered no /stats poll inside the window ({poll_errors[-1:]})")
    if sender is not None:
        sender.join(5)
    left = client.join(traffic.get("drain_s", 90))  # a closed loop's callers each finish the request they are in
    return {"t_open": t_open, "t_close": t_close, "records": list(client.records), "stats": stats,
            "snap0": snap0, "snap1": snap1, "left_in_flight": left, "planned": len(planned),
            "schedule": T.schedule_stats(planned), "poll_errors": len(poll_errors)}


def summarise(d: dict, seconds: float, limits: dict, timeout_ms: float = 120000.0) -> dict:
    every = d["records"]
    recs = [r for r in every if r.due_t >= d["t_open"]]  # due in the window (a closed loop's ramp is set-up)
    ttft = [1000 * r.ttft_s if r.ttft_s is not None else timeout_ms for r in recs]
    gaps = [1000 * g for r in recs for g in r.gaps_s()]
    arrived = sum(n for r in every for t, n in r.arrivals if d["t_open"] <= t < d["t_close"])
    done = [r for r in recs if r.done_t is not None]
    tpot = [1000 * (r.arrivals[-1][0] - r.arrivals[0][0]) / max(len(r.tokens) - 1, 1) for r in done if r.arrivals]
    inside = [r for r in done if 1000 * r.ttft_s <= limits.get("ttft_ms", float("inf"))
              and max([1000 * g for g in r.gaps_s()] or [0]) <= limits.get("gap_ms", float("inf"))]
    return {
        "attempted": len(recs), "failed": sum(1 for r in recs if r.error), "finished": len(done),
        "ttft_p50_ms": T.percentile(ttft, 50) if ttft else None,
        "ttft_p95_ms": T.percentile(ttft, 95) if ttft else None,
        "itl_p95_ms": T.percentile(gaps, 95) if gaps else None,
        "itl_p50_ms": T.percentile(gaps, 50) if gaps else None,
        "out_tok_s": arrived / seconds,
        "completed_tok_s": sum(len(r.tokens) for r in done if r.done_t < d["t_close"]) / seconds,
        "tpot_mean_ms": sum(tpot) / len(tpot) if tpot else None,
        "share_inside_limits": len(inside) / len(recs) if recs else None,
        "lateness": T.lateness(recs),
        "errors": sorted({r.error for r in recs if r.error})[:3],
    }


def window_means(run, d: dict) -> dict | None:
    """The window's means as the family's counts form them from the two registry snapshots (rows and
    slabs of a routed FFN a step, context a slot, ...): said in every run, traced or not, so that a
    seed whose weights make more or less work of the same offered traffic is seen beside its rate.
    None for a family whose counts form none."""
    import families
    from readers.registry_delta import total

    form = getattr(families.counts(run.sizes), "window_means", None)
    if form is None:
        return None

    def delta(**term):
        ends = [total(d[snap], **term) for snap in ("snap0", "snap1")]
        return None if None in ends else ends[1] - ends[0]

    return form(delta, run.w["engine"])


def run(run) -> dict:
    w = run.w
    say = jobs.say
    fleet = Fleet(run)
    fleet.start()
    try:
        repeat_same = fleet.warm()
    except Exception as e:  # noqa: BLE001 - any failure of a warm-up request fails the run
        raise fleet.fail(f"warm-up request failed: {type(e).__name__}: {e}") from None
    d = drive(fleet, run, run.seconds, w["traffic"], run.seed, trace=run.trace)
    setup_s = d["t_open"] - run.t_start
    s = summarise(d, run.seconds, w.get("limits", {}))
    if run.trace:
        deadline = time.time() + 60
        while not os.path.exists(os.path.join(run.out_dir, "ctl", "trace.done")) and time.time() < deadline:
            time.sleep(0.2)
        done = jobs.read_json(os.path.join(run.out_dir, "ctl", "trace.done")) or {}
        at = {k: round(done[k] - d["t_open"], 2) for k in ("start", "stop", "end") if k in done}
        say(f"[serve] the capture, in seconds after the window opened: {json.dumps(at)} (`stop` -> `end` is the export)")
    holders, others = jobs.chip_holders(run.staging)
    off_jax = all("serve_replica.py" in c for c in holders)
    device = jobs.read_json(os.path.join(run.out_dir, "device.json"))
    final_stats = get_json(fleet.replica + "/stats")
    rc, drained = fleet.stop()
    run.check_device(device)
    say(f"[serve] app={os.path.basename(fleet.app)} up in {fleet.launch_s:.1f}s, set-up {setup_s:.2f}s; "
        f"kv={final_stats.get('kv')} pages_total={final_stats.get('pages_total')} device={json.dumps(device)}; "
        f"{others} launcher process(es) off JAX, {len(holders)} chip-holding child(ren); "
        f"interrupt -> exit {rc}, drained cleanly={drained}")
    say(f"[serve] the schedule offered (draw_seed {w['traffic'].get('draw_seed', 0)}, the same for every --seed): "
        f"{json.dumps(d['schedule'])}")
    say(f"[serve] registry snapshots read {d['snap0']['t'] - d['t_open']:+.3f} s of the window's opening and "
        f"{d['snap1']['t'] - d['t_close']:+.3f} s of its close; /stats polls answered {len(d['stats'])}, failed {d['poll_errors']}")
    say(f"[serve] window {d['t_close'] - d['t_open']:.2f}s: {json.dumps(s)}; left in flight after the wait: "
        f"{d['left_in_flight']}; generator lateness mean {s['lateness']['mean_ms']:.2f} ms max {s['lateness']['max_ms']:.2f} ms")

    means = window_means(run, d)
    if means is not None:
        say(f"[serve] the window's means by the family's counts (what the seed's weights made of the offered work): "
            f"{json.dumps({k: round(v, 4) for k, v in means.items() if isinstance(v, (int, float))})}")

    # the comparison: a child of its own, now that the chip is free
    rng = np.random.default_rng(run.seed + 3)
    done = [r for r in d["records"] if r.done_t is not None and r.tokens]
    n_check = min(w["check"]["samples"], len(done))
    picks = [done[i] for i in rng.choice(len(done), n_check, replace=False)] if done else []
    chk = jobs.compare_in_child(run, {"samples": [{"prompt": r.planned.prompt, "tokens": r.tokens} for r in picks]},
                                "serve") if picks else {}
    limit = w["check"]["worst_gap_limit"]
    gap = chk.get("worst_gap")
    compared = [
        f"repeated greedy request identical: {repeat_same}",
        f"requests failed: {s['failed']} of {s['attempted']} {s['errors']}; launcher processes off JAX: {off_jax}; "
        f"drained cleanly: {drained and rc == 0}",
        f"worst_gap = {gap!r} over {chk.get('tokens')} tokens of {n_check} finished requests (limit {limit}; "
        f"argmax agrees at {chk.get('argmax_agree')}; check took {chk.get('seconds')}s in {chk.get('tries')} tries)",
    ]
    if "control_worst_gap" in chk:
        compared.append(f"control_worst_gap = {chk['control_worst_gap']!r} (the control: must lie above the limit)")
    ok = (repeat_same and s["failed"] == 0 and off_jax and drained and rc == 0
          and gap is not None and gap <= limit and d["left_in_flight"] == 0)

    e2e = {"setup_s": setup_s, "serve_ttft_p95_ms": s["ttft_p95_ms"], "serve_itl_p95_ms": s["itl_p95_ms"],
           "serve_out_tok_s": s["out_tok_s"]}
    ctx = {"run": run, "kind": "serve", "app_dir": fleet.app, "launch_s": fleet.launch_s, "device": device,
           "drive": d, "summary": s, "trace": None}
    dev_line = {k: device[k] for k in ("platform", "kind", "count")}
    dev_line["memory_peak_bytes"] = device.get("memory_peak_bytes", 0)
    breakdown = None
    if run.trace:
        tr = ctx["trace"] = trace_reduce.reduced_or_fail(os.path.join(run.out_dir, "trace"), run.work, run.cpu_rehearsal)
        say(f"[trace] leaf operations cover {tr.get('module_cover')} of the captured programs' time")
        dev_line.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        breakdown = tr.get("breakdown")
    return {"correct": bool(ok), "attempted": s["attempted"], "failed": s["failed"],
            "end_to_end": {k: v for k, v in e2e.items() if v is not None},
            "compared": compared, "device": dev_line, "ctx": ctx, "breakdown": breakdown}
