"""From a profiler trace (.xplane.pb) to the few numbers the benchmark reports.

Busy time is the union of the intervals in which a leaf operation ran on a
device (the `while` loops that contain them are left out: they would cover
every gap inside a scan), idle gaps its complement inside the traced window,
exposed collective time the part of the intervals of operations whose own
opcode is a collective (or that are a fusion around nothing else) that no
other leaf operation on that device covers. The interval arithmetic is plain Python over (start, end) pairs in
seconds and is tested on hand-built lists; only `read_xplane` needs JAX, and
the harness runs it in a child under JAX_PLATFORMS=cpu after the job has gone
(this module imports no JAX at the top, the harness imports it).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

#: an event's name is its whole HLO line, `%name = <shape> opcode(operands), ...`:
#: the opcode is the first lower-case word that opens a bracket after the `=`
#: (shapes and layouts open theirs after digits or capitals: `T(8,128)`, `S(1)`)
_OPCODE = re.compile(r"^%?[\w.\-]+ = .*?\b([a-z][\w\-]*)\(")
#: opcodes that move data between chips, with their -start and -done halves.
#: Only an event's own opcode counts: a fusion that reads `%all-gather.3` is compute
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute|"
                        r"collective-broadcast|send|recv)(-start|-done)?$")
#: a fusion that is nothing but a collective the core carries out itself calls a
#: computation named for it (`fusion(...), kind=kCustom, calls=%all-reduce-scatter.clone`:
#: 0.41 s a traced window at train_fsdp4, my chip run, PR 24). An
#: `async_collective_fusion` is the opposite: compute that a collective runs under
_CALLS = re.compile(r"\bcalls=%([a-z][a-z\-]*)")
_COLLECTIVE_CALLED = re.compile(r"^(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute)")
#: opcodes that only contain others (the time is their bodies'): they lie on the
#: "XLA Ops" line over their bodies, and are left out of everything
CONTAINER = re.compile(r"^(while|conditional|call)$")


#: host events that explain no gap: a thread asleep does nothing, and the replica's control threads poll by
#: sleeping (entry/serve_replica.py: 20 and 50 ms), so their sleeps, being short, would name every gap
ASLEEP = ("$time sleep",)


def opcode(name: str) -> str:
    """The event's own operation. A bare name (`all-gather.12`, `%fusion.3`)
    stands for itself without its number."""
    m = _OPCODE.match(name)
    return m.group(1) if m else re.sub(r"[.\d]+$", "", name.lstrip("%").split(" ")[0])


#: lines of a device plane that hold single operations (not steps or modules)
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """The parts of union(a) that union(b) does not cover."""
    out = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur:
                continue
            if bs >= e:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: list[tuple[float, float]], t0: float, t1: float) -> list[tuple[float, float]]:
    return subtract([(t0, t1)], busy)


def covering(host_events: list[tuple[str, float, float]], t: float) -> str:
    """The shortest host event that covers time t (the innermost call)."""
    best, best_len = "(no host event)", float("inf")
    for name, s, e in host_events:
        if s <= t <= e and e - s < best_len:
            best, best_len = name, e - s
    return best


def is_collective(name: str, op: str | None = None) -> bool:
    op = op or opcode(name)
    if COLLECTIVE.match(op):
        return True
    called = _CALLS.search(name) if op == "fusion" else None
    return bool(called and _COLLECTIVE_CALLED.match(called.group(1)))


def short_name(hlo: str, limit: int = 120) -> str:
    """An event's name is its whole HLO line: keep the result's name, the
    operation and the result's shape, which is what tells two fusions apart."""
    m = re.match(r"^(%?[\w.\-]+) = (\(?[^ ]+)\s.*?(\b[a-z][\w\-]*)\(", hlo)
    name = f"{m.group(1)} {m.group(3)} {m.group(2)}" if m else hlo
    if "tpu_custom_call" in hlo:
        name += " [pallas]"
    return name[:limit]


def summarise(device_ops: dict[str, list[tuple[str, float, float]]],
              host_events: list[tuple[str, float, float]],
              modules: dict[str, list[tuple[str, float, float]]] | None = None) -> dict:
    """device_ops: plane -> [(operation name, start_s, end_s)]. Times averaged
    over the planes (the chips used); the window is the span all planes share."""
    # leaf operations only: on this line they run one after another, so a
    # collective among them is time in which the chip computes nothing
    planes = {p: [(name, s, e, op) for name, s, e in ev if not CONTAINER.match(op := opcode(name))]
              for p, ev in device_ops.items()}
    planes = {p: ev for p, ev in planes.items() if ev}
    if not planes:
        return {"busy_s": 0.0, "window_s": 0.0, "planes": 0}
    host_events = [h for h in host_events if h[0] not in ASLEEP]
    t0 = min(s for ev in planes.values() for _, s, _, _ in ev)
    t1 = max(e for ev in planes.values() for _, _, e, _ in ev)
    n = len(planes)
    busy_s = exposed_s = coll_s = in_modules_s = covered_s = 0.0
    op_time: dict[str, float] = {}
    op_count: dict[str, int] = {}
    gap_time: dict[str, float] = {}
    for plane, ev in planes.items():
        busy = union([(s, e) for _, s, e, _ in ev])
        busy_s += total(busy) / n
        programs = union([(s, e) for _, s, e in (modules or {}).get(plane, [])])
        in_modules_s += total(programs)
        covered_s += total(programs) - total(subtract(programs, busy))
        coll = [(s, e) for name, s, e, op in ev if is_collective(name, op)]
        rest = [(s, e) for name, s, e, op in ev if not is_collective(name, op)]
        coll_s += total(union(coll)) / n
        exposed_s += total(subtract(coll, rest)) / n
        for name, s, e, _ in ev:
            op_time[name] = op_time.get(name, 0.0) + (e - s) / n
            op_count[name] = op_count.get(name, 0) + 1 / n
        for s, e in gaps(busy, t0, t1):
            who = covering(host_events, (s + e) / 2)
            gap_time[who] = gap_time.get(who, 0.0) + (e - s) / n
    top = sorted(op_time.items(), key=lambda kv: -kv[1])
    out = {
        "planes": n, "window_s": t1 - t0, "busy_s": busy_s,
        "collective_s": coll_s, "collective_exposed_s": exposed_s,
        "op_time_s": dict(top[:200]), "op_count": {k: op_count[k] for k, _ in top[:200]},
        "breakdown": {
            "device_ops": [[short_name(k), v] for k, v in top[:10]],
            "idle_gaps": [[k, v] for k, v in sorted(gap_time.items(), key=lambda kv: -kv[1])[:10]],
        },
    }
    if in_modules_s:
        # the part of the programs' own time (the "XLA Modules" line) in which some leaf operation ran
        out["module_cover"] = covered_s / in_modules_s
    if modules:
        out["modules"] = {}
        for ev in modules.values():
            for name, s, e in ev:
                out["modules"].setdefault(name, []).append(e - s)
    return out


def read_xplane(path: str) -> tuple[dict, list, dict]:
    """(device operations by plane, host events, module executions by plane)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, modules, host = {}, {}, []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:") and "CPU" not in plane.name
        for line in plane.lines:
            if is_device and line.name in OP_LINES + MODULE_LINES:
                dest = device_ops if line.name in OP_LINES else modules
                ev = dest.setdefault(plane.name, [])
                for e in line.events:
                    ev.append((e.name, e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9))
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.duration_ns >= 1_000_000:  # 1 ms: enough to name a gap
                        host.append((e.name, e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9))
    return device_ops, host, modules


def describe(path: str) -> dict:
    """Planes, lines and event counts: what to look at by hand first."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return {p.name: {ln.name: len(list(ln.events)) for ln in p.lines} for p in data.planes}


#: the least part of its programs' time that a capture's leaf operations may cover. A program runs its operations
#: back to back (0.9999 in the three traced four-chip training runs of PR 64), and a capture's operations lie inside its programs,
#: so the cover is at least the busy share of the window: 0.981-0.999 in six whole serving captures of PR 64. Now
#: and then a capture comes back with a program that stood still under the profiler, or with a third or more of the
#: operations' events missing, while the programs' line is whole (1.787 s of events in a 3.035 s window at PR 44,
#: 1.636 of 3.006 at PR 64: PERF.md section 6), and every share that sets the traced executions against the
#: operations' time then reads far over 100. Such a run fails as a run
MIN_MODULE_COVER = 0.75


def reduced_or_fail(search_dir: str, work: str, cpu_rehearsal: bool = False) -> dict:
    """The reduced trace of a traced run, or JobFailed where there is none or the capture lost its events:
    the run is then made again, and no share of it is read."""
    import jobs

    tr = reduce_in_child(search_dir, work)
    if tr is None or not (tr.get("busy_s") or cpu_rehearsal):
        raise jobs.JobFailed("the traced run left no device trace to reduce")
    cover = tr.get("module_cover")
    if cover is not None and cover < MIN_MODULE_COVER:
        raise jobs.JobFailed(f"the capture is not whole (events lost, or a program stood still): leaf operations cover {cover:.3f} of its programs' time "
                             f"(busy {tr['busy_s']:.3f} s of a {tr['window_s']:.3f} s window; a whole capture covers "
                             f"{MIN_MODULE_COVER} or more)")
    return tr


def reduce_in_child(search_dir: str, work: str) -> dict | None:
    """Find the newest trace under `search_dir`, reduce it in a child that
    cannot touch the chip, and return the summary."""
    import jobs

    traces = jobs.find_files(search_dir, ".xplane.pb")
    if not traces:
        return None
    path = max(traces, key=os.path.getmtime)
    out = os.path.join(work, "trace_summary.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.abspath(__file__), path, out],
                          env=env, capture_output=True, text=True, timeout=300)
    if proc.returncode != 0:
        print(f"[trace] reduction failed: {proc.stderr[-800:]}", flush=True)
        return None
    with open(out) as f:
        return json.load(f)


if __name__ == "__main__":
    src, dst = sys.argv[1], sys.argv[2]
    ops, host_ev, mods = read_xplane(src)
    summary = summarise(ops, host_ev, mods)
    summary["lines"] = describe(src)
    with open(dst, "w") as f:
        json.dump(summary, f)
