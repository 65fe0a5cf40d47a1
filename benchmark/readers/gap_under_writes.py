"""How much of the device's idle time lies under the stream writers: the
`tony.stream.write` annotations of every handler thread, laid over the gaps of
the traced window beside the engine thread's phases, all on the profiler's clock.

A handler thread annotates the encode, write and flush of each SSE event
(tony_tpu/models/serving_http.py `_stream_response`); the engine thread
annotates the phases of a pass (`tony.serve.<phase>`: gap_by_span.py). The
writers run on as many threads as there are streams and overlap, so they are
taken as one union. Idle time under `decode_wait` or `prefill_wait` is the
device's own and is set aside first; the rest is split four ways: under a
write and an engine host phase (the engine thread was at work, or waiting its
turn, while writers ran), under a write alone, under an engine host phase
alone, under neither. The five parts add up to the window's idle time.

The interval arithmetic is plain Python over sorted lists and tested on
hand-built ones; reduce.py's and gap_by_span.py's are imported, not edited,
and `read` parses the .xplane.pb in a child under JAX_PLATFORMS=cpu as they do.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

if __name__ == "__main__":  # the child: benchmark/ is not on its path yet
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import reduce as R  # noqa: E402
from readers import gap_by_span  # noqa: E402

WRITE = "tony.stream.write"
WAITING = ("decode_wait", "prefill_wait")
PARTS = ("both", "writes", "host", "neither", "waiting")


def intersect(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """The parts of union(a) that union(b) covers: one sweep over both."""
    a, b, out, j = R.union(a), R.union(b), [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            out.append((max(s, b[k][0]), min(e, b[k][1])))
            k += 1
    return out


def split(gaps: list[tuple[float, float]], writes: list[tuple[float, float]],
          phases: list[tuple[str, float, float]]) -> dict[str, float]:
    """Seconds of the gaps by PARTS."""
    host = [(s, e) for name, s, e in phases if name not in WAITING]
    rest = R.subtract(gaps, [(s, e) for name, s, e in phases if name in WAITING])  # outside the waiting phases
    under_writes = intersect(rest, writes)
    both = R.total(intersect(under_writes, host))
    host_s = R.total(intersect(rest, host))
    return {"both": both, "writes": R.total(under_writes) - both, "host": host_s - both,
            "neither": max(0.0, R.total(rest) - R.total(under_writes) - (host_s - both)),
            "waiting": max(0.0, R.total(R.union(gaps)) - R.total(rest))}


def summarise(device_ops: dict[str, list[tuple[str, float, float]]], writes: list[tuple[float, float]],
              phases: list[tuple[str, float, float]]) -> dict | None:
    """The split, averaged over the device planes, in the window reduce.py and
    gap_by_span.py use. None without a device plane, a phase or a write."""
    planes = [[(s, e) for name, s, e in ev if not R.CONTAINER.match(R.opcode(name))] for ev in device_ops.values()]
    planes = [ev for ev in planes if ev]
    if not planes or not phases or not writes:
        return None
    t0, t1 = min(s for ev in planes for s, _ in ev), max(e for ev in planes for _, e in ev)
    by = dict.fromkeys(PARTS, 0.0)
    for ev in planes:
        for part, sec in split(R.gaps(R.union(ev), t0, t1), writes, phases).items():
            by[part] += sec / len(planes)
    return {"window_s": t1 - t0, "writes": len(writes), "write_s": R.total(intersect([(t0, t1)], writes)), "gap_s": by}


def read_xplane(path: str) -> tuple[dict, list, list]:
    """(device operations by plane, the `tony.stream.write` intervals of every
    host thread, the engine thread's `tony.serve.*` phases)."""
    from jax.profiler import ProfileData

    device_ops, writes, phases = {}, [], []
    for plane in ProfileData.from_file(path).planes:
        is_device = plane.name.startswith("/device:") and "CPU" not in plane.name
        for line in plane.lines:
            if is_device and line.name in R.OP_LINES:
                device_ops.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9) for e in line.events)
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name == WRITE:
                        writes.append((e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9))
                    elif e.name.startswith(gap_by_span.PREFIX):
                        phases.append((e.name[len(gap_by_span.PREFIX):], e.start_ns / 1e9,
                                       (e.start_ns + e.duration_ns) / 1e9))
    return device_ops, writes, phases


def read(ctx):
    """Idle seconds under a write and under no waiting phase of the engine,
    as a share of the traced window (%). None without a traced run, a device
    plane, or the writers' annotations (a program from before them)."""
    import jobs

    if not ctx.get("trace"):
        return None
    traces = jobs.find_files(os.path.join(ctx["run"].out_dir, "trace"), ".xplane.pb")
    if not traces:
        return None
    out = os.path.join(ctx["run"].work, "gap_under_writes.json")
    proc = subprocess.run([sys.executable, os.path.abspath(__file__), max(traces, key=os.path.getmtime), out],
                          env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=300)
    if proc.returncode != 0:
        jobs.say(f"[span] the writers' reduction failed: {jobs.tail(proc.stderr, 3)}")
        return None
    got = jobs.read_json(out)
    if not got:
        jobs.say(f"[span] nothing to read: no device plane, no tony.serve.* phase or no {WRITE} event in the trace")
        return None
    by = got["gap_s"]
    jobs.say(f"[span] window {got['window_s']:.4f}s, idle {sum(by.values()):.4f}s; {got['writes']} {WRITE} events "
             f"cover {got['write_s']:.4f}s of it; idle seconds under writes and an engine host phase "
             f"{by['both']:.5f}, under writes alone {by['writes']:.5f}, under an engine host phase alone "
             f"{by['host']:.5f}, under neither {by['neither']:.5f}; under decode_wait/prefill_wait (the device's "
             f"own, in none of the four) {by['waiting']:.5f}")
    return 100.0 * (by["both"] + by["writes"]) / got["window_s"]


if __name__ == "__main__":
    summary = summarise(*read_xplane(sys.argv[1]))
    with open(sys.argv[2], "w") as f:
        json.dump(summary or {}, f)
