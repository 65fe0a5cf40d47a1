"""Whose time the device's idle gaps are: the engine thread's own phases laid
over the gaps of the traced window, both on the profiler's clock.

The engine opens a `tony.serve.<phase>` annotation for every phase of a pass
(tony_tpu/models/serving.py `_PhaseClock`), so the replica's .xplane.pb holds
them on the engine thread's line beside the device's operations. A gap that
lies under `decode_wait` or `prefill_wait` is the device's own (the host was
blocked on it); one under any other phase is time the host kept the chip
waiting. The interval arithmetic is plain Python and tested on hand-built
lists; only `read_xplane` needs JAX, and `read` runs it in a child under
JAX_PLATFORMS=cpu like reduce.py's (imported, not edited).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

if __name__ == "__main__":  # the child: benchmark/ is not on its path yet
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import reduce as R  # noqa: E402

PREFIX = "tony.serve."
NONE = "(no phase)"


def gap_seconds_by_phase(gaps: list[tuple[float, float]],
                         phases: list[tuple[str, float, float]]) -> dict[str, float]:
    """Seconds of the gaps under each phase, and under none. The engine's
    phase clock closes one phase as it opens the next, so the phases of one
    thread do not overlap: one sweep over both sorted lists."""
    flat, by, i = sorted(phases, key=lambda p: p[1]), {}, 0
    for s, e in sorted(gaps):
        while i < len(flat) and flat[i][2] <= s:
            i += 1
        j, covered = i, 0.0
        while j < len(flat) and flat[j][1] < e:
            name, a, b = flat[j]
            sec = min(b, e) - max(a, s)
            if sec > 0:
                by[name] = by.get(name, 0.0) + sec
                covered += sec
            j += 1
        if e - s - covered > 1e-12:
            by[NONE] = by.get(NONE, 0.0) + e - s - covered
    return by


def summarise(device_ops: dict[str, list[tuple[str, float, float]]],
              phases: list[tuple[str, float, float]]) -> dict | None:
    """Gap seconds by phase, averaged over the device planes, in the window
    reduce.py uses (first leaf operation's start to the last one's end)."""
    planes = [[(s, e) for name, s, e in ev if not R.CONTAINER.match(R.opcode(name))] for ev in device_ops.values()]
    planes = [ev for ev in planes if ev]
    if not planes or not phases:
        return None
    t0, t1 = min(s for ev in planes for s, _ in ev), max(e for ev in planes for _, e in ev)
    by: dict[str, float] = {}
    for ev in planes:
        for name, sec in gap_seconds_by_phase(R.gaps(R.union(ev), t0, t1), phases).items():
            by[name] = by.get(name, 0.0) + sec / len(planes)
    return {"window_s": t1 - t0, "gap_s": by}


def read_xplane(path: str) -> tuple[dict, list]:
    """(device operations by plane, the `tony.serve.*` annotations of the host's threads)."""
    from jax.profiler import ProfileData

    device_ops, phases = {}, []
    for plane in ProfileData.from_file(path).planes:
        is_device = plane.name.startswith("/device:") and "CPU" not in plane.name
        for line in plane.lines:
            if is_device and line.name in R.OP_LINES:
                device_ops.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9) for e in line.events)
            elif plane.name.startswith("/host:"):
                phases += [(e.name[len(PREFIX):], e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9)
                           for e in line.events if e.name.startswith(PREFIX)]
    return device_ops, phases


def read(ctx, waiting=("decode_wait", "prefill_wait")):
    """Idle seconds under a phase in which the host was not blocked on the
    device, as a share of the traced window (%). None without a traced run,
    a device plane, or the annotations (a program from before them)."""
    import jobs

    if not ctx.get("trace"):
        return None
    traces = jobs.find_files(os.path.join(ctx["run"].out_dir, "trace"), ".xplane.pb")
    if not traces:
        return None
    out = os.path.join(ctx["run"].work, "gap_by_span.json")
    proc = subprocess.run([sys.executable, os.path.abspath(__file__), max(traces, key=os.path.getmtime), out],
                          env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=300)
    if proc.returncode != 0:
        jobs.say(f"[span] the reduction failed: {jobs.tail(proc.stderr, 3)}")
        return None
    got = jobs.read_json(out)
    if not got:
        jobs.say("[span] nothing to read: no device plane or no tony.serve.* annotation in the trace")
        return None
    idle = sum(got["gap_s"].values())
    jobs.say(f"[span] window {got['window_s']:.4f}s, idle {idle:.4f}s, of it under a tony.serve.* phase "
             f"{100 * (1 - got['gap_s'].get(NONE, 0.0) / idle) if idle else 100.0:.1f}%; idle seconds by phase: "
             f"{json.dumps({k: round(v, 5) for k, v in sorted(got['gap_s'].items(), key=lambda kv: -kv[1])})}")
    host = sum(v for k, v in got["gap_s"].items() if k != NONE and k not in waiting)
    return 100.0 * host / got["window_s"]


if __name__ == "__main__":
    summary = summarise(*read_xplane(sys.argv[1]))
    with open(sys.argv[2], "w") as f:
        json.dump(summary or {}, f)
