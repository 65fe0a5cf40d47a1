"""Roofline share of one kernel of a serving replica over the traced window.

The serving counterpart of kernel_roofline. A training step makes the same
calls at the same sizes every step; a replica's calls follow its traffic. So
the sizes of a call are the window's means from the replica's own counters
(the registry snapshots `serve_cell.drive` takes: live slots a decode chunk,
visible positions a slot and step, the share of prefill chunks on the sparse
path), the number of calls is the traced executions of the jitted program that
makes them (one the capture cut counting as the part of the median execution
it lasted) times the calls an execution, and the time is the device time of
the trace events whose HLO line matches `match` and the kernel's own operand
shape. All three come from the family's counts by the kernel's name:
`<kernel>_operands`, `<kernel>_call`, `<kernel>_calls`; a family that has no
such kernel has no such counts, and the metric reads nothing there.

The device time is summed over EVERY leaf operation of the trace, in a child
under JAX_PLATFORMS=cpu as reduce.py's (its summary keeps the 200 longest
operations only, and a kernel that ran three times in the window is not among
them); without a trace file the summary's operations are what there is.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

if __name__ == "__main__":  # the child: benchmark/ is not on its path yet
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import reduce as R  # noqa: E402


def matching_seconds(device_ops: dict, patterns: list[str]) -> float:
    """Device seconds of the leaf operations whose HLO line matches every
    pattern, averaged over the device planes."""
    pats = [re.compile(p) for p in patterns]
    planes = [ev for ev in device_ops.values() if ev]
    return sum(e - s for ev in planes for name, s, e in ev
               if not R.CONTAINER.match(R.opcode(name)) and all(p.search(name) for p in pats)) / max(len(planes), 1)


def _device_seconds(ctx, patterns: list[str]) -> float:
    import jobs

    run = ctx["run"]
    traces = jobs.find_files(os.path.join(run.out_dir, "trace"), ".xplane.pb") if hasattr(run, "out_dir") else []
    if not traces:
        pats = [re.compile(p) for p in patterns]
        return sum(t for n, t in ctx["trace"]["op_time_s"].items() if all(p.search(n) for p in pats))
    out = os.path.join(run.work, "serve_roofline.json")
    proc = subprocess.run([sys.executable, os.path.abspath(__file__), max(traces, key=os.path.getmtime), out, *patterns],
                          env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=300)
    if proc.returncode != 0:
        jobs.say(f"[roofline] the reduction failed: {jobs.tail(proc.stderr, 3)}")
        return 0.0
    return float((jobs.read_json(out) or {}).get("device_s", 0.0))


def _delta(ctx, **term):
    from readers.registry_delta import total

    d = ctx["drive"]
    ends = [total(d.get(snap), **term) for snap in ("snap0", "snap1")]
    return None if any(v is None for v in ends) else ends[1] - ends[0]


def window_means(ctx) -> dict | None:
    """What the counters say of the window's calls; None where the program has
    no such counters (a program older than they are) or nothing was decoded."""
    chunks = _delta(ctx, name="tony_serve_engine_chunks_total")
    slots = _delta(ctx, name="tony_serve_decode_slots_total")
    seen = _delta(ctx, name="tony_serve_visible_tokens_total")
    prefill = _delta(ctx, name="tony_serve_prefill_chunks_total")
    sparse = _delta(ctx, name="tony_serve_prefill_chunks_total", where={"path": ["sparse"]})
    if None in (chunks, slots, seen, prefill, sparse) or not chunks or not slots:
        return None
    steps = ctx["run"].w["engine"].get("decode_chunk", 8)
    return {"live_slots": slots / chunks, "visible_per_slot": seen / (slots * steps),
            "sparse_chunk_share": sparse / prefill if prefill else 0.0}


def read(ctx, kernel, match):
    import counts
    import families

    tr, run = ctx.get("trace"), ctx["run"]
    if not tr or ctx["device"].get("platform") != "tpu":
        return None
    own = families.counts(run.sizes)
    parts = [getattr(own, f"{kernel}_{part}", None) for part in ("operands", "call", "calls")]
    if None in parts:  # another family's cell (a sweep of an unlisted workload scans every metric of its kind)
        return None
    operands, call, calls = parts
    means = window_means(ctx)
    if means is None:
        return None
    engine = run.w["engine"]
    device_s = _device_seconds(ctx, [match, operands(run.sizes, engine)])
    module, per_execution = calls(run.sizes, engine)
    # an execution the capture cut at either end has its time cut too: count it as the part of a whole one it is
    times = sorted(t for name, ts in tr.get("modules", {}).items() if module in name for t in ts)
    executions = sum(times) / times[len(times) // 2] if times and times[len(times) // 2] > 0 else 0
    if not device_s or not executions:
        return None
    peak = counts.peak_for(ctx["device"]["kind"], run.peaks)
    return 100.0 * counts.roofline_seconds(*call(run.sizes, engine, means), peak) * per_execution * executions / device_s


if __name__ == "__main__":
    ops, _, _ = R.read_xplane(sys.argv[1])
    with open(sys.argv[2], "w") as f:
        json.dump({"device_s": matching_seconds(ops, sys.argv[3:])}, f)
