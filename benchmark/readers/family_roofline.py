"""Roofline share of one kernel of a serving replica, the window's means being the family's own.

serve_roofline with one difference: what a call's sizes are read from. There the
means are three fixed ones; here the family's counts say which of the replica's
counters they need (`window_means(delta, engine)`: rows that landed on a held
expert, prompt rows a prefill chunk, ...), so a family whose work follows other
counters adds no reader. The number of units of work is the traced executions
of the jitted program that does them, counted a compiled variant at a time (a
prefill chunk has a variant a bucket, and a median over variants of different
lengths is no unit), one the capture cut counting as the part of its variant's
median it lasted; the time is the device time of every leaf operation whose HLO
line matches `match` and the kernel's operand shape (serve_roofline's child).
"""
from __future__ import annotations

from readers import serve_roofline as SR


def executions(modules: dict, module: str) -> float:
    total = 0.0
    for name, times in modules.items():
        times = sorted(times)
        if module in name and times and times[len(times) // 2] > 0:
            total += sum(times) / times[len(times) // 2]
    return total


def read(ctx, kernel, match):
    import counts
    import families

    tr, run = ctx.get("trace"), ctx["run"]
    if not tr or ctx["device"].get("platform") != "tpu":
        return None
    own = families.counts(run.sizes)
    parts = [getattr(own, name, None) for name in (f"{kernel}_operands", f"{kernel}_call", f"{kernel}_calls", "window_means")]
    if None in parts:  # another family's cell (a sweep of an unlisted workload scans every metric of its kind)
        return None
    operands, call, calls, window_means = parts
    engine = run.w["engine"]
    means = window_means(lambda **term: SR._delta(ctx, **term), engine)
    if means is None:  # a program without the counters
        return None
    device_s = SR._device_seconds(ctx, [match, operands(run.sizes, engine)])
    module, per_execution = calls(run.sizes, engine)
    n = executions(tr.get("modules", {}), module)
    if not device_s or not n:
        return None
    peak = counts.peak_for(ctx["device"]["kind"], run.peaks)
    return 100.0 * counts.roofline_seconds(*call(run.sizes, engine, means), peak) * per_execution * n / device_s
