"""Seconds of the spans of one name that ended before the window opened, from the job's span sink."""
import os

import jobs


def read(ctx, span, where=None):
    """Sum of the durations of `span`s whose attributes match `where`
    ({attribute: [values]}) and that ended before the window opened. None
    where the sink holds no span of that name at all (tracing off, or a
    program that writes none): 0.0 only where some were written and none matched."""
    t_open_ms = (ctx.get("drive") or ctx)["t_open"] * 1000.0
    seconds, seen = 0.0, False
    for path in jobs.find_files(os.path.join(ctx["app_dir"], "trace"), ".spans.jsonl"):
        for rec in jobs.read_jsonl(path):
            if rec.get("name") != span:
                continue
            seen = True
            start, end, attrs = rec.get("start_ms"), rec.get("end_ms"), rec.get("attrs") or {}
            if start is None or end is None or end > t_open_ms:
                continue
            if all(attrs.get(k) in vs for k, vs in (where or {}).items()):
                seconds += max(0.0, end - start) / 1000.0
    return seconds if seen else None
