import os

import jobs


def read(ctx, span):
    """Share of the stepping time (first step line -> last) that spans of
    this name cover, from the child's span sink."""
    files = jobs.find_files(os.path.join(ctx["app_dir"], "trace"), ".spans.jsonl")
    lines = ctx.get("lines") or []
    if not files or len(lines) < 2:
        return None
    t0, t1 = lines[0]["ts_ms"], lines[-1]["ts_ms"]
    covered, seen = 0.0, False
    for path in files:
        for rec in jobs.read_jsonl(path):
            seen = True
            if rec.get("name") != span:
                continue
            s = rec.get("start_ms")
            e = rec.get("end_ms", s)
            if s is None:
                continue
            covered += max(0.0, min(e, t1) - max(s, t0))
    return 100.0 * covered / (t1 - t0) if seen else None
