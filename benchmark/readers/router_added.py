def _sum_count(snap, name):
    for m in (snap or {}).get("metrics", []):
        if m["name"] == name:
            return (sum(s.get("sum", 0.0) for s in m["samples"]), sum(s.get("count", 0) for s in m["samples"]))
    return None


def read(ctx, histogram):
    d = ctx["drive"]
    a, b = _sum_count(d["snap0"], histogram), _sum_count(d["snap1"], histogram)
    firsts = [r.arrivals[0][0] - r.sent_t for r in d["records"]
              if r.arrivals and r.arrivals[0][0] <= d["t_close"]]
    if a is None or b is None or b[1] <= a[1] or not firsts:
        return None
    replica_ms = 1000.0 * (b[0] - a[0]) / (b[1] - a[1])
    return 1000.0 * sum(firsts) / len(firsts) - replica_ms
