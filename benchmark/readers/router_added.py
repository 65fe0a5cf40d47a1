def _sum_count(snap, name):
    for m in (snap or {}).get("metrics", []):
        if m["name"] == name:
            return (sum(s.get("sum", 0.0) for s in m["samples"]), sum(s.get("count", 0) for s in m["samples"]))
    return None


def read(ctx, histogram):
    """The clients' mean time to a first token less the replica's own, over the same requests: the
    replica's histogram is read at the two registry snapshots, so the clients' side ends where the
    closing snapshot was READ (its own stamp `t`), not where it was asked for: an answer that a
    capture's export delays then adds the same first tokens to both sides."""
    d = ctx["drive"]
    a, b = _sum_count(d["snap0"], histogram), _sum_count(d["snap1"], histogram)
    edge = (d["snap1"] or {}).get("t", d["t_close"])
    firsts = [r.arrivals[0][0] - r.sent_t for r in d["records"] if r.arrivals and r.arrivals[0][0] <= edge]
    if a is None or b is None or b[1] <= a[1] or not firsts:
        return None
    replica_ms = 1000.0 * (b[0] - a[0]) / (b[1] - a[1])
    return 1000.0 * sum(firsts) / len(firsts) - replica_ms
