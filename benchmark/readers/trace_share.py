def read(ctx, field, min_planes=1):
    """A time the trace reduction already holds, as a share of the traced window."""
    tr = ctx.get("trace")
    if not tr or ctx["device"].get("platform") != "tpu" or tr.get("planes", 0) < min_planes or not tr.get("window_s"):
        return None
    return 100.0 * tr[field] / tr["window_s"]
