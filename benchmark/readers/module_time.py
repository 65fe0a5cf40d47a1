import re
import statistics


def read(ctx, match, per=None):
    """Median device time (ms) of the jitted program whose name matches,
    divided by an engine setting (the decode chunk's token steps) if named."""
    tr = ctx.get("trace")
    if not tr or ctx["device"].get("platform") != "tpu":
        return None
    pat = re.compile(match)
    times = [t for name, ts in tr.get("modules", {}).items() if pat.search(name) for t in ts]
    if not times:
        return None
    div = ctx["run"].w["engine"].get(per, 1) if per else 1
    return 1000.0 * statistics.median(times) / div
