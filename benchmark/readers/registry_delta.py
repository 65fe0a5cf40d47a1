"""The change of the replica's own instruments over the window, as a quotient.

The harness snapshots the replica's metrics registry as the window opens and
as it closes (`serve_cell.drive`: `snap0`, `snap1`), so whatever the program
registers is here without an edit to the harness. A term names one instrument,
the field to take (`value` of a counter, `sum` or `count` of a histogram) and
optionally the label values to keep (`where`) or to leave out (`where_not`),
each as {label: [values]}; the samples kept are added up. `scale_size` names one of
the cell's own sizes (a key of `run.sizes`, a dotted path into a list: "held.1", the
experts this replica holds) to multiply by, for a quotient whose unit is the cell's.
"""


def total(snap, name, field="value", where=None, where_not=None):
    """The term's total in one snapshot; None where the instrument is not there."""
    for m in (snap or {}).get("metrics", []):
        if m["name"] == name:
            return sum(
                s.get(field, 0.0) for s in m["samples"]
                if all(s["labels"].get(k) in vs for k, vs in (where or {}).items())
                and not any(s["labels"].get(k) in vs for k, vs in (where_not or {}).items()))
    return None


def size(sizes: dict, path: str):
    """The size at a dotted path; None where the cell's family has no such size (a sweep of an unlisted
    workload scans every metric of its kind, another family's among them)."""
    for key in path.split("."):
        if isinstance(sizes, (list, tuple)):
            sizes = sizes[int(key)]
        elif key in sizes:
            sizes = sizes[key]
        else:
            return None
    return sizes


def read(ctx, num, den, scale=1.0, scale_size=None):
    """scale x (change of `num`) / (change of `den`): a mean in ms (a histogram's
    sum over its count, x 1000), a ratio of counters, or a share (x 100). None
    when either snapshot lacks either instrument or `den` did not move."""
    d = ctx["drive"]
    if scale_size is not None:
        own = size(ctx["run"].sizes, scale_size)
        if own is None:
            return None
        scale = scale * own
    ends = [total(d[snap], **term) for term in (num, den) for snap in ("snap0", "snap1")]
    if any(v is None for v in ends) or ends[3] <= ends[2]:
        return None
    return scale * (ends[1] - ends[0]) / (ends[3] - ends[2])
