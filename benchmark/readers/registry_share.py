"""One instrument's change over the window as a share of several instruments' changes.

registry_delta takes one instrument a side; a share of a whole that the replica
counts in parts (prompt tokens that came from shared pages, and prompt tokens
that were prefilled) needs the parts added up. `part` and each of `rest` are
registry_delta's terms.
"""
from readers.registry_delta import total


def read(ctx, part, rest, scale=1.0):
    """scale x (change of `part`) / (change of `part` + changes of `rest`). None when a snapshot
    lacks `part` or any of `rest`, or nothing moved."""
    d = ctx["drive"]
    changes = []
    for term in (part, *rest):
        ends = [total(d[snap], **term) for snap in ("snap0", "snap1")]
        if None in ends:
            return None
        changes.append(ends[1] - ends[0])
    return scale * changes[0] / sum(changes) if sum(changes) > 0 else None
