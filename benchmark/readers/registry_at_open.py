"""One of the replica's instruments as the window opens: what it counted since it started.

`snap0` (serve_cell.drive) is the replica's registry just before the window, so
a counter there holds the whole of set-up on the replica's side: its own
start-up and the harness's warm-up requests. `term` is registry_delta's.
"""
from readers.registry_delta import total


def read(ctx, term, scale=1.0):
    """scale x the term's total in `snap0`; None where the snapshot or the instrument is not there."""
    v = total(ctx["drive"]["snap0"], **term)
    return None if v is None else scale * v
