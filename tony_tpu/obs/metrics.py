"""Process-wide metrics registry with Prometheus text exposition.

The MetricsRpc analog grown up: instead of ad-hoc dicts pushed to the AM,
every process owns one :data:`REGISTRY` of named counters / gauges /
fixed-bucket histograms. Instrumented paths (RPC client/server latency,
``call_with_retry`` attempts/backoff, heartbeat RTT, scheduler queue wait,
checkpoint durations, sampled train-step time) record into it; exposition is

- ``GET /metrics`` on the portal (Prometheus text format 0.0.4), which merges
  its own registry with every running AM's via the ``get_metrics`` RPC, and
- the AM's ``get_metrics`` RPC returning :meth:`MetricsRegistry.snapshot`.

Snapshots are plain JSON (they ride the framed-JSON RPC), and
:func:`render_merged` turns any set of (snapshot, extra-labels) groups into
one valid exposition — the portal labels each AM's group with ``app=<id>``.

Everything is stdlib + threads; recording is a dict update under a per-metric
lock (the instrumented paths are control-plane rate, not the train step).
``set_enabled(False)`` (``tony.metrics.enabled=false``) turns every recording
call into an early return.
"""

from __future__ import annotations

import math
import threading
from bisect import bisect_left
from typing import Any, Iterable, Mapping, Sequence

_INF = float("inf")

#: Default latency buckets (seconds): sub-ms RPC dispatch up to multi-second
#: checkpoint/compile work.
DEFAULT_BUCKETS: tuple[float, ...] = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0,
)

#: Wider buckets for waits measured in seconds-to-minutes (queue admission,
#: gang registration, restarts).
WAIT_BUCKETS: tuple[float, ...] = (
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0, 600.0,
)

_enabled = True


def set_enabled(on: bool) -> None:
    """Gate all recording (tony.metrics.enabled); registration still works."""
    global _enabled
    _enabled = bool(on)


class _Metric:
    kind = ""

    def __init__(self, name: str, help_: str, labelnames: Sequence[str] = ()):
        self.name = name
        self.help = help_
        self.labelnames = tuple(labelnames)
        self._lock = threading.Lock()
        self._children: dict[tuple[str, ...], Any] = {}

    def _key(self, labels: Mapping[str, Any]) -> tuple[str, ...]:
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: labels {sorted(labels)} != declared {sorted(self.labelnames)}"
            )
        return tuple(str(labels[n]) for n in self.labelnames)

    def remove(self, **labels: Any) -> None:
        """Drop one label child from the exposition. For bounded-lifetime
        label values (e.g. the portal's per-app scrape-age gauge): without
        removal, every value ever labeled stays a frozen series forever —
        unbounded cardinality and permanently stale samples."""
        key = self._key(labels)
        with self._lock:
            self._children.pop(key, None)

    def _label_dicts(self) -> "list[tuple[tuple[str, ...], Any]]":
        with self._lock:
            # deep-copy histogram children: observe() mutates them under
            # this same lock, and a live reference would let a concurrent
            # observe tear the snapshot (counts summing to N+1, count N →
            # a non-monotone exposition scrapers reject)
            return [
                (k, dict(v, counts=list(v["counts"])) if isinstance(v, dict) else v)
                for k, v in self._children.items()
            ]


class Counter(_Metric):
    kind = "counter"

    def inc(self, amount: float = 1.0, **labels: Any) -> None:
        if not _enabled:
            return
        key = self._key(labels)
        with self._lock:
            self._children[key] = self._children.get(key, 0.0) + amount

    def value(self, **labels: Any) -> float:
        key = self._key(labels)
        with self._lock:
            return float(self._children.get(key, 0.0))


class Gauge(_Metric):
    kind = "gauge"

    def set(self, value: float, **labels: Any) -> None:
        if not _enabled:
            return
        key = self._key(labels)
        with self._lock:
            self._children[key] = float(value)

    def value(self, **labels: Any) -> float:
        key = self._key(labels)
        with self._lock:
            return float(self._children.get(key, 0.0))


#: Worst-offender exemplars kept per histogram label child (highest values).
EXEMPLAR_K = 5


class Histogram(_Metric):
    """Fixed-bucket histogram (per-bucket increments; cumulated at render)."""

    kind = "histogram"

    def __init__(self, name: str, help_: str, labelnames: Sequence[str] = (),
                 buckets: Sequence[float] = DEFAULT_BUCKETS, lock_of: "Histogram | None" = None):
        super().__init__(name, help_, labelnames)
        bs = sorted(float(b) for b in buckets)
        if not bs or any(not math.isfinite(b) for b in bs):
            raise ValueError(f"{name}: buckets must be finite and non-empty")
        self.buckets = tuple(bs)
        if lock_of is not None:  # two timings of one event: see observe_pair
            self._lock = lock_of._lock

    def ensure_bucket(self, bound: float) -> None:
        """Insert a bucket boundary (idempotent) — e.g. the configured SLO
        TTFT threshold, so good/bad request counts are exact from cumulative
        bucket counts rather than interpolated. Call at process startup:
        observations recorded before the insert stay in their original
        (coarser) bucket, so a mid-stream insert undercounts at the new edge.
        """
        b = float(bound)
        if not math.isfinite(b) or b <= 0:
            raise ValueError(f"{self.name}: SLO bucket bound must be finite and > 0")
        with self._lock:
            if b in self.buckets:
                return
            merged = sorted(self.buckets + (b,))
            idx = merged.index(b)
            self.buckets = tuple(merged)
            for child in self._children.values():
                child["counts"].insert(idx, 0)

    def observe(self, value: float, exemplar: Any = None, **labels: Any) -> None:
        if not _enabled:
            return
        key = self._key(labels)
        with self._lock:
            self._observe_locked(key, value, exemplar)

    def _observe_locked(self, key: tuple[str, ...], value: float, exemplar: Any = None) -> None:
        child = self._children.get(key)
        if child is None:
            # [per-bucket counts..., overflow], sum, count
            child = self._children[key] = {
                "counts": [0] * (len(self.buckets) + 1), "sum": 0.0, "count": 0,
                "exemplars": [],
            }
        child["counts"][bisect_left(self.buckets, value)] += 1  # the first bound not under it, else the overflow
        child["sum"] += value
        child["count"] += 1
        if exemplar is not None:
            # worst-K by value: lets an operator jump from a burning
            # latency SLO straight to the offending request ids
            ex = child["exemplars"]
            ex.append((float(value), str(exemplar)))
            ex.sort(key=lambda t: -t[0])
            del ex[EXEMPLAR_K:]

    def _snapshot_children(self) -> "tuple[list[float], list[tuple[tuple[str, ...], dict]]]":
        # buckets + children under ONE lock: ensure_bucket resizes counts in
        # place, and reading them separately could tear bucket/count lengths
        with self._lock:
            return list(self.buckets), [
                (k, {
                    "counts": list(v["counts"]), "sum": v["sum"], "count": v["count"],
                    "exemplars": [list(e) for e in v.get("exemplars", ())],
                })
                for k, v in self._children.items()
            ]


class MetricsRegistry:
    """Name → metric map; re-registering a name returns the existing metric
    (modules declare their instruments at import time, in any order)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: dict[str, _Metric] = {}

    def _register(self, cls, name: str, help_: str, labelnames: Sequence[str],
                  **kwargs: Any) -> Any:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help_, labelnames, **kwargs)
            elif not isinstance(m, cls) or m.labelnames != tuple(labelnames):
                raise ValueError(f"metric {name!r} re-registered with a different shape")
            return m

    def counter(self, name: str, help_: str = "", labelnames: Sequence[str] = ()) -> Counter:
        return self._register(Counter, name, help_, labelnames)

    def gauge(self, name: str, help_: str = "", labelnames: Sequence[str] = ()) -> Gauge:
        return self._register(Gauge, name, help_, labelnames)

    def histogram(self, name: str, help_: str = "", labelnames: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_BUCKETS, lock_of: Histogram | None = None) -> Histogram:
        return self._register(Histogram, name, help_, labelnames, buckets=buckets, lock_of=lock_of)

    def reset(self) -> None:
        """Drop all recorded values AND registrations (tests only)."""
        with self._lock:
            self._metrics.clear()

    # ---------------------------------------------------------- exposition
    def snapshot(self) -> list[dict[str, Any]]:
        """JSON-able view of every metric — the ``get_metrics`` RPC payload."""
        with self._lock:
            metrics = list(self._metrics.values())
        out: list[dict[str, Any]] = []
        for m in metrics:
            entry: dict[str, Any] = {
                "name": m.name, "type": m.kind, "help": m.help,
                "labelnames": list(m.labelnames), "samples": [],
            }
            if isinstance(m, Histogram):
                buckets, children = m._snapshot_children()
                entry["buckets"] = buckets
                for key, child in children:
                    entry["samples"].append({
                        "labels": dict(zip(m.labelnames, key)),
                        "counts": child["counts"],
                        "sum": child["sum"],
                        "count": child["count"],
                        "exemplars": child["exemplars"],
                    })
            else:
                for key, value in m._label_dicts():
                    entry["samples"].append({
                        "labels": dict(zip(m.labelnames, key)), "value": value,
                    })
            out.append(entry)
        return out

    def render(self) -> str:
        """This process's registry as Prometheus text format."""
        return render_merged([(self.snapshot(), {})])


#: The process-wide default registry every instrumented module records into.
REGISTRY = MetricsRegistry()


def counter(name: str, help_: str = "", labelnames: Sequence[str] = ()) -> Counter:
    return REGISTRY.counter(name, help_, labelnames)


def gauge(name: str, help_: str = "", labelnames: Sequence[str] = ()) -> Gauge:
    return REGISTRY.gauge(name, help_, labelnames)


def histogram(name: str, help_: str = "", labelnames: Sequence[str] = (),
              buckets: Sequence[float] = DEFAULT_BUCKETS, lock_of: Histogram | None = None) -> Histogram:
    return REGISTRY.histogram(name, help_, labelnames, buckets=buckets, lock_of=lock_of)


def observe_pair(first: Histogram, a: float, second: Histogram, b: float) -> None:
    """One unlabelled observation in each of two histograms under ONE lock
    acquisition, for a hot path that takes two timings of one event (a stream
    writer's own time and the event's delay). ``second`` was made with
    ``lock_of=first``, so each histogram's own ``observe`` and snapshot hold
    the same lock."""
    if not _enabled:
        return
    if second._lock is not first._lock:
        raise ValueError(f"{second.name} does not share {first.name}'s lock (lock_of)")
    with first._lock:
        first._observe_locked((), a)
        second._observe_locked((), b)


# ------------------------------------------------------- Prometheus text
def _escape_label(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_labels(labels: Mapping[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_escape_label(str(v))}"' for k, v in labels.items())
    return "{" + inner + "}"


def _fmt_value(v: float) -> str:
    f = float(v)
    if f == math.inf:
        return "+Inf"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return f"{f:.10g}"


def render_merged(
    groups: Iterable[tuple[list[dict[str, Any]], Mapping[str, str]]],
) -> str:
    """Merge (snapshot, extra_labels) groups into one Prometheus exposition.

    Metrics sharing a name across groups (the portal's own registry + each
    AM's) are emitted under a single HELP/TYPE header, their samples
    distinguished by the group's extra labels (e.g. ``app="application_…"``).
    """
    by_name: dict[str, list[tuple[dict[str, Any], Mapping[str, str]]]] = {}
    order: list[str] = []
    for snapshot, extra in groups:
        for metric in snapshot:
            name = metric["name"]
            if name not in by_name:
                by_name[name] = []
                order.append(name)
            by_name[name].append((metric, extra))
    lines: list[str] = []
    for name in order:
        entries = by_name[name]
        mtype = entries[0][0].get("type", "untyped")
        help_ = entries[0][0].get("help", "")
        if help_:
            lines.append(f"# HELP {name} {help_}")
        lines.append(f"# TYPE {name} {mtype}")
        for metric, extra in entries:
            for sample in metric.get("samples", []):
                labels = {**sample.get("labels", {}), **extra}
                if mtype == "histogram":
                    cum = 0
                    for ub, n in zip(metric.get("buckets", []), sample["counts"]):
                        cum += n
                        blabels = {**labels, "le": _fmt_value(ub)}
                        lines.append(f"{name}_bucket{_fmt_labels(blabels)} {cum}")
                    blabels = {**labels, "le": "+Inf"}
                    lines.append(f"{name}_bucket{_fmt_labels(blabels)} {sample['count']}")
                    lines.append(f"{name}_sum{_fmt_labels(labels)} {_fmt_value(sample['sum'])}")
                    lines.append(f"{name}_count{_fmt_labels(labels)} {sample['count']}")
                else:
                    lines.append(f"{name}{_fmt_labels(labels)} {_fmt_value(sample['value'])}")
    return "\n".join(lines) + ("\n" if lines else "")
