"""Per-task resource metrics sampling.

Analog of the reference's GPU/CPU utilization pipeline (SURVEY.md §2.1 "GPU
metrics", §5.5): where the reference forks ``nvidia-smi -q -x`` and JAXB-parses
the XML, the TPU rebuild reads device state through PJRT —
``jax.local_devices()[i].memory_stats()`` — plus ``/proc`` for host CPU/RSS.
Executors push these snapshots over the MetricsRpc analog; the AM attaches the
latest snapshot to each TaskInfo and emits METRICS_SNAPSHOT events.
"""

from __future__ import annotations

import os
import time
from typing import Any

_CLK = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100
_PAGE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


def sample_host_metrics(pid: int | None = None) -> dict[str, Any]:
    """CPU seconds + RSS for a process tree root, from /proc (no psutil)."""
    pid = pid or os.getpid()
    out: dict[str, Any] = {"timestamp_ms": int(time.time() * 1000), "pid": pid}
    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        # fields are post-comm: [state, ppid, ...]; utime=11, stime=12 (0-based here)
        utime, stime = int(fields[11]), int(fields[12])
        out["cpu_seconds"] = (utime + stime) / _CLK
        out["rss_bytes"] = int(fields[21]) * _PAGE
    except (OSError, IndexError, ValueError):
        pass
    try:
        load1, load5, load15 = os.getloadavg()
        out["host_load1"] = round(load1, 3)
    except OSError:
        pass
    return out


def sample_tpu_metrics() -> dict[str, Any]:
    """HBM usage per local TPU device via PJRT memory stats (nvidia-smi analog).

    Safe to call when jax is absent/unavailable — returns {} rather than
    raising, because metrics must never take down an executor.
    """
    try:
        import jax

        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 — metrics are strictly best-effort
        return {}
    per_device = []
    for d in devices:
        entry: dict[str, Any] = {"id": d.id, "kind": getattr(d, "device_kind", "unknown")}
        try:
            stats = d.memory_stats() or {}
            entry["hbm_bytes_in_use"] = stats.get("bytes_in_use", 0)
            entry["hbm_bytes_limit"] = stats.get("bytes_limit", 0)
        except Exception:  # noqa: BLE001
            pass
        per_device.append(entry)
    return {"devices": per_device} if per_device else {}


class HostMetricsSampler:
    """Whole-host CPU utilization and memory pressure, and this process's
    resident set, from /proc; ``cpu_util_pct`` is since the previous call."""

    def __init__(self):
        self._last: tuple[int, int] | None = None

    def sample(self) -> dict:
        ncpus = os.cpu_count() or 1
        try:
            with open("/proc/stat") as f:
                parts = [int(x) for x in f.readline().split()[1:9]]
            total, idle = sum(parts), parts[3] + parts[4]
            util = 0.0
            if self._last and total > self._last[0]:
                util = 100.0 * (1 - (idle - self._last[1]) / (total - self._last[0]))
            self._last = (total, idle)
            mem = {}
            with open("/proc/meminfo") as f:
                for line in f:
                    k, v = line.split(":", 1)
                    mem[k] = int(v.split()[0])
            total_kb = mem.get("MemTotal", 0)
            avail_kb = mem.get("MemAvailable", 0)
            with open("/proc/self/statm") as f:
                rss_pages = int(f.read().split()[1])
            return {
                "cpu_util_pct": round(util, 2),
                "mem_used_pct": round(100.0 * (1 - avail_kb / total_kb), 2) if total_kb else 0.0,
                "mem_total_mb": round(total_kb / 1024, 1),
                "rss_mb": round(rss_pages * _PAGE / 2**20, 1),
                "ncpus": ncpus,
            }
        except OSError:
            return {"cpu_util_pct": 0.0, "mem_used_pct": 0.0, "mem_total_mb": 0.0,
                    "rss_mb": 0.0, "ncpus": ncpus}


class MetricsSampler:
    """Combined host+TPU snapshot builder used by the executor push loop.

    Whole-host CPU utilization / memory pressure comes from
    :class:`HostMetricsSampler`; per-process CPU/RSS and per-device HBM are
    sampled here.
    """

    def __init__(self, child_pid: int | None = None, with_tpu: bool = True):
        self.child_pid = child_pid
        self.with_tpu = with_tpu
        self._host = HostMetricsSampler()

    def sample(self) -> dict[str, Any]:
        m = sample_host_metrics(self.child_pid)
        try:
            m["host"] = self._host.sample()
        except Exception:  # noqa: BLE001 — metrics are strictly best-effort
            pass
        if self.with_tpu:
            tpu = sample_tpu_metrics()
            if tpu:
                m["tpu"] = tpu
        return m
