"""``tony goodput <app_id>`` — where did this job's wall-clock go?

Prints the exact phase partition (obs/goodput.py) of a job's wall-time — a
row a phase of ``obs_goodput.PHASE_ORDER`` that took any time, the one list
of phase names there is — plus the badput breakdown,
per-rank step-time skew (straggler attribution), and the job's alert
history. Works on finalized jobs (artifacts only) and live jobs (artifacts
up to "now", with the AM's ``get_goodput`` RPC adding live skew and the
currently-firing alerts).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any

from tony_tpu import constants
from tony_tpu.obs import artifacts as obs_artifacts
from tony_tpu.obs import goodput as obs_goodput


def _alert_history(events: list[Any]) -> list[dict[str, Any]]:
    """ALERT_FIRED/ALERT_RESOLVED records from the event stream, in order."""
    out = []
    for ev in events:
        if ev.type.value in ("ALERT_FIRED", "ALERT_RESOLVED"):
            out.append({
                "state": "fired" if ev.type.value == "ALERT_FIRED" else "resolved",
                "ts_ms": ev.timestamp_ms,
                **{k: ev.payload.get(k) for k in
                   ("rule", "value", "threshold", "reason") if k in ev.payload},
            })
    return out


def _straggler_history(events: list[Any]) -> list[dict[str, Any]]:
    out = []
    for ev in events:
        if ev.type.value in ("STRAGGLER_DETECTED", "STRAGGLER_RESOLVED"):
            out.append({
                "state": ("detected" if ev.type.value == "STRAGGLER_DETECTED"
                          else "resolved"),
                "ts_ms": ev.timestamp_ms,
                "task": ev.payload.get("task"),
                "ratio": ev.payload.get("ratio"),
            })
    return out


def render(ledger: obs_goodput.Ledger,
           live: dict[str, Any] | None,
           alert_history: list[dict[str, Any]],
           straggler_history: list[dict[str, Any]],
           window_ms: int) -> str:
    wall_s = ledger.wall_ms / 1000.0
    lines = [
        f"{ledger.app_id}  {'LIVE' if ledger.live else 'finalized'}  "
        f"wall {wall_s:.1f}s  goodput {ledger.goodput_fraction:.1%}"
        + (f"  (trailing {window_ms / 1000:.0f}s: "
           f"{ledger.window_fraction(window_ms):.1%})" if ledger.live else ""),
        "",
        "phase ledger (exact partition of wall-time):",
    ]
    for phase in obs_goodput.PHASE_ORDER:
        ms = ledger.phases_ms.get(phase, 0)
        if not ms:
            continue
        pct = ms / ledger.wall_ms if ledger.wall_ms else 0.0
        bar = "#" * int(round(pct * 30))
        lines.append(f"  {phase:<16s} {ms / 1000.0:>9.2f}s  {pct:>6.1%}  {bar}")
    lines.append(f"  {'total':<16s} {ledger.wall_ms / 1000.0:>9.2f}s  100.0%")

    badput = ledger.badput_ms()
    if badput:
        total_bad = sum(badput.values())
        lines += ["", f"badput breakdown ({total_bad / 1000.0:.2f}s lost):"]
        for phase, ms in badput.items():
            lines.append(f"  {phase:<16s} {ms / 1000.0:>9.2f}s  "
                         f"{ms / total_bad:>6.1%} of badput")
    if ledger.restarts or ledger.resizes or ledger.takeovers:
        lines += ["", f"episodes: {ledger.restarts} restart(s), "
                      f"{ledger.resizes} resize(s), {ledger.takeovers} takeover(s)"]

    skew = (live or {}).get("skew") or ledger.skew_by_task()
    stragglers = set((live or {}).get("stragglers") or ())
    if not stragglers:
        # final flagged state replays the history IN ORDER — a rank resolved
        # by a gang restart and re-detected afterwards is still flagged
        state: dict[str, bool] = {}
        for h in straggler_history:
            state[h["task"]] = h["state"] == "detected"
        stragglers = {t for t, on in state.items() if on}
    if skew or stragglers:
        lines += ["", "per-rank step-time skew (vs gang median):"]
        for task in sorted(set(skew) | stragglers):
            ratio = skew.get(task)
            cell = f"{ratio:>6.2f}x" if ratio is not None else "     ?x"
            mark = "  << STRAGGLER" if task in stragglers else ""
            step_ms = ledger.step_time_by_task_ms.get(task)
            detail = f"  ({step_ms:.1f}ms/step)" if step_ms else ""
            lines.append(f"  {task:<16s} {cell}{detail}{mark}")
    if straggler_history:
        lines += ["", "straggler events:"]
        for h in straggler_history:
            lines.append(
                f"  {h['ts_ms']}  {h['state']:<9s} {h['task']}"
                + (f"  ratio {h['ratio']}" if h.get("ratio") is not None else ""))

    active = (live or {}).get("alerts") or []
    if active:
        lines += ["", "alerts firing NOW:"]
        for a in active:
            lines.append(f"  {a['rule']}: value {a.get('value')} vs "
                         f"threshold {a.get('threshold')}")
    if alert_history:
        lines += ["", "alert history:"]
        for h in alert_history:
            detail = (f"  value {h['value']} vs {h['threshold']}"
                      if h.get("value") is not None else "")
            if h.get("reason"):
                detail += f"  ({h['reason']})"
            lines.append(f"  {h['ts_ms']}  {h['state']:<9s} {h.get('rule')}{detail}")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="tony goodput",
        description="exact goodput/badput phase accounting of a job's "
                    "wall-time, with straggler attribution and alert history "
                    "(docs/observability.md)")
    p.add_argument("app_id", help="application id (staging dir name)")
    p.add_argument("--staging", default=None,
                   help="staging root holding <app_id>/ (default: $TONY_ROOT)")
    p.add_argument("--window", type=float, default=60.0,
                   help="trailing window (s) for the live goodput figure")
    p.add_argument("--json", action="store_true",
                   help="machine-readable ledger instead of the table")
    args = p.parse_args(argv)

    staging = args.staging or constants.default_tony_root()
    art = obs_artifacts.index(staging, args.app_id)
    events, _complete = art.read_events()
    if not events:
        print(f"no history events for {args.app_id} under {staging} — "
              "has the job started?", file=sys.stderr)
        return 1
    spans = obs_artifacts.load_spans(art.trace_dir)
    import time as _time

    ledger = obs_goodput.build_ledger(
        args.app_id, events, spans, now_ms=int(_time.time() * 1000))

    live: dict[str, Any] | None = None
    if ledger.live:
        cli = art.am_client(timeout_s=5.0)
        if cli is not None:
            try:
                live = cli.call("get_goodput")
            except Exception:  # noqa: BLE001 — AM mid-exit: artifacts still answer
                live = None
            finally:
                cli.close()

    window_ms = int(args.window * 1000)
    if args.json:
        print(json.dumps({
            **ledger.to_dict(),
            "window_ms": window_ms,
            "window_fraction": ledger.window_fraction(window_ms),
            "alert_history": _alert_history(events),
            "straggler_history": _straggler_history(events),
            # "live_view" like the portal payload: the ledger's own "live"
            # boolean (spread above) must not be clobbered by the RPC dict
            "live_view": live,
        }))
        return 0
    print(render(ledger, live, _alert_history(events),
                 _straggler_history(events), window_ms))
    return 0


if __name__ == "__main__":
    sys.exit(main())
