"""One phase of the program's own goodput ledger, in seconds before the window opens.

The ledger is built the way `tony goodput` builds it (obs.artifacts ->
goodput.build_ledger_from_artifacts on the job's directory): an exact partition
of the job's wall time from the `.jhist`, whose start-up stages are claimed from
stamps the client, the AM and the chip-holding child take themselves. It is
built once a run and kept on `ctx`.
"""
import os
import time


def ledger(ctx):
    if "goodput_ledger" not in ctx:
        from tony_tpu.obs import artifacts, goodput

        staging, app_id = os.path.split(ctx["app_dir"].rstrip("/"))
        ctx["goodput_ledger"] = goodput.build_ledger_from_artifacts(
            artifacts.index(staging, app_id), now_ms=int(time.time() * 1000))
    return ctx["goodput_ledger"]


def read(ctx, phase):
    """Seconds of `phase` before the window opened (a serve cell's `drive`,
    a train cell's `warm_lines`-th step line). None where the program has no
    such phase, or its ledger carries no stamps (a program before the stamps:
    the phases it has are then the coarse ones, and say nothing by stage)."""
    from tony_tpu.obs import goodput

    if phase not in goodput.PHASE_ORDER:
        return None
    led = ledger(ctx)
    if not getattr(led, "stamps", None):
        return None
    t_open_ms = (ctx.get("drive") or ctx)["t_open"] * 1000.0
    return sum(max(0.0, min(end, t_open_ms) - start) for ph, start, end in led.episodes if ph == phase) / 1000.0
