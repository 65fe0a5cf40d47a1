#!/usr/bin/env python3
"""Builder-side: find the knee of an open-loop serving cell once, on the chip.

    python benchmark/sweep.py --workload <cell> --rates 2,3,4,5,6,7 --seconds 30 [--seed n]

One fleet is kept up through all rates (the cell's engine settings and
traffic shapes; only the arrival rate changes). For each rate it prints the
rate completed, the TTFT and gap percentiles, what was still in flight at the
window's end and the queue depth the replica reported. The knee is the
highest rate at which completions keep up with arrivals and nothing is left
queued; the cell's file then gets 0.8 of it, as a number. Not part of a
check's runs; the benchmark never searches for a rate.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import jobs  # noqa: E402
import run as harness  # noqa: E402
import serve_cell  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=424242)
    a = p.parse_args()
    r = harness.Run(argparse.Namespace(workload=a.workload, seed=a.seed, seconds=a.seconds, trace=0, control=0))
    fleet = serve_cell.Fleet(r)
    try:
        fleet.start()
        print(f"[sweep] fleet up in {fleet.launch_s:.1f}s; warm-up repeat identical: {fleet.warm()}", flush=True)
        for i, rate in enumerate(float(x) for x in a.rates.split(",")):
            traffic = copy.deepcopy(r.w["traffic"])
            traffic["arrivals"] = {"process": "poisson", "rate": rate}
            traffic["drain_s"] = 45
            d = serve_cell.drive(fleet, r, a.seconds, traffic, a.seed + i)
            s = serve_cell.summarise(d, a.seconds, r.w.get("limits", {}))
            depth = [st.get("queue_depth", 0) for st in d["stats"]]
            active = [st.get("slots_active", 0) for st in d["stats"]]
            late_half = [x for x in depth[len(depth) // 2:]] or [0]
            print(f"[sweep] rate={rate} " + json.dumps({
                "sent_per_s": s["attempted"] / a.seconds, "finished_in_window_tok_s": s["completed_tok_s"],
                "out_tok_s": s["out_tok_s"], "ttft_p50_ms": s["ttft_p50_ms"], "ttft_p95_ms": s["ttft_p95_ms"],
                "itl_p50_ms": s["itl_p50_ms"], "itl_p95_ms": s["itl_p95_ms"], "tpot_mean_ms": s["tpot_mean_ms"],
                "failed": s["failed"], "left_in_flight": d["left_in_flight"],
                "queue_depth_max": max(depth or [0]), "queue_depth_mean_2nd_half": sum(late_half) / len(late_half),
                "slots_active_mean": sum(active) / max(len(active), 1), "lateness": s["lateness"]}), flush=True)
            time.sleep(3)
        rc, drained = fleet.stop()
        print(f"[sweep] fleet stopped: exit {rc}, drained cleanly={drained}", flush=True)
    except jobs.JobFailed as e:
        print(f"sweep: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        jobs.kill_all(r.staging)
    return 0


if __name__ == "__main__":
    sys.exit(main())
