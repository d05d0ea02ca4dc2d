"""Find an open-loop cell's knee: one warm engine, one window per
offered rate, and for each what was completed, the tails, how late the
generator ran and how many answers were still owed when the window
closed (a growing backlog). The knee is the highest rate whose completed
rate keeps up with no backlog growing over the window.

    python3 bench/tools/sweep.py --workload <cell> --rates 500,1000 \
        --seconds 4 --seed 7
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from bench import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    ready = run.prepare(args.workload)
    if isinstance(ready, int):
        return ready
    cell, devices, _ = ready
    import numpy as np
    from bench.lib import harness, traffic as T

    route = cell.config["route"]
    pool = T.make_pool(cell.config, cell.traffic, args.seed)
    eng = harness.build_engine(cell.config, devices)
    harness.warm(eng, route, pool)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        sched = T.open_schedule(rate, args.seconds, pool, args.seed + k)
        before = harness.read_counters(eng, route)
        t0, s = harness.drive_open(eng, route, pool, sched, args.seconds,
                                   args.seed + k)
        after = harness.read_counters(eng, route)
        close = t0 + args.seconds
        done_at = t0 + sched.due_s + s.latency_s
        owed = int(np.sum(done_at > close))
        lat = s.latency_s * 1e3
        real = after["images"] - before["images"]
        pad = after["padded"] - before["padded"]
        print(json.dumps({
            "offered_per_s": rate, "requests": len(lat),
            "completed_per_s": s.completed_in_window / args.seconds,
            "owed_at_close": owed, "failed": int((~s.ok).sum()),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "late_median_ms": float(np.median(s.late_s)) * 1e3,
            "late_max_ms": float(np.max(s.late_s)) * 1e3,
            "occupancy_pct": 100 * real / max(real + pad, 1),
            "batches": after["batches"] - before["batches"]}), flush=True)
    eng.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
