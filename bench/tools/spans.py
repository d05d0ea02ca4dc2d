"""Split one cell's window by the engine's spans: runs the cell once
under the profiler and prints, per batch, the host gather, the H2D
inside the launch and the D2H inside the scatter
(``route.stage_seconds{stage=gather,h2d,d2h}``) beside ``launch_ms`` and
``scatter_ms``, the mean wait of a request from submit to its batch's
gather (``route.queue_wait_seconds``) beside the mean latency, and the
device's idle time split by the innermost ``fcm.*`` span open over it
(``bench/lib/span_idle.py``). One JSON line last.

    python3 bench/tools/spans.py --workload <cell> --seconds 30 --seed <n>

The cell's own run, comparison and result are the harness's; this tool
only reads four more engine counters and keeps the trace until it is
split. On a program without the engine spans the counters read 0 and
the split is empty.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from bench import run  # noqa: E402

EXTRA = {"gather_s": ("route.stage_seconds", {"stage": "gather"}),
         "h2d_s": ("route.stage_seconds", {"stage": "h2d"}),
         "d2h_s": ("route.stage_seconds", {"stage": "d2h"}),
         "queue_wait_s": ("route.queue_wait_seconds", {})}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=31337)
    args = ap.parse_args()
    ready = run.prepare(args.workload)
    if isinstance(ready, int):
        return ready
    cell, devices, peak = ready
    from bench.lib import harness
    from bench.lib import span_idle as SI
    from bench.lib import trace as TR

    harness._COUNTERS.update(EXTRA)
    reads, seen = [], {}
    read_counters = harness.read_counters

    def counting(eng, route):
        reads.append(read_counters(eng, route))
        return reads[-1]
    harness.read_counters = counting
    for name in ("drive_open", "drive_closed"):
        orig = getattr(harness, name)

        def wrapped(*a, _orig=orig, **k):
            t0, served = _orig(*a, **k)
            seen["served"] = served
            return t0, served
        setattr(harness, name, wrapped)

    trace_dir = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        opt = harness.Options(seed=args.seed, seconds=args.seconds,
                              trace=True, t_process=T_PROCESS, peak=peak,
                              trace_dir=trace_dir)
        res = harness.run_cell(cell, opt, devices)
        trace = TR.read_xplane(TR.find_xplane(trace_dir),
                               [d.id for d in devices[:cell.chips]])
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    before, after = reads[0], reads[1]
    d = {k: after[k] - before[k] for k in after}
    n = max(d["batches"], 1.0)
    lat = seen["served"].latency_s
    by_span = SI.idle_by_span(trace)
    window = res["device"]["window_s"]
    out = {
        "workload": args.workload, "seed": args.seed,
        "correct": res["correct"], "batches": d["batches"],
        "queue_wait_ms": 1e3 * d["queue_wait_s"] / max(d["images"], 1.0),
        "host_gather_ms": 1e3 * d["gather_s"] / n,
        "launch_ms": 1e3 * d["solve_s"] / n, "h2d_ms": 1e3 * d["h2d_s"] / n,
        "scatter_ms": 1e3 * d["materialize_s"] / n,
        "d2h_ms": 1e3 * d["d2h_s"] / n,
        "latency_mean_ms": 1e3 * float(lat.mean()),
        "idle_share": 100.0 * (1.0 - res["device"]["busy_s"] / window),
        "idle_in_flush": 100.0 * sum(
            v for k, v in by_span.items() if k != SI.OUTSIDE_FLUSH) / window,
        "idle_by_span_s": by_span}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
