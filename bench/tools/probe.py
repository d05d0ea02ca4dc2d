"""Look at one traced window of a cell by hand: which planes and lines
the profiler wrote, the device operations by time with their metadata,
and how device and host clocks line up. Keeps the trace, and writes a
small slice of its events (``small_trace.json``) for the reducer's test.

    python3 bench/tools/probe.py --workload <cell> --seconds 2 --out <dir>
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from bench import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=424242)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    ready = run.prepare(args.workload)
    if isinstance(ready, int):
        return ready
    cell, devices, peak = ready
    from jax.profiler import ProfileData
    from bench.lib import harness, trace as TR

    out = os.path.join(args.out, args.workload)
    os.makedirs(out, exist_ok=True)
    opt = harness.Options(seed=args.seed, seconds=args.seconds, trace=True,
                          t_process=T_PROCESS, peak=peak,
                          trace_dir=tempfile.mkdtemp(prefix="probe-"))
    res = harness.run_cell(cell, opt, devices)
    print(json.dumps(res))
    path = TR.find_xplane(opt.trace_dir)
    t = time.perf_counter()
    data = ProfileData.from_file(path)
    print(f"xplane {os.path.getsize(path)} bytes, read in "
          f"{time.perf_counter() - t:.3f} s")
    for plane in data.planes:
        lines = [(ln.name, len(list(ln.events))) for ln in plane.lines]
        print(f"PLANE {plane.name}: {lines[:12]}")
        if not plane.name.startswith("/device:"):
            continue
        for ln in plane.lines:
            tot = collections.Counter()
            meta = {}
            ev = list(ln.events)
            for e in ev:
                tot[e.name] += e.duration_ns
                meta.setdefault(e.name, TR._stat_text(e)[:300])
            if ev:
                print(f"  LINE {ln.name}: {len(ev)} events, "
                      f"t {ev[0].start_ns:.0f} .. {ev[-1].start_ns:.0f}")
            for name, ns in tot.most_common(25):
                print(f"    {ns * 1e-6:10.3f} ms  {name}  | {meta[name]}")
    host = [p for p in data.planes if p.name.startswith("/host:")]
    for p in host:
        for ln in p.lines:
            ev = list(ln.events)
            if ev:
                names = collections.Counter(e.name for e in ev)
                print(f"  HOST {p.name} {ln.name}: {len(ev)} events, t "
                      f"{ev[0].start_ns:.0f} .. {ev[-1].start_ns:.0f}; "
                      f"{names.most_common(8)}")
    tr = TR.read_xplane(path, [d.id for d in devices[:cell.chips]])
    d0 = sorted(tr.devices[min(tr.devices)], key=lambda o: o[1])
    if d0:
        mid = d0[len(d0) // 2][1]
        lo, hi = mid, mid + 3e6
        small = TR.Trace(
            {min(tr.devices): [o for o in d0 if lo <= o[1] < hi]},
            [h for h in tr.host if h[1] < hi and h[1] + h[2] > lo
             and h[2] < 5e6])
        with open(os.path.join(out, "small_trace.json"), "w") as f:
            json.dump(small.to_json(), f)
        print(f"small trace: {len(small.devices[min(tr.devices)])} device "
              f"ops, {len(small.host)} host events")
    shutil.rmtree(opt.trace_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
