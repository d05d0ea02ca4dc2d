"""Look for the host stalls of one cell's window: runs the cell once,
with a heartbeat thread (1 ms sleeps), the collector's callbacks and a
timer around each batch's gather, launch and scatter on the flusher,
and prints every event of 20 ms or more as an offset from the window's
start, beside the generator's own stalls (the run's log).

    python3 bench/tools/stall.py --workload <cell> --seconds 20 --out <dir>
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from bench import run  # noqa: E402

OVER = 0.02


def proc_status() -> dict:
    out = {}
    with open("/proc/self/status") as f:
        for line in f:
            if "ctxt_switches" in line or line.startswith("Threads"):
                k, v = line.split(":")
                out[k] = int(v)
    with open("/proc/loadavg") as f:
        out["loadavg"] = f.read().split()[:3]
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=31337)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    ready = run.prepare(args.workload)
    if isinstance(ready, int):
        return ready
    cell, devices, peak = ready
    import jax
    from bench.lib import harness
    from repro.serving.fcm_engine import RouteProgram

    events = []                     # (perf_counter, what, seconds)
    stop = threading.Event()

    def heartbeat():
        while not stop.is_set():
            t = time.perf_counter()
            time.sleep(0.001)
            late = time.perf_counter() - t - 0.001
            if late >= OVER:
                events.append((t, "heartbeat", late))

    gc_start = {}

    def on_gc(phase, info):
        if phase == "start":
            gc_start["t"] = time.perf_counter()
        elif "t" in gc_start:
            d = time.perf_counter() - gc_start["t"]
            if d >= 0.005:
                events.append((gc_start["t"], f"gc gen{info['generation']}",
                               d))

    batches = []                    # (t_gather, bucket, n, g, l, s)

    def timed(eng):
        orig = eng._program_for

        def program_for(route, chunk, bucket):
            prog = orig(route, chunk, bucket)
            if prog is None:
                return None
            box = {}

            def gather(e, ch, b):
                box["t"] = time.perf_counter()
                out = prog.gather(e, ch, b)
                box["g"] = time.perf_counter() - box["t"]
                box["b"], box["n"] = b, len(ch)
                return out

            def launch(*inputs):
                t = time.perf_counter()
                outs = jax.block_until_ready(prog.launch(*inputs))
                box["l"] = time.perf_counter() - t
                return outs

            def scatter(e, ch, outs):
                t = time.perf_counter()
                out = prog.scatter(e, ch, outs)
                batches.append((box["t"], box["b"], box["n"], box["g"],
                                box["l"], time.perf_counter() - t))
                return out
            return RouteProgram(gather, launch, scatter, prog.impls)
        eng._program_for = program_for

    starts = {}
    for name in ("drive_open", "drive_closed"):
        orig = getattr(harness, name)

        def wrapped(*a, _orig=orig, **k):
            starts["before"] = proc_status()
            t0, served = _orig(*a, **k)
            starts["t0"] = t0
            starts["after"] = proc_status()
            return t0, served
        setattr(harness, name, wrapped)

    opt = harness.Options(seed=args.seed, seconds=args.seconds, trace=False,
                          t_process=T_PROCESS, peak=peak, fault=timed)
    hb = threading.Thread(target=heartbeat, daemon=True)
    gc.callbacks.append(on_gc)
    hb.start()
    res = harness.run_cell(cell, opt, devices)
    stop.set()
    hb.join()
    gc.callbacks.remove(on_gc)
    t0 = starts["t0"]
    print(json.dumps({k: res[k] for k in ("correct", "metrics")}))
    print(f"process: before {starts['before']}, after {starts['after']}, "
          f"cpus {len(os.sched_getaffinity(0))}")
    inside = [b for b in batches if b[0] >= t0]
    gaps = [(b[0] - (a[0] + a[3] + a[4] + a[5]), b) for a, b in
            zip(inside, inside[1:])]
    for phase, i in (("gather", 3), ("launch", 4), ("scatter", 5)):
        xs = sorted(b[i] for b in inside)
        if xs:
            print(f"{phase}: {len(xs)} batches, median {xs[len(xs) // 2]:.6f}"
                  f" s, p99 {xs[int(len(xs) * 0.99)]:.6f} s, max "
                  f"{xs[-1]:.6f} s")
    rows = [(t - t0, what, d) for t, what, d in events if t >= t0]
    for b in inside:
        for phase, i in (("gather", 3), ("launch", 4), ("scatter", 5)):
            if b[i] >= OVER:
                rows.append((b[0] - t0, f"{phase} bucket {b[1]} n {b[2]}",
                             b[i]))
    rows += [(b[0] - t0, f"flusher idle before bucket {b[1]} n {b[2]}", g)
             for g, b in gaps if g >= 0.05]
    for off, what, d in sorted(rows):
        print(f"  {off:9.3f} s  {d:.4f} s  {what}")
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"stall_{args.workload}.json"),
              "w") as f:
        json.dump({"t0": t0, "batches": batches, "events": events}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
