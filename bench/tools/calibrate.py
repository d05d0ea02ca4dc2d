"""Readings for the limits of a cell's comparison, in one process:
the program's numbers on many seeds (a short window at the cell's own
load each, the same comparison a run makes), then the control's, the
reference computed in bfloat16 in the program's place, on the first
few of those seeds.

    python3 bench/tools/calibrate.py --workload <cell> --seeds 12 \
        --control-seeds 3 --seconds 3 --base-seed 1000
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
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--base-seed", type=int, default=1000)
    args = ap.parse_args()
    ready = run.prepare(args.workload)
    if isinstance(ready, int):
        return ready
    cell, devices, _ = ready
    from bench.lib import compare, harness, traffic as T

    route = cell.config["route"]
    eng = None
    rows = []
    for i in range(args.seeds):
        seed = args.base_seed + 7919 * i
        pool = T.make_pool(cell.config, cell.traffic, seed)
        if eng is None:
            eng = harness.build_engine(cell.config, devices)
            harness.warm(eng, route, pool)
        if cell.traffic["loop"] == "open":
            sched = T.open_schedule(float(cell.params["rate_per_s"]),
                                    args.seconds, pool, seed)
            _, s = harness.drive_open(eng, route, pool, sched, args.seconds,
                                      seed)
        else:
            _, s = harness.drive_closed(eng, route, pool,
                                        int(cell.traffic["clients"]),
                                        args.seconds, seed)
        r = {"seed": seed, "kind": "program", "failed": int((~s.ok).sum()),
             **compare.readings(cell, pool, s.kept)}
        print(json.dumps(r), flush=True)
        rows.append(r)
        if i < args.control_seeds:
            c = {"seed": seed, "kind": "control_bf16",
                 **compare.readings(cell, pool, s.kept, control=True)}
            print(json.dumps(c), flush=True)
            rows.append(c)
    eng.shutdown()
    for kind, pick in (("program", max), ("control_bf16", min)):
        sel = [r for r in rows if r["kind"] == kind]
        print(json.dumps({"summary": kind, "n": len(sel), **{
            k: pick(r[k] for r in sel)
            for k in ("center_dev", "iter_gap", "label_mismatch")}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
