"""Run the four-chip cell on four virtual CPU devices with one planted
fault (``none`` for a sound run) and print the result line. Where
``BENCHMARK.json`` does not list the cell yet, it runs from a scratch
copy of the benchmark that does, as a later change adding it would.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python bench/tests/mesh_runner.py study-fcms-batch-mesh4 <fault>
"""
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


MESH_CELL = {"name": "study-fcms-batch-mesh4",
             "config": "brainweb-t1-noisy-fcms-mesh4",
             "traffic": "closed-studies", "chips": 4,
             "why": "four-chip host, batch axis sharded over data=4"}


def bench_listing(cell: str, scratch: str):
    """The benchmark directory whose BENCHMARK.json lists ``cell``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    if any(w["name"] == cell for w in bm["workloads"]):
        return None
    bm["workloads"].append(dict(MESH_CELL))
    for m in bm["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    shutil.copytree(os.path.join(ROOT, "bench"),
                    os.path.join(scratch, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(scratch, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return os.path.join(scratch, "bench")


class _Patch:
    """The part of pytest's monkeypatch the faults use."""

    def setattr(self, obj, name, value):
        setattr(obj, name, value)


def main() -> int:
    from bench.tests import drive

    cell, fault = sys.argv[1], sys.argv[2]
    kw = {}
    if fault == "control":
        kw["control"] = True
    elif fault == "state_unchanged":
        drive.state_unchanged(_Patch())
    elif fault != "none":
        kw["fault"] = getattr(drive, fault)
    with tempfile.TemporaryDirectory() as scratch:
        bench_dir = bench_listing(cell, scratch)
        print(json.dumps(drive.run(cell, bench_dir=bench_dir, **kw)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
