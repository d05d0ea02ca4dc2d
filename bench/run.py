"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, fixed rate, comparison limits
and per-layer metric readers are found by name (``bench/lib/spec.py``).
The run builds the engine, warms every bucket of the cell's route at the
cell's shape (set-up), drives the window through
``FCMServeEngine.submit_async``, compares a seeded sample of the answers
with the plain reference, and prints one JSON object as the last line
of standard output. With ``--trace 0`` its metrics are the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the
profiler and the metrics are the cell's per-layer metrics.

It exits non-zero, printing no result, where JAX finds no chip listed in
``bench/lib/peaks.py`` or fewer chips than the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def prepare(workload: str):
    """(cell, devices, peak) on a machine with the chips the cell needs;
    prints why and returns an exit code otherwise."""
    # A fixed path inside the checkout, so only a cell's first run there
    # compiles; a directory the caller set wins.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.lib import harness, peaks, spec

    cell = spec.resolve(workload)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        print(f"FAIL: the program is not here ({e})", file=sys.stderr)
        return 2
    devices = jax.devices()
    try:
        peak = peaks.lookup(devices[0].device_kind)
    except peaks.UnknownDevice as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"FAIL: the cell needs {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 4
    harness.log(f"device {devices[0].device_kind!r} x{len(devices)}, "
                f"jax {jax.__version__}, cache "
                f"{os.environ['JAX_COMPILATION_CACHE_DIR']}")
    return cell, devices, peak


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    ready = prepare(args.workload)
    if isinstance(ready, int):
        return ready
    cell, devices, peak = ready
    from bench.lib import harness
    opt = harness.Options(seed=args.seed % (1 << 63), seconds=args.seconds,
                          trace=bool(args.trace), t_process=T_PROCESS,
                          peak=peak)
    result = harness.run_cell(cell, opt, devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
