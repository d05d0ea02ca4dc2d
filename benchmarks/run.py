"""Benchmark harness: one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV lines and writes the consolidated
``benchmarks/out/BENCH_pr9.json`` aggregating the batched / spatial /
superpixel serving numbers (engine-overhead + tracing-overhead gates,
per-route latency percentiles, convergence telemetry), the declarative
variant-zoo sweep (now including the 8-fake-device distributed solver
cells), the roofline-vs-achieved kernel report, and the async serving
load-generator section (open-loop Poisson QPS/p99 sweep + the
continuous-batching 3x gate), validates the result against
``bench_schema.py``, renders the accuracy-vs-speed frontier and
perf-trajectory figures, and regression-gates EVERY ledger metric
through ``repro.analysis.trajectory.diff`` against the newest committed
``BENCH_pr*.json`` — so the perf trajectory is machine-readable AND
regression-guarded per-metric across PRs (not just one hardcoded B=64
engine-seconds check).

  table1_variants    — paper Table 1 analogue (variant ladder)
  fig7_dsc           — paper Fig. 7 DSC parity (parallel == sequential)
  table3_speedup     — paper Table 3 exec times + Fig. 8 speedup curve
                       (sequential vs device, one solve() entry point)
  sweep              — declarative variant x backend x size x batch x
                       seed grid + serving routes + kernel roofline
                       cells (always runs: BENCH needs full coverage)
  batched_throughput — beyond-paper: images/sec vs batch size for the
                       histogram AND batched-spatial serving paths
  load_gen           — beyond-paper: open-loop Poisson load on the
                       async admission front door vs the sync baseline
  spatial_fcm        — FCM_S noise-robustness + wall clock
  superpixel_fcm     — pixels-vs-superpixels compression ladder

  PYTHONPATH=src python -m benchmarks.run [--tiny] [--skip-paper-tables]
"""
from __future__ import annotations

import argparse
import json
import os

#: This PR's ledger slot: the consolidated record lands in
#: ``BENCH_pr{CURRENT_PR}.json`` and the regression baseline
#: auto-resolves to the newest committed ``BENCH_pr*.json`` with an
#: older pr number (no more hand-bumping a hardcoded baseline path).
CURRENT_PR = 10

OUT_DIR = os.path.join(os.path.dirname(__file__), "out")
FIG_DIR = os.path.join(OUT_DIR, "figures")


def _faults_snapshot() -> dict:
    """The BENCH record's faults section: the live global injector's
    snapshot if one is somehow installed (a chaos run that must be
    flagged), otherwise the explicit all-clean marker."""
    from repro import faults as FI

    inj = FI.get()
    return inj.snapshot() if inj is not None else FI.clean_snapshot()


def perf_gate(bench: dict, baseline_path: str = None) -> None:
    """Per-metric regression gate through the trajectory ledger:
    ``trajectory.diff`` compares every ledger metric (engine seconds,
    overhead ratios, spatial/superpixel speedups, DSC parity, tracing
    overhead, iteration counts) against the newest committed baseline
    under its per-metric policy. Relative gates apply to comparable
    (full-vs-full) runs; absolute ceilings/floors — engine overhead
    <= 5x, tracing overhead <= 1.25x, spatial batched speedup >= 5x,
    DSC parity <= 0.05 — and missing-metric checks gate every run,
    including --tiny CI."""
    from repro.analysis import trajectory

    if baseline_path is None:
        baseline_path = trajectory.resolve_baseline(OUT_DIR,
                                                    before=CURRENT_PR)
    if baseline_path is None or not os.path.exists(baseline_path):
        print("# perf-gate: no committed baseline, skipping")
        return
    result = trajectory.diff(trajectory.load_bench(baseline_path), bench)
    print(f"# perf-gate baseline: {os.path.basename(baseline_path)}")
    for line in result.report().splitlines():
        print(f"# {line}")
    if not result.ok:
        raise SystemExit(
            "FAIL perf-gate: " + "; ".join(
                f"{v.metric}: {v.detail}" for v in result.failures))
    print("# perf-gate OK (trajectory.diff: "
          f"{len(result.verdicts)} metrics checked)")


def render_figures(bench: dict, fig_dir: str = FIG_DIR) -> list:
    """The two analysis figures: the perf-trajectory small multiples
    over every committed BENCH record (plus this run) and this run's
    accuracy-vs-speed frontier from the sweep's solver cells."""
    from repro.analysis import trajectory

    os.makedirs(fig_dir, exist_ok=True)
    paths = []
    try:
        ledger = [(pr, b) for pr, b in trajectory.load_ledger(OUT_DIR)
                  if pr != bench.get("pr")]
        ledger.append((bench.get("pr"), bench))
        paths.append(trajectory.render_trajectory(
            ledger, os.path.join(fig_dir, "perf_trajectory.png")))
        paths.append(trajectory.render_frontier(
            bench, os.path.join(fig_dir, "frontier.png")))
        for p in paths:
            print(f"wrote {p}")
    except Exception as e:       # figures are artifacts, not gates
        print(f"# figure rendering failed (non-fatal): {e!r}")
    return paths


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: small images, single timing reps")
    ap.add_argument("--skip-paper-tables", action="store_true",
                    help="run only the serving/sweep sections that feed "
                         "the BENCH record")
    args = ap.parse_args(argv)

    import jax

    import repro
    repro.enable_compile_cache()
    from . import (batched_throughput, bench_schema, fig7_dsc, load_gen,
                   roofline_report, spatial_fcm, superpixel_fcm, sweep,
                   table1_variants, table3_speedup)

    print("benchmark,us_per_call,derived")
    if not args.skip_paper_tables:
        table1_variants.run()
        fig7_dsc.run()
        table3_speedup.run()

    # The variant-zoo sweep always runs (even --skip-paper-tables): the
    # BENCH schema requires coverage of every registered kernel cell,
    # serving route, and solver variant. Its embedded roofline report
    # doubles as the bench["roofline"] section (one measurement).
    sweep_section = sweep.run_sweep(tiny=args.tiny)
    roofline = sweep_section.pop("roofline")
    roofline_report.run(smoke=args.tiny, report=roofline)

    throughput = batched_throughput.run(tiny=args.tiny)
    spatial_argv = [] if jax.default_backend() == "tpu" else ["--no-pallas"]
    if args.tiny:
        spatial_argv += ["--size", "48"]
    spatial = spatial_fcm.main(spatial_argv)
    superpixel = superpixel_fcm.main(["--tiny"] if args.tiny else [])
    load = load_gen.run_load_gen(tiny=args.tiny)

    bench = {
        "pr": CURRENT_PR,
        "backend": jax.default_backend(),
        "tiny": args.tiny,
        # serving-path throughput (batched histogram + batched spatial),
        # incl. the engine/tracing overhead gates, stage breakdown, and
        # per-route latency + convergence telemetry
        "batched_throughput": throughput,
        # FCM_S robustness/wall-clock sweep
        "spatial_fcm": spatial,
        # superpixel compression ladder
        "superpixel_fcm": superpixel,
        # roofline-vs-achieved, one cell per registered kernel impl
        "roofline": roofline,
        # declarative variant-zoo grid (solver/serving/kernel/
        # distributed families)
        "sweep": sweep_section,
        # async serving under open-loop Poisson load: sustained QPS,
        # p50/p99, queue depth, batch occupancy + the 3x gate
        "load_gen": load,
        # fault-injection provenance: the benchmark harness never
        # installs an injector, so a clean snapshot here is the record's
        # proof it was not a chaos run (bench_schema enforces the
        # consistency of injected/chaos).
        "faults": _faults_snapshot(),
    }
    bench_schema.validate(bench)
    print("# BENCH schema OK")
    perf_gate(bench)
    os.makedirs(OUT_DIR, exist_ok=True)
    out_path = os.path.join(OUT_DIR, f"BENCH_pr{CURRENT_PR}.json")
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=1)
    print(f"wrote {out_path}")
    render_figures(bench)
    return bench


if __name__ == '__main__':
    main()
