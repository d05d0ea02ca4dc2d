"""Declarative variant-zoo sweep runner.

The repo's zoo — plain pixel / histogram / vector(superpixel) / spatial
FCM, times solver backends, problem sizes, batch sizes and seeds — is
measured here from ONE grid declaration instead of hand-rolled per-PR
scripts (the zoology pattern: a config-generated experiment grid whose
results render into figures). A :class:`SweepSpec` names ordered axes
plus skip predicates; :func:`expand` turns it into deterministic cells
(stable, human-readable ``cell_id``s); each cell executes through the
unified ``solve()`` / ``solve_batched()`` / ``FCMServeEngine`` entry
points with the obs layer scoped to the cell — latency percentiles,
per-lane convergence telemetry — and the kernel family folds in the
roofline achieved-vs-bound probe for every registered (kind, impl)
dispatch cell. Skipped cells are recorded WITH their reason: the grid
accounts for every declared combination, nothing is silently dropped.

Four families:

* ``solver``  — variant x backend x size x batch x seed through the one
  solver entry point; batch=1 cells also score per-class DSC against
  the phantom ground truth, so accuracy-vs-speed frontiers (the paper's
  Table 3 and Fig. 7 are the ``pixel/sequential`` and ``pixel/auto``
  cells of this grid) come straight from the records.
* ``serving`` — every registered engine route x batch, cold-cache
  end-to-end with the engine's per-route latency / convergence /
  stage-seconds blocks.
* ``kernel``  — one roofline achieved-vs-bound cell per (kind, impl) in
  the ``kernels/ops.py`` dispatch registry (reuses the
  ``roofline_report`` probes; coverage asserted by ``bench_schema``).
* ``distributed`` — shard_map solver cells under 8 fake host devices
  (subprocess, see ``_dist_cells.py``): batch-axis sharding on a ragged
  histogram batch plus pixel-axis sharding of one image, each with a
  parity block vs its single-device twin.

Each cell record is validated against ``bench_schema.validate_cell``
before it is emitted — one JSON record per cell under
``benchmarks/out/sweep/`` plus the consolidated section
``benchmarks/run.py`` folds into ``BENCH_pr8.json``.

Run:  PYTHONPATH=src python -m benchmarks.sweep [--tiny] [--out PATH]
"""
from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import os
from typing import (Any, Callable, Dict, List, Mapping, Optional,
                    Sequence, Tuple)

import numpy as np

try:
    from .common import emit, time_fn
except ImportError:                      # run as a plain script
    from common import emit, time_fn

SWEEP_DIR = os.path.join(os.path.dirname(__file__), "out", "sweep")

#: Interpret-mode Pallas cells (off-TPU) time the Python interpreter,
#: not the kernel; above this many pixels they are skipped off-TPU
#: (the kernel family still probes every impl in interpret mode).
INTERPRET_MAX_PIXELS = 48 * 48


# ---------------------------------------------------------------------------
# Grid declaration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """One declarative grid: named axes (each a value tuple) expanded as
    a cartesian product, minus the cells a ``skip`` predicate claims.
    Predicates take the cell's axes dict and return a human-readable
    reason string (skip) or None (run)."""
    name: str
    family: str
    axes: Mapping[str, Tuple[Any, ...]]
    skip: Tuple[Callable[[Dict[str, Any]], Optional[str]], ...] = ()


def cell_id(family: str, axes: Mapping[str, Any]) -> str:
    """Deterministic, order-independent cell id:
    ``family/key=value,...`` with keys sorted — the stable primary key
    per-cell records and resume logic can rely on."""
    return family + "/" + ",".join(
        f"{k}={axes[k]}" for k in sorted(axes))


def expand(spec: SweepSpec) -> Tuple[List[Dict[str, Any]],
                                     List[Dict[str, Any]]]:
    """(runnable cells, skipped cells). Axis order inside the product
    follows sorted axis names so the expansion order is deterministic
    regardless of how the axes dict was declared."""
    names = sorted(spec.axes)
    cells, skipped = [], []
    for combo in itertools.product(*(spec.axes[n] for n in names)):
        axes = dict(zip(names, combo))
        base = {"cell_id": cell_id(spec.family, axes),
                "family": spec.family, "axes": axes}
        reason = next((r for r in (p(axes) for p in spec.skip) if r), None)
        if reason:
            skipped.append({**base, "status": "skipped",
                            "skip_reason": reason})
        else:
            cells.append(base)
    return cells, skipped


# -- solver-family skip predicates (platform passed in, so tests can
#    exercise both sides deterministically) --------------------------------

def solver_skips(platform: str):
    """The solver grid's eligibility rules, as named predicates."""

    def backend_variant(ax):
        v, b = ax["variant"], ax["backend"]
        if b == "sequential" and v != "pixel":
            return ("sequential is the scalar unweighted pixel CPU "
                    "baseline only")
        if b == "pallas" and v == "vector":
            return "flat pallas step is scalar-only; vector rows are D=3"
        if b == "resident" and v in ("pixel", "vector"):
            return ("rows exceed the VMEM-resident bounds; streamed "
                    "coverage lives in the kernel family")
        return None

    def batched_backend(ax):
        if ax["batch"] > 1 and ax["backend"] not in ("reference",
                                                     "resident"):
            return ("solve_batched runs the reference or resident "
                    "impls only")
        return None

    def vector_batching(ax):
        if ax["variant"] == "vector" and ax["batch"] > 1:
            return ("superpixel K varies per image; cross-request "
                    "batching is measured on the serving route")
        return None

    def interpret_cost(ax):
        if platform == "tpu" or ax["backend"] not in ("pallas",
                                                      "resident"):
            return None
        if ax["size"] * ax["size"] > INTERPRET_MAX_PIXELS:
            return (f"off-{platform} interpret mode times the "
                    "interpreter, not the kernel; size capped at "
                    f"{INTERPRET_MAX_PIXELS} pixels")
        return None

    return (backend_variant, batched_backend, vector_batching,
            interpret_cost)


def default_specs(tiny: bool, platform: str) -> List[SweepSpec]:
    """The standing grid. ``--tiny`` shrinks sizes/reps but keeps full
    *coverage*: every variant, every eligible backend, every serving
    route (the acceptance surface CI validates)."""
    from repro.serving import fcm_engine as FE

    sizes = (32, 48) if tiny else (64, 128)
    batches = (1, 4) if tiny else (1, 8)
    seeds = (0,) if tiny else (0, 1)
    backends = ("reference", "sequential", "pallas", "resident")
    solver = SweepSpec(
        name="solver-zoo", family="solver",
        axes={"variant": ("pixel", "histogram", "spatial", "vector"),
              "backend": backends, "size": sizes, "batch": batches,
              "seed": seeds},
        skip=solver_skips(platform))
    serving = SweepSpec(
        name="serving-routes", family="serving",
        axes={"route": tuple(FE.METHODS),
              "batch": (2,) if tiny else (4, 16)})
    return [solver, serving]


# ---------------------------------------------------------------------------
# Cell executors
# ---------------------------------------------------------------------------

def _cfgs():
    from repro.core import fcm as F
    from repro.core import spatial as SP
    from repro.superpixel import pipeline as SX
    cfg = F.FCMConfig(max_iters=300)
    scfg = SP.SpatialFCMConfig(max_iters=300, neighbors=8)
    spcfg = SX.SuperpixelFCMConfig(max_iters=300)
    return cfg, scfg, spcfg


def _gray(size: int, seed: int, i: int = 0):
    from repro.data import phantom
    return phantom.phantom_slice(size, size, noise=4.0 + (i % 3),
                                 seed=seed * 101 + i)


def _rgb(size: int, seed: int, i: int = 0):
    from repro.data import phantom
    return phantom.phantom_slice_rgb(size, size, noise=4.0 + (i % 3),
                                     seed=seed * 101 + i)


def _mean_dsc(dsc: Dict[str, float]) -> float:
    return float(np.mean(list(dsc.values())))


def _dsc_gray(labels, centers, gt):
    from repro.data import phantom
    pred = phantom.match_labels_to_classes(np.asarray(labels),
                                           np.asarray(centers))
    d = phantom.dice_per_class(pred, gt)
    return {n: round(float(v), 4)
            for n, v in zip(phantom.CLASS_NAMES, d)}


def _convergence_block(reg) -> Dict[str, Any]:
    """Cell-scoped solver telemetry -> the record's convergence block
    (same keys as the engine's per-route block, so downstream tooling
    reads one schema)."""
    h = None
    for kind in ("flat", "stencil"):
        cand = reg.peek("solver.iters", kind=kind)
        if cand is not None and cand.count:
            h = cand
            break
    g = (reg.peek("solver.last_final_delta", kind="flat")
         or reg.peek("solver.last_final_delta", kind="stencil"))
    return {
        "lanes": h.count if h else 0,
        "mean_iters": h.mean if h else None,
        "p50_iters": h.quantile(0.50) if h else None,
        "p99_iters": h.quantile(0.99) if h else None,
        "last_final_delta": g.snapshot() if g else None,
    }


def _run_solver_cell(cell: Dict[str, Any], tiny: bool) -> Dict[str, Any]:
    """One (variant, backend, size, batch, seed) cell through the one
    solver entry point, obs-scoped."""
    import jax

    from repro import obs
    from repro.core import batched as B
    from repro.core import solver as SV
    from repro.superpixel import pipeline as SX

    ax = cell["axes"]
    variant, backend = ax["variant"], ax["backend"]
    size, batch, seed = ax["size"], ax["batch"], ax["seed"]
    cfg, scfg, spcfg = _cfgs()
    interpret = (backend in ("pallas", "resident")
                 and jax.default_backend() != "tpu") or None
    reps = 1 if tiny else 3
    compress_s = 0.0
    accuracy = None

    if batch == 1:
        if variant == "vector":
            img, gt = _rgb(size, seed)
            imgf = img.astype(np.float32)
            if size <= 96:
                spcfg = dataclasses.replace(spcfg, n_segments=64)
            comp = SX.compress(imgf, spcfg)
            compress_s = time_fn(lambda: SX.compress(imgf, spcfg),
                                 iters=reps)
            problem = SV.vector_problem(comp.features, comp.weights, spcfg)
        else:
            img, gt = _gray(size, seed)
            x = img.ravel().astype(np.float32)
            if variant == "pixel":
                problem = SV.pixel_problem(x, cfg)
            elif variant == "histogram":
                problem = SV.histogram_problem(x, cfg)
            else:
                problem = SV.spatial_problem(img.astype(np.float32), scfg)

        def run():
            return SV.solve(problem, backend=backend, interpret=interpret)

        with obs.scoped_registry() as reg:
            res = run()                                   # warm + result
            lat = reg.histogram("sweep.cell_seconds",
                                edges=obs.LATENCY_EDGES)
            for _ in range(reps):
                lat.record(time_fn(run, warmup=0, iters=1))
            # best-of-reps is the stablest single-cell statistic on a
            # noisy box; the full distribution rides in the latency block
            fit_s = lat.vmin
            latency = lat.snapshot()
            convergence = _convergence_block(reg)
            obs_snapshot = reg.snapshot()

        if variant == "vector":
            labels = SX.broadcast_labels(res.labels, comp.label_map)
            from repro.data import phantom
            pred = phantom.match_labels_to_means(
                np.asarray(labels), np.asarray(res.centers),
                phantom.CLASS_MEANS_RGB)
            d = phantom.dice_per_class(pred, gt)
            dsc = {n: round(float(v), 4)
                   for n, v in zip(phantom.CLASS_NAMES, d)}
        elif variant == "histogram":
            # bin labels -> pixel labels through the bin LUT
            lut = np.asarray(res.labels)
            bins = np.clip(np.round(np.asarray(img)), 0,
                           lut.shape[0] - 1).astype(np.int64)
            dsc = _dsc_gray(lut[bins], res.centers, gt)
        elif variant == "spatial":
            dsc = _dsc_gray(res.labels, res.centers, gt)
        else:
            dsc = _dsc_gray(np.asarray(res.labels).reshape(img.shape),
                            res.centers, gt)
        accuracy = {"dsc": dsc, "mean_dsc": round(_mean_dsc(dsc), 4)}
        n_iters = int(res.n_iters)
    else:
        imgs = [_gray(size, seed, i)[0] for i in range(batch)]
        if variant == "pixel":
            feats = np.stack([im.ravel().astype(np.float32)
                              for im in imgs])
            problem = SV.batch_problems(feats, cfg=cfg)
        elif variant == "histogram":
            hists = B.histograms_of(imgs)
            problem = SV.batch_problems(B.hist_rows(hists), hists, cfg=cfg)
        else:
            problem = SV.batch_problems(
                np.stack(imgs).astype(np.float32),
                stencil=SV.StencilSpec(alpha=scfg.alpha,
                                       neighbors=scfg.neighbors),
                cfg=scfg)

        def run():
            return SV.solve_batched(problem, backend=backend,
                                    interpret=interpret)

        with obs.scoped_registry() as reg:
            res = run()
            lat = reg.histogram("sweep.cell_seconds",
                                edges=obs.LATENCY_EDGES)
            for _ in range(reps):
                lat.record(time_fn(run, warmup=0, iters=1))
            fit_s = lat.vmin
            latency = lat.snapshot()
            convergence = _convergence_block(reg)
            obs_snapshot = reg.snapshot()
        n_iters = int(np.max(res.n_iters))

    wall_s = float(fit_s) + float(compress_s)
    metrics = {"wall_s": wall_s, "fit_s": float(fit_s),
               "compress_s": float(compress_s),
               "per_image_s": wall_s / batch, "n_iters": n_iters}
    return {**cell, "status": "ok", "metrics": metrics,
            "accuracy": accuracy, "latency": latency,
            "convergence": convergence, "obs": obs_snapshot}


def _run_serving_cell(cell: Dict[str, Any], tiny: bool) -> Dict[str, Any]:
    """One cold-cache (route, batch) cell end-to-end through the
    serving engine; the engine's own obs layer supplies the latency /
    convergence / stage blocks."""
    from repro.serving.fcm_engine import FCMServeEngine

    ax = cell["axes"]
    route, batch = ax["route"], ax["batch"]
    size = 32 if tiny else 64
    cfg, scfg, spcfg = _cfgs()
    if size <= 96:
        spcfg = dataclasses.replace(spcfg, n_segments=64)
    maker = _rgb if route == "superpixel" else _gray
    imgs = [maker(size, 0, i)[0].astype(np.float32) for i in range(batch)]

    def run():
        eng = FCMServeEngine(cfg, batch_sizes=(batch,), cache_size=0,
                             spatial_cfg=scfg, superpixel_cfg=spcfg)
        eng.segment(imgs, method=route)
        return eng

    eng = run()                                           # warm compile
    wall_s = time_fn(run, warmup=0, iters=1 if tiny else 3)
    eng = run()                                           # fresh stats
    s = eng.stats()
    metrics = {"wall_s": float(wall_s),
               "per_image_s": float(wall_s) / batch,
               "stage_seconds": s["stage_seconds"][route]}
    return {**cell, "status": "ok", "metrics": metrics,
            "latency": s["latency"][route],
            "convergence": s["convergence"][route]}


def _distributed_cells(tiny: bool) -> List[Dict[str, Any]]:
    """The multi-device family: shard_map solver cells measured in a
    subprocess under ``--xla_force_host_platform_device_count=8`` (the
    flag must precede jax init, so the parent process cannot host
    them). Each mode carries a parity block against its single-device
    twin; a dead subprocess becomes one error cell per required mode so
    the schema's coverage check fails loudly."""
    import subprocess
    import sys as _sys

    try:
        from . import bench_schema
    except ImportError:
        import bench_schema

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "_dist_cells.py")
    cmd = [_sys.executable, script] + (["--tiny"] if tiny else [])
    # The child fakes 8 CPU devices; it must never reach for the chip
    # this process holds.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=1800, check=True, env=env)
        payload = json.loads(out.stdout.strip().splitlines()[-1])
    except Exception as e:
        return [{"cell_id": cell_id("distributed",
                                    {"mode": mode, "devices": 8}),
                 "family": "distributed",
                 "axes": {"mode": mode, "devices": 8},
                 "status": "error", "error": repr(e)}
                for mode in bench_schema.REQUIRED_DIST_MODES]
    cells = []
    for row in payload["cells"]:
        axes = {"mode": row["mode"], "devices": payload["devices"]}
        cells.append({
            "cell_id": cell_id("distributed", axes),
            "family": "distributed", "axes": axes, "status": "ok",
            "metrics": {"wall_s": row["wall_s"],
                        "per_image_s": row["per_image_s"],
                        "batch": row["batch"]},
            "parity": row["parity"],
        })
    return cells


def _kernel_cells(tiny: bool) -> Tuple[List[Dict[str, Any]], dict]:
    """The registry-coverage family: every (kind, impl) dispatch cell as
    a roofline achieved-vs-bound probe (also writes
    benchmarks/out/roofline_report.json, so the standalone report and
    the sweep stay one measurement)."""
    try:
        from . import roofline_report
    except ImportError:
        import roofline_report
    report = roofline_report.write_kernel_report(smoke=tiny)
    cells = []
    for row in report["cells"]:
        axes = {"kind": row["kind"], "impl": row["impl"]}
        cell = {"cell_id": cell_id("kernel", axes), "family": "kernel",
                "axes": axes, "kernel": row}
        if "error" in row:
            cell.update(status="error", error=row["error"])
        else:
            cell["status"] = "ok"
        cells.append(cell)
    return cells, report


# ---------------------------------------------------------------------------
# Sweep driver
# ---------------------------------------------------------------------------

_EXECUTORS = {"solver": _run_solver_cell, "serving": _run_serving_cell}


def run_sweep(tiny: bool = False, write_cells: bool = True,
              sweep_dir: str = SWEEP_DIR) -> dict:
    """Expand the standing grid, execute every cell, validate each
    record against the schema, and return the consolidated sweep
    section (with the full roofline report riding along under
    ``"roofline"`` so ``benchmarks/run.py`` measures kernels once)."""
    import jax

    from repro import obs

    try:
        from . import bench_schema
    except ImportError:
        import bench_schema

    platform = jax.default_backend()
    cells: List[Dict[str, Any]] = []
    skipped: List[Dict[str, Any]] = []
    for spec in default_specs(tiny, platform):
        todo, skip = expand(spec)
        skipped.extend(skip)
        for cell in todo:
            try:
                rec = _EXECUTORS[spec.family](cell, tiny)
            except Exception as e:       # keep the cell, name the failure
                rec = {**cell, "status": "error", "error": repr(e)}
            cells.append(rec)
            _emit_cell(rec)

    kcells, roofline = _kernel_cells(tiny)
    cells.extend(kcells)
    dcells = _distributed_cells(tiny)
    cells.extend(dcells)
    for rec in dcells:
        _emit_cell(rec)

    section = {
        "name": "fcm-variant-zoo",
        "tiny": tiny,
        "backend": platform,
        "n_cells": len(cells),
        "n_skipped": len(skipped),
        "coverage": {
            "solver_variants": sorted({c["axes"]["variant"] for c in cells
                                       if c["family"] == "solver"}),
            "serving_routes": sorted({c["axes"]["route"] for c in cells
                                      if c["family"] == "serving"}),
            "kernel_cells": sorted(f"{c['axes']['kind']}/{c['axes']['impl']}"
                                   for c in cells
                                   if c["family"] == "kernel"),
            "distributed_modes": sorted({c["axes"]["mode"] for c in cells
                                         if c["family"] == "distributed"}),
        },
        "cells": obs.json_safe(cells),
        "skipped": skipped,
    }
    bench_schema.check_sweep_section(section)
    if write_cells:
        os.makedirs(sweep_dir, exist_ok=True)
        for rec in section["cells"]:
            fname = rec["cell_id"].replace("/", "__") + ".json"
            with open(os.path.join(sweep_dir, fname), "w") as f:
                json.dump(rec, f, indent=1)
        print(f"# sweep: wrote {len(section['cells'])} cell records "
              f"to {sweep_dir}")
    errors = [c["cell_id"] for c in cells if c["status"] == "error"]
    print(f"# sweep: {len(cells)} cells ({len(errors)} errored), "
          f"{len(skipped)} skipped with reasons")
    section["roofline"] = roofline
    return section


def _emit_cell(rec: Dict[str, Any]) -> None:
    if rec["status"] == "error":
        emit(f"sweep/{rec['cell_id']}", 0.0, f"ERROR {rec['error']}")
        return
    m = rec.get("metrics", {})
    derived = ""
    if rec.get("accuracy"):
        derived = f"mean_dsc={rec['accuracy']['mean_dsc']:.4f}"
    conv = rec.get("convergence") or {}
    if conv.get("mean_iters") is not None:
        derived += f" mean_iters={conv['mean_iters']:.1f}"
    emit(f"sweep/{rec['cell_id']}", m.get("wall_s", 0.0) * 1e6,
         derived.strip())


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: reduced sizes/reps, full coverage")
    ap.add_argument("--out", default=None,
                    help="also write the consolidated sweep section "
                         "to this JSON path")
    args = ap.parse_args(argv)
    print("benchmark,us_per_call,derived")
    section = run_sweep(tiny=args.tiny)
    print("# sweep schema OK (every cell validated, coverage checked)")
    if args.out:
        payload = {k: v for k, v in section.items() if k != "roofline"}
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"wrote {args.out}")
    return section


if __name__ == "__main__":
    main()
