"""Sweep harness: declarative grid expansion (skip predicates, seed
fan-out, deterministic cell ids), executed-cell schema round-trips, and
the sweep section's coverage enforcement."""
import itertools
import json

import pytest

from benchmarks import bench_schema, sweep


# ---------------------------------------------------------------------------
# Grid expansion
# ---------------------------------------------------------------------------

def _spec(axes, skip=()):
    return sweep.SweepSpec(name="t", family="solver", axes=axes, skip=skip)


def test_expand_is_full_cartesian_product_without_skips():
    spec = _spec({"a": (1, 2), "b": ("x", "y", "z"), "c": (0,)})
    cells, skipped = sweep.expand(spec)
    assert len(cells) == 6 and not skipped
    assert len({c["cell_id"] for c in cells}) == 6


def test_cell_id_is_deterministic_and_axis_order_independent():
    assert (sweep.cell_id("solver", {"b": 2, "a": "x"})
            == sweep.cell_id("solver", {"a": "x", "b": 2})
            == "solver/a=x,b=2")


def test_expand_order_is_deterministic():
    axes = {"b": (1, 2), "a": ("p", "q")}
    ids1 = [c["cell_id"] for c in sweep.expand(_spec(axes))[0]]
    ids2 = [c["cell_id"] for c in sweep.expand(_spec(dict(
        reversed(list(axes.items())))))[0]]
    assert ids1 == ids2


def test_skip_predicates_record_reasons_not_silence():
    spec = _spec({"a": (1, 2, 3)},
                 skip=(lambda ax: "odd is out" if ax["a"] % 2 else None,))
    cells, skipped = sweep.expand(spec)
    assert [c["axes"]["a"] for c in cells] == [2]
    assert [s["axes"]["a"] for s in skipped] == [1, 3]
    assert all(s["skip_reason"] == "odd is out" for s in skipped)
    assert all(s["status"] == "skipped" for s in skipped)


def test_first_matching_skip_predicate_wins():
    spec = _spec({"a": (1,)}, skip=(lambda ax: "first",
                                    lambda ax: "second"))
    _, skipped = sweep.expand(spec)
    assert skipped[0]["skip_reason"] == "first"


def test_seed_axis_fans_out_into_distinct_cells():
    spec = _spec({"seed": (0, 1, 2), "size": (32,)})
    cells, _ = sweep.expand(spec)
    assert sorted(c["axes"]["seed"] for c in cells) == [0, 1, 2]
    assert len({c["cell_id"] for c in cells}) == 3


# ---------------------------------------------------------------------------
# The solver grid's eligibility rules
# ---------------------------------------------------------------------------

def _skip_reason(ax, platform="cpu"):
    return next((r for r in (p(ax) for p in sweep.solver_skips(platform))
                 if r), None)


def test_sequential_is_pixel_only():
    base = {"backend": "sequential", "size": 32, "batch": 1, "seed": 0}
    assert _skip_reason({**base, "variant": "pixel"}) is None
    for v in ("histogram", "spatial", "vector"):
        assert "sequential" in _skip_reason({**base, "variant": v})


def test_pallas_rejects_vector_rows():
    ax = {"variant": "vector", "backend": "pallas", "size": 32,
          "batch": 1, "seed": 0}
    assert "scalar-only" in _skip_reason(ax)


def test_batched_cells_run_reference_or_resident_only():
    base = {"variant": "pixel", "size": 32, "batch": 4, "seed": 0}
    assert "solve_batched" in _skip_reason({**base, "backend": "pallas"})
    assert _skip_reason({**base, "backend": "reference"}) is None


def test_vector_batching_is_a_serving_concern():
    ax = {"variant": "vector", "backend": "reference", "size": 32,
          "batch": 4, "seed": 0}
    assert "serving route" in _skip_reason(ax)


def test_interpret_mode_size_cap_applies_off_tpu_only():
    ax = {"variant": "histogram", "backend": "resident", "size": 128,
          "batch": 1, "seed": 0}
    assert "interpret" in _skip_reason(ax, platform="cpu")
    assert _skip_reason(ax, platform="tpu") is None
    small = {**ax, "size": 32}
    assert _skip_reason(small, platform="cpu") is None


def test_default_specs_cover_all_variants_and_routes():
    from repro.serving import fcm_engine as FE
    specs = sweep.default_specs(tiny=True, platform="cpu")
    by_family = {s.family: s for s in specs}
    assert set(by_family["solver"].axes["variant"]) == {
        "pixel", "histogram", "spatial", "vector"}
    assert set(by_family["serving"].axes["route"]) == set(FE.METHODS)


# ---------------------------------------------------------------------------
# Executed cells round-trip the schema
# ---------------------------------------------------------------------------

def _roundtrip(rec):
    """Executed record -> JSON text -> parsed -> schema-valid."""
    from repro import obs
    parsed = json.loads(json.dumps(obs.json_safe(rec)))
    bench_schema.validate_cell(parsed)
    return parsed


def test_solver_cell_record_roundtrips_schema():
    axes = {"variant": "histogram", "backend": "reference", "size": 32,
            "batch": 1, "seed": 0}
    cell = {"cell_id": sweep.cell_id("solver", axes), "family": "solver",
            "axes": axes}
    rec = _roundtrip(sweep._run_solver_cell(cell, tiny=True))
    assert rec["status"] == "ok"
    assert rec["metrics"]["wall_s"] > 0
    assert rec["accuracy"]["mean_dsc"] > 0.9
    assert rec["latency"]["count"] >= 1
    assert rec["convergence"]["lanes"] >= 1


def test_batched_solver_cell_record_roundtrips_schema():
    axes = {"variant": "spatial", "backend": "reference", "size": 32,
            "batch": 2, "seed": 0}
    cell = {"cell_id": sweep.cell_id("solver", axes), "family": "solver",
            "axes": axes}
    rec = _roundtrip(sweep._run_solver_cell(cell, tiny=True))
    assert rec["status"] == "ok"
    # 2 lanes per solve x (1 warm + 1 timed rep in tiny mode): the
    # convergence block accumulates over every solve in the cell scope
    assert rec["convergence"]["lanes"] == 4
    assert rec["accuracy"] is None               # batch cells skip DSC


def test_serving_cell_record_roundtrips_schema():
    axes = {"route": "histogram", "batch": 2}
    cell = {"cell_id": sweep.cell_id("serving", axes),
            "family": "serving", "axes": axes}
    rec = _roundtrip(sweep._run_serving_cell(cell, tiny=True))
    assert rec["status"] == "ok"
    assert set(rec["metrics"]["stage_seconds"]) == {
        "ingest", "gather", "solve", "materialize"}


def test_solver_cell_telemetry_does_not_leak_to_default_registry():
    from repro import obs
    before = obs.default_registry().snapshot()
    axes = {"variant": "pixel", "backend": "reference", "size": 32,
            "batch": 1, "seed": 0}
    cell = {"cell_id": sweep.cell_id("solver", axes), "family": "solver",
            "axes": axes}
    sweep._run_solver_cell(cell, tiny=True)
    assert obs.default_registry().snapshot() == before


# ---------------------------------------------------------------------------
# Schema: per-cell and section-level checks
# ---------------------------------------------------------------------------

def test_validate_cell_rejects_skipped_without_reason():
    cell = {"cell_id": "solver/x=1", "family": "solver",
            "axes": {"x": 1}, "status": "skipped"}
    with pytest.raises(ValueError, match="skip_reason"):
        bench_schema.validate_cell(cell)


def test_validate_cell_rejects_ok_solver_cell_missing_blocks():
    cell = {"cell_id": "solver/x=1", "family": "solver",
            "axes": {"x": 1, "batch": 1}, "status": "ok",
            "metrics": {"wall_s": 1.0}}
    with pytest.raises(ValueError) as exc:
        bench_schema.validate_cell(cell)
    msg = str(exc.value)
    assert "latency" in msg and "convergence" in msg
    assert "accuracy.mean_dsc" in msg


def test_check_sweep_section_requires_kernel_registry_coverage():
    section = {"name": "t", "tiny": True, "backend": "cpu",
               "coverage": {}, "cells": [], "skipped": []}
    with pytest.raises(ValueError) as exc:
        bench_schema.check_sweep_section(section)
    msg = str(exc.value)
    assert "no ok kernel cell" in msg
    assert "flat/resident_streamed" in msg
    assert "no ok serving cell" in msg


def test_check_sweep_section_counts_error_cells_as_missing_coverage():
    from repro.kernels import ops as kops
    required = {(i.kind, i.name) for i in kops.step_impls()}
    required.update(bench_schema.REQUIRED_CELLS)
    cells = [{"cell_id": f"kernel/impl={impl},kind={kind}",
              "family": "kernel", "axes": {"kind": kind, "impl": impl},
              "status": "ok",
              "kernel": {k: 1 for k in bench_schema.CELL_KEYS}}
             for kind, impl in sorted(required)]
    # break one cell: an errored probe must still fail coverage
    cells[0]["status"] = "error"
    cells[0]["error"] = "boom"
    section = {"name": "t", "tiny": True, "backend": "cpu",
               "coverage": {}, "cells": cells, "skipped": []}
    with pytest.raises(ValueError, match="no ok kernel cell"):
        bench_schema.check_sweep_section(section)


# ---------------------------------------------------------------------------
# Distributed family + load_gen section (PR 9)
# ---------------------------------------------------------------------------

def _dist_cell(mode="batch_hist", ok=True):
    return {"cell_id": f"distributed/devices=8,mode={mode}",
            "family": "distributed",
            "axes": {"mode": mode, "devices": 8}, "status": "ok",
            "metrics": {"wall_s": 0.1, "per_image_s": 0.01, "batch": 6},
            "parity": {"ok": ok, "max_center_delta": 0.0}}


def test_distributed_cell_roundtrips_schema():
    bench_schema.validate_cell(json.loads(json.dumps(_dist_cell())))


def test_distributed_cell_failed_parity_is_a_schema_violation():
    with pytest.raises(ValueError, match="parity failed"):
        bench_schema.validate_cell(_dist_cell(ok=False))


def test_check_sweep_section_requires_distributed_modes():
    section = {"name": "t", "tiny": True, "backend": "cpu",
               "coverage": {}, "cells": [], "skipped": []}
    with pytest.raises(ValueError) as exc:
        bench_schema.check_sweep_section(section)
    msg = str(exc.value)
    for mode in bench_schema.REQUIRED_DIST_MODES:
        assert f"no ok distributed cell for mode '{mode}'" in msg


def _load_gen_section(**over):
    rate = {k: 1.0 for k in bench_schema.RATE_KEYS}
    section = {
        "tiny": True, "backend": "cpu", "devices": 1,
        "route": "histogram",
        "sync_baseline": {k: 1.0 for k in bench_schema.SYNC_BASELINE_KEYS},
        "rates": [dict(rate)], "sustained": dict(rate),
        "qps_ratio_vs_sync": 3.5,
        "gate": {"enforced": True, "min_ratio": 3.0, "ok": True},
    }
    section.update(over)
    return section


def test_check_load_gen_section_roundtrips():
    bench_schema.check_load_gen_section(
        json.loads(json.dumps(_load_gen_section())))


def test_check_load_gen_section_flags_enforced_failed_gate():
    bad = _load_gen_section(
        gate={"enforced": True, "min_ratio": 3.0, "ok": False})
    with pytest.raises(ValueError, match="gate failed"):
        bench_schema.check_load_gen_section(bad)
    # Unenforced failure is recorded, not fatal.
    bench_schema.check_load_gen_section(_load_gen_section(
        gate={"enforced": False, "min_ratio": 3.0, "ok": False}))


def test_check_load_gen_section_names_missing_rate_keys():
    sec = _load_gen_section()
    del sec["rates"][0]["p99_s"]
    del sec["sustained"]["queue_depth"]
    with pytest.raises(ValueError) as exc:
        bench_schema.check_load_gen_section(sec)
    msg = str(exc.value)
    assert "rates[0]: missing 'p99_s'" in msg
    assert "sustained: missing 'queue_depth'" in msg


def test_check_load_gen_section_requires_empty_rates_to_fail():
    with pytest.raises(ValueError, match="rates sweep empty"):
        bench_schema.check_load_gen_section(_load_gen_section(rates=[]))


def test_validate_requires_load_gen_from_pr9():
    base = {k: {} for k in bench_schema.TOP_KEYS
            if k not in ("pr", "load_gen")}
    with pytest.raises(ValueError,
                       match="missing top-level key 'load_gen'"):
        bench_schema.validate({**base, "pr": 9, "tiny": True})
    # pr 8 records predate the harness and stay valid without it.
    try:
        bench_schema.validate({**base, "pr": 8, "tiny": True})
    except ValueError as e:
        assert "load_gen" not in str(e)
