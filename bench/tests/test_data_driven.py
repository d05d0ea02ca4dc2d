"""A cell, a configuration, a traffic mix and a per-layer metric added as
new files (and entries in BENCHMARK.json) are found by name and run,
with no file of the benchmark edited."""
import hashlib
import json
import os
import shutil

from bench.lib import spec
from bench.tests import drive

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def digests(bench_dir):
    out = {}
    for d, _, files in os.walk(bench_dir):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, bench_dir)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    bench = root / "bench"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = digests(bench)

    with open(bench / "configs" / "brainweb-t1-slice.json") as f:
        cfg = json.load(f)
    cfg.update(name="added-config", max_wait_ms=5.0)
    (bench / "configs" / "added-config.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "added-mix.json").write_text(json.dumps(
        {"loop": "closed", "clients": 2, "studies": 2,
         "slice_positions": [0.3, 0.7]}))
    (bench / "workloads" / "added-cell.json").write_text(json.dumps(
        {"limits": {"center_dev": 0.5, "iter_gap": 2, "label_mismatch": 0,
                    "unresolved": 0}}))
    (bench / "metrics" / "added_metric.py").write_text(
        "def read(ctx):\n    return 42.0 + ctx.delta('batches') * 0\n")

    with open(root / "BENCHMARK.json") as f:
        bm = json.load(f)
    bm["configs"].append(dict(bm["configs"][0], name="added-config",
                              file="bench/configs/added-config.json"))
    bm["workloads"].append({"name": "added-cell", "config": "added-config",
                            "traffic": "added-mix", "chips": 1,
                            "why": "added by a test"})
    for m in bm["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("added-cell")
    bm["per_layer"].append({"name": "added_metric.batch", "unit": "x",
                            "better": "higher", "source": "program_counter",
                            "layer": "batching", "moves": "requests_per_s",
                            "workloads": ["added-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = spec.resolve("added-cell", bench_dir=str(bench))
    assert cell.config["max_wait_ms"] == 5.0
    assert cell.traffic["clients"] == 2
    assert [m["name"] for m in cell.per_layer] == ["added_metric.batch"]
    assert "requests_per_s" in [m["name"] for m in cell.end_to_end]

    res = drive.run("added-cell", trace=True, bench_dir=str(bench))
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["added_metric.batch"]["value"] == 42.0

    after = digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == sorted([
        "configs/added-config.json", "traffic/added-mix.json",
        "workloads/added-cell.json", "metrics/added_metric.py"])
