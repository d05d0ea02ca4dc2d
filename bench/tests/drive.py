"""Drive one cell's run on the CPU for the tests: the harness's look for
a chip is skipped, slices are cut to ``SIZE`` and open-loop rates to
``OPEN_RATE`` so a test run holds it; everything else is the run's own
path. Planted faults break the timed path underneath the harness."""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.lib import harness, peaks, spec

SIZE = (64, 48)
OPEN_RATE = 200.0


def run(cell_name: str, *, fault=None, control: bool = False,
        seconds: float = 1.0, trace: bool = False, bench_dir=None,
        devices=None):
    import jax

    cell = spec.resolve(cell_name, **({"bench_dir": bench_dir}
                                      if bench_dir else {}))
    cell.config = dict(cell.config, height=SIZE[0], width=SIZE[1])
    scale = 1.0
    if cell.traffic["loop"] == "open":
        scale = OPEN_RATE / float(cell.params["rate_per_s"])
    opt = harness.Options(seed=2 ** 33 + 17, seconds=seconds, trace=trace,
                          t_process=time.perf_counter(),
                          peak=peaks.PEAKS["TPU v5 lite"],
                          height=SIZE[0], width=SIZE[1], rate_scale=scale,
                          fault=fault, control=control)
    return harness.run_cell(cell, opt, devices or jax.devices())


# -- faults ------------------------------------------------------------------

def _wrap_programs(eng, wrap):
    from repro.serving.fcm_engine import RouteProgram

    orig = eng._program_for

    def program_for(route, chunk, bucket):
        prog = orig(route, chunk, bucket)
        return None if prog is None else RouteProgram(
            *wrap(prog, bucket), prog.impls)
    eng._program_for = program_for


def half_batch_left_out(eng):
    """The second half of each batch's real lanes gets the first half's
    answers: half the batch is never solved."""
    def wrap(prog, bucket):
        def scatter(e, chunk, outs):
            out = prog.scatter(e, chunk, outs)
            res = out[0]
            h = len(res) // 2
            for i in range(h, 2 * h):
                res[i] = dataclasses.replace(
                    res[i], labels=res[i - h].labels.copy(),
                    centers=res[i - h].centers.copy(),
                    n_iters=res[i - h].n_iters)
            return out
        return prog.gather, prog.launch, scatter
    _wrap_programs(eng, wrap)


def answer_altered(eng):
    """Every answer leaves the scatter with its middle pixel relabelled."""
    def wrap(prog, bucket):
        def scatter(e, chunk, outs):
            out = prog.scatter(e, chunk, outs)
            for r in out[0]:
                lab = r.labels.copy()
                h, w = lab.shape
                lab[h // 2, w // 2] = (lab[h // 2, w // 2] + 1) % 4
                r.labels = lab
            return out
        return prog.gather, prog.launch, scatter
    _wrap_programs(eng, wrap)


def exchange_left_out(eng):
    """On a sharded launch, every chip's lanes come back as the first
    chip's: the gather of the other shards is left out."""
    def wrap(prog, bucket):
        mesh = eng._mesh_for_bucket(bucket)
        if mesh is None:
            return prog.gather, prog.launch, prog.scatter
        k = bucket // mesh.size

        def launch(*inputs):
            outs = prog.launch(*inputs)
            return tuple(
                np.tile(np.asarray(o)[:k], (mesh.size,) + (1,) * (o.ndim - 1))
                if o.ndim else o for o in outs)
        return prog.gather, launch, prog.scatter
    _wrap_programs(eng, wrap)


def state_unchanged(monkeypatch):
    """Every solver step returns the centers it was given."""
    from repro.core import solver as SV
    from repro.core import spatial as SP
    from repro.serving import fcm_engine as FE

    monkeypatch.setattr(SV, "weighted_center_step",
                        lambda feats, w, v, m: v)
    monkeypatch.setattr(SP, "spatial_center_step",
                        lambda img, v, m=2.0, alpha=1.0, neighbors=4: v)
    FE._LAUNCH_CACHE.clear()
