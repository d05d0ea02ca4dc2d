"""What decides ``correct``: the answers the window served, against the
plain reference, each number within the cell's limit.

For every kept answer (a seeded sample of the requests due in the
window) the reference solves the same uint8 slice from the same init:

- ``center_dev``: the largest gap between a served center and the
  reference's, in units of that request's stopping tolerance
  ``eps * range * 0.1`` (two correct solvers stop within about one
  tolerance of each other, since both stop on a step smaller than it);
- ``iter_gap``: the largest difference in iterations;
- ``label_mismatch``: the largest share of a slice's pixels whose served
  label differs from the reference's labels of the served centers,
  counting only pixels whose two least effective distances differ by
  more than ``GAP_MIN`` of the second (a closer call is within
  rounding, and either label is right);
- ``unresolved``: requests due in the window that failed or never
  answered.

The limits are in ``bench/workloads/<cell>.json``; ``PERF.md`` gives the
readings each was set from.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from . import reference

GAP_MIN = 1e-4
NO_ANSWER = 1e9           # a number that fails every limit


def readings(cell, pool, kept: List[tuple], control: bool = False,
             log=print) -> Dict[str, float]:
    if not kept:
        return {"center_dev": NO_ANSWER, "iter_gap": NO_ANSWER,
                "label_mismatch": NO_ANSWER}
    import jax.numpy as jnp

    sem = dict(cell.config["fcm"])
    imgs = np.stack([pool.slice(s, k) for s, k, *_ in kept])
    v_ref, it_ref, tol = reference.solve(imgs, sem)
    if control:
        v, it, _ = reference.solve(imgs, sem, dtype=jnp.bfloat16)
        lab, _ = reference.labels(imgs, v, sem, dtype=jnp.bfloat16)
    else:
        v = np.stack([r[2] for r in kept]).reshape(v_ref.shape)
        it = np.array([r[4] for r in kept])
        lab = np.stack([r[3] for r in kept])
    own, gap = reference.labels(imgs, v, sem)
    dev = np.abs(v - v_ref).max(axis=1) / tol
    clear = gap > GAP_MIN
    miss = ((lab != own) & clear).reshape(len(kept), -1).mean(axis=1)
    log(f"compared {len(kept)} answers: mean iterations {it.mean():.3f} "
        f"(reference {it_ref.mean():.3f}), pixels within rounding of a "
        f"tie {(~clear).mean():.3e}")
    return {"center_dev": float(dev.max()),
            "iter_gap": float(np.abs(it - it_ref).max()),
            "label_mismatch": float(miss.max())}


def check(cell, pool, kept: List[tuple], failed: int,
          control: bool = False) -> Dict[str, Dict[str, Any]]:
    limits = cell.params["limits"]
    got = readings(cell, pool, kept, control)
    got["unresolved"] = float(failed)
    return {k: {"value": got[k], "limit": float(limits[k])} for k in got}
