"""The one traffic generator: every mix is a JSON file of parameters
under ``bench/traffic/`` that this module reads.

A mix draws a pool of whole studies from the seed (``studies`` x the
configuration's ``slices_per_study`` axial slices) and one of two
arrival processes:

- ``"loop": "open"``: readers scroll through the pool's studies one
  slice at a time; arrivals are Poisson at the cell's fixed
  ``rate_per_s``. The gaps are drawn once from a fixed generator and
  only their order comes from the seed, so every seed offers the same
  number of requests over the same span, in another order.
- ``"loop": "closed"``: ``clients`` clients each submit a whole study
  at once, wait for every slice, then send the next study.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import numpy as np

from . import phantom

#: the fixed generator the open loop's gaps come from
GAP_SEED = 20_161_601


@dataclasses.dataclass
class Pool:
    images: np.ndarray            # (studies, slices, H, W) uint8

    @property
    def n_studies(self) -> int:
        return self.images.shape[0]

    @property
    def n_slices(self) -> int:
        return self.images.shape[1]

    def slice(self, study: int, k: int) -> np.ndarray:
        return self.images[study % self.n_studies, k % self.n_slices]


def make_pool(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
              height: int = None, width: int = None) -> Pool:
    """The studies of one run. ``height``/``width`` override the
    configuration's slice size (tests only)."""
    h = height or cfg["height"]
    w = width or cfg["width"]
    labels = phantom.study_labels(h, w, cfg["slices_per_study"],
                                  *mix["slice_positions"])
    rng = np.random.default_rng([seed, 1])
    imgs = np.stack([phantom.study(labels, cfg["noise_sigma"],
                                   cfg["impulse_fraction"], rng)
                     for _ in range(mix["studies"])])
    return Pool(imgs)


@dataclasses.dataclass
class OpenSchedule:
    due_s: np.ndarray             # (N,) offsets from the window's start
    study: np.ndarray             # (N,) pool study of each request
    slice: np.ndarray             # (N,) slice within the study


def open_schedule(rate: float, seconds: float, pool: Pool,
                  seed: int) -> OpenSchedule:
    """Poisson arrivals at ``rate`` over ``seconds``: the same gaps for
    every seed (fixed generator), permuted by the seed; request ``i``
    goes to reader ``i mod studies``, who reads the next slice of their
    study from a seeded starting slice."""
    base = np.random.default_rng(GAP_SEED)
    gaps = base.exponential(1.0 / rate, size=int(rate * seconds * 1.5) + 64)
    n = int(np.searchsorted(np.cumsum(gaps), seconds))
    rng = np.random.default_rng([seed, 2])
    due = np.cumsum(rng.permutation(gaps[:n]))
    idx = np.arange(n)
    start = rng.integers(0, pool.n_slices, size=pool.n_studies)
    study = idx % pool.n_studies
    return OpenSchedule(due, study, (start[study] + idx // pool.n_studies)
                        % pool.n_slices)


def closed_order(pool: Pool, clients: int, seed: int) -> List[List[int]]:
    """Per client, the order in which it sends the pool's studies
    (cycled for as long as the window lasts)."""
    rng = np.random.default_rng([seed, 3])
    return [list(rng.permutation(pool.n_studies)) for _ in range(clients)]
