"""The ranking that turns cycles and energy into answers: objectives per
candidate and the Pareto front (copied from the program's explorer, so
that the yardstick does not move when the program's ranking does)."""

from __future__ import annotations

from typing import Sequence

import numpy as np


def pareto_front(objectives: np.ndarray) -> np.ndarray:
    """Indices of the non-dominated rows of a (B, M) minimization problem,
    sorted by the first objective, ties by row order; exact duplicates
    keep the first row; rows with a non-finite objective are ignored."""
    objs = np.asarray(objectives, np.float64)
    finite = np.isfinite(objs).all(axis=1)
    rows = np.nonzero(finite)[0]
    if rows.size == 0:
        return np.zeros(0, np.int64)
    sub = objs[rows]
    m = sub.shape[1]
    order = np.lexsort(tuple(sub[:, j] for j in range(m - 1, -1, -1)))
    ss = sub[order]
    dom = (ss[None, :, :] <= ss[:, None, :]).all(axis=2)
    dom &= np.tri(len(ss), k=-1, dtype=bool)
    return np.asarray(rows[order[~dom.any(axis=1)]], np.int64)


def objectives(cycles: np.ndarray, energy: np.ndarray, cost: np.ndarray,
               base_c: np.ndarray, base_e: np.ndarray,
               cols: Sequence[int]) -> np.ndarray:
    """(B, 3) latency, energy, cost of candidates over the cells ``cols``:
    latency and energy are means of the per-cell ratios to theta = 1."""
    cols = np.asarray(cols, np.int64)
    lat = (cycles[:, cols] / base_c[cols]).mean(axis=1)
    en = (energy[:, cols] / np.maximum(base_e[cols], 1e-30)).mean(axis=1)
    return np.stack([lat, en, np.asarray(cost, np.float64)], axis=1)
