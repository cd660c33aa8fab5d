"""Linear-programming arithmetic shared by the references, in float64,
row block by row block on a pool of threads (numpy releases the
interpreter lock inside each block's array operations), so that an
n = 16384 instance is checked in seconds and fits the host's memory."""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict

import numpy as np

BLOCK = 512
THREADS = max(1, min(16, len(os.sched_getaffinity(0)) - 1))


def certify(cost_rows: Callable[[int, int], np.ndarray], n_rows: int,
            row_mass: np.ndarray, col_mass: np.ndarray, y_b: np.ndarray,
            y_a: np.ndarray, slack: float) -> Dict[str, float]:
    """Sums over every row block of the cost matrix ``cost_rows(lo, hi)``
    (a fresh float64 array, which this function overwrites):

    - ``lower``: sum_i row_mass_i * min_j (C_ij - y_a_j) + <col_mass, y_a>,
      a lower bound on the optimum for any y_a (the c-transform of y_a is
      a feasible row dual), over rows and columns of positive mass;
    - ``viol``: edges of positive mass with y_b_i + y_a_j - C_ij > slack;
    - ``viol_max``: the largest y_b_i + y_a_j - C_ij over those edges.
    """
    cols = col_mass > 0
    y_a_live = y_a[cols]

    def block(lo):
        hi = min(lo + BLOCK, n_rows)
        c = cost_rows(lo, hi)
        if not cols.all():
            c = c[:, cols]
        c -= y_a_live
        rows = row_mass[lo:hi] > 0
        if not rows.all():
            c = c[rows]
        yb = y_b[lo:hi][rows]
        rmin = c.min(axis=1)
        return (float(np.dot(row_mass[lo:hi][rows], rmin)),
                int(np.count_nonzero(c < (yb - slack)[:, None])),
                float(np.max(yb - rmin, initial=-np.inf)))

    with ThreadPoolExecutor(THREADS) as ex:
        parts = list(ex.map(block, range(0, n_rows, BLOCK)))
    return {"lower": sum(p[0] for p in parts)
            + float(np.dot(col_mass[cols], y_a_live)),
            "viol": sum(p[1] for p in parts),
            "viol_max": max(p[2] for p in parts)}
