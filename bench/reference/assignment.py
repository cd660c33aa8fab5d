"""Plain reference for the assignment configurations: the Euclidean cost
of two point sets scaled to max 1, in float64; the answer is judged by
the assignment LP (every row matched to its own column, weak duality for
the certificate)."""
from __future__ import annotations

from typing import Dict

import numpy as np
from scipy.spatial import ConvexHull

from .lp import certify

# a dual constraint counts as broken when it exceeds the eps/3 slack by
# more than this share of it: f32 duals and costs are off by ~1e-5 of the
# slack at most, a solver run at eps instead of eps/3 by up to 200 %
SLACK_TOL = 1e-3
# the numbers ``check`` returns; a configuration's ``limits`` name some
NUMBERS = ("plan_err", "cost_err", "excess", "dual_viol",
           "viol_max_over_slack")


def _hull(p: np.ndarray) -> np.ndarray:
    return p[ConvexHull(p).vertices] if len(p) > 3 else p


def check(inst: dict, ans: dict, eps: float) -> Dict[str, float]:
    """Numbers for one answer ``{"cost", "y_b", "y_a", "matching"}``."""
    x = np.asarray(inst["x"], np.float64)
    y = np.asarray(inst["y"], np.float64)
    n = len(x)
    # the largest distance lies between vertices of the two hulls
    hx, hy = _hull(x), _hull(y)
    top = np.sqrt(np.sum((hx[:, None] - hy[None]) ** 2, -1)).max()

    def cost_rows(lo, hi):
        return np.hypot(np.subtract.outer(x[lo:hi, 0], y[:, 0]),
                        np.subtract.outer(x[lo:hi, 1], y[:, 1])) / top

    match = np.asarray(ans["matching"], np.int64)
    ok = (match >= 0) & (match < n)
    plan_err = n - np.unique(match[ok]).size
    primal = float(np.sum(np.hypot(*(x[ok] - y[match[ok]]).T)) / top)
    ones = np.ones(n)
    cert = certify(cost_rows, n, ones, ones,
                   np.asarray(ans["y_b"], np.float64),
                   np.asarray(ans["y_a"], np.float64),
                   eps / 3.0 * (1.0 + SLACK_TOL))
    bound = eps * n
    return {"plan_err": float(plan_err),
            "cost_err": abs(float(ans["cost"]) - primal) / bound,
            "excess": (primal - cert["lower"]) / bound,
            "dual_viol": float(cert["viol"]),
            "viol_max_over_slack": cert["viol_max"] / (eps / 3.0)}
