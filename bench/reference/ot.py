"""Plain reference for the grid OT configurations: squared-Euclidean
cost between the pixel centres of a side x side grid in the unit square,
in float64; the answer is judged by the transport LP (the plan's
marginals, weak duality for the certificate)."""
from __future__ import annotations

from typing import Dict

import numpy as np

from .assignment import SLACK_TOL
from .lp import certify

NUMBERS = ("plan_err", "cost_err", "excess", "dual_viol",
           "viol_max_over_slack")


def check(inst: dict, ans: dict, eps: float) -> Dict[str, float]:
    """Numbers for one answer ``{"cost", "y_b", "y_a", "rows", "cols",
    "vals"}`` (the plan's nonzero entries)."""
    side = int(inst["side"])
    n = side * side
    idx = np.arange(n)
    r, c = (idx // side) / max(side - 1, 1), (idx % side) / max(side - 1, 1)
    nu = np.asarray(inst["nu"], np.float64)
    mu = np.asarray(inst["mu"], np.float64)
    i = np.asarray(ans["rows"], np.int64)
    j = np.asarray(ans["cols"], np.int64)
    v = np.asarray(ans["vals"], np.float64)

    def cost_rows(lo, hi):
        return (np.subtract.outer(r[lo:hi], r) ** 2
                + np.subtract.outer(c[lo:hi], c) ** 2)

    scale = 2.0          # the grid's largest squared distance
    primal = float(np.dot(v, (r[i] - r[j]) ** 2 + (c[i] - c[j]) ** 2))
    # the plan's marginals and sign, against the largest mass
    plan_err = max(np.abs(np.bincount(i, v, n) - nu).max(),
                   np.abs(np.bincount(j, v, n) - mu).max(),
                   -v.min(initial=0.0)) / max(nu.max(), mu.max())
    cert = certify(cost_rows, n, nu, mu,
                   np.asarray(ans["y_b"], np.float64),
                   np.asarray(ans["y_a"], np.float64),
                   eps / 3.0 * scale * (1.0 + SLACK_TOL))
    bound = eps * nu.sum() * scale
    return {"plan_err": float(plan_err),
            "cost_err": abs(float(ans["cost"]) - primal) / bound,
            "excess": (primal - cert["lower"]) / bound,
            "dual_viol": float(cert["viol"]),
            "viol_max_over_slack": cert["viol_max"] / (eps / 3.0 * scale)}
