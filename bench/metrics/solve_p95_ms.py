"""95th percentile of the times of all solve calls in the window (host
clock, linear interpolation between order statistics)."""
import numpy as np


def read(run):
    if not run.units:
        return None
    return 1e3 * float(np.percentile([u.wall_s for u in run.units], 95))
