"""Certified instances completed per second: every lane of every solve
call in the window over the window's length (host clock)."""


def read(run):
    if not run.units:
        return None
    return sum(u.lanes for u in run.units) / run.window_s
