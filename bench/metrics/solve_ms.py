"""Time per certified solve call: the window's length over the calls it
completed (host clock). A call ends when its cost and duals are on the
host and its certificate has been read."""


def read(run):
    if not run.units:
        return None
    return 1e3 * run.window_s / len(run.units)
