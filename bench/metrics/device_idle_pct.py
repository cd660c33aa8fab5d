"""Share of the traced window in which no operation ran on a chip: one
minus the union of the chip's operation intervals over the window, mean
over the chips the cell uses."""


def read(run):
    if run.trace is None or not run.trace.devices or run.trace_window_s <= 0:
        return None
    return 100.0 * (1.0 - run.busy_s / run.trace_window_s)
