"""The devices' idle share of the traced window (%): 1 minus the union
of the intervals in which an operation ran on a device, over the window,
averaged over the cell's chips (``bench/trace.py``)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.mean_busy_s / run.trace.window_s)
