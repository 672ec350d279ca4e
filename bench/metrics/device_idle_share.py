"""Device: 100 x (1 - busy union / traced window), from the profiler's
device events inside the window span (moves ``fit_s``)."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)
