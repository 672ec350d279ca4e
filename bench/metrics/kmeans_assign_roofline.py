"""Pallas kernel ``kmeans_assign``: its share of its roofline, from the
device time of its events in the trace (moves ``fit_s``; KME cells)."""


def read(run):
    return run.kernel_roofline("kmeans_assign")
