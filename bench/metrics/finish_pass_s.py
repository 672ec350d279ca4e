"""Trainer and System execution: seconds per fit in K-Means' end of a
restart, from the profiler trace: the time covered by ``repro.finish``
spans (the inertia pass and the labels pass, with their reads to the
host) over the traced fits (moves ``fit_s``; KME cells).  None where
the trace holds no ``repro.finish`` span."""
from bench.metrics.init_draw_s import span_seconds_per_fit


def read(run):
    return span_seconds_per_fit(run, "repro.finish")
