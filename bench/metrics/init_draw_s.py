"""Trainer and System execution: seconds per fit in K-Means' init draw,
from the profiler trace: the time covered by ``repro.init`` spans (the
host's ``rng.choice`` over every row, the gather of the k rows and their
first cast) over the traced fits (moves ``fit_s``; KME cells).  None
where the trace holds no ``repro.init`` span."""
from bench import spans


def span_seconds_per_fit(run, name: str):
    """Seconds covered by the spans ``name`` over the traced fits; None
    without a trace or without such a span."""
    if run.trace is None or not run.traced_fits:
        return None
    found = spans.spans(run.trace, name)
    if not len(found):
        return None
    return spans.total_ns(found) / 1e9 / run.traced_fits


def read(run):
    return span_seconds_per_fit(run, "repro.init")
