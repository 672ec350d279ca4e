"""Trainer and System execution: host seconds per fit inside the
trainer's steps, from the profiler trace: the time covered by
``repro.step`` spans less the part inside ``repro.read`` (where the
host waits on the device), over the traced fits (moves ``fit_s``).
None where the trace holds no ``repro.step`` span."""
from bench import spans


def read(run):
    if run.trace is None or not run.traced_fits:
        return None
    steps = spans.spans(run.trace, "repro.step")
    if not len(steps):
        return None
    reads = spans.spans(run.trace, "repro.read")
    own = spans.total_ns(steps) - spans.overlap_ns(steps, reads)
    return own / 1e9 / run.traced_fits
