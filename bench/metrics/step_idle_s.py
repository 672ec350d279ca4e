"""Trainer and System execution: device idle seconds per fit inside the
trainer's steps, from the profiler trace: the holes in the device's
busy union that fall inside ``repro.step`` spans, averaged over the
chips, over the traced fits (moves ``fit_s``).  None where the trace
holds no ``repro.step`` span or no device."""
from bench import spans


def read(run):
    if run.trace is None or not run.traced_fits or not run.trace.device_ops:
        return None
    steps = spans.spans(run.trace, "repro.step")
    if not len(steps):
        return None
    chips = len(run.trace.device_ops)
    idle = sum(spans.overlap_ns(steps, spans.idle(run.trace, c))
               for c in range(chips)) / chips
    return idle / 1e9 / run.traced_fits
