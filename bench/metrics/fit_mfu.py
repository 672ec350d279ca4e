"""Whole fit: the least time the chip could take for the work one fit
requires (``bench/work/<config>.py`` against ``bench/peaks.json``), over
the seconds per fit of the traced span, in % (moves ``fit_s``).  It
still bounds a gain after a later change takes a kernel off the path."""


def read(run):
    if run.trace is None or not run.traced_fits:
        return None
    w = run.work()
    return 100.0 * run.roofline_s(w["flops"], w["bytes"]) \
        * run.traced_fits / run.trace.window_s
