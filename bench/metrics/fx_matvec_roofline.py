"""Pallas kernel ``fx_matvec``: its share of its roofline, from the device
time of its events in the trace (moves ``fit_s``; LOG cells)."""


def read(run):
    return run.kernel_roofline("fx_matvec")
