"""Trainer and System execution: ``TransferStats.pim_to_cpu`` (the reduce
legs back to the host) over the window, per fit (moves ``fit_s``)."""


def read(run):
    return run.stats["pim_to_cpu"] / run.fits if run.fits else None
