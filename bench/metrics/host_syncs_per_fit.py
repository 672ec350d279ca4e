"""Trainer and System execution: ``TransferStats.host_syncs`` over the
window, per fit (moves ``fit_s``)."""


def read(run):
    return run.stats["host_syncs"] / run.fits if run.fits else None
