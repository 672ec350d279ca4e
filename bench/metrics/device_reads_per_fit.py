"""Trainer and System execution: ``TransferStats.device_reads`` (the
calls of ``System.read``, where the host blocks on device results and
copies them back) over the window, per fit (moves ``fit_s``).  None
where the program has no such counter."""


def read(run):
    reads = run.stats.get("device_reads")
    return reads / run.fits if reads is not None and run.fits else None
