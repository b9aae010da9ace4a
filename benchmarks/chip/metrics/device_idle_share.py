"""Device: share of the traced window, in percent, in which no operation
ran on the device (1 - union of the operation intervals / window)."""


def read(run):
    share = run.trace.idle_share if run.trace is not None else None
    return None if share is None else 100.0 * share
