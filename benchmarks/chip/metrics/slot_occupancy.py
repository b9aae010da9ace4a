"""Pool (serving/scheduler.py): mean share of the pool's slots that
advanced at each chunk boundary of the window, in percent (the pool's
``active_frac`` time series)."""


def read(run):
    fracs = [s["active_frac"] for s in run.timeseries]
    return 100.0 * sum(fracs) / len(fracs) if fracs else None
