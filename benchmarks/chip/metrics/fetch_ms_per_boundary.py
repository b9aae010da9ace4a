"""Pool (serving/scheduler.py): mean host milliseconds per chunk boundary
of the window spent in the pool's ``snapshot_fetch`` span (resolving the
retirement and partial-logits snapshots: the wait for the device, then
the device-to-host copy), from the program's boundary samples."""


def read(run):
    secs = [s["snapshot_fetch_s"] for s in run.timeseries
            if "snapshot_fetch_s" in s]
    return 1e3 * sum(secs) / len(secs) if secs else None
