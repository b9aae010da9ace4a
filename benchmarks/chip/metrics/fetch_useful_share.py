"""Pool (serving/scheduler.py): share of the logits rows fetched to the
host in the window that were delivered, in percent (the boundary
samples' ``fetch_rows_kept`` over ``fetch_rows``; a whole-bank
retirement snapshot fetches every slot's every row)."""


def read(run):
    rows = sum(s.get("fetch_rows", 0) for s in run.timeseries)
    kept = sum(s.get("fetch_rows_kept", 0) for s in run.timeseries)
    return 100.0 * kept / rows if rows else None
