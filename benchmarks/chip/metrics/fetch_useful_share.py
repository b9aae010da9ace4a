"""Pool (serving/scheduler.py): share of the logits rows fetched to the
host in the window that were delivered, in percent (the boundary
samples' ``fetch_rows_kept`` over ``fetch_rows``).  A retirement gather
fetches R = 32 slots' every row (R x T_pad), of which each retiree keeps
the rows up to its cursor and a padded block's spare slots keep none; a
chunk snapshot, where a mix takes partial logits, keeps each partial's
new rows."""


def read(run):
    rows = sum(s.get("fetch_rows", 0) for s in run.timeseries)
    kept = sum(s.get("fetch_rows_kept", 0) for s in run.timeseries)
    return 100.0 * kept / rows if rows else None
