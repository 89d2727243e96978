"""Rows of all completed operations of the window over the window's
elapsed time: all the work over all the time, no chunks."""


def read(run):
    rows = sum(r["rows"] for r in run.records if r["ok"])
    return rows / run.elapsed_s if rows else None
