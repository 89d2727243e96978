"""95th percentile over all queries of the window, on the clock of
``query_ms.p50``.  With some ten queries to a window it is close to
the maximum, which is why it is no end-to-end metric yet."""

from lib.stats import percentile


def read(run):
    ms = [(r["t_end"] - r["t_start"]) * 1e3 for r in run.records]
    return percentile(ms, 95)
