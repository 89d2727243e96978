"""Median over all queries of the window, client side: submit to the
poll that returned."""

from lib.stats import percentile


def read(run):
    ms = [(r["t_end"] - r["t_start"]) * 1e3 for r in run.records]
    return percentile(ms, 50)
