"""Median over the window of the benchmark's own span around
``convert_to_rows``, ending in ``block_until_ready``."""

from lib.stats import percentile


def read(run):
    ms = [r["spans"]["to_rows"] * 1e3 for r in run.records
          if "to_rows" in r.get("spans", {})]
    return percentile(ms, 50)
