"""Median ``rows`` span of the window's queries: device-to-host of the
answer and the building of its nested lists."""

from lib import spans


def read(run):
    return spans.median_span_ms(run, "rows")
