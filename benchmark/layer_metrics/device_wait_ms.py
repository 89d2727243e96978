"""Median ``device_wait`` span of the window's queries: the host's
``block_until_ready`` on the outputs, which is the query's own
executable plus the wait behind the other streams' on one device."""

from lib import spans


def read(run):
    return spans.median_span_ms(run, "device_wait")
