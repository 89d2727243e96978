"""Median ``ingest`` span of the window's queries: the runner's
generator or file read with its host-to-device enqueues, measured
inside the window, while the other stream competes for the host."""

from lib import spans


def read(run):
    return spans.median_span_ms(run, "ingest")
