"""Share of the window in which no stream had an executable enqueued
or running: 100 x (1 - union of [start of ``dispatch``, end of
``device_wait``] / elapsed).  A lower bound of the device's idle share
at steady state (transfers and pads of ``ingest`` and ``stage_bind``
run on the device outside it and are small)."""

from lib import spans


def read(run):
    t = spans.timeline(run)
    if t is None:
        return None
    fed = spans.fed_seconds(t)
    if fed is None:
        return None
    return 100.0 * (1.0 - fed / run.elapsed_s)
