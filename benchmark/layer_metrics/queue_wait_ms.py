"""Median queue wait of the window's queries: ``wait_ns`` of the
server's ``server_dequeue`` journal events (admission to a worker
picking the job up; ``server/server.py`` ``_worker_loop``)."""

import statistics

from lib import spans


def read(run):
    t = spans.timeline(run)
    if t is None or t["dequeue_wait_ns"] is None:
        return None
    return statistics.median(t["dequeue_wait_ns"]) / 1e6
