"""Median per query of the host's wait on the map stage: the
``device_wait`` spans whose attribute ``stage`` is the traffic file's
``map_stage`` (the stage that holds the date filter, the segment sums
and the join probe).  A host wait, not the stage's device time: from
the stage's dispatch to its results ready, so it holds the time the
stage queued behind the other streams' work on the one device too.
The stage's own device time is in the traced run's ``breakdown``.
``None`` where the traffic names no such stage, the program does not
name the stage on its spans, or a window query has none."""

import statistics

from lib import spans


def read(run):
    stage = run.cell.traffic.get("map_stage")
    t = spans.timeline(run)
    if stage is None or t is None:
        return None
    waits = []
    for q in t["queries"]:
        mine = [s for s in q["spans"].get("device_wait", ())
                if (s.get("attrs") or {}).get("stage") == stage]
        if not mine:
            return None
        waits.append(sum(s["dur_ns"] for s in mine))
    return statistics.median(waits) / 1e6
