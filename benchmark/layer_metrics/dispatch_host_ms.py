"""Median host time from the start of ``execute`` to the start of
``device_wait``.  On the stage path that is plan lookup, ``stage_bind``
(the ``jnp.pad`` dispatches), the executable cache and ``dispatch``; on
the hand-written path ``dispatch`` alone, so the gap between the two
cells is the stage compiler's host cost."""

import statistics

from lib import spans


def read(run):
    t = spans.timeline(run)
    if t is None:
        return None
    gaps = []
    for q in t["queries"]:
        e, w = q["spans"].get("execute"), q["spans"].get("device_wait")
        if not e or not w:
            return None
        gaps.append(w[0]["t_ns"] - e[0]["t_ns"])
    return statistics.median(gaps) / 1e6
