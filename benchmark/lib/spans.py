"""The program's own query timeline, cut to the untraced window.

The program records, per served query, a ``server_query:<query>`` root
span with ``ingest``, ``execute``, ``dispatch``, ``device_wait`` and
``rows`` under it (``spark_rapids_tpu/observability/tracing.py``;
docs/observability.md, Tracing), and a ``server_dequeue`` journal event
with the queue wait.  Both are stamped with ``time.monotonic_ns()``;
the benchmark's records with ``time.perf_counter()``.  Where the two
name one clock, the window's spans are those that start inside
``[min t_start, max t_end]`` of ``run.records`` and no clock arithmetic
is needed.  ``timeline`` returns ``None``, and every metric that reads
it is left out of the line, where the clocks differ, where the program
has no such recorder or dropped records, or where a window query has no
root span (as on a program that predates the timeline)."""

import statistics
import time

from lib import trace

ROOT = "server_query:"


def same_clock():
    """Whether ``perf_counter`` and ``monotonic`` read one clock."""
    a = time.get_clock_info("perf_counter")
    b = time.get_clock_info("monotonic")
    return (a.implementation == b.implementation
            and a.monotonic and b.monotonic)


def program_records():
    """(finished spans, ``server_dequeue`` events, spans dropped) of
    this process, or ``None`` where the program has no such recorder."""
    try:
        from spark_rapids_tpu import observability as obs
        return (obs.TRACER.records(),
                obs.JOURNAL.records("server_dequeue"),
                obs.TRACER.dropped)
    except (ImportError, AttributeError):
        return None


def _end(span):
    return span["t_ns"] + span["dur_ns"]


def timeline(run):
    """The window's queries, in the order they ended::

        {"lo_ns", "hi_ns", "wall_ns": [...],
         "dequeue_wait_ns": [...] or None,
         "queries": [{"wait_ns", "root": span,
                      "spans": {name: [span, ...]}}, ...]}

    ``wall_ns`` holds the benchmark's ``submit``-to-``poll`` walls (as
    many as there are queries; the records carry no query id, so they
    are not paired: sums and medians need no pairing); everything else
    is the program's.  ``None`` as the module says."""
    records = [r for r in run.records if r["ok"]]
    if not records or len(records) != len(run.records):
        return None
    if not same_clock():
        return None
    got = program_records()
    if got is None:
        return None
    spans, dequeues, dropped = got
    if dropped:
        return None
    lo = min(r["t_start"] for r in records) * 1e9
    hi = max(r["t_end"] for r in records) * 1e9
    inside = [s for s in spans if lo <= s["t_ns"] <= hi]
    roots = sorted((s for s in inside if s["parent_id"] is None
                    and s["name"].startswith(ROOT)), key=_end)
    if len(roots) != len(records):
        return None         # a window query without its root span
    # the journal's ring is the smaller one (8192 events of every
    # kind): where it no longer holds the whole window, no queue wait
    waits = [e["wait_ns"] for e in dequeues if lo <= e["t_ns"] <= hi]
    if len(waits) != len(records):
        waits = None
    queries = []
    for root in roots:
        by_name = {}
        for s in inside:
            if s["trace_id"] == root["trace_id"]:
                by_name.setdefault(s["name"], []).append(s)
        for group in by_name.values():
            group.sort(key=lambda s: s["t_ns"])
        queries.append({
            "wait_ns": (root.get("attrs") or {}).get("wait_ns", 0),
            "root": root, "spans": by_name})
    return {"lo_ns": lo, "hi_ns": hi, "dequeue_wait_ns": waits,
            "wall_ns": [(r["t_end"] - r["t_start"]) * 1e9
                        for r in records],
            "queries": queries}


def median_span_ms(run, name):
    """Median over the window's queries of the time each spent in its
    spans called ``name``; ``None`` where a query has none."""
    t = timeline(run)
    if t is None:
        return None
    if not all(name in q["spans"] for q in t["queries"]):
        return None
    return statistics.median(
        sum(s["dur_ns"] for s in q["spans"][name])
        for q in t["queries"]) / 1e6


def fed_seconds(t):
    """Seconds of the window in which some stream had an executable
    enqueued or running: the union over all queries of [start of the
    first ``dispatch``, end of the last ``device_wait``]."""
    held = []
    for q in t["queries"]:
        d, w = q["spans"].get("dispatch"), q["spans"].get("device_wait")
        if not d or not w:
            return None
        held.append((d[0]["t_ns"], _end(w[-1]) - d[0]["t_ns"]))
    merged = trace.clip(trace.union(held), t["lo_ns"], t["hi_ns"])
    return trace.busy_seconds(merged)
