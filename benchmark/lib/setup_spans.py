"""The program's own spans of set-up: those of ``obs.TRACER`` that
ended before the window's first operation started.

The warm-up query's timeline (``server_query:<query>`` and what is
under it: ``table_load`` with ``table_gen``, ``table_upload`` and
``table_order``, the stage runs) and every JAX compile step of the
process, a ``compile`` span each (attributes ``step``: trace, lower or
backend; ``fun``; on a backend step ``persistent``: hit, miss or off)
under the span open on the compiling thread, a nested trace under the
trace that caused it (``spark_rapids_tpu/observability/__init__.py``;
docs/observability.md).  The same checks as ``lib/spans.py``: ``None``
where the clocks differ, where the program keeps no such recorder or
its ring dropped spans, or where the window has no operation."""

from lib import spans, trace

COMPILE = "compile"


def setup(run):
    """The set-up's finished spans, or ``None`` as the module says."""
    if not run.records or not spans.same_clock():
        return None
    got = spans.program_records()
    if got is None:
        return None
    recs, _dequeues, dropped = got
    if dropped:
        return None
    lo = min(r["t_start"] for r in run.records) * 1e9
    return [s for s in recs if s["t_ns"] + s["dur_ns"] <= lo]


def named(run, name):
    """The set-up's spans called ``name``; ``None`` where it has none
    (as on a program that does not record them)."""
    found = [s for s in setup(run) or () if s["name"] == name]
    return found or None


def seconds(run, name):
    """Seconds of the set-up's spans called ``name``, or ``None``."""
    found = named(run, name)
    if found is None:
        return None
    return sum(s["dur_ns"] for s in found) / 1e9


def compile_seconds(found, lo=None, hi=None):
    """Seconds in which some span of ``found`` was open: a trace nested
    in another, and steps that ran side by side on other threads, count
    once; cut to ``[lo, hi)`` where given."""
    merged = trace.union((s["t_ns"], s["dur_ns"]) for s in found)
    if lo is not None:
        merged = trace.clip(merged, lo, hi)
    return trace.busy_seconds(merged)


def under(parent, found, every):
    """The spans of ``found`` whose chain of parents, through the spans
    of ``every``, reaches ``parent``."""
    by_id = {s["span_id"]: s for s in every}

    def reaches(s):
        while s is not None and s["parent_id"] is not None:
            if s["parent_id"] == parent["span_id"]:
                return True
            s = by_id.get(s["parent_id"])
        return False
    return [s for s in found if reaches(s)]
