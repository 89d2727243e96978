"""Resident-table loads inside the window: the program's ``table_load``
spans (``models/resident.py``, where ``srt_resident_table_total
{outcome=load}`` counts them) under the window's queries.  0 expected:
the database is loaded by the warm-up query in set-up.  ``None`` where
the program keeps no timeline (``lib/spans.py``)."""

from lib import spans


def read(run):
    t = spans.timeline(run)
    if t is None:
        return None
    return sum(len(q["spans"].get("table_load", ())) for q in t["queries"])
