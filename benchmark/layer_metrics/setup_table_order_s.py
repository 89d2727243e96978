"""Seconds of set-up in span ``table_order`` (under ``table_load``:
each fact put in date order on the device and its window searched,
``plan/catalog.py`` ``q5_channels_tables``) less the ``compile`` spans
under it, which ``setup_compile_s`` counts.  ``None`` where the set-up
has no such span (``lib/setup_spans.py``)."""

from lib import setup_spans


def read(run):
    orders = setup_spans.named(run, "table_order")
    if orders is None:
        return None
    found = setup_spans.setup(run)
    compiles = [s for s in found if s["name"] == setup_spans.COMPILE]
    left = 0
    for o in orders:
        inside = setup_spans.compile_seconds(
            setup_spans.under(o, compiles, found), o["t_ns"],
            o["t_ns"] + o["dur_ns"])
        left += o["dur_ns"] / 1e9 - inside
    return left
