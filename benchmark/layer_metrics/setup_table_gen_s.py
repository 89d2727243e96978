"""Seconds of set-up in span ``table_gen`` (under ``table_load``): the
host's generator of the resident database (``models/__init__.py``
``_run_q5_channels``' loader, ``tpcds.gen_q5_db``).  ``None`` where
the set-up has no such span (``lib/setup_spans.py``)."""

from lib import setup_spans


def read(run):
    return setup_spans.seconds(run, "table_gen")
