"""Seconds of set-up in span ``table_upload`` (under ``table_load``):
the padding and ``device_put`` of every column of the resident
database, to ``block_until_ready`` (``plan/catalog.py``
``q5_channels_tables``).  ``None`` where the set-up has no such span
(``lib/setup_spans.py``)."""

from lib import setup_spans


def read(run):
    return setup_spans.seconds(run, "table_upload")
