"""Seconds of set-up in which the process compiled: the union of the
set-up's ``compile`` spans (JAX's trace, lowering and backend steps;
a cache read is a backend step), so a nested trace, or compiles side
by side on a pool's threads, count once.  ``None`` where the set-up
has no ``compile`` span (``lib/setup_spans.py``)."""

from lib import setup_spans


def read(run):
    found = setup_spans.named(run, setup_spans.COMPILE)
    if found is None:
        return None
    return setup_spans.compile_seconds(found)
