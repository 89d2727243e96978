"""Median per profiled query of the device time inside the web join's
hash exchange (pack, all-to-all, unpack): the operations whose scope
holds one of the traffic's ``exchange_scopes`` (``srt/<stage>/<Exchange
node>``), averaged over the devices (``lib/scopes.py``, a profile of
the reader's own after the window).  ``None`` without ``--trace 1``,
on a rehearsal and on a program whose plan has no such node."""

from lib.scopes import scope_ms


def read(run):
    return scope_ms(run, run.cell.traffic.get("exchange_scopes", []))
