"""Share of the HBM roofline of ``convert_to_rows``: the direction's
least bytes (columns read, rows written) over the chip's peak, over
the median device-busy time inside the ``to_rows`` brackets of a
profile of the round trip (``lib/direction.py``)."""

from lib.direction import roofline_share


def read(run):
    return roofline_share(run, "to_rows")
