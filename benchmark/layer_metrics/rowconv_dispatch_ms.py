"""Median per round trip of the program's own ``to_rows`` and
``from_rows`` spans inside the untraced window.  They end where the
call returns (the enqueue), so this is the host's share of a round
trip: layout, padding, the executable cache, dispatch, and any
readback the call makes.  ``None`` on a program without the spans."""

from lib.direction import program_span_ms


def read(run):
    return program_span_ms(run, ("to_rows", "from_rows"))
