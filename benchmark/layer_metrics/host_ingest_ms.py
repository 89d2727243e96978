"""The catalog layer timed from outside: the cell's own generator to
``block_until_ready`` after the window, median of three (the driver's
probe).  To be replaced by a span inside the runner."""


def read(run):
    return run.probes.get("host_ingest_ms")
