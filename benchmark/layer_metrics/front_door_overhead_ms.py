"""What the front door adds to a query: mean over the window's queries
of the benchmark's ``submit``-to-``poll`` wall minus the queue wait and
the ``server_query`` span (the runner).  What is left is admission,
finalize and the wake-up of ``poll``."""

from lib import spans


def read(run):
    t = spans.timeline(run)
    if t is None:
        return None
    inside = sum(q["wait_ns"] + q["root"]["dur_ns"]
                 for q in t["queries"])
    return (sum(t["wall_ns"]) - inside) / len(t["queries"]) / 1e6
