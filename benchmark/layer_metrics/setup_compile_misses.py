"""Backend compiles of set-up that missed the persistent compile cache:
the set-up's ``compile`` spans with ``step`` backend and ``persistent``
miss, where XLA really compiled.  0 on a run after a checkout's first.
``None`` where the set-up has no ``compile`` span with a ``step``
(``lib/setup_spans.py``)."""

from lib import setup_spans


def read(run):
    found = setup_spans.named(run, setup_spans.COMPILE)
    steps = [(s.get("attrs") or {}) for s in found or ()]
    steps = [a for a in steps if "step" in a]
    if not steps:
        return None
    return sum(1 for a in steps
               if a["step"] == "backend" and a.get("persistent") == "miss")
