"""Exchange capacity doublings inside the window: the program's counter
``srt_exchange_capacity_doublings_total`` (``parallel/exchange.py``
``with_capacity_retry``); where it has counted any, its journal events
``exchange_capacity_doubling`` stamped inside the window's extent.  0
expected: a doubling is a recompile.  ``None`` where the program has
no such counter or the clocks differ."""

from lib import spans


def read(run):
    try:
        from spark_rapids_tpu import observability as obs
        family = obs.METRICS.snapshot()[
            "srt_exchange_capacity_doublings_total"]
    except (ImportError, AttributeError, KeyError):
        return None
    if not sum(s["value"] for s in family.get("series", [])):
        return 0
    records = [r for r in run.records if r["ok"]]
    if not records or not spans.same_clock():
        return None
    lo = min(r["t_start"] for r in records) * 1e9
    hi = max(r["t_end"] for r in records) * 1e9
    return sum(1 for e in obs.JOURNAL.records("exchange_capacity_doubling")
               if lo <= e["t_ns"] <= hi)
