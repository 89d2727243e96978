"""Tables held on the device between queries.

A catalog runner that reads a database (not a seed's worth of rows per
query) binds to it here: the first query on a database generates it,
pads each fact table once to its row bucket and puts it on the device
(``plan.compiler.Padded``); every later query binds to the arrays held.
The registry counts the bytes it holds and keeps at most its byte
budget, dropping the least recently used database first.  A database
larger than the whole budget is still loaded (a query needs it), with
everything else dropped.

A loader may put a fact's true rows in the order of a column and give
the table a ``window`` (``plan.compiler.Padded``): the q5 loader orders
each fact by its date, a shard by its own chip, and sizes a slice for
the widest window of the query's days, so that a query reads one slice
of each fact where it would mask all of it.  The capacity is fixed for
the life of the database, so its executables are too.

Counted by ``srt_resident_table_total{outcome=load|hit|evict}``; a
load is the timeline's ``table_load`` span (attributes ``rows``,
``bytes`` and, for tables held in order, ``window_capacity``), under
the query that paid for it.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Callable, Dict, Hashable

from spark_rapids_tpu import observability as _obs

# half of one v5e's 16 GB: a database and the working sets of the
# queries beside it, counted on the fullest device (table_bytes)
DEFAULT_BUDGET_BYTES = 8 << 30


def _leaves(tables: Dict[str, object]):
    for value in tables.values():
        yield from (value if isinstance(value, tuple) else (value,))


def _device_bytes(a) -> int:
    """Bytes of ``a`` on its most-loaded device: a shard of an array
    sharded over devices, the whole of one replicated or on one."""
    shards = getattr(a, "addressable_shards", None)
    if not shards:
        return int(getattr(a, "nbytes", 0))
    per = {}
    for s in shards:
        per[s.device] = per.get(s.device, 0) + int(s.data.nbytes)
    return max(per.values())


def table_bytes(tables: Dict[str, object]) -> int:
    """Device bytes of a database, padding too, as one device holds
    them: each array's bytes on its most-loaded device, so a database
    sharded over chips is held against the budget of one."""
    return sum(_device_bytes(a) for a in _leaves(tables))


def table_rows(tables: Dict[str, object]) -> int:
    """True rows of a database's padded (fact) tables."""
    return sum(int(getattr(v, "rows", 0)) for v in tables.values())


def table_windows(tables: Dict[str, object]) -> str:
    """The slice capacity of each table held in order, as
    ``name=rows`` joined by commas ('' where none is)."""
    return ",".join(f"{name}={v.window}" for name, v in tables.items()
                    if getattr(v, "window", None) is not None)


class ResidentTables:
    """Databases on the device, keyed by what defines them (name,
    sizes, seed), least recently used first."""

    def __init__(self, budget_bytes: int = DEFAULT_BUDGET_BYTES):
        self.budget_bytes = int(budget_bytes)
        self._lock = threading.Lock()
        self._held: "OrderedDict[Hashable, tuple]" = OrderedDict()
        self._loading: Dict[Hashable, threading.Event] = {}

    def get(self, key: Hashable, load: Callable[[], Dict[str, object]]
            ) -> Dict[str, object]:
        """The database ``key``, loaded by ``load()`` if it is not held.
        Concurrent first queries on one key load it once: the others
        wait for that load and then bind to it."""
        while True:
            with self._lock:
                held = self._held.get(key)
                if held is not None:
                    self._held.move_to_end(key)
                    _obs.record_resident_table("hit")
                    return held[0]
                waiting = self._loading.get(key)
                if waiting is None:
                    done = self._loading[key] = threading.Event()
                    break
            waiting.wait()
        try:
            with _obs.TRACER.span("table_load", kind="phase") as span:
                tables = load()
                nbytes = table_bytes(tables)
                span.set_attr("rows", table_rows(tables))
                span.set_attr("bytes", nbytes)
                windows = table_windows(tables)
                if windows:
                    span.set_attr("window_capacity", windows)
            with self._lock:
                self._held[key] = (tables, nbytes)
                _obs.record_resident_table("load")
                self._evict_locked(keep=key)
            return tables
        finally:
            with self._lock:
                self._loading.pop(key)
            done.set()

    def _evict_locked(self, keep: Hashable) -> None:
        while self.held_bytes() > self.budget_bytes and len(self._held) > 1:
            oldest = next(k for k in self._held if k != keep)
            del self._held[oldest]
            _obs.record_resident_table("evict")

    def held_bytes(self) -> int:
        return sum(nbytes for _t, nbytes in self._held.values())

    def keys(self) -> list:
        with self._lock:
            return list(self._held)

    def clear(self) -> None:
        with self._lock:
            self._held.clear()


# the catalog's registry: one per process, as the compiled pipelines are
REGISTRY = ResidentTables()
