"""Named query catalog — the model pipelines as a serving surface.

The query server (``spark_rapids_tpu/server/``) admits work as
``(tenant, query_name, params)`` triples; this module is the registry
that turns a name into a runnable pipeline.  Every built-in runner is a
pure function of its ``params`` dict (data generated from a seed,
pipeline compiled once per parameter signature and cached), so a query
executed interleaved with seven neighbors returns bytes identical to
the same query executed alone — the property the server soak gate
(`make server-smoke`) asserts.

Runners receive an optional :class:`QueryContext` carrying tenant /
query-id attribution and a cooperative cancel flag; the built-in
pipelines are single jitted programs (not interruptible mid-dispatch),
so they check the flag at the recompute boundary only.  Custom runners
registered via :func:`register_query` can poll ``ctx.check_cancel()``
wherever they like.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

from spark_rapids_tpu import observability as _obs
from spark_rapids_tpu.robustness import lifeguard as _lifeguard


class QueryCancelled(Exception):
    """Raised by a runner that observed its cancel flag (the server
    folds it into a 'cancelled' outcome, never an error)."""


class QueryDeadlineExceeded(QueryCancelled):
    """Raised by a cooperative checkpoint once the query's deadline
    has passed (subclass of :class:`QueryCancelled` so existing
    runners unwind unchanged; the server reports a distinct
    ``deadline`` outcome)."""


class QueryContext:
    """Per-execution attribution + cooperative cancellation/deadline
    handle.  Every ``check_cancel`` poll doubles as a lifeguard
    heartbeat — a runner that checkpoints is "slow", never "hung"."""

    __slots__ = ("query_id", "tenant", "_cancel", "deadline_ns")

    def __init__(self, query_id: str = "", tenant: str = "",
                 cancel_event: Optional[threading.Event] = None,
                 deadline_ns: Optional[int] = None):
        self.query_id = query_id
        self.tenant = tenant
        self._cancel = cancel_event
        self.deadline_ns = deadline_ns

    def cancelled(self) -> bool:
        return self._cancel is not None and self._cancel.is_set()

    def remaining_s(self) -> Optional[float]:
        """Seconds until the deadline (negative once past), or None
        when the query has no deadline."""
        if self.deadline_ns is None:
            return None
        return (self.deadline_ns - time.monotonic_ns()) / 1e9

    def phase(self, name: str, **attrs):
        """One span of this query's timeline, for
        ``with ctx.phase("ingest"):`` — kind ``phase``, parented under
        the span open on this thread (the server's ``server_query``
        root, whose ``query_id`` it inherits).  Recorded under the
        metrics or the tracing switch; the shared no-op span with
        both off."""
        return _obs.TRACER.start_span(name, kind="phase",
                                      attrs=attrs or None)

    def check_cancel(self) -> None:
        _lifeguard.beat(f"ctx:{self.query_id or 'query'}")
        # an explicit cancel wins over the deadline: the server keys
        # the outcome off its cancel_reason, so a user-cancelled job
        # whose deadline ALSO lapsed reports "cancelled", not a bogus
        # deadline failure (which would count as a quarantine death)
        if self.cancelled():
            raise QueryCancelled(self.query_id or "query")
        if self.deadline_ns is not None \
                and time.monotonic_ns() > self.deadline_ns:
            raise QueryDeadlineExceeded(self.query_id or "query")


class UnknownQueryError(KeyError):
    """Submitted name is not in the catalog (typed so the server front
    door can map it to a clean error response)."""


# name -> fn(params: dict, ctx: QueryContext) -> JSON-able result
_CATALOG: Dict[str, Callable] = {}
_CATALOG_LOCK = threading.Lock()
# compiled pipelines keyed by (name, param signature): concurrent
# tenants share one executable per shape (the jit_cache story at the
# pipeline level), and serial-vs-interleaved runs execute the SAME
# program — the byte-identity precondition.  LRU-bounded: the
# signature includes tenant-supplied params (join_capacity, stores,
# ...), so an adversarial tenant varying them must recycle cache
# slots, not grow the process without limit.
_PIPELINES: Dict[tuple, Any] = {}
_PIPELINES_LOCK = threading.Lock()
_PIPELINES_MAX = 32


def register_query(name: str, fn: Callable) -> None:
    """Register (or replace) a catalog entry.  ``fn(params, ctx)``
    must be safe to call from multiple pool threads at once."""
    with _CATALOG_LOCK:
        _CATALOG[name] = fn


def unregister_query(name: str) -> None:
    with _CATALOG_LOCK:
        _CATALOG.pop(name, None)


def catalog_queries() -> List[str]:
    with _CATALOG_LOCK:
        return sorted(_CATALOG)


def has_query(name: str) -> bool:
    with _CATALOG_LOCK:
        return name in _CATALOG


def run_catalog_query(name: str, params: Optional[dict] = None,
                      ctx: Optional[QueryContext] = None):
    """Resolve ``name`` and run it — the server's execution entry, and
    equally usable standalone (the soak's serial baseline)."""
    with _CATALOG_LOCK:
        fn = _CATALOG.get(name)
    if fn is None:
        raise UnknownQueryError(name)
    return fn(dict(params or {}), ctx or QueryContext())


def _pipeline(key: tuple, build: Callable):
    with _PIPELINES_LOCK:
        fn = _PIPELINES.pop(key, None)
        if fn is not None:
            _PIPELINES[key] = fn      # re-insert at the LRU tail
            return fn
    # build OUTSIDE the lock: a first-touch signature must not stall
    # every other pool thread's cache hit behind its construction.
    # Racing builders are pure and rare; the first published wins so
    # all callers share ONE program per shape.
    fn = build()
    with _PIPELINES_LOCK:
        fn = _PIPELINES.pop(key, fn)  # keep an earlier publisher
        _PIPELINES[key] = fn
        while len(_PIPELINES) > _PIPELINES_MAX:
            _PIPELINES.pop(next(iter(_PIPELINES)))
        return fn


def _rows(*arrays) -> List[list]:
    """Host-materialize pipeline outputs as plain nested lists (ints
    and floats only) — JSON-able across the socket front door and
    directly comparable for byte-identity.  The timeline's ``rows``
    span: device-to-host of the answer and the list building."""
    import numpy as np
    with _obs.TRACER.start_span("rows", kind="phase") as span:
        cols = [np.asarray(a).reshape(-1) for a in arrays]
        out = []
        for row in zip(*cols):
            out.append([float(v) if isinstance(v, np.floating)
                        else int(v) for v in row])
        span.set_attr("rows_out", len(out))
    return out


def _ingest(ctx: QueryContext, read: Callable, /, **kwargs):
    """A runner's source call (seeded generator or file read) as the
    timeline's ``ingest`` span: the numpy draw or page decode and the
    ``jnp.asarray`` enqueues, with the rows and bytes handed over."""
    import numpy as np
    with ctx.phase("ingest") as span:
        data = read(**kwargs)
        if span is not _obs.NOOP_SPAN:
            leaves = data if isinstance(data, tuple) else (data,)
            shape = np.shape(leaves[0]) if leaves else ()
            span.set_attr("rows", int(shape[0]) if shape else 0)
            span.set_attr("bytes", sum(int(getattr(a, "nbytes", 0))
                                       for a in leaves))
    return data


def _execute(ctx: QueryContext, path: str, run: Callable, /, *args,
             **kwargs):
    """The pipeline (``path="handfused"``) or stage (``"stage"``) call
    as the timeline's ``execute`` span, from the call to its outputs
    ready.  A stage run waits for its outputs itself (``device_wait``
    inside ``stage_run``); a hand-written jit returns at the enqueue,
    so its ``device_wait`` is taken here, where the runner's first
    host read of an output blocked anyway."""
    with ctx.phase("execute", path=path):
        out = run(*args, **kwargs)
        if path == "handfused":
            import jax
            with ctx.phase("device_wait"):
                jax.block_until_ready(out)
    return out


# ------------------------------------------------------- built-in runners
# (each: seeded data + cached pipeline + overflow check + host rows)


def _run_q5(params: dict, ctx: QueryContext):
    import numpy as np

    from spark_rapids_tpu.models import tpcds
    ctx.check_cancel()
    rows = int(params.get("rows", 2048))
    stores = int(params.get("stores", 8))
    seed = int(params.get("seed", 5))
    cap = int(params.get("join_capacity", 1 << 12))
    d = _ingest(ctx, tpcds.gen_q5, rows=rows, stores=stores, days=60,
                seed=seed)
    q = _pipeline(("q5", stores, cap),
                  lambda: tpcds.make_q5(stores, join_capacity=cap))
    k, sales, rets, profit, of = _execute(ctx, "handfused", q, d)
    if bool(np.asarray(of)):
        raise RuntimeError("q5 join capacity overflow")
    return _rows(k, sales, rets, profit)


def _run_q9(params: dict, ctx: QueryContext):
    from spark_rapids_tpu.models import tpcds
    ctx.check_cancel()
    rows = int(params.get("rows", 4096))
    seed = int(params.get("seed", 9))
    data = _ingest(ctx, tpcds.gen_q9, rows=rows, seed=seed)
    counts, avg_p, avg_n = _execute(ctx, "handfused", tpcds.run_q9,
                                    *data)
    return _rows(counts, avg_p, avg_n)


def _run_q72(params: dict, ctx: QueryContext):
    import numpy as np

    from spark_rapids_tpu.models import tpcds
    ctx.check_cancel()
    rows = int(params.get("rows", 2048))
    items = int(params.get("items", 64))
    max_week = int(params.get("max_week", 16))
    seed = int(params.get("seed", 72))
    cap = int(params.get("join_capacity", 1 << 17))
    week0 = 11_000 // 7
    d = _ingest(ctx, tpcds.gen_q72, cs_rows=rows, inv_rows=rows // 2,
                items=items, days=35, seed=seed)
    q = _pipeline(("q72", items, max_week, cap),
                  lambda: tpcds.make_q72(items, max_week,
                                         join_capacity=cap,
                                         week0=week0))
    i, w, c, of = _execute(ctx, "handfused", q, d)
    if bool(np.asarray(of)):
        raise RuntimeError("q72 join capacity overflow")
    return _rows(i, w, c)


def _run_q3(params: dict, ctx: QueryContext):
    from spark_rapids_tpu.models import tpcds
    ctx.check_cancel()
    rows = int(params.get("rows", 2048))
    items = int(params.get("items", 128))
    brands = int(params.get("brands", 16))
    manufact = int(params.get("manufact", 3))
    seed = int(params.get("seed", 3))
    base = 10_957
    d = _ingest(ctx, tpcds.gen_q3, rows=rows, items=items, days=730,
                brands=brands, seed=seed)
    q = _pipeline(("q3", base, brands, manufact),
                  lambda: tpcds.make_q3(base, years=2, brands=brands,
                                        manufact=manufact))
    year, brand, sums, total = _execute(ctx, "handfused", q, d)
    return _rows(year, brand, sums) + [[int(total)]]


def _run_q7(params: dict, ctx: QueryContext):
    from spark_rapids_tpu.models import tpcds
    ctx.check_cancel()
    rows = int(params.get("rows", 2048))
    items = int(params.get("items", 64))
    seed = int(params.get("seed", 7))
    d = _ingest(ctx, tpcds.gen_q7, rows=rows, items=items, demos=256,
                promos=32, seed=seed)
    q = _pipeline(("q7", items), lambda: tpcds.make_q7(items))
    return _rows(*_execute(ctx, "handfused", q, d))


# stage-IR variants (plan/catalog.py, ISSUE 13): the SAME queries
# compiled through the whole-stage fusion compiler — byte-identical
# to the hand-fused twins by the PR-11 contract, but every execution
# reports typed per-stage records to the query profiler, so a server
# tenant submitting these gets a real EXPLAIN ANALYZE plan tree.
# (The hand-fused entries stay untouched as the byte-identity
# oracles; the compiler memoizes CompiledStage per plan digest, so no
# _pipeline cache layer is needed here.)


def _run_q5_fused(params: dict, ctx: QueryContext):
    import numpy as np

    from spark_rapids_tpu.models import tpcds
    from spark_rapids_tpu.plan import catalog as plan_catalog
    ctx.check_cancel()
    rows = int(params.get("rows", 2048))
    stores = int(params.get("stores", 8))
    seed = int(params.get("seed", 5))
    cap = int(params.get("join_capacity", 1 << 12))
    d = _ingest(ctx, tpcds.gen_q5, rows=rows, stores=stores, days=60,
                seed=seed)
    k, sales, rets, profit, of = _execute(
        ctx, "stage", plan_catalog.run_q5, d, stores, cap)
    if bool(np.asarray(of)):
        raise RuntimeError("q5 join capacity overflow")
    return _rows(k, sales, rets, profit)


def _run_q3_fused(params: dict, ctx: QueryContext):
    from spark_rapids_tpu.models import tpcds
    from spark_rapids_tpu.plan import catalog as plan_catalog
    ctx.check_cancel()
    rows = int(params.get("rows", 2048))
    items = int(params.get("items", 128))
    brands = int(params.get("brands", 16))
    manufact = int(params.get("manufact", 3))
    seed = int(params.get("seed", 3))
    d = _ingest(ctx, tpcds.gen_q3, rows=rows, items=items, days=730,
                brands=brands, seed=seed)
    year, brand, sums, total = _execute(
        ctx, "stage", plan_catalog.run_q3, d, 10_957, years=2,
        brands=brands, manufact=manufact)
    return _rows(year, brand, sums) + [[int(total)]]


def _run_q72_fused(params: dict, ctx: QueryContext):
    import numpy as np

    from spark_rapids_tpu.models import tpcds
    from spark_rapids_tpu.plan import catalog as plan_catalog
    ctx.check_cancel()
    rows = int(params.get("rows", 2048))
    items = int(params.get("items", 64))
    max_week = int(params.get("max_week", 16))
    seed = int(params.get("seed", 72))
    cap = int(params.get("join_capacity", 1 << 17))
    d = _ingest(ctx, tpcds.gen_q72, cs_rows=rows, inv_rows=rows // 2,
                items=items, days=35, seed=seed)
    i, w, c, of = _execute(ctx, "stage", plan_catalog.run_q72, d,
                           items, max_week, cap, week0=11_000 // 7)
    if bool(np.asarray(of)):
        raise RuntimeError("q72 join capacity overflow")
    return _rows(i, w, c)


def _q5_mesh_run(shape: dict, limit: int, mesh):
    """q5 over a mesh through the exchange's capacity retry: a run
    whose exchange sent a chip more rows than a slot holds is run again
    at twice the slots.  ``run(tables, day)`` returns (outputs, send
    counts by table, overflowed) and web_sales' slot."""
    from spark_rapids_tpu.parallel.exchange import with_capacity_retry
    from spark_rapids_tpu.plan import catalog as plan_catalog

    def make_step(capacity):
        slots = dict(plan_catalog.q5_exchange_slots(shape, capacity)[0])

        def step(tables, day):
            *out, sent = plan_catalog.run_q5_channels(
                tables, shape, day, limit, mesh=mesh, capacity=capacity)
            over = any(int(sent[t].max()) > slots[t] for t in sent)
            return out, sent, over
        return step

    return with_capacity_retry(make_step, shape["exchange_slots"][-1][1])


def _run_q5_channels(params: dict, ctx: QueryContext):
    """TPC-DS q5 as its template writes it (three channels, the web
    returns-to-sales join, ROLLUP) over a database held on the device:
    ``sizes`` and ``db_seed`` name the database (SF10 by default), which
    the first query loads into ``resident.REGISTRY`` and later queries
    bind to; ``sales_date`` and ``limit`` (the template's _LIMIT, 100
    by default) are the substitutions.  ``chips`` (1 by default) shards
    the database over the first ``chips`` devices, one executor a chip,
    and the web join's sides meet through Spark's hash exchange over
    them.  Rows are (channel, id, sales, returns, profit), NULL as
    -1."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from spark_rapids_tpu.models import resident, tpcds
    from spark_rapids_tpu.plan import catalog as plan_catalog
    ctx.check_cancel()
    sizes = tpcds.q5_sizes(params.get("sizes"))
    db_seed = int(params.get("db_seed", 5))
    chips = int(params.get("chips", 1))
    day = tpcds.q5_day(params.get("sales_date", tpcds.Q5_SALES_DATE))
    mesh = None
    if chips > 1:
        if chips > jax.device_count():
            raise ValueError(f"q5 over {chips} chips: {jax.device_count()} "
                             f"devices")
        mesh = Mesh(np.array(jax.devices()[:chips]), ("data",))
    tables = resident.REGISTRY.get(
        ("tpcds_q5", tuple(sorted(sizes.items())), db_seed, chips),
        lambda: plan_catalog.q5_channels_tables(
            tpcds.gen_q5_db(sizes, db_seed), mesh, tpcds.Q5_WINDOW_DAYS))
    shape = plan_catalog.q5_channels_shape(
        sizes, tpcds.q5_dim_ids(sizes), tpcds.Q5_WINDOW_DAYS, chips,
        plan_catalog.q5_windows(tables))
    limit = int(params.get("limit", tpcds.Q5_LIMIT))
    scan_rows, skipped = plan_catalog.q5_scan_rows(tables)
    for table, rows in skipped.items():
        _obs.record_pruned_rows(table, rows)
    # _execute's span, with the probe's true pair count on it
    with ctx.phase("execute", path="stage") as span:
        span.set_attr("scan_rows", scan_rows)
        if mesh is None:
            *rows, of, pairs = plan_catalog.run_q5_channels(tables, shape,
                                                            day, limit)
        else:
            run = _pipeline(
                ("q5_channels_mesh", tuple(sorted(shape.items())), limit),
                lambda: _q5_mesh_run(shape, limit, mesh))
            (out, sent, _over), capacity = run(tables, day)
            *rows, of, pairs = out
            for table, counts in sent.items():
                _obs.record_exchange_rows(table, int(counts.sum()))
            span.set_attr("exchange_capacity", capacity)
            span.set_attr("exchange_max_dest_rows",
                          max(int(c.max()) for c in sent.values()))
        span.set_attr("join_pairs", int(np.asarray(pairs)))
    if bool(np.asarray(of)):
        raise RuntimeError("q5 capacity overflow: a web sale key "
                           "repeats, a date window holds more than "
                           f"{plan_catalog.Q5_WINDOW_KEYS} date keys, or "
                           "a fact's window more rows than its slice")
    return _rows(*rows)


# file-backed variants (models/filesource.py): same seeded data via a
# parquet round trip through io/parquet_reader, same cached pipeline,
# byte-identical rows — registered thin so pyarrow loads on first use
def _run_q3_file(params: dict, ctx: QueryContext):
    from spark_rapids_tpu.models import filesource
    return filesource.run_q3_file(params, ctx)


# ------------------------------------------------ incremental runners
# (ISSUE 19): the q5/q72 partials/finish split as an INCREMENTAL mode.
# The stream source's ingest epoch says how many batches have arrived;
# only batches past the resident partial-aggregate state's watermark
# run the map side, each folding into the state via the exact-int64
# merge property (segment sums are additive across batches, overflow
# flags OR) — then one finish pass.  With the cache off (or cold)
# every batch recomputes, which IS the differential baseline: the two
# paths share this body, so byte-identity is structural.


def _run_q5_incremental(params: dict, ctx: QueryContext):
    import numpy as np

    from spark_rapids_tpu.models import tpcds
    from spark_rapids_tpu.perf import result_cache as _rc
    from spark_rapids_tpu.plan import catalog as _cat
    ctx.check_cancel()
    rows = int(params.get("rows", 2048))
    stores = int(params.get("stores", 8))
    seed = int(params.get("seed", 5))
    cap = int(params.get("join_capacity", 1 << 12))
    source = str(params.get("source", "q5_stream"))
    # epoch N means N batches ARRIVED after the initial one:
    # a fresh stream (epoch 0) still has its base batch
    batches = _rc.ingest_epoch(source) + 1
    key = ("q5_state", rows, stores, seed, source)
    state, upto = None, 0
    if _rc.cache_enabled():
        got = _rc.CACHE.get_subplan(key)
        if got is not None:
            meta, arrays = got
            w = int(meta.get("upto", 0))
            if 0 < w <= batches:     # a shrunk stream can't rewind
                state, upto = list(arrays), w
                cap = max(cap, int(meta.get("cap", cap)))
    for b in range(upto, batches):
        ctx.check_cancel()
        d = _ingest(ctx, tpcds.gen_q5, rows=rows, stores=stores,
                    days=60, seed=seed + 7919 * b)
        outs, cap = _execute(
            ctx, "stage", _cat.run_q5_partials,
            (d.s_date, d.s_store, d.s_price, d.s_profit,
             d.r_date, d.r_store, d.r_amt, d.r_loss, d.d_date),
            stores, cap, ctx=ctx)
        delta = [np.asarray(o) for o in outs]
        if state is None:
            state = delta
        else:
            state = _rc.fold_partials(state, delta, or_indices=(4,))
            _rc.CACHE.record_fold("tpcds_q5_incremental")
    if _rc.cache_enabled() and batches > upto:
        _rc.CACHE.put_subplan(key, state,
                              {"upto": batches, "cap": cap})
    # dimension labels come from the BASE batch (st_id is a seeded
    # permutation; partials are keyed by store INDEX, so the labels
    # must not drift with the arriving batches)
    d0 = _ingest(ctx, tpcds.gen_q5, rows=stores, stores=stores,
                 days=60, seed=seed)
    k, sales, rets, profit, g_of = _execute(
        ctx, "stage", _cat.run_q5_finish,
        state[0], state[1], state[2], state[3], state[4],
        d0.st_id, stores)
    if bool(np.asarray(g_of)):
        raise RuntimeError("q5 join capacity overflow")
    return _rows(k, sales, rets, profit)


def _run_q72_incremental(params: dict, ctx: QueryContext):
    import numpy as np

    from spark_rapids_tpu.models import tpcds
    from spark_rapids_tpu.perf import result_cache as _rc
    from spark_rapids_tpu.plan import catalog as _cat
    ctx.check_cancel()
    rows = int(params.get("rows", 2048))
    items = int(params.get("items", 64))
    max_week = int(params.get("max_week", 16))
    seed = int(params.get("seed", 72))
    cap = int(params.get("join_capacity", 1 << 17))
    limit = int(params.get("limit", 100))
    week0 = 11_000 // 7
    source = str(params.get("source", "q72_stream"))
    # epoch N means N batches ARRIVED after the initial one:
    # a fresh stream (epoch 0) still has its base batch
    batches = _rc.ingest_epoch(source) + 1
    key = ("q72_state", rows, items, max_week, seed, source)
    state, upto = None, 0
    if _rc.cache_enabled():
        got = _rc.CACHE.get_subplan(key)
        if got is not None:
            meta, arrays = got
            w = int(meta.get("upto", 0))
            if 0 < w <= batches:
                state, upto = list(arrays), w
                cap = max(cap, int(meta.get("cap", cap)))
    for b in range(upto, batches):
        ctx.check_cancel()
        d = _ingest(ctx, tpcds.gen_q72, cs_rows=rows,
                    inv_rows=rows // 2, items=items, days=35,
                    seed=seed + 7919 * b)
        outs, cap = _execute(
            ctx, "stage", _cat.run_q72_partials,
            (d.cs_item, d.cs_date, d.cs_qty,
             d.inv_item, d.inv_date, d.inv_qty, d.item_id),
            items, max_week, cap, week0)
        delta = [np.asarray(o) for o in outs]
        if state is None:
            state = delta
        else:
            state = _rc.fold_partials(state, delta, or_indices=(1,))
            _rc.CACHE.record_fold("tpcds_q72_incremental")
    if _rc.cache_enabled() and batches > upto:
        _rc.CACHE.put_subplan(key, state,
                              {"upto": batches, "cap": cap})
    i, w, c, g_of = _execute(ctx, "stage", _cat.run_q72_finish,
                             state[0], state[1], items, max_week,
                             limit, week0)
    if bool(np.asarray(g_of)):
        raise RuntimeError("q72 join capacity overflow")
    return _rows(i, w, c)


def _run_q7_file(params: dict, ctx: QueryContext):
    from spark_rapids_tpu.models import filesource
    return filesource.run_q7_file(params, ctx)


def _run_q9_file(params: dict, ctx: QueryContext):
    from spark_rapids_tpu.models import filesource
    return filesource.run_q9_file(params, ctx)


register_query("tpcds_q3", _run_q3)
register_query("tpcds_q5", _run_q5)
register_query("tpcds_q7", _run_q7)
register_query("tpcds_q9", _run_q9)
register_query("tpcds_q72", _run_q72)
register_query("tpcds_q3_fused", _run_q3_fused)
register_query("tpcds_q5_fused", _run_q5_fused)
register_query("tpcds_q72_fused", _run_q72_fused)
register_query("tpcds_q5_channels", _run_q5_channels)
register_query("tpcds_q3_file", _run_q3_file)
register_query("tpcds_q7_file", _run_q7_file)
register_query("tpcds_q9_file", _run_q9_file)
register_query("tpcds_q5_incremental", _run_q5_incremental)
register_query("tpcds_q72_incremental", _run_q72_incremental)

# result-cache specs (ISSUE 19): the generator-backed catalog queries
# are pure functions of their parameter binding (seeded synthetic
# data, no external reads), so their results are shareable across
# tenants — the safety gate's "identical digests over shared sources"
# case.  The incremental queries additionally key on their stream
# source's ingest epoch (source_param lets a binding name its own
# stream).  The _file queries read operator-supplied paths and are
# deliberately NOT registered: an unregistered query is uncacheable.
from spark_rapids_tpu.perf.result_cache import \
    register_cache_spec as _reg_spec  # noqa: E402

for _q in ("tpcds_q3", "tpcds_q5", "tpcds_q7", "tpcds_q9",
           "tpcds_q72", "tpcds_q3_fused", "tpcds_q5_fused",
           "tpcds_q72_fused"):
    _reg_spec(_q, shared=True)
_reg_spec("tpcds_q5_incremental", shared=True,
          sources=("q5_stream",), source_param="source")
_reg_spec("tpcds_q72_incremental", shared=True,
          sources=("q72_stream",), source_param="source")
