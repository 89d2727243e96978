"""TPC-DS q5 as its template writes it (``tpcds_q5_channels``) over a
database held on the device: the served answer against the plain
reference of the benchmark (``benchmark/reference/tpcds_q5.py``), the
rollup's rows and order, the web returns-to-sales join, the resident
registry, the benchmark cell at toy size, the control, and the
store-channel shape that shares the channel body."""

import argparse
import importlib.util
import os
import sys

import jax
import numpy as np
import pytest

from spark_rapids_tpu import observability as obs
from spark_rapids_tpu.models import resident, tpcds
from spark_rapids_tpu.plan import catalog as C
from spark_rapids_tpu.plan.compiler import Padded
from spark_rapids_tpu.server import QueryServer, ServerConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(os.path.join(BENCH, "reference", "tpcds_q5.py"), "ref_tpcds_q5")

# the benchmark configuration's toy database: 150 catalog pages, so
# LIMIT 100 cuts inside the catalog channel
SIZES = dict(store_sales=20_000, store_returns=2_000, catalog_sales=40_000,
             catalog_returns=4_000, web_sales=12_000, web_returns=1_200,
             date_dim=73_049, store=12, catalog_page=150, web_site=6,
             item=1_000)
DATES = ["1998-08-01", "1999-08-15", "2000-08-23", "2000-08-30",
         "2001-08-07", "2002-08-30", "2002-08-01", "1999-08-29"]


def _ref_db(host):
    """The program's host database in the reference's form."""
    days = np.datetime64("1970-01-01") + host["d_date"].astype(np.int64)
    db = {"date_dim": (host["d_date_sk"].astype(np.int64), days)}
    for dim in ("store", "catalog_page", "web_site"):
        db[dim] = (np.arange(1, len(host[dim]) + 1), host[dim])
    for fact in tpcds.Q5_FACTS:
        db[fact] = host[fact]
    return db


def _want(db, sales_date, limit=100):
    return REF.answer({"db": db, "sales_date": sales_date,
                       "limit": limit}, {})


def _shape(sizes, tables=None):
    sizes = tpcds.q5_sizes(sizes)
    return C.q5_channels_shape(sizes, tpcds.q5_dim_ids(sizes),
                               tpcds.Q5_WINDOW_DAYS, 1,
                               tables and C.q5_windows(tables))


def _raw(tables, sales_date, limit=100, sizes=SIZES):
    """The plan's outputs over ``tables``, as numpy arrays."""
    return [np.asarray(a) for a in C.run_q5_channels(
        tables, _shape(sizes, tables), tpcds.q5_day(sales_date), limit)]


def _run(host, sales_date, limit=100, sizes=SIZES):
    """The plan over ``host`` put on the device as the registry does."""
    *cols, of, pairs = _raw(C.q5_channels_tables(host), sales_date, limit,
                            sizes)
    rows = [[int(v) for v in r] for r in zip(*cols)]
    return REF.from_served(rows), bool(of), int(pairs)


@pytest.fixture(scope="module")
def host():
    return tpcds.gen_q5_db(SIZES, 2002)


def test_the_reference_draws_the_programs_database(host):
    got, want = REF.database(dict(SIZES), 2002), _ref_db(host)
    assert set(got) == set(want)
    for name in got:
        for a, b in zip(got[name], want[name]):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("db_seed", [2002, 7])
def test_served_answers_equal_the_reference(db_seed):
    resident.REGISTRY.clear()
    srv = QueryServer(ServerConfig(max_concurrency=2)).start()
    try:
        qids = [(d, srv.submit(("tenant_a", "tenant_b")[i % 2],
                               "tpcds_q5_channels",
                               {"sizes": SIZES, "db_seed": db_seed,
                                "sales_date": d}))
                for i, d in enumerate(DATES)]
        got = [(d, srv.poll(q, timeout_s=300.0)) for d, q in qids]
    finally:
        srv.stop()
    db = REF.database(dict(SIZES), db_seed)
    for d, st in got:
        assert st["state"] == "done", st
        assert REF.compare(REF.from_served(st["result"]),
                           _want(db, d)) == {"values_differing": 0}, d


def test_rollup_rows_and_nulls_first_order(host):
    rows, of, _pairs = _run(host, "2000-08-23")
    assert not of and len(rows["rows"]) == 100
    assert rows == _want(_ref_db(host), "2000-08-23")
    head = rows["rows"]
    # grand total, the catalog subtotal, then catalog ids ascending:
    # LIMIT 100 cuts inside the catalog channel
    assert head[0][:2] == [None, None] and head[1][:2] == [
        "catalog channel", None]
    ids = [r[1] for r in head[2:]]
    assert all(r[0] == "catalog channel" for r in head[2:])
    assert ids == sorted(ids) and len(ids) == 98
    everything = _run(host, "2000-08-23", limit=1000)[0]["rows"]
    assert everything == _want(_ref_db(host), "2000-08-23", 1000)["rows"]
    for name in REF.CHANNELS:
        mine = [r for r in everything if r[0] == name]
        assert mine[0][1] is None             # its subtotal first
        assert mine[0][2:] == [sum(r[k] for r in mine[1:])
                               for k in (2, 3, 4)]
    assert everything[0][2:] == [
        sum(r[k] for r in everything if r[0] and r[1] is None)
        for k in (2, 3, 4)]


def _window_rows(dates, sales_date):
    """Rows of a fact's date keys inside SALES_DATE's fortnight."""
    lo = tpcds.q5_day(sales_date) - tpcds.D_DATE0 + tpcds.D_DATE_SK0
    return np.flatnonzero((dates >= lo) & (dates < lo + tpcds.Q5_WINDOW_DAYS))


def test_web_return_without_its_sale_is_dropped(host):
    """Every other return of the fortnight loses its sale.  The returns'
    date filter lies below the join, so the probe pairs the fortnight's
    returns alone."""
    ws, wr = host["web_sales"], host["web_returns"]
    sold = {(int(i), int(o)): k for k, (i, o) in enumerate(zip(ws[4], ws[5]))}
    inside = _window_rows(wr[0], "2000-08-23")
    gone = [sold[(int(wr[1][k]), int(wr[2][k]))] for k in inside[::2]]
    assert len(gone) >= 3
    keep = np.ones(len(ws[0]), bool)
    keep[gone] = False
    cut = dict(host, web_sales=tuple(c[keep] for c in ws))
    sizes = dict(SIZES, web_sales=int(keep.sum()))
    rows, of, pairs = _run(cut, "2000-08-23", 1000, sizes)
    assert not of and pairs == len(inside) - len(gone)
    assert rows == _want(_ref_db(cut), "2000-08-23", 1000)
    assert rows != _run(host, "2000-08-23", 1000)[0]


def test_packed_key_at_its_37_bit_edge():
    """Items up to 2^17 - 1 and order numbers up to 2^20 - 1: the
    packed key's every bit; neighbours one item or one order apart
    each find their own sale."""
    sizes = dict(SIZES, item=2 ** 17 - 1, web_sales=48, web_returns=24)
    host = tpcds.gen_q5_db(sizes, 11)
    date, site, price, profit, _item, _order = host["web_sales"]
    row = np.arange(48)
    item = (2 ** 17 - 1 - row % 2).astype(np.int32)
    order = (2 ** 20 - 1 - row // 2).astype(np.int32)
    site = (row % 6 + 1).astype(np.int32)
    # every sale inside the window, so each return's site is summed
    day_sk = tpcds.q5_day("2000-08-23") - tpcds.D_DATE0 + tpcds.D_DATE_SK0
    date = np.full(48, day_sk, np.int32)
    host["web_sales"] = (date, site, price, profit, item, order)
    pick = np.arange(0, 48, 2)
    r = host["web_returns"]
    host["web_returns"] = (date[pick] + 1, item[pick], order[pick],
                           r[3], r[4])
    assert _shape(sizes)["item_bits"] == 17
    rows, of, pairs = _run(host, "2000-08-23", 1000, sizes)
    assert not of and pairs == 24
    want = _want(_ref_db(host), "2000-08-23", 1000)
    assert rows == want
    web = [w for w in want["rows"] if w[0] == "web channel"]
    assert sum(w[3] for w in web if w[1] is not None) == int(r[3].sum())


def _resident_counts():
    series = obs.RESIDENT_TABLE.snapshot()["series"]
    return {o: sum(x["value"] for x in series if x["labels"] == [o])
            for o in ("load", "hit", "evict")}


@pytest.fixture
def counting():
    prior = obs.is_enabled()
    obs.enable()
    before = _resident_counts()
    yield lambda: {k: v - before[k] for k, v in _resident_counts().items()}
    if not prior:
        obs.disable()


def test_registry_loads_once_hits_evicts_and_loads_another(counting):
    loads = []

    def loader(seed, n):
        def load():
            loads.append(seed)
            return {"f": Padded((jax.numpy.zeros(n, np.int64),), n - 1)}
        return load

    reg = resident.ResidentTables(budget_bytes=3100)
    first = reg.get(("db", 1), loader(1, 256))         # 2,048 B
    assert reg.get(("db", 1), loader(1, 256)) is first
    assert loads == [1] and counting() == {"load": 1, "hit": 1, "evict": 0}
    reg.get(("db", 2), loader(2, 128))                 # 1,024 B: both fit
    assert reg.keys() == [("db", 1), ("db", 2)]
    reg.get(("db", 1), loader(1, 256))                 # 1 used last
    reg.get(("db", 3), loader(3, 64))                  # over: 2 goes
    assert reg.keys() == [("db", 1), ("db", 3)] and loads == [1, 2, 3]
    assert counting() == {"load": 3, "hit": 2, "evict": 1}
    assert reg.held_bytes() == 2048 + 512
    spans = [s for s in obs.TRACER.records() if s["name"] == "table_load"]
    assert spans and spans[-1]["attrs"]["bytes"] == 512
    assert spans[-1]["attrs"]["rows"] == 63


def test_served_queries_bind_to_the_held_database(counting):
    resident.REGISTRY.clear()
    params = {"sizes": SIZES, "db_seed": 3, "sales_date": DATES[0]}
    from spark_rapids_tpu.models import run_catalog_query
    first = run_catalog_query("tpcds_q5_channels", params)
    again = run_catalog_query("tpcds_q5_channels",
                              dict(params, sales_date=DATES[1]))
    assert first != again
    assert counting() == {"load": 1, "hit": 1, "evict": 0}
    resident.REGISTRY.clear()


def _drive(trace=0):
    sys.path.insert(0, BENCH)
    try:
        harness = _load(os.path.join(BENCH, "run.py"), "bench_run_q5")
    finally:
        sys.path.remove(BENCH)
    args = argparse.Namespace(workload="sf10-q5-streams2",
                              seed=2_147_483_659, seconds=0.3, trace=trace,
                              size="toy")
    code, result = harness.run_cell(args)
    assert code == 0
    return result


@pytest.mark.skipif(os.environ.get("JAX_PLATFORMS") != "cpu",
                    reason="a rehearsal: set JAX_PLATFORMS=cpu")
def test_the_cell_at_toy_size_reads_correct_and_a_broken_answer_not(
        monkeypatch):
    result = _drive(trace=1)
    assert result["correct"] is True
    assert result["compared"]["answers_compared"] == result["attempted"]
    metrics = result["metrics"]
    assert metrics["table_loads"]["value"] == 0
    assert metrics["window_compiles"]["value"] == 0
    assert "map_stage_host_wait_ms" in metrics

    import spark_rapids_tpu.models as models
    real = models._rows

    def rows(*arrays):
        out = real(*arrays)
        if out and len(out[0]) == 5:
            out[0][2] += 1            # the grand total's sales, off by one
        return out
    monkeypatch.setattr(models, "_rows", rows)
    result = _drive()
    assert result["correct"] is False
    assert result["compared"]["values_differing"]["value"] > 0


@pytest.fixture
def wrong_sale(monkeypatch):
    """A planted fault in the web probe: each return takes the site of
    the next line of its order (``wj.ri`` + 1), not of its own sale.
    Amounts and dates still come through ``wj.li``, so every sum of a
    channel and the grand total stay as they were."""
    import dataclasses

    from spark_rapids_tpu.plan.ir import Bin, Col, Idx, Lit, Project, Where
    real = C.q5_channels_map_plan

    def planted(*args, **kwargs):
        plan = real(*args, **kwargs)
        outlet = Bin("sub", Idx(Col("ws_outlet"),
                                Bin("add", Col("wj.ri"), Lit(1))), Lit(1))
        nodes = tuple(
            Project("wr_st", Where(Col("wr_keep"), outlet, Lit(0)))
            if getattr(n, "out", None) == "wr_st" else n
            for n in plan.nodes)
        assert nodes != plan.nodes
        return dataclasses.replace(plan, nodes=nodes)
    monkeypatch.setattr(C, "q5_channels_map_plan", planted)


def test_a_wrong_web_sale_hides_under_limit_100_only(host, wrong_sale):
    db = _ref_db(host)
    assert _run(host, "2000-08-23")[0] == _want(db, "2000-08-23")
    every = _run(host, "2000-08-23", limit=1000)[0]
    assert every != _want(db, "2000-08-23", 1000)
    assert REF.compare(every, _want(db, "2000-08-23", 1000))[
        "values_differing"] > 0


@pytest.mark.skipif(os.environ.get("JAX_PLATFORMS") != "cpu",
                    reason="a rehearsal: set JAX_PLATFORMS=cpu")
def test_the_cell_reads_a_wrong_web_sale_as_not_correct(wrong_sale):
    """The cell's traffic serves every row of the rollup, so a return
    paired with the wrong sale reads ``correct: false``."""
    assert REF.make_inputs(dict(SIZES, db_seed=5, rows=0), {"limit": 12076},
                           1)["limit"] == 12076
    result = _drive()
    assert result["correct"] is False
    assert result["compared"]["values_differing"]["value"] > 0


@pytest.mark.parametrize("seed", [11, 2_147_483_659])
def test_the_control_is_not_correct_where_bfloat16_rounds(seed):
    sizes = dict(SIZES, db_seed=5, rows=0)
    inputs = REF.make_inputs(sizes, {}, seed)
    want = REF.answer(inputs, {})
    assert REF.compare(REF.answer(inputs, {}), want) == {
        "values_differing": 0}
    broken = REF.compare(REF.control_answer(inputs, {}), want)
    assert broken["values_differing"] > 0


def test_min_bytes_counts_what_a_query_must_read():
    full = tpcds.Q5_SF10
    assert sum(full[t] for t in tpcds.Q5_FACTS) == 55_434_216
    assert REF.min_bytes(full, {}) == 1_390_878_580


def test_store_channel_shape_keeps_its_plan_and_its_oracle():
    # the digests of the store-channel plans before the channel body
    # was shared (PR 37): same nodes, same executables
    assert C.q5_partials_plan(8, 4096).digest == "9b222cfb4983bf48"
    assert C.q5_pipeline(8, 4096).digest == "fd7f756823d628a3"
    d = tpcds.gen_q5(rows=3000, stores=8, days=60, seed=5)
    k, sales, rets, profit, of = C.run_q5(d, 8, 1 << 12)
    assert not bool(of)
    got = [(int(a), int(b), int(c), int(e))
           for a, b, c, e in zip(np.asarray(k), np.asarray(sales),
                                 np.asarray(rets), np.asarray(profit))
           if a != 2 ** 31 - 1]
    assert got == tpcds.oracle_q5(d, 8)


# --------------------------------------- the zone map over ordered facts

# the first and the last sale day, and a fortnight after every fact's
# last date (a return 90 days after the last sale): no fact holds a row
EDGE_DATES = ["1998-01-02", "2002-12-31", "2003-07-01"]
# each fact one row past a power of two
ABOVE = dict(SIZES, store_sales=2 ** 14 + 1, store_returns=2 ** 11 + 1,
             catalog_sales=2 ** 15 + 1, catalog_returns=2 ** 12 + 1,
             web_sales=2 ** 13 + 1, web_returns=2 ** 10 + 1)


def _whole(host):
    """The database as it was held before it was ordered: each fact in
    its drawn order, padded, with no window, so the map stage reads
    every row of it and masks them."""
    from spark_rapids_tpu.perf.jit_cache import bucket_rows
    tables = C.q5_channels_tables(host)
    for inp in C.Q5_CHANNEL_INPUTS[:6]:
        cols = host[C._Q5_FACT_TABLES[inp.name]]
        b = bucket_rows(len(cols[0]))
        tables[inp.name] = Padded(tuple(
            jax.device_put(np.concatenate(
                [c, np.full(b - len(c), spec.pad, c.dtype)]))
            for spec, c in zip(inp.columns, cols)), len(cols[0]))
    return tables


def _widest(dates):
    """The most of ``dates`` in any window of the query's days,
    counted here from the sorted keys."""
    d = np.sort(dates)
    return int((np.searchsorted(d, d + tpcds.Q5_WINDOW_DAYS) -
                np.arange(len(d))).max())


@pytest.mark.parametrize("sizes", [SIZES, ABOVE], ids=["toy", "above"])
def test_slices_answer_as_the_reference_and_the_whole_tables(sizes):
    host = tpcds.gen_q5_db(sizes, 2002)
    sliced, whole = C.q5_channels_tables(host), _whole(host)
    assert all(sliced[s].window < len(sliced[s][0]) for s in C._Q5_FACT_TABLES)
    assert C.q5_windows(whole) == {}
    db = _ref_db(host)
    for date in DATES[:5] + EDGE_DATES:
        got = _raw(sliced, date, 1000, sizes)
        for a, b in zip(got, _raw(whole, date, 1000, sizes)):
            np.testing.assert_array_equal(a, b)
        rows = [[int(v) for v in r] for r in zip(*got[:-2])]
        assert not got[-2] and REF.from_served(rows) == _want(db, date, 1000)


def _skewed(sizes, seed):
    """A database with half of each fact's rows on one day, 2000-08-25."""
    host = tpcds.gen_q5_db(sizes, seed)
    hot = tpcds.q5_day("2000-08-25") - tpcds.D_DATE0 + tpcds.D_DATE_SK0
    for fact in tpcds.Q5_FACTS:
        host[fact][0][::2] = hot
    return host


def test_skewed_dates_stay_exact():
    host = _skewed(SIZES, 5)
    tables = C.q5_channels_tables(host)
    assert tables["cs"].window >= SIZES["catalog_sales"] // 2
    db = _ref_db(host)
    for date in ("2000-08-11", "2000-08-25"):
        *cols, of, _pairs = _raw(tables, date, 1000)
        rows = [[int(v) for v in r] for r in zip(*cols)]
        assert not of and REF.from_served(rows) == _want(db, date, 1000)


def test_window_capacity_is_the_widest_window_rounded_up():
    host = _skewed(ABOVE, 3)
    tables = C.q5_channels_tables(host)
    for side, fact in C._Q5_FACT_TABLES.items():
        widest = _widest(host[fact][0])
        bucket = len(tables[side][0])
        assert tables[side].window == min(bucket, -(-widest // 1024) * 1024)
        # the true rows in date order, the pad rows at the tail
        date = np.asarray(tables[side][0])
        np.testing.assert_array_equal(date[:len(host[fact][0])],
                                      np.sort(host[fact][0]))
        assert not date[len(host[fact][0]):].any()


def test_a_capacity_one_short_raises_rather_than_answers(monkeypatch):
    """A slice one row short of the widest window: the query over that
    window fails with the overflow error, and one over a thinner window
    still answers."""
    monkeypatch.setattr(C, "_window_capacity", lambda widest, b: widest - 1)
    resident.REGISTRY.clear()
    sizes = dict(SIZES, web_returns=600)
    dates = tpcds.gen_q5_db(sizes, 4)["store_sales"][0]
    d = np.sort(dates)
    first = d[np.argmax(np.searchsorted(d, d + tpcds.Q5_WINDOW_DAYS)
                        - np.arange(len(d)))]
    day = int(first) - tpcds.D_DATE_SK0 + tpcds.D_DATE0
    widest = str(np.datetime64("1970-01-01") + day)
    from spark_rapids_tpu.models import run_catalog_query
    params = {"sizes": sizes, "db_seed": 4, "limit": 1000}
    try:
        with pytest.raises(RuntimeError, match="overflow"):
            run_catalog_query("tpcds_q5_channels",
                              dict(params, sales_date=widest))
        run_catalog_query("tpcds_q5_channels",
                          dict(params, sales_date="2003-07-01"))
    finally:
        resident.REGISTRY.clear()


def test_the_map_stage_reads_slices_not_buckets(host):
    """The lowered map stage: store_sales' columns feed the binary
    search's loop and the slice alone, and every ``IsIn`` compare runs
    over a slice's rows."""
    from spark_rapids_tpu.plan.compiler import CompiledStage
    tables = C.q5_channels_tables(host)
    shape = _shape(SIZES, tables)
    plan = C.q5_channels_map_plan(
        shape["outlets"], shape["ids"], shape["item_bits"],
        shape["join_capacity"], shape["window_days"],
        exchange_slots=shape["exchange_slots"], windows=shape["windows"])
    stage = CompiledStage(plan)
    args, _parts, _b = stage._bind_args(
        {**tables, "q": (np.int32(tpcds.q5_day("2000-08-23")),)})
    jaxpr = jax.make_jaxpr(stage._fused_callable())(*args).jaxpr
    ss = set(jaxpr.invars[:4])
    assert {e.primitive.name for e in jaxpr.eqns
            if ss & {v for v in e.invars if hasattr(v, "count")}} == {
                "scan", "dynamic_slice"}
    caps = set(dict(shape["windows"]).values())
    assert caps == {1024}
    assert {e.invars[0].aval.shape for e in jaxpr.eqns
            if e.primitive.name == "eq"} == {(1024,)}
    assert shape["join_capacity"] == 1024


def test_q3_and_store_channel_digests_stay():
    assert C.q3_plan(10_957, 2, 16, 3).digest == "f7f4e7f97573f7b3"
    assert C.q5_pipeline(8, 4096).digest == "fd7f756823d628a3"


def test_a_served_query_counts_what_it_pruned():
    prior = obs.is_enabled()
    obs.enable()
    resident.REGISTRY.clear()

    def pruned():
        series = obs.PRUNED_ROWS.snapshot()["series"]
        return {s["labels"][0]: s["value"] for s in series}
    try:
        before = pruned()
        from spark_rapids_tpu.models import run_catalog_query
        run_catalog_query("tpcds_q5_channels", {
            "sizes": SIZES, "db_seed": 6, "sales_date": DATES[0]})
        after = pruned()
        spans = obs.TRACER.records()
    finally:
        resident.REGISTRY.clear()
        if not prior:
            obs.disable()
    for table in tpcds.Q5_FACTS:
        assert after[table] - before.get(table, 0) == SIZES[table] - 1024
    execute = [s for s in spans if s["name"] == "execute"][-1]["attrs"]
    assert execute["scan_rows"] == 5 * 1024 + SIZES["web_sales"]
    load = [s for s in spans if s["name"] == "table_load"][-1]["attrs"]
    assert load["window_capacity"] == (
        "ss=1024,sr=1024,cs=1024,cr=1024,ws=1024,wr=1024")
