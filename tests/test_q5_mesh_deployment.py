"""TPC-DS q5 (``tpcds_q5_channels``) over a database sharded across
four devices (``chips``): each device holds a contiguous shard of every
fact, the web join's sides meet through Spark's hash exchange
(``plan.ir.Exchange`` over ``parallel.exchange``), and the whole
pipeline is one executable (``plan.compiler.MeshPipeline``).  The
served rows against the plain reference of the benchmark and against
the one-chip path, shards of differing true rows, a planted
partitioning fault, the one-chip lowering of the ``Exchange`` nodes,
the resident registry's bytes a device, and the benchmark cell at toy
size.  On the 8 virtual CPU devices of ``conftest.py``."""

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_tpu import observability as obs
from spark_rapids_tpu.models import resident, run_catalog_query, tpcds
from spark_rapids_tpu.parallel import exchange as ex
from spark_rapids_tpu.plan import catalog as C
from spark_rapids_tpu.plan import ir
from spark_rapids_tpu.plan.compiler import CompiledStage, Padded

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(os.path.join(BENCH, "reference", "tpcds_q5.py"),
            "ref_tpcds_q5_for_mesh")
SIZES = dict(store_sales=20_003, store_returns=2_001, catalog_sales=40_002,
             catalog_returns=4_003, web_sales=12_001, web_returns=1_203,
             date_dim=73_049, store=12, catalog_page=150, web_site=6,
             item=1_000)
DATES = ["1998-08-01", "1999-08-15", "2000-08-23", "2000-08-30",
         "2001-08-07", "2002-08-30", "2002-08-01", "1999-08-29"]
# the first and the last sale day, and a fortnight after every fact's
# last date: no fact holds a row
EDGE_DATES = ["1998-01-02", "2002-12-31", "2003-07-01"]
LIMIT = 1000          # past the toy rollup's 163 rows: every row served


def _mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), ("data",))


def _served(chips, db_seed, date):
    return run_catalog_query("tpcds_q5_channels", {
        "sizes": SIZES, "db_seed": db_seed, "sales_date": date,
        "limit": LIMIT, "chips": chips})


@pytest.mark.parametrize("db_seed", [2002, 7])
def test_mesh_answers_equal_the_reference_and_one_chip(db_seed):
    resident.REGISTRY.clear()
    db = REF.database(dict(SIZES), db_seed)
    for date in DATES + EDGE_DATES:
        mesh, one = _served(4, db_seed, date), _served(1, db_seed, date)
        assert mesh == one, date
        want = REF.answer({"db": db, "sales_date": date, "limit": LIMIT}, {})
        assert REF.compare(REF.from_served(mesh), want) == {
            "values_differing": 0}, date
    resident.REGISTRY.clear()


def _uneven_tables(host, mesh, shares, ordered=True):
    """``q5_channels_tables(host, mesh)`` with each fact cut at
    ``shares`` of its rows instead of in equal parts, each shard put in
    date order here and the slice sized for the widest shard's window;
    not ``ordered``, each shard in its drawn order and read whole."""
    from spark_rapids_tpu.perf.jit_cache import bucket_rows
    tables = C.q5_channels_tables(host, mesh)
    devices = list(mesh.devices.flat)
    for inp in C.Q5_CHANNEL_INPUTS:
        if not inp.bucket:
            continue
        cols = host[C._Q5_FACT_TABLES[inp.name]]
        rows = len(cols[0])
        edges = [0] + [int(rows * s) for s in np.cumsum(shares)[:-1]] + [rows]
        parts = list(zip(edges[:-1], edges[1:]))
        b = bucket_rows(max(hi - lo for lo, hi in parts))
        sharding = NamedSharding(mesh, P("data"))
        orders = [lo + (np.argsort(cols[0][lo:hi], kind="stable")
                        if ordered else np.arange(hi - lo))
                  for lo, hi in parts]
        widest = max(_widest(cols[0][lo:hi]) for lo, hi in parts)
        tables[inp.name] = Padded(tuple(
            jax.make_array_from_single_device_arrays(
                (b * len(devices),), sharding,
                [jax.device_put(np.concatenate(
                    [c[o], np.full(b - len(o), spec.pad, c.dtype)]), d)
                 for d, o in zip(devices, orders)])
            for spec, c in zip(inp.columns, cols)), rows,
            [hi - lo for lo, hi in parts],
            window=C._window_capacity(widest, b) if ordered else None)
    return tables


def _widest(dates):
    """The most of ``dates`` in any window of the query's days."""
    d = np.sort(dates)
    return int((np.searchsorted(d, d + tpcds.Q5_WINDOW_DAYS) -
                np.arange(len(d))).max())


def _window_rows(dates, date):
    """Rows of a fact's date keys inside SALES_DATE's fortnight."""
    lo = tpcds.q5_day(date) - tpcds.D_DATE0 + tpcds.D_DATE_SK0
    return int(((dates >= lo) & (dates < lo + tpcds.Q5_WINDOW_DAYS)).sum())


def _mesh_shape(tables):
    sizes = tpcds.q5_sizes(SIZES)
    return C.q5_channels_shape(sizes, tpcds.q5_dim_ids(sizes),
                               tpcds.Q5_WINDOW_DAYS, 4, C.q5_windows(tables))


def _raw_mesh(tables, date, mesh, capacity=None):
    """The mesh pipeline's outputs over ``tables`` as numpy arrays, and
    the send counts."""
    *out, sent = C.run_q5_channels(
        tables, _mesh_shape(tables), tpcds.q5_day(date), LIMIT, mesh=mesh,
        capacity=capacity)
    return [np.asarray(a) for a in out] + [sent]


def _run_mesh(tables, date, mesh, capacity=None):
    *cols, of, pairs, sent = _raw_mesh(tables, date, mesh, capacity)
    rows = [[int(v) for v in r] for r in zip(*cols)]
    return REF.from_served(rows), bool(of), int(pairs), sent


def test_shards_of_differing_rows_give_the_same_answers():
    """The largest shard sends each chip more than the slot the shape
    sizes for equal quarters: the runner's capacity retry runs it again
    at twice the slots, and the answers are the same."""
    from spark_rapids_tpu.models import _q5_mesh_run
    host = tpcds.gen_q5_db(SIZES, 2002)
    mesh = _mesh()
    tables = _uneven_tables(host, mesh, [0.1, 0.2, 0.3, 0.4])
    assert tables["ss"].shard_rows == (2000, 4000, 6001, 8002)
    run = _q5_mesh_run(_mesh_shape(tables), LIMIT, mesh)
    db = REF.database(dict(SIZES), 2002)
    for date in DATES[:3]:
        (out, sent, over), capacity = run(tables, tpcds.q5_day(date))
        *cols, of, pairs = out
        assert not over and not bool(of) and capacity == 2048
        # every return of the fortnight finds its sale
        returns = _window_rows(host["web_returns"][0], date)
        assert int(pairs) == returns > 0
        rows = [[int(v) for v in r]
                for r in zip(*(np.asarray(c) for c in cols))]
        assert REF.from_served(rows) == REF.answer(
            {"db": db, "sales_date": date, "limit": LIMIT}, {})
        # every row of web_sales and each return of the fortnight was
        # sent once, pad rows none
        assert int(sent["web_sales"].sum()) == SIZES["web_sales"]
        assert int(sent["web_returns"].sum()) == returns
        assert sent["web_sales"].shape == (4, 4)
        assert int(sent["web_sales"].max()) > 1024


def test_mesh_slices_answer_as_the_whole_shards():
    """Each chip's slice of its date-ordered shard against the same
    shards in their drawn order, read whole: the same arrays."""
    host = tpcds.gen_q5_db(SIZES, 2002)
    mesh = _mesh()
    sliced = C.q5_channels_tables(host, mesh)
    whole = _uneven_tables(host, mesh, [0.25] * 4, ordered=False)
    assert C.q5_windows(whole) == {}
    # a quarter of the toy's small facts fits in one slice
    assert [sliced[s].window * 4 < len(sliced[s][0])
            for s in C._Q5_FACT_TABLES] == [1, 0, 1, 0, 1, 0]
    db = REF.database(dict(SIZES), 2002)
    for date in DATES[:2] + EDGE_DATES:
        got, want = (_raw_mesh(t, date, mesh) for t in (sliced, whole))
        for a, b in zip(got[:-1], want[:-1]):
            np.testing.assert_array_equal(a, b)
        rows = [[int(v) for v in r] for r in zip(*got[:-3])]
        assert REF.from_served(rows) == REF.answer(
            {"db": db, "sales_date": date, "limit": LIMIT}, {})


def test_each_chip_orders_its_shard_and_the_widest_sizes_the_slice():
    host = tpcds.gen_q5_db(SIZES, 2002)
    hot = tpcds.q5_day("2000-08-25") - tpcds.D_DATE0 + tpcds.D_DATE_SK0
    host["store_sales"][0][:len(host["store_sales"][0]) // 8] = hot
    tables = C.q5_channels_tables(host, _mesh())
    for side, fact in C._Q5_FACT_TABLES.items():
        t, dates = tables[side], host[fact][0]
        held = np.asarray(t[0]).reshape(4, -1)
        widest, lo = 0, 0
        for shard, n in zip(held, t.shard_rows):
            np.testing.assert_array_equal(shard[:n], np.sort(dates[lo:lo + n]))
            assert not shard[n:].any()
            widest = max(widest, _widest(dates[lo:lo + n]))
            lo += n
        assert t.window == min(held.shape[1], -(-widest // 1024) * 1024)
    # the first shard holds all of the hot day's rows, and sizes the slice
    assert tables["ss"].window == 3072


def test_a_side_partitioned_with_another_seed_reads_as_differing(
        monkeypatch):
    """A planted fault: web_sales is partitioned with murmur3's seed 7,
    web_returns with Spark's 42, so most returns look for their sale on
    a chip that does not hold it."""
    from spark_rapids_tpu.columns import dtypes
    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.ops.hash import murmur3_32
    real, calls = ex.hash_partitions, []

    def planted(keys, valid, n_parts):
        calls.append(1)
        if len(calls) % 2:                     # web_returns: as it is
            return real(keys, valid, n_parts)
        rows = int(keys[0].shape[0])
        h = murmur3_32([Column(dtypes.from_numpy(k.dtype), rows, data=k)
                        for k in keys], seed=7).data
        return jnp.where(valid, h % n_parts, n_parts).astype(jnp.int32)

    host = tpcds.gen_q5_db(SIZES, 2002)
    mesh = _mesh()
    tables = C.q5_channels_tables(host, mesh)
    monkeypatch.setattr(ex, "hash_partitions", planted)
    # slots of 2,048 rows: a plan no other test compiles, so the planted
    # partitioning is traced into an executable of its own
    rows, _of, pairs, _sent = _run_mesh(tables, "2000-08-23", mesh, 2048)
    assert calls and pairs < SIZES["web_returns"] // 2
    want = REF.answer({"db": REF.database(dict(SIZES), 2002),
                       "sales_date": "2000-08-23", "limit": LIMIT}, {})
    assert REF.compare(rows, want)["values_differing"] > 0


def test_hash_partitions_are_sparks_murmur3_pmod():
    from spark_rapids_tpu.columns import dtypes
    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.ops.hash import murmur3_32
    rng = np.random.default_rng(3)
    item = jnp.asarray(rng.integers(1, 204_001, 4096, dtype=np.int32))
    order = jnp.asarray(rng.integers(1, 6_000_105, 4096, dtype=np.int32))
    valid = jnp.asarray(rng.random(4096) < 0.9)
    got = np.asarray(ex.hash_partitions([item, order], valid, 4))
    h = np.asarray(murmur3_32([Column(dtypes.INT32, 4096, data=item),
                               Column(dtypes.INT32, 4096, data=order)],
                              seed=42).data).astype(np.int64)
    want = np.where(np.asarray(valid), h % 4, 4)
    np.testing.assert_array_equal(got, want)
    assert set(np.unique(got)) == {0, 1, 2, 3, 4}


def _rewrite(x, subst):
    if isinstance(x, ir.Col) and x.name in subst:
        return subst[x.name]
    if isinstance(x, tuple):
        return tuple(_rewrite(v, subst) for v in x)
    if isinstance(x, (ir.Expr, ir.Node)) and dataclasses.is_dataclass(x):
        return dataclasses.replace(x, **{
            f.name: _rewrite(getattr(x, f.name), subst)
            for f in dataclasses.fields(x)})
    return x


def _without_exchanges(plan):
    """The plan with each Exchange taken out and its outputs read as
    what one chip makes of them: its columns and its ``valid``."""
    subst, nodes = {}, []
    for n in plan.nodes:
        if isinstance(n, ir.Exchange):
            subst.update({f"{n.prefix}.{c}": ir.Col(c) for c in n.columns})
            subst[f"{n.prefix}.valid"] = n.valid
        else:
            nodes.append(_rewrite(n, subst))
    return dataclasses.replace(plan, nodes=tuple(nodes))


def _map_plan(sizes):
    sizes = tpcds.q5_sizes(sizes)
    shape = C.q5_channels_shape(sizes, tpcds.q5_dim_ids(sizes),
                                tpcds.Q5_WINDOW_DAYS)
    return C.q5_channels_map_plan(
        shape["outlets"], shape["ids"], shape["item_bits"],
        shape["join_capacity"], shape["window_days"],
        exchange_slots=shape["exchange_slots"])


def test_one_chip_map_stage_is_the_plan_before_the_exchange():
    plan = _map_plan(None)
    assert sum(isinstance(n, ir.Exchange) for n in plan.nodes) == 2
    # the SF10 map stage over facts read whole, the returns' date
    # filter below the join
    assert _without_exchanges(plan).digest == "e72f6a52c56f27a4"


def test_one_chip_lowering_holds_no_collective_and_no_extra_sort():
    plan = _map_plan(SIZES)
    host = tpcds.gen_q5_db(SIZES, 2002)
    tables = C.q5_channels_tables(host)
    inputs = {**tables, "q": (np.int32(tpcds.q5_day("2000-08-23")),)}
    texts = []
    for p in (plan, _without_exchanges(plan)):
        stage = CompiledStage(p)
        args, _parts, _b = stage._bind_args(inputs)
        texts.append(str(jax.make_jaxpr(stage._fused_callable())(*args)))
        hlo = jax.jit(stage._fused_callable()).lower(*args).as_text()
        assert "all_to_all" not in hlo and "all-to-all" not in hlo
    assert texts[0] == texts[1]


def test_resident_bytes_are_the_fullest_devices():
    mesh = _mesh()
    rows = np.arange(4 * 1024, dtype=np.int64)
    sharded = jax.device_put(rows, NamedSharding(mesh, P("data")))
    whole = jax.device_put(np.arange(300, dtype=np.int32),
                           NamedSharding(mesh, P()))
    one = jax.device_put(np.arange(100, dtype=np.int64), jax.devices()[5])
    tables = {"f": Padded((sharded,), 4000, [1000] * 4), "d": (whole,),
              "o": (one,)}
    assert resident.table_bytes(tables) == 1024 * 8 + 300 * 4 + 100 * 8
    reg = resident.ResidentTables(budget_bytes=12_000)
    reg.get(("db", 1), lambda: tables)
    reg.get(("db", 2), lambda: {"d": (whole,)})
    assert reg.keys() == [("db", 1), ("db", 2)]      # 10,000 + 1,200 B


def test_served_mesh_query_counts_the_rows_its_exchange_sent():
    prior = obs.is_enabled()
    obs.enable()
    resident.REGISTRY.clear()

    def sent():
        series = obs.EXCHANGE_ROWS.snapshot()["series"]
        return {s["labels"][0]: s["value"] for s in series}
    try:
        before = sent()
        _served(4, 3, DATES[0])
        after = sent()
        spans = [s for s in obs.TRACER.records() if s["name"] == "execute"]
    finally:
        resident.REGISTRY.clear()
        if not prior:
            obs.disable()
    returns = tpcds.gen_q5_db(SIZES, 3)["web_returns"][0]
    assert after["web_sales"] - before.get("web_sales", 0) == SIZES[
        "web_sales"]
    assert after["web_returns"] - before.get("web_returns", 0) == (
        _window_rows(returns, DATES[0]))
    attrs = spans[-1]["attrs"]
    assert attrs["exchange_capacity"] == 1024
    assert 0 < attrs["exchange_max_dest_rows"] <= 1024


@pytest.mark.skipif(os.environ.get("JAX_PLATFORMS") != "cpu",
                    reason="a rehearsal: set JAX_PLATFORMS=cpu")
def test_the_cell_at_toy_size_reads_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "sf100-q5-mesh4", "--seed", "2147483659", "--seconds", "0.3",
         "--size", "toy"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert result["compared"]["answers_compared"] == result["attempted"]


def test_the_mesh_reference_is_tpcds_q5s_with_a_chips_bytes():
    mesh_ref = _load(os.path.join(BENCH, "reference", "tpcds_q5_mesh.py"),
                     "ref_tpcds_q5_mesh")
    with open(os.path.join(BENCH, "configs",
                           "tpcds-sf100-q5-mesh4.json")) as f:
        sizes = json.load(f)["sizes"]
    params = {"chips": 4, "limit": 20617}
    assert sum(sizes["full"][t] for t in tpcds.Q5_FACTS) == (
        sizes["full"]["rows"])
    assert mesh_ref.min_bytes(sizes["full"], params) == 13_910_219_376 / 4
    assert round(mesh_ref.exchange_bytes(sizes["full"], params)) == (
        199_790_551)
    # every drawn SALES_DATE answered in threads, as tpcds_q5 answers it
    seeds = [3, 2_147_483_659, 77, 91]
    drawn = [mesh_ref.query_params(sizes["toy"], params, s) for s in seeds]
    for seed, q in zip(seeds, drawn):
        inputs = mesh_ref.make_inputs(sizes["toy"], params, seed)
        assert inputs["sales_date"] == q["sales_date"]
        want = REF.answer({"db": REF.database(q["sizes"], q["db_seed"]),
                           "sales_date": q["sales_date"],
                           "limit": 20617}, {})
        assert mesh_ref.answer(inputs, params) == want
        assert mesh_ref.compare(mesh_ref.control_answer(inputs, params),
                                want)["values_differing"] > 0
