#!/usr/bin/env python3
"""The quickest proof that the served query path still starts on the chip.

One process, one TPU v5e chip (``--chips 4`` for the mesh path).  It
drives the system through the entry points a user calls — the resident
``QueryServer`` (submit/poll as two tenants) over the TPC-DS catalog at
SF10 (``store_sales`` = 28,800,991 rows, TPC-DS spec table 3-2), the
JCUDF row conversion at the reference benchmark's shape (212 columns x
2^19 rows) and the
eager group-by / join operators — and compares every
answer with a plain numpy reference computed here over the same seeded
arrays.  Any phase failing, any answer differing, any warm call that
compiles: non-zero exit.  No timing printed here is a benchmark number.

    python chip_smoke.py                   # one chip, full size
    python chip_smoke.py --chips 4         # mesh phase only, four chips
    python chip_smoke.py --phases rowconv  # one chip, that phase only
    JAX_PLATFORMS=cpu python chip_smoke.py --size toy     # CPU rehearsal

The last line of standard output is one JSON object naming the device
the run really used.  Without a TPU the script exits non-zero before any
phase runs, unless the caller itself asked for the CPU rehearsal.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# fact rows: TPC-DS SF10 store_sales (spec table 3-2); q5's 14-of-60-day
# window matches ~6.7 M pairs, hence the join capacity.  ops = the
# bench_all.py shapes (rows, groups, join keyspace).
# q5_tables: the template's q5 database (None = SF10's row counts,
# models/tpcds.Q5_SF10; toy = the benchmark configuration's toy sizes)
SIZES = {
    "full": dict(rows=28_800_991, join_capacity=1 << 23,
                 rowconv_rows=1 << 19, rowconv_schemas=8,
                 ops=(10_000_000, 10_000, 1_000_000), xchg_rows=1 << 22,
                 q5_tables=None),
    "toy": dict(rows=4096, join_capacity=1 << 12, rowconv_rows=4096,
                rowconv_schemas=4,
                ops=(1 << 16, 100, 1 << 12), xchg_rows=1 << 12,
                q5_tables=dict(store_sales=20_000, store_returns=2_000,
                               catalog_sales=40_000,
                               catalog_returns=4_000, web_sales=12_000,
                               web_returns=1_200, date_dim=73_049,
                               store=12, catalog_page=150, web_site=6,
                               item=1_000)),
}
Q5_DB_SEED = 2002
# (rows, columns, nulls in every column, every fifth column a
# decimal128): the schemas around the 212-column phase.  On a TPU the
# Pallas tile kernel takes the rows its VMEM holds (599 cycled columns
# is the widest) and XLA's word assembly the wider ones; the toy size
# runs the first four (the wide ones compile for a minute on a CPU).
ROWCONV_SCHEMAS = ((7, 3, True, False), (100, 8, True, False),
                   (1000, 20, True, True), (1024, 33, False, True),
                   (5000, 212, True, False), (3000, 500, True, False),
                   (3000, 599, True, False), (3000, 1000, True, False))
Q5_STORES = 8
Q5_DAYS = 60
Q3 = dict(items=128, brands=16, manufact=3)
Q3_BASE, Q3_YEARS, Q3_MONTH = 10_957, 2, 11
Q9_BUCKETS = ((1, 20), (21, 40), (41, 60), (61, 80), (81, 100))
SENTINEL32 = 2 ** 31 - 1


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(**kv) -> None:
    print(json.dumps(kv, sort_keys=True), flush=True)


# --------------------------------------------------------------- counters


class Compiles:
    """New executables built in this process: the stage compiler's own
    counter (perf/jit_cache) and every XLA backend compile JAX reports
    (plain ``jax.jit`` pipelines such as q9 never touch the former)."""

    def __init__(self):
        import jax.monitoring
        self.backend = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend += 1

    def snap(self):
        from spark_rapids_tpu.perf.jit_cache import CACHE
        return CACHE.stats()["compiles"], self.backend

    def since(self, snap):
        now = self.snap()
        return {"jit_cache": now[0] - snap[0], "backend": now[1] - snap[1]}


def device_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}


def stage_outcomes():
    from spark_rapids_tpu import observability as obs
    fam = obs.METRICS.snapshot().get("srt_stage_fusion_total", {})
    return {tuple(s["labels"]): s["value"] for s in fam.get("series", [])}


def timed_twice(run, compiles: Compiles, seeds):
    """``run(seed)`` once cold and once warm; the warm call may build
    nothing.  ``run`` returns the seconds the system itself took (data
    generation and the numpy reference are outside them).  Returns the
    per-call records."""
    out = []
    for label, seed in zip(("cold", "warm"), seeds):
        snap = compiles.snap()
        seconds = run(seed)
        out.append({"call": label, "seconds": round(seconds, 3),
                    "compiles": compiles.since(snap)})
    warm = out[1]["compiles"]
    check(warm["jit_cache"] == 0 and warm["backend"] == 0,
          f"warm call compiled: {warm}")
    return out


# ------------------------------------------------------------ references


def ref_q5(d, stores):
    """q5-shape over host arrays: facts joined to the date window,
    per-store exact int64 sums, ordered by store id."""
    import numpy as np
    window = np.asarray(d.d_date)
    st_id = np.asarray(d.st_id)
    sales = np.zeros(stores, np.int64)
    rets = np.zeros(stores, np.int64)
    profit = np.zeros(stores, np.int64)
    seen = np.zeros(stores, np.int64)
    s_in = np.isin(np.asarray(d.s_date), window)
    r_in = np.isin(np.asarray(d.r_date), window)
    s_store, r_store = np.asarray(d.s_store), np.asarray(d.r_store)
    s_price, s_profit = np.asarray(d.s_price), np.asarray(d.s_profit)
    r_amt, r_loss = np.asarray(d.r_amt), np.asarray(d.r_loss)
    for k in range(stores):
        ms = s_in & (s_store == k)
        mr = r_in & (r_store == k)
        sales[k] = s_price[ms].sum(dtype=np.int64)
        rets[k] = r_amt[mr].sum(dtype=np.int64)
        profit[k] = (s_profit[ms].sum(dtype=np.int64)
                     - r_loss[mr].sum(dtype=np.int64))
        seen[k] = int(ms.sum()) + int(mr.sum())
    key = np.where(seen > 0, st_id, SENTINEL32)
    order = np.argsort(key, kind="stable")
    pairs = int(s_in.sum()), int(r_in.sum())
    return [[int(key[i]), int(sales[i]), int(rets[i]), int(profit[i])]
            for i in order], pairs


def ref_q3(d):
    """q3-shape: month/manufacturer filters through the dense dims,
    (year, brand) exact sums, ORDER BY year, sum DESC, brand."""
    import numpy as np
    brands = Q3["brands"]
    s_item = np.asarray(d.s_item)
    di = np.asarray(d.s_date) - Q3_BASE
    d_year = np.asarray(d.d_year)
    year_idx = d_year[di] - d_year[0]
    keep = ((np.asarray(d.d_moy)[di] == Q3_MONTH)
            & (np.asarray(d.i_manufact)[s_item] == Q3["manufact"])
            & (year_idx >= 0) & (year_idx < Q3_YEARS))
    gid = (year_idx[keep] * brands + np.asarray(d.i_brand)[s_item[keep]])
    n_groups = Q3_YEARS * brands
    sums = np.zeros(n_groups, np.int64)
    np.add.at(sums, gid, np.asarray(d.s_price)[keep])
    cnts = np.bincount(gid, minlength=n_groups)
    live = sorted((g // brands + int(d_year[0]), -int(sums[g]), g % brands)
                  for g in range(n_groups) if cnts[g] > 0)
    return [[y, b, -negs] for y, negs, b in live], int(cnts.sum())


def ref_q9(quantity, price, profit):
    import numpy as np
    q, p, n = (np.asarray(a) for a in (quantity, price, profit))
    rows = []
    for lo, hi in Q9_BUCKETS:
        m = (q >= lo) & (q <= hi)
        c = int(m.sum())
        rows.append((c, p[m].sum(dtype=np.int64) / max(c, 1),
                     n[m].sum(dtype=np.int64) / max(c, 1)))
    return rows


# ---------------------------------------------------------- phase: serve


def bench_reference(name: str):
    """``benchmark/reference/<name>.py``, the benchmark's plain numpy
    reference (it imports nothing of the program)."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmark", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location("ref_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def phase_serve(rows: int, cap: int, seed0: int, q5_tables,
                compiles: Compiles) -> None:
    """q5_fused, q3_fused, q9 through QueryServer.submit/poll as two
    tenants, each once cold and once warm (another seed, same shapes,
    result cache off so the device really runs); then the template's
    q5 (``tpcds_q5_channels``) once, loading its database onto the
    device, every row of its rollup against the benchmark's numpy
    reference."""
    import itertools
    import math

    from spark_rapids_tpu.models import tpcds
    from spark_rapids_tpu.perf import result_cache
    from spark_rapids_tpu.server import (ServerConfig, ensure_server,
                                         stop_server)

    check(not result_cache.cache_enabled(), "result cache must be off")
    # a first-touch compile at this size outlasts the 30 s default
    # silent-worker threshold; the deployment's own knob, not a bypass
    server, created = ensure_server(ServerConfig(
        max_concurrency=2, hang_s=900.0))
    check(created, "a query server was already running")
    turn = itertools.cycle(("tenant_a", "tenant_b"))

    def serve(query, params):
        t0 = time.perf_counter()
        qid = server.submit(next(turn), query, params)
        st = server.poll(qid, timeout_s=1100.0)
        seconds = time.perf_counter() - t0
        check(st.get("state") == "done",
              f"{query}: {st.get('state')} {st.get('error')}")
        return st["result"], seconds

    def q5(seed):
        got, seconds = serve("tpcds_q5_fused", {
            "rows": rows, "stores": Q5_STORES, "seed": seed,
            "join_capacity": cap})
        want, pairs = ref_q5(tpcds.gen_q5(
            rows=rows, stores=Q5_STORES, days=Q5_DAYS, seed=seed),
            Q5_STORES)
        check(max(pairs) <= cap, f"reference pairs {pairs} > {cap}")
        check(got == want, f"q5 seed {seed}: {got[:2]} != {want[:2]}")
        return seconds

    def q3(seed):
        got, seconds = serve("tpcds_q3_fused", {
            "rows": rows, "seed": seed, **Q3})
        want, total = ref_q3(tpcds.gen_q3(
            rows=rows, items=Q3["items"], days=730,
            brands=Q3["brands"], seed=seed))
        live = [r for r in got[:-1] if r[0] != SENTINEL32]
        check(live == want and got[-1] == [total],
              f"q3 seed {seed}: {live[:2]} != {want[:2]}")
        return seconds

    def q9(seed):
        got, seconds = serve("tpcds_q9", {"rows": rows, "seed": seed})
        want = ref_q9(*tpcds.gen_q9(rows=rows, seed=seed))
        check(len(got) == len(want), "q9 row count")
        for g, w in zip(got, want):
            # counts exact; the averages are f64 divides the chip
            # emulates, so they agree to rounding, not to the bit
            check(g[0] == w[0]
                  and math.isclose(g[1], w[1], rel_tol=1e-9)
                  and math.isclose(g[2], w[2], rel_tol=1e-9),
                  f"q9 seed {seed}: {g} != {w}")
        return seconds

    try:
        for name, run, seeds in (("tpcds_q5_fused", q5, (5, 105)),
                                 ("tpcds_q3_fused", q3, (3, 103)),
                                 ("tpcds_q9", q9, (9, 109))):
            before = stage_outcomes()
            calls = timed_twice(
                run, compiles, [seed0 + x for x in seeds])
            after = stage_outcomes()
            ran = sorted("%s:%s" % k for k, v in after.items()
                         if v > before.get(k, 0))
            say(phase="serve", query=name, rows=rows, calls=calls,
                stages=ran, device=device_bytes())
        ref = bench_reference("tpcds_q5")
        sizes = dict(q5_tables or tpcds.Q5_SF10, db_seed=Q5_DB_SEED)
        # every row of the rollup, so each channel's ids are compared
        every = {"limit": 2 ** 31 - 1}
        params = ref.query_params(sizes, every, seed0 + 5)
        got, seconds = serve("tpcds_q5_channels", params)
        bad = ref.compare(ref.from_served(got), ref.answer(
            ref.make_inputs(sizes, every, seed0 + 5), every))
        check(bad == {"values_differing": 0},
              f"q5 channels {params['sales_date']}: {bad}")
        say(phase="serve", query="tpcds_q5_channels",
            rows=sum(sizes[t] for t in tpcds.Q5_FACTS),
            sales_date=params["sales_date"],
            calls=[{"call": "cold", "seconds": round(seconds, 3)}],
            device=device_bytes())
    finally:
        stop_server()


# -------------------------------------------------------- phase: rowconv


def phase_rowconv(rows: int, compiles: Compiles) -> None:
    """convert_to_rows -> convert_from_rows round trip at the reference
    benchmark's shape (benchmarks/row_conversion.cpp: 212 cycled
    fixed-width columns) on the engine a fixed-width schema has per
    direction on this backend; both directions must produce numpy's
    bytes."""
    import jax
    import numpy as np

    import bench_impl
    from spark_rapids_tpu.ops import row_conversion as RC
    from spark_rapids_tpu.perf.jit_cache import CACHE

    table = bench_impl._make_table(rows, 212)
    schema = [c.dtype for c in table.columns]
    layout = RC.compute_layout(schema)
    row_size = (layout[2] + 7) // 8 * 8
    want = bench_impl._numpy_to_rows_reference(table, layout)
    originals = [c.to_numpy() for c in table.columns]

    def round_trip(_seed):
        t0 = time.perf_counter()
        rows_col = RC.convert_to_rows(table)
        back = RC.convert_from_rows(rows_col, schema)
        jax.block_until_ready([c.data for c in back.columns])
        seconds = time.perf_counter() - t0
        words = rows_col.children[0].data
        got = np.asarray(words).view(np.uint8).reshape(rows, row_size)
        check(np.array_equal(got, want), "to-rows bytes != numpy bytes")
        check(back.num_rows == rows, "from-rows row count")
        for i, (c, orig) in enumerate(zip(back.columns, originals)):
            check(c.to_numpy().tobytes() == orig.tobytes(),
                  f"from-rows column {i} ({c.dtype.kind}) differs")
        return seconds

    calls = timed_twice(round_trip, compiles, (0, 0))
    built = {k: v["misses"] for k, v in CACHE.stats()["kernels"].items()
             if k.startswith(("pallas.", "row_conversion."))}
    # to-rows is the Pallas tile kernel on a TPU, XLA's word assembly
    # on a rehearsal; from-rows is the word slices everywhere
    to_rows = ("pallas.to_rows" if jax.default_backend() == "tpu"
               else "row_conversion.to_rows")
    check(built.get(to_rows) and built.get("row_conversion.from_rows"),
          f"an engine did not run: {built}")
    say(phase="rowconv", rows=rows, columns=212, row_bytes=row_size,
        calls=calls, kernels=built, device=device_bytes())


def schema_table(rows: int, columns: int, nulls: bool, decimals: bool):
    """A seeded table of the cycled fixed-width dtypes over the whole
    range of each, optionally with nulls in every column and a
    decimal128 in every fifth place."""
    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_tpu.columns import dtypes
    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.columns.table import Table

    cycle = [dtypes.INT64, dtypes.INT32, dtypes.FLOAT64, dtypes.FLOAT32,
             dtypes.INT16, dtypes.INT8, dtypes.BOOL8,
             dtypes.TIMESTAMP_MICROS]
    rng = np.random.default_rng(rows + columns)
    cols = []
    for i in range(columns):
        dt = cycle[i % len(cycle)]
        valid = (rng.integers(0, 2, rows).astype(np.uint8) if nulls
                 else None)
        if decimals and i % 5 == 4:
            limbs = rng.integers(-2 ** 31, 2 ** 31, (rows, 4))
            cols.append(Column(
                dtypes.decimal128(-2), rows,
                data=jnp.asarray(limbs.astype(np.int32)),
                validity=None if valid is None else jnp.asarray(valid)))
            continue
        if dt.kind == "float32":
            arr = rng.normal(size=rows).astype(np.float32)
        elif dt.kind == "float64":
            arr = rng.normal(size=rows)
        elif dt.kind == "bool8":
            arr = rng.integers(0, 2, rows).astype(np.uint8)
        else:
            info = np.iinfo(dt.np_dtype)
            arr = rng.integers(info.min, info.max, rows,
                               endpoint=True).astype(dt.np_dtype)
        cols.append(Column.from_numpy(arr, validity=valid, dtype=dt))
    return Table(cols)


def phase_rowconv_schemas(count: int) -> None:
    """The round trip over the first ``count`` of ROWCONV_SCHEMAS:
    to-rows must give numpy's bytes on the engine the schema has on
    this backend, and every column must come back with its validity:
    a vector where the column has a null, none where it has not."""
    import jax
    import numpy as np

    import bench_impl
    from spark_rapids_tpu import observability as obs
    from spark_rapids_tpu.ops import row_conversion as RC

    def engines():
        fam = obs.METRICS.snapshot().get("srt_row_conversion_total", {})
        return {":".join(s["labels"]): s["value"]
                for s in fam.get("series", [])}

    for rows, columns, nulls, decimals in ROWCONV_SCHEMAS[:count]:
        table = schema_table(rows, columns, nulls, decimals)
        schema = [c.dtype for c in table.columns]
        layout = RC.compute_layout(schema)
        row_size = (layout[2] + 7) // 8 * 8
        want = bench_impl._numpy_to_rows_reference(table, layout)
        before = engines()
        rows_col = RC.convert_to_rows(table)
        back = RC.convert_from_rows(rows_col, schema)
        ran = sorted(k for k, v in engines().items()
                     if v > before.get(k, 0))
        to_rows = ("pallas" if jax.default_backend() == "tpu"
                   and RC._tile_fits(schema, row_size) else "words")
        what = f"{rows} x {columns} (nulls {nulls}, decimals {decimals})"
        check(ran == ["from_rows:words", "to_rows:" + to_rows],
              f"{what}: engines {ran}")
        got = np.asarray(rows_col.children[0].data).view(np.uint8)
        check(np.array_equal(got.reshape(rows, row_size), want),
              f"{what}: to-rows bytes != numpy bytes")
        for i, (b, c) in enumerate(zip(back.columns, table.columns)):
            check(np.asarray(b.data).tobytes()
                  == np.asarray(c.data).tobytes(),
                  f"{what}: from-rows column {i} ({c.dtype.kind}) differs")
            check(np.array_equal(np.asarray(b.valid_mask()),
                                 np.asarray(c.valid_mask())),
                  f"{what}: from-rows validity {i} differs")
            check(b.has_validity == (c.null_count() > 0),
                  f"{what}: from-rows column {i} has a validity vector "
                  f"without a null, or a null without one")
        say(phase="rowconv_schema", rows=rows, columns=columns,
            nulls=nulls, decimals=decimals, row_bytes=row_size,
            engines=ran)


# ------------------------------------------------------------ phase: ops


def phase_ops(n: int, groups: int, keyspace: int, seed0: int,
              compiles: Compiles) -> None:
    """The eager group-by and inner join at the bench_all.py shapes;
    on a chip both take their device twins (ops/groupby._group_ids,
    ops/joins device_sort — pinned, so calibration cannot route the
    smoke around the engine it is here to start)."""
    import jax
    import numpy as np

    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.columns.table import Table
    from spark_rapids_tpu.ops import groupby as gb
    from spark_rapids_tpu.ops import joins

    on_chip = jax.default_backend() != "cpu"

    def groupby(seed):
        rng = np.random.default_rng(seed)
        k = rng.integers(0, groups, n, dtype=np.int64)
        v = rng.integers(-1000, 1000, n).astype(np.float64)
        keys, vals = Table([Column.from_numpy(k)]), Column.from_numpy(v)
        t0 = time.perf_counter()
        out = gb.groupby_aggregate(keys, [vals, vals], [gb.SUM, gb.COUNT])
        jax.block_until_ready([c.data for c in out.columns])
        seconds = time.perf_counter() - t0
        got_k = out.columns[0].to_numpy()
        order = np.argsort(got_k, kind="stable")
        want_c = np.bincount(k, minlength=groups)
        want_s = np.bincount(k, weights=v, minlength=groups)
        present = np.nonzero(want_c)[0]
        check(np.array_equal(got_k[order], present), "group keys")
        check(np.array_equal(out.columns[2].to_numpy()[order],
                             want_c[present]), "group counts")
        # integer-valued doubles below 2^53: every order sums exactly
        check(np.array_equal(out.columns[1].to_numpy()[order],
                             want_s[present]), "group sums")
        return seconds

    def join(seed):
        rng = np.random.default_rng(seed)
        lk = rng.integers(0, keyspace, n, dtype=np.int64)
        left = Table([Column.from_numpy(lk)])
        right = Table([Column.from_numpy(
            np.arange(keyspace, dtype=np.int64))])
        t0 = time.perf_counter()
        li, ri = joins.sort_merge_inner_join(left, right)
        jax.block_until_ready((li, ri))
        seconds = time.perf_counter() - t0
        li, ri = np.asarray(li), np.asarray(ri)
        # the right side is the identity on [0, keyspace): every left
        # row matches exactly once, at right row == its key
        check(li.shape[0] == n, f"join pairs {li.shape[0]} != {n}")
        check(np.array_equal(np.sort(li), np.arange(n, dtype=li.dtype)),
              "join left indices are not a permutation")
        check(np.array_equal(lk[li], ri.astype(np.int64)),
              "join right index != left key")
        return seconds

    calls = timed_twice(groupby, compiles, (seed0, seed0 + 100))
    say(phase="ops", op="groupby_aggregate", rows=n, groups=groups,
        engine="device" if on_chip else "host", calls=calls,
        device=device_bytes())
    os.environ["SPARK_RAPIDS_TPU_PATH_JOIN_INNER"] = "device_sort"
    try:
        calls = timed_twice(join, compiles, (seed0 + 1, seed0 + 101))
    finally:
        del os.environ["SPARK_RAPIDS_TPU_PATH_JOIN_INNER"]
    say(phase="ops", op="sort_merge_inner_join", left_rows=n,
        right_rows=keyspace, engine="device_sort", calls=calls,
        device=device_bytes())


# ----------------------------------------------------- phase: four chips


def phase_mesh(rows: int, cap: int, xchg_rows: int, seed0: int,
               compiles: Compiles) -> None:
    """The mesh path on four chips: q5 with facts row-sharded, dims
    replicated and a psum reduce, and the all-to-all hash exchange —
    both against the single-chip answer / a numpy count."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from spark_rapids_tpu.models import tpcds
    from spark_rapids_tpu.models.query import \
        make_distributed_hash_aggregate

    n = 4
    devices = jax.devices()[:n]
    check(len(devices) == n, f"need {n} devices, have {len(devices)}")
    mesh = Mesh(np.array(devices), ("data",))
    shard = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())

    def spread(arr, name):
        per = [s.data.nbytes for s in arr.addressable_shards]
        check(len({s.device for s in arr.addressable_shards}) == n
              and max(per) <= arr.nbytes // n + 64,
              f"{name}: shards {per} are not a quarter each")
        return per

    d = tpcds.gen_q5(rows=rows, stores=Q5_STORES, days=Q5_DAYS,
                     seed=seed0 + 5)
    host = [np.asarray(a) for a in d]
    want, _pairs = ref_q5(d, Q5_STORES)

    # shard_map splits rows evenly: pad the facts up to a multiple of
    # the mesh with rows no date matches (the stage IR's own pad rule)
    def padded(cols):
        extra = -len(cols[0]) % n
        out = [np.concatenate([cols[0], np.full(extra, -1, cols[0].dtype)])]
        out += [np.concatenate([c, np.zeros(extra, c.dtype)])
                for c in cols[1:]]
        return out

    snap = compiles.snap()
    t0 = time.perf_counter()
    single = tpcds.make_q5(Q5_STORES, cap)(d)
    jax.block_until_ready(single)
    t_single = time.perf_counter() - t0
    check(not bool(single[4]), "single-chip q5 overflowed")
    del d

    facts = [jax.device_put(a, shard)
             for a in padded(host[0:4]) + padded(host[4:8])]
    dims = [jax.device_put(a, rep) for a in host[8:10]]
    fact_bytes = [spread(a, f"q5 fact {i}")[0] for i, a in enumerate(facts)]
    q5m = tpcds.make_q5_multichip(mesh, Q5_STORES, cap)
    t0 = time.perf_counter()
    multi = q5m(*facts, *dims)
    jax.block_until_ready(multi)
    t_multi = time.perf_counter() - t0
    check(not bool(multi[4]), "four-chip q5 overflowed")
    for name, a, b in zip(("store", "sales", "returns", "profit"),
                          single, multi):
        check(np.asarray(a).tobytes() == np.asarray(b).tobytes(),
              f"q5 {name}: four chips != one chip")
    got = [[int(v) for v in row]
           for row in zip(*(np.asarray(a) for a in multi[:4]))]
    check(got == want, f"q5 four chips != numpy: {got[:2]} {want[:2]}")
    say(phase="mesh", query="q5_multichip", rows=rows,
        seconds_single=round(t_single, 3), seconds_four=round(t_multi, 3),
        bytes_per_device=sum(fact_bytes), compiles=compiles.since(snap),
        device=device_bytes())
    del facts, dims, single, multi

    # all-to-all exchange + bucketed aggregate (as __graft_entry__
    # drives it): per-bucket totals over the devices == numpy's count
    buckets = 4096
    xcap = xchg_rows // (n * n) * 5 // 4 + 64
    step, sharding = make_distributed_hash_aggregate(
        mesh, n_parts=n, num_buckets=buckets, capacity=xcap)
    rng = np.random.default_rng(seed0)
    keys_h = rng.integers(0, 1 << 40, xchg_rows, dtype=np.int64)
    vals_h = (keys_h % 7).astype(np.float32)
    keys = jax.device_put(jnp.asarray(keys_h), sharding)
    vals = jax.device_put(jnp.asarray(vals_h), sharding)
    spread(keys, "exchange keys")
    snap = compiles.snap()
    t0 = time.perf_counter()
    sums, counts, send_counts = step(keys, vals)
    jax.block_until_ready((sums, counts, send_counts))
    t_x = time.perf_counter() - t0
    check(int(np.asarray(send_counts).max()) <= xcap,
          "exchange overflowed its capacity")
    spread(counts, "exchange counts")
    got_c = np.asarray(counts).reshape(n, buckets).sum(axis=0)
    got_s = np.asarray(sums).reshape(n, buckets).sum(axis=0)
    b = (keys_h % buckets).astype(np.int64)
    check(np.array_equal(got_c, np.bincount(b, minlength=buckets)),
          "exchange bucket counts != numpy")
    # small integer values: f32 sums are exact in any order
    check(np.array_equal(got_s, np.bincount(
        b, weights=vals_h, minlength=buckets).astype(np.float32)),
        "exchange bucket sums != numpy")
    say(phase="mesh", query="distributed_hash_aggregate", rows=xchg_rows,
        seconds=round(t_x, 3), compiles=compiles.since(snap),
        device=device_bytes())


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--size", choices=("full", "toy"), default="full",
                    help="toy = CPU rehearsal of the same code path")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the mesh phase and its comparison only")
    ap.add_argument("--seed", type=int, default=0,
                    help="added to every generated data set's seed")
    ap.add_argument("--phases", default="serve,rowconv,ops",
                    help="the one-chip phases to run, comma-separated")
    args = ap.parse_args(argv)
    toy = args.size == "toy"
    size = SIZES[args.size]

    import jax

    import spark_rapids_tpu  # noqa: F401  (turns x64 on)
    from spark_rapids_tpu import observability as obs
    from spark_rapids_tpu.perf.jit_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    dev = jax.devices()[0]
    rehearsal = (toy and dev.platform == "cpu"
                 and os.environ.get("JAX_PLATFORMS", "") == "cpu")
    if dev.platform != "tpu" and not rehearsal:
        print(f"chip_smoke: no TPU (platform {dev.platform!r}); the CPU "
              "is accepted only as `JAX_PLATFORMS=cpu chip_smoke.py "
              "--size toy`", file=sys.stderr)
        return 2
    if jax.device_count() < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs that many devices, "
              f"JAX reports {jax.device_count()}", file=sys.stderr)
        return 2
    if rehearsal:
        print("chip_smoke: REHEARSAL on the CPU at toy size - not a chip run")
    # each run calibrates afresh: no verdict file outside the checkout
    os.environ.setdefault("SPARK_RAPIDS_TPU_CALIB_CACHE", "")
    obs.enable()
    from spark_rapids_tpu.memory import native_adaptor
    from spark_rapids_tpu.utils import native
    say(phase="start", platform=dev.platform, kind=dev.device_kind,
        devices=jax.device_count(), size=args.size, chips=args.chips,
        compile_cache=cache_dir,
        native={"columnar": native.available(),
                "sra": native_adaptor.available()})

    compiles = Compiles()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(size["rows"], size["join_capacity"], size["xchg_rows"],
                   args.seed, compiles)
    else:
        phases = args.phases.split(",")
        if "serve" in phases:
            phase_serve(size["rows"], size["join_capacity"], args.seed,
                        size["q5_tables"], compiles)
        if "rowconv" in phases:
            phase_rowconv(size["rowconv_rows"], compiles)
            phase_rowconv_schemas(size["rowconv_schemas"])
        if "ops" in phases:
            phase_ops(*size["ops"], args.seed, compiles)
    say(phase="done", seconds=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
