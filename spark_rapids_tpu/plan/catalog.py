"""TPC-DS catalog stages expressed in the stage IR (ISSUE 11).

The hand-fused kernels in models/tpcds.py stay exactly where they are
— they are the byte-identity ORACLES — and this module re-expresses
the same queries as :class:`~spark_rapids_tpu.plan.ir.StagePlan`
pipelines compiled through plan/compiler.py:

  * q3, q9     — one stage each (no shuffle boundary): scan-bind ->
                 project/filter -> segment aggregate -> sort/limit as
                 ONE executable;
  * q5, q72    — two stages joined by a typed ShuffleBoundary
                 (partials | finish), the exact seam the PR-10
                 distributed runner ships over the kudo socket
                 shuffle; single-process runs hand the carry straight
                 across, a mesh rank fuses the WHOLE pipeline into one
                 shard_map program with psum at the Reduce nodes;
  * q5 as its template writes it (``tpcds_q5_channels``) — two
                 stages over a database held on the device: the three
                 channels' date filter, sums and web join probe, then
                 ROLLUP(channel, id), NULLS FIRST, LIMIT 100; its
                 sides share ``_fact_side`` with the store-channel q5;
                 over a mesh the database is sharded, the web join's
                 sides meet through a hash ``Exchange`` and the whole
                 pipeline is one executable (``MeshPipeline``);
  * q67-shape  — GROUP BY ROLLUP(category, class) + rank() OVER
                 (PARTITION BY category ORDER BY sales DESC): the new
                 Rollup and WindowRank nodes (real q67 uses exactly
                 this pair);
  * q89-shape  — sum(sales) OVER (PARTITION BY store) broadcast back
                 to each (store, item) group: the WindowSum node.

Every expression here mirrors its hand-kernel twin operation for
operation (same dtypes, same literal promotion, exact int64
aggregates), which is what makes the fused outputs byte-identical.
Fact inputs pad their join-key columns with side-specific sentinels
(-1 left, -2 right) so bucket-pad rows can never match each other,
and dense-lookup filters AND in ``Mask(input)`` so pad rows never
reach an aggregate.
"""

from __future__ import annotations

from typing import Optional

from spark_rapids_tpu.plan.compiler import (compile_pipeline,
                                            compile_stage,
                                            fused_pipeline_fn)
from spark_rapids_tpu.plan.ir import (Arange, Bin, Col, ColSpec, Exchange,
                                      Idx, IsIn, JoinProbe, Lit, Mask,
                                      Pipeline, Project, Reduce, Rollup,
                                      ScanBind, SegmentSum,
                                      ShuffleBoundary, Sl, Sort,
                                      StagePlan, Stack, Un, Where,
                                      WindowRank, WindowSlice, WindowSum)

I64_SENTINEL = Lit(2 ** 62, "int64")


def _and(*es):
    out = es[0]
    for e in es[1:]:
        out = Bin("and", out, e)
    return out


def _or(*es):
    out = es[0]
    for e in es[1:]:
        out = Bin("or", out, e)
    return out


def _gt0(e):
    return Bin("gt", e, Lit(0))


# ------------------------------------------------------------------- q5


def _fact_side(side: str, keep, outlet, amt_a, amt_b,
               outlets: int) -> list:
    """One fact of a q5 channel: the rows ``keep`` selects (the date
    filter, and the join that found them), grouped by ``outlet`` (a
    dense key, 0-based), with the exact sums of its two amounts and
    its row count: ``<side>_sum_a``, ``<side>_sum_b``,
    ``<side>_seen``, each ``outlets`` long.  The store-channel shape
    and each of the template's three channels build their sales and
    their returns side from it."""
    return [
        Project(f"{side}_st", Where(keep, outlet, Lit(0))),
        SegmentSum(f"{side}_sum_a", Where(keep, amt_a, Lit(0)),
                   Col(f"{side}_st"), outlets),
        SegmentSum(f"{side}_sum_b", Where(keep, amt_b, Lit(0)),
                   Col(f"{side}_st"), outlets),
        SegmentSum(f"{side}_seen", Un("i64", keep), Col(f"{side}_st"),
                   outlets),
    ]


def q5_partials_plan(stores: int, join_capacity: int) -> StagePlan:
    """Map side of q5 (mirrors models.tpcds._q5_partials): two
    fact-to-date-window join probes, per-store segment sums, overflow
    flag."""
    nodes = []
    for side, (date, key, amt_a, amt_b, j) in (
            ("s", ("s_date", "s_store", "s_price", "s_profit", "j1")),
            ("r", ("r_date", "r_store", "r_amt", "r_loss", "j2"))):
        li = Col(f"{j}.li")
        nodes.append(JoinProbe(j, Col(date), Col("d_date"),
                               join_capacity))
        nodes += _fact_side(side, Col(f"{j}.valid"), Idx(Col(key), li),
                            Idx(Col(amt_a), li), Idx(Col(amt_b), li),
                            stores)
    nodes += [
        Project("profit", Bin("sub", Col("s_sum_b"), Col("r_sum_b"))),
        Project("seen", Bin("add", Col("s_seen"), Col("r_seen"))),
        Project("of", Bin("or",
                          Bin("gt", Col("j1.total"),
                              Lit(join_capacity)),
                          Bin("gt", Col("j2.total"),
                              Lit(join_capacity)))),
    ]
    return StagePlan(
        name="q5_partials",
        inputs=(
            ScanBind("s", (ColSpec("s_date", pad=-1),
                           ColSpec("s_store"), ColSpec("s_price"),
                           ColSpec("s_profit"))),
            ScanBind("r", (ColSpec("r_date", pad=-1),
                           ColSpec("r_store"), ColSpec("r_amt"),
                           ColSpec("r_loss"))),
            ScanBind("d", (ColSpec("d_date", pad=-2),)),
        ),
        nodes=tuple(nodes),
        outputs=("s_sum_a", "r_sum_a", "profit", "seen", "of"),
    )


def q5_finish_plan(stores: int) -> StagePlan:
    """Reduce side of q5 (mirrors models.tpcds._q5_finish): global
    group table -> ORDER BY s_store_id.  The Reduce nodes are the
    cross-shard seam: identity single-chip, psum on the mesh, replaced
    by the kudo exchange in the distributed runner."""
    return StagePlan(
        name="q5_finish",
        inputs=(
            ScanBind("xchg", (ColSpec("s_sum_a"), ColSpec("r_sum_a"),
                              ColSpec("profit"), ColSpec("seen"),
                              ColSpec("of")), bucket=False),
            ScanBind("dims", (ColSpec("st_id"),), bucket=False),
        ),
        nodes=(
            Reduce("g_sales", Col("s_sum_a")),
            Reduce("g_rets", Col("r_sum_a")),
            Reduce("g_profit", Col("profit")),
            Reduce("g_seen", Col("seen")),
            Reduce("g_of", Col("of"), kind="any"),
            Project("key", Where(_gt0(Col("g_seen")), Col("st_id"),
                                 Lit(2 ** 31 - 1, "int32"))),
            Sort(("key_s", "sales_s", "ret_s", "profit_s"),
                 (Col("key"), Col("g_sales"), Col("g_rets"),
                  Col("g_profit")), num_keys=1),
        ),
        outputs=("key_s", "sales_s", "ret_s", "profit_s", "g_of"),
    )


def q5_pipeline(stores: int, join_capacity: int) -> Pipeline:
    return Pipeline(
        name="q5",
        stages=(q5_partials_plan(stores, join_capacity),
                q5_finish_plan(stores)),
        boundaries=(ShuffleBoundary(
            ("s_sum_a", "r_sum_a", "profit", "seen", "of")),),
    )


def _note_estimates(stage: str, rows_by_input) -> None:
    """Register generator-size row estimates for a stage's scan
    inputs (the est side of ISSUE 20's est-vs-actual feedback loop).
    One attribute read when the stats plane is off."""
    from spark_rapids_tpu import observability as _obs
    if not _obs.STATS.enabled:
        return
    _obs.STATS.register_input_estimates(
        stage, {k: len(v) for k, v in rows_by_input.items()},
        origin="catalog")


def run_q5(d, stores: int, capacity: int):
    """Fused q5 under the centralized capacity-retry driver.  Returns
    the same tuple as models.tpcds.make_q5(...)(d)."""
    from spark_rapids_tpu.parallel.exchange import with_capacity_retry

    _note_estimates("q5_partials", {"s": d.s_date, "r": d.r_date,
                                    "d": d.d_date})

    def build(cap):
        pipe = compile_pipeline(q5_pipeline(stores, cap))
        return lambda *a: pipe.run({"s": a[0:4], "r": a[4:8],
                                    "d": (a[8],), "dims": (a[9],)})

    outs, _cap = with_capacity_retry(build, capacity, max_doublings=16)(
        d.s_date, d.s_store, d.s_price, d.s_profit,
        d.r_date, d.r_store, d.r_amt, d.r_loss, d.d_date, d.st_id)
    return outs


def run_q5_partials(args, stores: int, capacity: int, *, ctx=None):
    """Distributed map side: ONE executable per rank before the kudo
    exchange.  ``args`` = 8 sharded fact columns + the replicated
    d_date window; returns ((sales, rets, profit, seen, of), cap).

    ``ctx`` (optional QueryContext) makes the stage CANCELLABLE: the
    elastic fleet's speculative re-executions pass their cancel-capable
    context so a speculation whose original arrived mid-run unwinds
    between capacity attempts through the lifeguard machinery instead
    of finishing a result nobody will merge."""
    from spark_rapids_tpu.parallel.exchange import with_capacity_retry

    def build(cap):
        st = compile_stage(q5_partials_plan(stores, cap))
        return lambda *a: st.run({"s": a[0:4], "r": a[4:8],
                                  "d": (a[8],)})

    return with_capacity_retry(
        build, capacity, max_doublings=16,
        check=ctx.check_cancel if ctx is not None else None)(*args)


def run_q5_finish(sales, rets, profit, seen, of, st_id, stores: int):
    """Distributed reduce side: ONE executable per rank after the
    exchange (inputs are already globally summed; the plan's Reduce
    nodes are identity here)."""
    st = compile_stage(q5_finish_plan(stores))
    return st.run({"xchg": (sales, rets, profit, seen, of),
                   "dims": (st_id,)})


# ------------------------------------- q5 as its template writes it

# the three channels in the order their names sort ('catalog channel'
# < 'store channel' < 'web channel'), which is their served code:
# (channel, sales input, returns input, outlet dim input)
Q5_CHANNELS = (("catalog", "cs", "cr", "catalog_page"),
               ("store", "ss", "sr", "store"),
               ("web", "ws", "wr", "web_site"))
# date keys a 15-day window can hold (one date_dim row a date); more
# survivors flag an overflow
Q5_WINDOW_KEYS = 16
NULL_CODE = -1                      # a rollup row's NULL channel / id
DEAD_CODE = 2 ** 31 - 1             # an output slot past the live rows


def _fact(name, *cols, pads=None):
    pads = pads or {}
    return ScanBind(name, tuple(ColSpec(f"{name}_{c}", pad=pads.get(c, 0))
                                for c in cols))


# the map stage's inputs: six facts, held on the device padded to their
# row buckets (join keys with side-specific pads), date_dim, each outlet
# dim's business id by surrogate key (key k at k - 1), and SALES_DATE
Q5_CHANNEL_INPUTS = (
    _fact("ss", "date", "outlet", "price", "profit"),
    _fact("sr", "date", "outlet", "amt", "loss"),
    _fact("cs", "date", "outlet", "price", "profit"),
    _fact("cr", "date", "outlet", "amt", "loss"),
    _fact("ws", "date", "outlet", "price", "profit", "item", "order",
          pads={"item": -2}),
    _fact("wr", "date", "item", "order", "amt", "loss",
          pads={"item": -1}),
    ScanBind("dd", (ColSpec("d_date_sk"), ColSpec("d_date")),
             bucket=False),
    ScanBind("store", (ColSpec("s_id"),), bucket=False),
    ScanBind("catalog_page", (ColSpec("cp_id"),), bucket=False),
    ScanBind("web_site", (ColSpec("web_id"),), bucket=False),
    ScanBind("q", (ColSpec("sales_date"),), bucket=False),
)
_DIM_ID = {"store": "s_id", "catalog_page": "cp_id", "web_site": "web_id"}


def q5_slots(ids):
    """The group table's layout for ``ids`` business ids a channel (in
    Q5_CHANNELS order): slot 0 the grand total, then per channel its
    subtotal and its ids in dictionary order.  Returns (subtotal slot
    per channel, slots)."""
    subs, at = [], 1
    for n in ids:
        subs.append(at)
        at += 1 + n
    return tuple(subs), at


def _q5_scan(side: str, cols, windows: dict):
    """How the map stage reads fact ``side``'s columns ``cols`` (names
    without the side's prefix): through a ``WindowSlice`` on its date
    where ``windows`` gives the side a capacity (a resident fact
    ordered at load), whole and masked otherwise.  Returns (nodes,
    column by name, validity)."""
    names = tuple(f"{side}_{c}" for c in cols)
    if side not in windows:
        return [], {c: Col(n) for c, n in zip(cols, names)}, Mask(side)
    p = f"{side}_w"
    return ([WindowSlice(p, side, Col(f"{side}_date"), Col("win_lo"),
                         Col("win_hi"), names, windows[side])],
            {c: Col(f"{p}.{n}") for c, n in zip(cols, names)},
            Col(f"{p}.valid"))


def q5_channels_map_plan(outlets, ids, item_bits: int,
                         join_capacity: int,
                         window_days: int = 15, *,
                         exchange_slots, windows=()) -> StagePlan:
    """q5's map side, all three channels in one stage.

    * date_dim is filtered first (``d_date`` between SALES_DATE and
      SALES_DATE + ``window_days`` - 1, read at run time) and hands
      over its surviving keys, as Spark's dynamic pruning does; each
      fact keeps the rows whose date key is among them (``IsIn``);
    * a fact that ``windows`` ((side, capacity), ...) names was put in
      date order at load (``q5_channels_tables``) and is read as one
      ``WindowSlice``: the capacity's rows from its first row dated in
      the window, found by a binary search, so the filter, the sums
      and the probe's left side see the fortnight and not the table;
      a window that held more rows than its slice raises ``of``.  A
      fact without a capacity is read whole, masked;
    * web returns find their sale through a JoinProbe on the packed
      key (order number << ``item_bits``) | item over the whole of
      web_sales, and take the sale's site; both sides reach the probe
      through an ``Exchange`` on (item, order number), as Spark's
      sort-merge join does: nothing on one chip, an all-to-all on a
      mesh, whose slots are ``exchange_slots`` ((table, rows), ...,
      web_returns first).  The returns' date filter lies below the
      join, ahead of the exchange: a predicate on the left side's own
      column under an inner join, as Spark's optimizer pushes it, so
      only the window's returns are sent and probed;
    * each fact side sums into its outlets (``_fact_side``), and each
      channel's outlets fold through the outlet dim's business ids into
      the one group table of ``q5_slots`` — the UNION ALL of the
      channels."""
    subs, n_slots = q5_slots(ids)
    k = Q5_WINDOW_KEYS
    windows = dict(windows)
    first_key = Idx(Col("d_key_s"), Lit(0))
    nodes = [
        Project("d_in", _and(
            Bin("ge", Col("d_date"), Col("sales_date")),
            Bin("le", Col("d_date"),
                Bin("add", Col("sales_date"), Lit(window_days - 1))))),
        Project("d_key", Where(Col("d_in"), Col("d_date_sk"),
                               Lit(2 ** 31 - 1, "int32"))),
        Sort(("d_key_s",), (Col("d_key"),), num_keys=1),
        Project("win_n", Un("sum", Un("i32", Col("d_in")))),
        Project("win_any", _gt0(Col("win_n"))),
        # the empty slots repeat a surviving key: they match nothing new
        Project("win_keys", Where(
            Bin("lt", Arange(k, "int32"), Col("win_n")),
            Sl(Col("d_key_s"), 0, k), first_key)),
        # the first and the last surviving key: the slices' bounds
        Project("win_lo", first_key),
        Project("win_hi", Idx(Col("d_key_s"), Bin(
            "max", Bin("sub", Col("win_n"), Lit(1)), Lit(0)))),
    ]
    read, over = {}, []
    for inp in Q5_CHANNEL_INPUTS[:6]:
        side = inp.name
        cols = tuple(c.name[len(side) + 1:] for c in inp.columns)
        if side == "ws":            # its sales; the join reads it whole
            cols = cols[:4]
        found, read[side], valid = _q5_scan(side, cols, windows)
        nodes += found
        over += [Col(f"{n.prefix}.over") for n in found]
        nodes.append(Project(f"{side}_keep", _and(
            IsIn(read[side]["date"], Col("win_keys")), Col("win_any"),
            valid)))
    wr = read["wr"]
    shift = Lit(1 << item_bits)
    for side, order, item in (("wr", wr["order"], wr["item"]),
                              ("ws", Col("ws_order"), Col("ws_item"))):
        nodes.append(Project(f"{side}_key", Bin(
            "add", Bin("mul", Un("i64", order), shift), Un("i64", item))))
    # Spark's plan for the web join: both sides behind Exchange
    # hashpartitioning(item, order number); on one chip it is nothing
    wr_slot, ws_slot = (s for _t, s in exchange_slots)
    amt, loss = wr["amt"].name, wr["loss"].name
    nodes += [
        Exchange("web_returns", (wr["item"], wr["order"]),
                 ("wr_key", amt, loss), Col("wr_keep"), wr_slot),
        Exchange("web_sales", (Col("ws_item"), Col("ws_order")),
                 ("ws_key", "ws_outlet"), Mask("ws"), ws_slot),
    ]
    li, ri = Col("wj.li"), Col("wj.ri")
    nodes.append(JoinProbe(
        "wj", Col("web_returns.wr_key"), Col("web_sales.ws_key"),
        join_capacity, left_valid=Col("web_returns.valid"),
        right_valid=Col("web_sales.valid")))
    totals = {"sales": [], "returns": [], "profit": [], "cnt": []}
    for (channel, sold, ret, dim), n_out, sub in zip(Q5_CHANNELS,
                                                    outlets, subs):
        s = read[sold]
        nodes += _fact_side(sold, Col(f"{sold}_keep"),
                            Bin("sub", s["outlet"], Lit(1)),
                            s["price"], s["profit"], n_out)
        if ret == "wr":
            nodes += _fact_side(
                ret, Col("wj.valid"),
                Bin("sub", Idx(Col("web_sales.ws_outlet"), ri), Lit(1)),
                Idx(Col(f"web_returns.{amt}"), li),
                Idx(Col(f"web_returns.{loss}"), li), n_out)
        else:
            r = read[ret]
            nodes += _fact_side(ret, Col(f"{ret}_keep"),
                                Bin("sub", r["outlet"], Lit(1)),
                                r["amt"], r["loss"], n_out)
        slot = Col(f"{channel}_slot")
        nodes.append(Project(f"{channel}_slot", Bin(
            "add", Col(_DIM_ID[dim]), Lit(sub + 1))))
        for name, value in (
                ("sales", Col(f"{sold}_sum_a")),
                ("returns", Col(f"{ret}_sum_a")),
                ("profit", Bin("sub", Col(f"{sold}_sum_b"),
                               Col(f"{ret}_sum_b"))),
                ("cnt", Bin("add", Col(f"{sold}_seen"),
                            Col(f"{ret}_seen")))):
            nodes.append(SegmentSum(f"{channel}_{name}", value, slot,
                                    n_slots))
            totals[name].append(Col(f"{channel}_{name}"))
    for name, parts in totals.items():
        nodes.append(Project(name, Bin("add", Bin("add", *parts[:2]),
                                       parts[2])))
    nodes += [
        Project("pairs", Col("wj.total")),
        Project("of", _or(Bin("gt", Col("wj.total"), Lit(join_capacity)),
                          Bin("gt", Col("win_n"), Lit(k)), *over)),
    ]
    return StagePlan(name="q5_channels_map", inputs=Q5_CHANNEL_INPUTS,
                     nodes=tuple(nodes),
                     outputs=("sales", "returns", "profit", "cnt", "of",
                              "pairs"))


def q5_channels_finish_plan(ids, limit: int = 100) -> StagePlan:
    """q5's finish: the global group table (``Reduce``, the seam a mesh
    psums over), GROUP BY ROLLUP(channel, id) — a channel's subtotal
    and the grand total summed from its ids — ORDER BY channel, id
    with NULLS FIRST (the slot order), LIMIT ``limit``.  A NULL
    channel or id is served as NULL_CODE, a slot past the live rows
    with channel DEAD_CODE."""
    subs, n_slots = q5_slots(ids)
    slot = Col("slot")

    def channel_of(e):
        return Bin("add", Un("i64", Bin("ge", e, Lit(subs[1]))),
                   Un("i64", Bin("ge", e, Lit(subs[2]))))

    def is_rollup_row(e):
        return _or(Bin("eq", e, Lit(0)),
                   *(Bin("eq", e, Lit(s)) for s in subs))

    nodes = [Reduce(f"g_{v}", Col(v))
             for v in ("sales", "returns", "profit", "cnt")]
    nodes += [Reduce("g_of", Col("of"), kind="any"),
              Reduce("g_pairs", Col("pairs")),
              Project("slot", Arange(n_slots, "int64")),
              Project("ch", channel_of(slot)),
              Project("is_sub", is_rollup_row(slot))]
    for v in ("sales", "returns", "profit", "cnt"):
        nodes += [
            SegmentSum(f"sub_{v}", Col(f"g_{v}"), Col("ch"), 3),
            Project(f"r_{v}", Where(
                Bin("eq", slot, Lit(0)), Un("sum", Col(f"g_{v}")),
                Where(Col("is_sub"), Idx(Col(f"sub_{v}"), Col("ch")),
                      Col(f"g_{v}")))),
        ]
    top = Col("top")
    nodes += [
        Project("key", Where(_gt0(Col("r_cnt")), slot, I64_SENTINEL)),
        Sort(("key_s", "sales_s", "returns_s", "profit_s"),
             (Col("key"), Col("r_sales"), Col("r_returns"),
              Col("r_profit")), num_keys=1),
        Project("top", Sl(Col("key_s"), 0, limit)),
        Project("top_ch", channel_of(top)),
        Project("channel_out", Where(
            Bin("eq", top, I64_SENTINEL), Lit(DEAD_CODE),
            Where(Bin("eq", top, Lit(0)), Lit(NULL_CODE),
                  Col("top_ch")))),
        Project("id_out", Where(
            _or(is_rollup_row(top), Bin("eq", top, I64_SENTINEL)),
            Lit(NULL_CODE),
            Bin("sub", Bin("sub", top, Lit(1)), Where(
                Bin("eq", Col("top_ch"), Lit(0)), Lit(subs[0]),
                Where(Bin("eq", Col("top_ch"), Lit(1)), Lit(subs[1]),
                      Lit(subs[2])))))),
        Project("sales_out", Sl(Col("sales_s"), 0, limit)),
        Project("returns_out", Sl(Col("returns_s"), 0, limit)),
        Project("profit_out", Sl(Col("profit_s"), 0, limit)),
    ]
    return StagePlan(
        name="q5_channels_finish",
        inputs=(ScanBind("xchg", tuple(ColSpec(c) for c in (
            "sales", "returns", "profit", "cnt", "of", "pairs")),
            bucket=False),),
        nodes=tuple(nodes),
        outputs=("channel_out", "id_out", "sales_out", "returns_out",
                 "profit_out", "g_of", "g_pairs"))


def q5_channels_pipeline(outlets, ids, item_bits: int,
                         join_capacity: int, limit: int = 100,
                         window_days: int = 15, *,
                         exchange_slots, windows=()) -> Pipeline:
    return Pipeline(
        name="q5_channels",
        stages=(q5_channels_map_plan(outlets, ids, item_bits,
                                     join_capacity, window_days,
                                     exchange_slots=exchange_slots,
                                     windows=windows),
                q5_channels_finish_plan(ids, limit)),
        boundaries=(ShuffleBoundary(
            ("sales", "returns", "profit", "cnt", "of", "pairs")),))


_Q5_FACT_TABLES = dict(zip(("ss", "sr", "cs", "cr", "ws", "wr"),
                           ("store_sales", "store_returns", "catalog_sales",
                            "catalog_returns", "web_sales", "web_returns")))


def _window_capacity(widest: int, bucket: int) -> int:
    """A slice's rows for a window that holds at most ``widest`` rows of
    a shard: to a multiple of 1,024, within the shard's bucket."""
    return min(bucket, max(1024, -(-widest // 1024) * 1024))


def _order_rows(n_valid, cols, keys, days: int):
    """The first ``n_valid`` rows of ``cols`` in the order of the first
    column (its values carried as the sort's key, the others as its
    payload; the pad rows after them stay at the tail, as they are),
    and the most of them that ``days`` consecutive values from any of
    ``keys`` hold: a binary search of the ordered column for each key
    and for each key plus ``days``, so no pass over the rows."""
    import jax.numpy as jnp
    from jax import lax

    from spark_rapids_tpu.plan.compiler import first_rows
    real = lax.iota(jnp.int32, cols[0].shape[0]) < n_valid
    key = jnp.where(real, cols[0], jnp.iinfo(cols[0].dtype).max)
    # rows of one key may come in any order: an unstable sort compiles
    # in about a third of a stable one's time for a v5e
    out = lax.sort((key,) + tuple(cols[1:]), num_keys=1, is_stable=False)
    date = jnp.where(real, out[0], cols[0])
    first = first_rows(date, n_valid, jnp.concatenate([keys, keys + days]),
                        False)
    widest = jnp.max(first[len(keys):] - first[:len(keys)])
    return (date,) + tuple(out[1:]), widest[None]


def q5_channels_tables(host: dict, mesh=None, window_days: int = 15) -> dict:
    """A q5 database (``models.tpcds.gen_q5_db``) as the map stage
    binds it, on the device: each fact padded once to its row bucket
    with its columns' pad values (``Padded``), the dims as they are.
    On a ``mesh`` each device holds one contiguous shard of every fact
    (as a scan of files splits a table among executors), each padded
    to the bucket of the largest, and the dims whole.

    Each fact's true rows are put in the order of its date (the first
    column) on the device, a shard by its own chip, and the table keeps
    its ``window``: the most rows of a shard that ``window_days``
    consecutive date keys from date_dim's hold, to a multiple of 1,024
    (``_window_capacity``).  That is the capacity of the
    ``WindowSlice`` the map stage reads it through, fixed for the life
    of the database: one executable a stage still."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import numpy as np
    from jax import lax, shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_tpu.perf.jit_cache import bucket_rows
    from spark_rapids_tpu.plan.compiler import Padded

    def padded(c, b, pad):
        return np.concatenate([c, np.full(b - len(c), pad, c.dtype)])

    whole = None if mesh is None else NamedSharding(mesh, P())
    out = {"dd": (jax.device_put(host["d_date_sk"], whole),
                  jax.device_put(host["d_date"], whole))}

    def order_rows(n_valid, cols, keys):
        return _order_rows(n_valid, cols, keys, window_days)
    if mesh is None:
        order = jax.jit(order_rows)
    else:
        axis = mesh.axis_names[0]
        order = jax.jit(shard_map(
            lambda counts, cols, keys: order_rows(
                counts[lax.axis_index(axis)], cols, keys),
            mesh=mesh, in_specs=(P(), P(axis), P()), out_specs=P(axis)))
    # each fact on the device as it was drawn: (order's arguments,
    # true rows, shard rows, bucket)
    held = {}
    for inp in Q5_CHANNEL_INPUTS[:6]:
        cols = host[_Q5_FACT_TABLES[inp.name]]
        rows = len(cols[0])
        if mesh is None:
            b = bucket_rows(rows)
            held[inp.name] = ((np.int32(rows), tuple(
                jax.device_put(padded(c, b, spec.pad))
                for spec, c in zip(inp.columns, cols)), out["dd"][0]),
                rows, None, b)
            continue
        devices = list(mesh.devices.flat)
        edges = [rows * i // len(devices) for i in range(len(devices) + 1)]
        shards = list(zip(edges[:-1], edges[1:]))
        b = bucket_rows(max(hi - lo for lo, hi in shards))
        sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
        counts = np.asarray([hi - lo for lo, hi in shards], np.int32)
        held[inp.name] = ((counts, tuple(
            jax.make_array_from_single_device_arrays(
                (b * len(devices),), sharding,
                [jax.device_put(padded(c[lo:hi], b, spec.pad), d)
                 for d, (lo, hi) in zip(devices, shards)])
            for spec, c in zip(inp.columns, cols)), out["dd"][0]),
            rows, counts, b)
    # the six sorts compile side by side (a sort of a few payload
    # columns takes a v5e's compiler a minute), then run one by one
    lowered = {name: order.lower(*h[0]) for name, h in held.items()}
    with ThreadPoolExecutor(len(lowered)) as pool:
        compiled = dict(zip(lowered, pool.map(lambda low: low.compile(),
                                              lowered.values())))
    for name in list(held):
        args, rows, shard_rows, b = held.pop(name)
        cols, widest = compiled[name](*args)
        out[name] = Padded(cols, rows, shard_rows, window=_window_capacity(
            int(np.asarray(widest).max()), b))
    for dim in _DIM_ID:
        out[dim] = (jax.device_put(host[dim], whole),)
    return out


def q5_windows(tables: dict) -> dict:
    """Each fact's slice capacity, by map-stage input, of the facts a
    q5 database holds in date order (``Padded.window``)."""
    return {name: t.window for name, t in tables.items()
            if getattr(t, "window", None) is not None}


def q5_scan_rows(tables: dict):
    """What a query's map stage reads of a q5 database: the true fact
    rows it reads (a fact read through a slice at most the slice's
    capacity a shard; web_sales whole, which the join reads whole),
    and by table the true rows that a slice's scan skips."""
    read, skipped = 0, {}
    for side, table in _Q5_FACT_TABLES.items():
        t = tables[side]
        if t.window is None:
            read += t.rows
            continue
        took = sum(min(r, t.window) for r in t.shard_rows or (t.rows,))
        skipped[table] = t.rows - took
        read += t.rows if side == "ws" else took
    return read, skipped


def q5_channels_shape(sizes: dict, ids: dict, window_days: int,
                      chips: int = 1, windows=None) -> dict:
    """The static parameters of a q5 database's plan: outlets a channel
    (from ``sizes``), business ids a channel (``ids``, by outlet dim),
    the item key's bits, the date window's length in days, the chips
    the facts are sharded over, the slice capacity of each fact held in
    date order (``windows``, ``q5_windows``), each web table's
    exchange slot (the rows one chip sends one chip: its share under
    uniform hashing and eight standard deviations, to a power of two
    and at most the bucket of what it sends: web_returns' slice where
    it has one, its shard otherwise) and the probe's capacity:
    web_sales' key is unique, so a return finds at most one sale and
    the probe needs a slot a return it holds (web_returns' slice, or
    its row bucket, on one chip; its received slots on a mesh)."""
    import math

    from spark_rapids_tpu.perf.jit_cache import bucket_rows
    windows = dict(windows or {})
    slots = []
    for table, sent in (("web_returns", windows.get("wr")),
                        ("web_sales", None)):
        shard = sent or -(-int(sizes[table]) // chips)
        share = shard / chips
        slots.append((table, min(bucket_rows(shard), bucket_rows(
            math.ceil(share + 8 * math.sqrt(share))))))
    one_chip = windows.get("wr") or bucket_rows(sizes["web_returns"])
    return {"outlets": tuple(sizes[dim] for _c, _s, _r, dim in Q5_CHANNELS),
            "ids": tuple(ids[dim] for _c, _s, _r, dim in Q5_CHANNELS),
            "item_bits": int(sizes["item"]).bit_length(),
            "join_capacity": (one_chip if chips == 1
                              else chips * slots[0][1]),
            "window_days": int(window_days),
            "chips": int(chips),
            "exchange_slots": tuple(slots),
            "windows": tuple(sorted(windows.items()))}


def q5_exchange_slots(shape: dict, capacity: Optional[int] = None):
    """((table, slot rows), ...) of the web exchange and the probe's
    capacity when web_sales' slot is ``capacity`` (the shape's by
    default): web_returns' slot and the probe's capacity scale with
    it, as the capacity retry doubles it."""
    slots, join_capacity = shape["exchange_slots"], shape["join_capacity"]
    if capacity is None:
        return slots, join_capacity
    first = slots[-1][1]
    return (tuple((t, s * capacity // first) for t, s in slots),
            join_capacity * capacity // first)


def run_q5_channels(tables: dict, shape: dict, sales_day: int,
                    limit: int = 100, mesh=None,
                    capacity: Optional[int] = None):
    """q5 over a database held on the device (``q5_channels_tables``)
    for SALES_DATE ``sales_day`` (days since 1970-01-01).  Returns
    (channel, id, sales, returns, profit) of the first ``limit`` rows
    (a limit past the rollup's rows serves every row, from one
    executable), the overflow flag and the probe's true pair count.

    On one chip the two-stage pipeline, one executable a stage.  On a
    ``mesh`` (tables sharded over it) the whole pipeline is one
    executable (``MeshPipeline``), and a dict of each web table's send
    counts, (devices, devices), follows the outputs, which
    ``capacity`` sizes (``q5_exchange_slots``)."""
    import numpy as np

    from spark_rapids_tpu.plan.compiler import MeshPipeline
    limit = min(int(limit), q5_slots(shape["ids"])[1])
    slots, join_capacity = q5_exchange_slots(shape, capacity)
    pipeline = q5_channels_pipeline(
        shape["outlets"], shape["ids"], shape["item_bits"], join_capacity,
        limit, shape["window_days"], exchange_slots=slots,
        windows=shape["windows"])
    inputs = {**tables, "q": (np.int32(sales_day),)}
    if mesh is None:
        return compile_pipeline(pipeline).run(inputs)
    out, sent = MeshPipeline(pipeline, mesh).run(inputs)
    return (*out, sent)

# ------------------------------------------------------------------ q72


def q72_partials_plan(items: int, max_week: int, join_capacity: int,
                      week0: int) -> StagePlan:
    """Map side of q72 (mirrors models.tpcds._q72_partials): fact-fact
    join probe + week-offset/shortage filters + (item, week) counts."""
    n_groups = items * max_week
    return StagePlan(
        name="q72_partials",
        inputs=(
            ScanBind("cs", (ColSpec("cs_item", pad=-1),
                            ColSpec("cs_date"), ColSpec("cs_qty"))),
            ScanBind("inv", (ColSpec("inv_item", pad=-2),
                             ColSpec("inv_date"), ColSpec("inv_qty"))),
            ScanBind("dim", (ColSpec("item_id"),), bucket=False),
        ),
        nodes=(
            JoinProbe("j", Col("cs_item"), Col("inv_item"),
                      join_capacity),
            Project("ow", Bin("floordiv",
                              Idx(Col("cs_date"), Col("j.li")),
                              Lit(7))),
            Project("iw", Bin("floordiv",
                              Idx(Col("inv_date"), Col("j.ri")),
                              Lit(7))),
            Project("wk", Bin("sub", Col("ow"), Lit(week0))),
            Project("keep", _and(
                Col("j.valid"),
                Bin("eq", Col("iw"), Bin("add", Col("ow"), Lit(1))),
                Bin("lt", Idx(Col("inv_qty"), Col("j.ri")),
                    Idx(Col("cs_qty"), Col("j.li"))),
                Bin("ge", Col("wk"), Lit(0)),
                Bin("lt", Col("wk"), Lit(max_week)))),
            Project("iid", Idx(Col("item_id"),
                               Idx(Col("cs_item"), Col("j.li")))),
            Project("gid", Where(
                Col("keep"),
                Bin("add", Bin("mul", Col("iid"), Lit(max_week)),
                    Col("wk")), Lit(0))),
            SegmentSum("counts", Un("i64", Col("keep")), Col("gid"),
                       n_groups),
            Project("of", Bin("gt", Col("j.total"),
                              Lit(join_capacity))),
        ),
        outputs=("counts", "of"),
    )


def q72_finish_plan(items: int, max_week: int, limit: int,
                    week0: int) -> StagePlan:
    """Reduce side of q72 (mirrors models.tpcds._q72_finish): top-k
    over the global count vector."""
    n_groups = items * max_week
    return StagePlan(
        name="q72_finish",
        inputs=(ScanBind("xchg", (ColSpec("counts"), ColSpec("of")),
                         bucket=False),),
        nodes=(
            Reduce("g_counts", Col("counts")),
            Reduce("g_of", Col("of"), kind="any"),
            Project("gidx", Arange(n_groups, "int64")),
            Project("skey", Where(_gt0(Col("g_counts")),
                                  Un("neg", Col("g_counts")),
                                  I64_SENTINEL)),
            Sort(("_k", "gid_s", "cnt_s"),
                 (Col("skey"), Col("gidx"), Col("g_counts")),
                 num_keys=2),
            Project("item", Bin("floordiv", Sl(Col("gid_s"), 0, limit),
                                Lit(max_week))),
            Project("week", Bin("add",
                                Bin("mod", Sl(Col("gid_s"), 0, limit),
                                    Lit(max_week)), Lit(week0))),
            Project("cnt", Sl(Col("cnt_s"), 0, limit)),
        ),
        outputs=("item", "week", "cnt", "g_of"),
    )


def q72_pipeline(items: int, max_week: int, join_capacity: int,
                 limit: int = 100, week0: int = 0) -> Pipeline:
    return Pipeline(
        name="q72",
        stages=(q72_partials_plan(items, max_week, join_capacity,
                                  week0),
                q72_finish_plan(items, max_week, limit, week0)),
        boundaries=(ShuffleBoundary(("counts", "of")),),
    )


def run_q72(d, items: int, max_week: int, capacity: int,
            limit: int = 100, week0: int = 0):
    """Fused q72 under capacity retry — same tuple as make_q72."""
    from spark_rapids_tpu.parallel.exchange import with_capacity_retry

    _note_estimates("q72_partials", {"cs": d.cs_item,
                                     "inv": d.inv_item,
                                     "dim": d.item_id})

    def build(cap):
        pipe = compile_pipeline(
            q72_pipeline(items, max_week, cap, limit, week0))
        return lambda *a: pipe.run({"cs": a[0:3], "inv": a[3:6],
                                    "dim": (a[6],)})

    outs, _cap = with_capacity_retry(build, capacity, max_doublings=16)(
        d.cs_item, d.cs_date, d.cs_qty, d.inv_item, d.inv_date,
        d.inv_qty, d.item_id)
    return outs


def run_q72_partials(args, items: int, max_week: int, capacity: int,
                     week0: int):
    from spark_rapids_tpu.parallel.exchange import with_capacity_retry

    def build(cap):
        st = compile_stage(
            q72_partials_plan(items, max_week, cap, week0))
        return lambda *a: st.run({"cs": a[0:3], "inv": a[3:6],
                                  "dim": (a[6],)})

    return with_capacity_retry(build, capacity, max_doublings=16)(*args)


def run_q72_finish(counts, of, items: int, max_week: int, limit: int,
                   week0: int):
    st = compile_stage(q72_finish_plan(items, max_week, limit, week0))
    return st.run({"xchg": (counts, of)})


# ------------------------------------------------------------------- q3


def q3_plan(base: int, years: int, brands: int, manufact: int,
            month: int = 11, limit: int = 100) -> StagePlan:
    """q3 as ONE stage (mirrors models.tpcds._q3_kernel): dense date +
    item dim lookups, month/manufacturer filters, (year, brand) sums,
    three-key order-by with LIMIT."""
    n_groups = years * brands
    return StagePlan(
        name="q3",
        inputs=(
            ScanBind("s", (ColSpec("s_date", pad=base),
                           ColSpec("s_item"), ColSpec("s_price"))),
            ScanBind("dims", (ColSpec("d_moy"), ColSpec("d_year"),
                              ColSpec("i_brand"),
                              ColSpec("i_manufact")), bucket=False),
        ),
        nodes=(
            Project("di", Bin("sub", Col("s_date"), Lit(base))),
            Project("year_idx", Bin("sub",
                                    Idx(Col("d_year"), Col("di")),
                                    Idx(Col("d_year"), Lit(0)))),
            # Mask('s') last: pad rows (s_date=base -> a real day)
            # must never reach the aggregates
            Project("keep", _and(
                Bin("eq", Idx(Col("d_moy"), Col("di")), Lit(month)),
                Bin("eq", Idx(Col("i_manufact"), Col("s_item")),
                    Lit(manufact)),
                Bin("ge", Col("year_idx"), Lit(0)),
                Bin("lt", Col("year_idx"), Lit(years)),
                Mask("s"))),
            Project("brand", Idx(Col("i_brand"), Col("s_item"))),
            Project("gid", Where(
                Col("keep"),
                Bin("add", Bin("mul", Col("year_idx"), Lit(brands)),
                    Col("brand")), Lit(0))),
            Project("amt", Where(Col("keep"), Col("s_price"),
                                 Lit(0))),
            SegmentSum("sums0", Col("amt"), Col("gid"), n_groups),
            Reduce("sums", Col("sums0")),
            SegmentSum("cnts0", Un("i64", Col("keep")), Col("gid"),
                       n_groups),
            Reduce("cnts", Col("cnts0")),
            Project("gidx", Arange(n_groups, "int64")),
            Project("year_of_g", Bin("floordiv", Col("gidx"),
                                     Lit(brands))),
            Project("brand_of_g", Bin("mod", Col("gidx"),
                                      Lit(brands))),
            Project("k1", Where(_gt0(Col("cnts")), Col("year_of_g"),
                                I64_SENTINEL)),
            Project("k2", Where(_gt0(Col("cnts")),
                                Un("neg", Col("sums")),
                                I64_SENTINEL)),
            Sort(("_a", "_b", "_c", "g_s", "sum_s", "cnt_s"),
                 (Col("k1"), Col("k2"), Col("brand_of_g"),
                  Col("gidx"), Col("sums"), Col("cnts")), num_keys=3),
            Project("live", _gt0(Sl(Col("cnt_s"), 0, limit))),
            Project("yrs", Where(
                Col("live"),
                Bin("add", Bin("floordiv", Sl(Col("g_s"), 0, limit),
                               Lit(brands)),
                    Idx(Col("d_year"), Lit(0))),
                Lit(2 ** 31 - 1, "int64"))),
            Project("brands_out", Bin("mod", Sl(Col("g_s"), 0, limit),
                                      Lit(brands))),
            Project("sums_out", Sl(Col("sum_s"), 0, limit)),
            Project("total", Un("sum", Col("cnts"))),
        ),
        outputs=("yrs", "brands_out", "sums_out", "total"),
    )


def run_q3(d, base: int, years: int, brands: int, manufact: int,
           month: int = 11, limit: int = 100):
    _note_estimates("q3", {"s": d.s_date, "dims": d.d_moy})
    st = compile_stage(q3_plan(base, years, brands, manufact, month,
                               limit))
    return st.run({"s": (d.s_date, d.s_item, d.s_price),
                   "dims": (d.d_moy, d.d_year, d.i_brand,
                            d.i_manufact)})


# ------------------------------------------------------------------- q9

_Q9_BUCKETS = ((1, 20), (21, 40), (41, 60), (61, 80), (81, 100))


def q9_plan() -> StagePlan:
    """q9 as ONE stage (mirrors models.tpcds._run_q9_jit): five
    CASE-WHEN quantity buckets, exact int64 sums, f64 avgs at the
    edge.  Pad rows carry quantity 0, outside every bucket."""
    nodes = []
    cs, aps, ans = [], [], []
    for k, (lo, hi) in enumerate(_Q9_BUCKETS):
        m = f"m{k}"
        nodes += [
            Project(m, Bin("and",
                           Bin("ge", Col("quantity"), Lit(lo)),
                           Bin("le", Col("quantity"), Lit(hi)))),
            Project(f"c{k}", Un("sum", Un("i64", Col(m)))),
            Project(f"sp{k}", Un("sum", Where(Col(m), Col("price"),
                                              Lit(0)))),
            Project(f"sn{k}", Un("sum", Where(Col(m), Col("profit"),
                                              Lit(0)))),
            Project(f"ap{k}", Bin("div", Un("f64", Col(f"sp{k}")),
                                  Un("f64", Bin("max", Col(f"c{k}"),
                                                Lit(1))))),
            Project(f"an{k}", Bin("div", Un("f64", Col(f"sn{k}")),
                                  Un("f64", Bin("max", Col(f"c{k}"),
                                                Lit(1))))),
        ]
        cs.append(Col(f"c{k}"))
        aps.append(Col(f"ap{k}"))
        ans.append(Col(f"an{k}"))
    nodes += [Project("counts", Stack(tuple(cs))),
              Project("avg_p", Stack(tuple(aps))),
              Project("avg_n", Stack(tuple(ans)))]
    return StagePlan(
        name="q9",
        inputs=(ScanBind("f", (ColSpec("quantity"), ColSpec("price"),
                               ColSpec("profit"))),),
        nodes=tuple(nodes),
        outputs=("counts", "avg_p", "avg_n"),
    )


def run_q9(quantity, price, profit):
    st = compile_stage(q9_plan())
    return st.run({"f": (quantity, price, profit)})


# ------------------------------------------- q67-shape (rollup + rank)


def q67_plan(ncat: int, ncls: int) -> StagePlan:
    """q67-shape: sum(sales) GROUP BY ROLLUP(category, class), then
    rank() OVER (PARTITION BY category ORDER BY sum DESC) on the
    finest level, presented sorted by (category, rank).  Dead groups
    sort last under int sentinels."""
    n = ncat * ncls
    return StagePlan(
        name="q67",
        inputs=(ScanBind("f", (ColSpec("cat"), ColSpec("cls"),
                               ColSpec("sales"))),),
        nodes=(
            Rollup("r", (Col("cat"), Col("cls")), (ncat, ncls),
                   Col("sales"), Mask("f"), mode="rollup"),
            Project("part", Bin("floordiv", Arange(n, "int64"),
                                Lit(ncls))),
            Project("okey", Where(_gt0(Col("r.cnt0")),
                                  Un("neg", Col("r.sum0")),
                                  I64_SENTINEL)),
            WindowRank("rank", Col("part"), Col("okey")),
            Project("kcat", Where(_gt0(Col("r.cnt0")), Col("part"),
                                  Lit(2 ** 31 - 1, "int64"))),
            Sort(("cat_s", "rank_s", "gid_s", "sum_s", "cnt_s"),
                 (Col("kcat"), Col("rank"), Arange(n, "int64"),
                  Col("r.sum0"), Col("r.cnt0")), num_keys=2),
            Project("cls_s", Bin("mod", Col("gid_s"), Lit(ncls))),
        ),
        outputs=("cat_s", "cls_s", "sum_s", "rank_s", "cnt_s",
                 "r.sum1", "r.sumt"),
    )


def run_q67(d, ncat: int, ncls: int):
    st = compile_stage(q67_plan(ncat, ncls))
    return st.run({"f": (d.cat, d.cls, d.sales)})


def cube_plan(ncat: int, ncls: int) -> StagePlan:
    """The CUBE variant of the grouping-sets node: all four grouping
    sets of (cat, cls) as exact int64 folds of the finest level."""
    return StagePlan(
        name="cube2",
        inputs=(ScanBind("f", (ColSpec("cat"), ColSpec("cls"),
                               ColSpec("sales"))),),
        nodes=(Rollup("r", (Col("cat"), Col("cls")), (ncat, ncls),
                      Col("sales"), Mask("f"), mode="cube"),),
        outputs=("r.sum0", "r.cnt0", "r.sum1", "r.cnt1", "r.sumt",
                 "r.cntt", "r.sum2", "r.cnt2"),
    )


def run_cube(d, ncat: int, ncls: int):
    st = compile_stage(cube_plan(ncat, ncls))
    return st.run({"f": (d.cat, d.cls, d.sales)})


# ------------------------------------ q89-shape (sum-over-partition)


def q89_plan(stores: int, items: int) -> StagePlan:
    """q89-shape: per-(store, item) sales vs the whole store's total —
    sum(sales) OVER (PARTITION BY store) broadcast back to each group
    row, presented sorted by (store, item), live groups first."""
    n = stores * items
    return StagePlan(
        name="q89",
        inputs=(ScanBind("f", (ColSpec("store"), ColSpec("item"),
                               ColSpec("sales"))),),
        nodes=(
            Project("gid", Where(
                Mask("f"),
                Bin("add", Bin("mul", Un("i64", Col("store")),
                               Lit(items)),
                    Un("i64", Col("item"))), Lit(0))),
            Project("w", Where(Mask("f"), Col("sales"), Lit(0))),
            SegmentSum("g_sales", Col("w"), Col("gid"), n),
            SegmentSum("g_cnt", Un("i64", Mask("f")), Col("gid"), n),
            Project("part", Bin("floordiv", Arange(n, "int64"),
                                Lit(items))),
            WindowSum("tot", Col("part"), Col("g_sales"), stores),
            Project("key", Where(_gt0(Col("g_cnt")),
                                 Arange(n, "int64"), I64_SENTINEL)),
            Sort(("key_s", "gid_s", "sales_s", "tot_s", "cnt_s"),
                 (Col("key"), Arange(n, "int64"), Col("g_sales"),
                  Col("tot"), Col("g_cnt")), num_keys=1),
            Project("store_s", Bin("floordiv", Col("gid_s"),
                                   Lit(items))),
            Project("item_s", Bin("mod", Col("gid_s"), Lit(items))),
        ),
        outputs=("store_s", "item_s", "sales_s", "tot_s", "cnt_s"),
    )


def run_q89(d, stores: int, items: int):
    st = compile_stage(q89_plan(stores, items))
    return st.run({"f": (d.store, d.item, d.sales)})


# --------------------------------------------------- mesh (shard_map)


def make_q5_multichip_fused(mesh, stores: int, join_capacity: int):
    """The WHOLE q5 pipeline as ONE shard_map program per mesh rank
    (facts row-sharded, date window / store dim replicated, psum at
    the Reduce seam) — the fused twin of models.tpcds
    make_q5_multichip."""
    import jax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map as smap

    axis = mesh.axis_names[0]
    fn, n_args = fused_pipeline_fn(q5_pipeline(stores, join_capacity),
                                   reduce_axis=axis)
    assert n_args == 10
    shard, rep = P(axis), P()
    return jax.jit(smap(fn, mesh=mesh,
                        in_specs=(shard,) * 8 + (rep, rep),
                        out_specs=(rep,) * 5))


def make_q72_multichip_fused(mesh, items: int, max_week: int,
                             join_capacity: int, limit: int = 100,
                             week0: int = 0):
    """Fused twin of make_q72_multichip: one program per rank."""
    import jax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map as smap

    axis = mesh.axis_names[0]
    fn, n_args = fused_pipeline_fn(
        q72_pipeline(items, max_week, join_capacity, limit, week0),
        reduce_axis=axis)
    assert n_args == 7
    shard, rep = P(axis), P()
    return jax.jit(smap(fn, mesh=mesh,
                        in_specs=(shard, shard, shard) + (rep,) * 4,
                        out_specs=(rep,) * 4))
