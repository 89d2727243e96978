"""Whole-stage fusion (plan/, ISSUE 11): IR digest stability, fused
stages byte-identical to the hand-fused oracles (incl. null validity
and string presentation), zero recompiles on same-bucket repeats,
window/rollup goldens vs numpy, multi-input calibration digests, and
distributed fused-stage byte-identity at world=2."""

import os
import threading

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from spark_rapids_tpu.models import tpcds
from spark_rapids_tpu.perf.calibrate import operands_digest
from spark_rapids_tpu.perf.jit_cache import CACHE, bucket_rows
from spark_rapids_tpu.plan import catalog as C
from spark_rapids_tpu.plan import compiler as PC
from spark_rapids_tpu.plan import ir

STORES = 16
ITEMS = 64
MAX_WEEK = 16
WEEK0 = 11_000 // 7


def _assert_bytes(got, want):
    for i, (g, w) in enumerate(zip(got, want)):
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes(), i


# ----------------------------------------------------------- digests


class TestDigests:

    def test_plan_digest_stable_across_builds(self):
        a = C.q5_partials_plan(STORES, 1 << 13)
        b = C.q5_partials_plan(STORES, 1 << 13)
        assert a is not b and a.digest == b.digest
        assert C.q5_pipeline(STORES, 1 << 13).digest == \
            C.q5_pipeline(STORES, 1 << 13).digest

    def test_plan_digest_tracks_parameters(self):
        base = C.q5_partials_plan(STORES, 1 << 13).digest
        assert C.q5_partials_plan(STORES, 1 << 14).digest != base
        assert C.q5_partials_plan(STORES * 2, 1 << 13).digest != base
        assert C.q3_plan(10_957, 3, 8, 2).digest != \
            C.q3_plan(10_957, 3, 8, 3).digest

    def test_operands_digest_folds_all_operands(self):
        """Regression (ISSUE 11 satellite): a multi-input verdict key
        must change when ANY operand's schema or size class changes —
        the old per-op digest ignored the other side's bucket, so a
        stage whose build side crossed a size class reused a verdict
        measured at another scale."""
        base = operands_digest([("int64", 1000), ("int64", 1000)])
        # same size classes -> same key (bucket granularity)
        assert operands_digest([("int64", 900),
                                ("int64", 600)]) == base
        # the RIGHT side crossing a size class must re-key
        assert operands_digest([("int64", 1000),
                                ("int64", 100_000)]) != base
        # ... and so must the LEFT side
        assert operands_digest([("int64", 100_000),
                                ("int64", 1000)]) != base
        # ... and either side's schema
        assert operands_digest([("int64", 1000),
                                ("int32", 1000)]) != base
        assert operands_digest([("int64", 1000), ("int64", 1000)],
                               extra="x") != base

    def test_join_digest_keys_on_both_sides(self):
        """The join router's calibration key (ops/joins.py) now rides
        operands_digest: growing the build side past a size class
        yields a different verdict key."""
        sm = operands_digest([("sdl", 1 << 18), ("sdr", 1 << 10)],
                             extra="join:EQUAL")
        lg = operands_digest([("sdl", 1 << 18), ("sdr", 1 << 20)],
                             extra="join:EQUAL")
        assert sm != lg

    def test_validate_rejects_bad_plans(self):
        with pytest.raises(ValueError, match="undefined"):
            ir.StagePlan(
                "bad", (ir.ScanBind("f", (ir.ColSpec("a"),)),),
                (), ("missing",)).validate()
        with pytest.raises(ValueError, match="duplicate"):
            ir.StagePlan(
                "bad2", (ir.ScanBind("f", (ir.ColSpec("a"),)),),
                (ir.Project("a", ir.Col("a")),), ("a",)).validate()


# ----------------------------------------------- fused byte-identity


class TestFusedByteIdentity:

    def test_q5(self):
        d = tpcds.gen_q5(rows=4000, stores=STORES, days=60)
        _assert_bytes(C.run_q5(d, STORES, 1 << 13),
                      tpcds.make_q5(STORES, join_capacity=1 << 13)(d))

    def test_q72(self):
        d = tpcds.gen_q72(cs_rows=3000, inv_rows=3000, items=ITEMS,
                          days=35)
        _assert_bytes(
            C.run_q72(d, ITEMS, MAX_WEEK, 1 << 18, week0=WEEK0),
            tpcds.make_q72(ITEMS, MAX_WEEK, join_capacity=1 << 18,
                           week0=WEEK0)(d))

    def test_q3(self):
        d = tpcds.gen_q3(rows=6000, items=64, days=730, brands=8)
        _assert_bytes(
            C.run_q3(d, 10_957, years=3, brands=8, manufact=2),
            tpcds.make_q3(10_957, years=3, brands=8, manufact=2)(d))

    def test_q9(self):
        q, p, n = tpcds.gen_q9(rows=20_000)
        _assert_bytes(C.run_q9(q, p, n), tpcds.run_q9(q, p, n))

    def test_q72_fused_capacity_retry(self):
        """A too-small join budget doubles through the centralized
        capacity-retry driver until the fused stage's overflow flag
        clears — same contract as the hand pipeline."""
        d = tpcds.gen_q72(cs_rows=1200, inv_rows=1200, items=4,
                          days=35)
        outs = C.run_q72(d, 4, MAX_WEEK, 1 << 18, week0=WEEK0)
        assert not bool(np.asarray(outs[-1]))
        assert _rows72(outs) == tpcds.oracle_q72(d, 4, MAX_WEEK,
                                                 week0=WEEK0)

    def test_q5_string_presentation(self):
        """Strings stay at the presentation boundary: the fused q5
        output drives present_q5's dictionary-id -> string decode
        exactly like the hand pipeline's."""
        d = tpcds.gen_q5(rows=2000, stores=8, days=60)
        names = ["S%02d" % i for i in range(8)]
        rows = tpcds.present_q5(C.run_q5(d, 8, 1 << 12), names)
        want = tpcds.oracle_q5(d, 8)
        assert rows == [(names[w[0]], w[1], w[2], w[3]) for w in want]

    @pytest.mark.parametrize("stage", ["q3", "q5_partials"])
    def test_unfused_engine_byte_identical(self, stage):
        """The reference walk (``run_unfused``) and the fused
        executable (``run``) give the same bytes."""
        if stage == "q3":
            d = tpcds.gen_q3(rows=6000, items=64, days=730, brands=8)
            plan = C.q3_plan(10_957, 3, 8, 2)
            inputs = {"s": (d.s_date, d.s_item, d.s_price),
                      "dims": (d.d_moy, d.d_year, d.i_brand,
                               d.i_manufact)}
        else:
            d = tpcds.gen_q5(rows=1500, stores=STORES, days=60)
            plan = C.q5_partials_plan(STORES, 1 << 12)
            inputs = {"s": (d.s_date, d.s_store, d.s_price,
                            d.s_profit),
                      "r": (d.r_date, d.r_store, d.r_amt, d.r_loss),
                      "d": (d.d_date,)}
        st = PC.compile_stage(plan)
        want = st.run_unfused(inputs)
        got = st.run(inputs)
        assert len(got) == len(want) == len(plan.outputs)
        _assert_bytes(got, want)


def _rows72(outs):
    items, weeks, cnts, _of = outs
    cnts = np.asarray(cnts)
    live = cnts > 0
    return [tuple(int(x) for x in row) for row in zip(
        np.asarray(items)[live], np.asarray(weeks)[live], cnts[live])]


# -------------------------------------------------- nulls in a stage


class TestNullValidity:

    def test_join_probe_with_validity_column(self):
        """A fact side carrying a null-validity column: invalid rows
        never match (the inner_join_device NULL-inequality contract),
        and bucket-pad rows ride the same validity lane (pad=0 ==
        invalid)."""
        rows, stores = 3000, 8
        d = tpcds.gen_q5(rows=rows, stores=stores, days=60)
        ok = np.asarray(
            np.arange(rows) % 3 != 0)  # every 3rd fact row is null
        plan = ir.StagePlan(
            name="q5_nulls",
            inputs=(
                ir.ScanBind("s", (ir.ColSpec("s_date", pad=-1),
                                  ir.ColSpec("s_store"),
                                  ir.ColSpec("s_price"),
                                  ir.ColSpec("s_ok"))),
                ir.ScanBind("d", (ir.ColSpec("d_date", pad=-2),)),
            ),
            nodes=(
                ir.JoinProbe("j", ir.Col("s_date"), ir.Col("d_date"),
                             1 << 13,
                             left_valid=ir.Un("b", ir.Col("s_ok"))),
                ir.Project("st", ir.Where(
                    ir.Col("j.valid"),
                    ir.Idx(ir.Col("s_store"), ir.Col("j.li")),
                    ir.Lit(0))),
                ir.SegmentSum("sales", ir.Where(
                    ir.Col("j.valid"),
                    ir.Idx(ir.Col("s_price"), ir.Col("j.li")),
                    ir.Lit(0)), ir.Col("st"), stores),
                ir.SegmentSum("seen", ir.Un("i64", ir.Col("j.valid")),
                              ir.Col("st"), stores),
            ),
            outputs=("sales", "seen"),
        )
        st = PC.compile_stage(plan)
        sales, seen = st.run({
            "s": (d.s_date, d.s_store, d.s_price,
                  ok.astype(np.int8)),
            "d": (d.d_date,)})
        # numpy oracle over only the valid rows
        dd = set(np.asarray(d.d_date).tolist())
        want_sales = np.zeros(stores, np.int64)
        want_seen = np.zeros(stores, np.int64)
        sdate = np.asarray(d.s_date)
        sstore = np.asarray(d.s_store)
        sprice = np.asarray(d.s_price)
        for i in range(rows):
            if ok[i] and int(sdate[i]) in dd:
                want_sales[sstore[i]] += sprice[i]
                want_seen[sstore[i]] += 1
        assert np.asarray(sales).tolist() == want_sales.tolist()
        assert np.asarray(seen).tolist() == want_seen.tolist()


# --------------------------------------------------- compile reuse


class TestCompileReuse:

    def test_one_executable_per_stage_zero_on_repeat(self):
        """The acceptance gate's core property: each stage compiles
        ONE executable, and a second same-bucket query (different row
        count) compiles ZERO."""
        CACHE.clear(reset_stats=True)
        d1 = tpcds.gen_q5(rows=4000, stores=STORES, days=60)
        C.run_q5(d1, STORES, 1 << 13)
        ks = CACHE.stats()["kernels"]
        assert ks["stage.q5_partials"]["misses"] == 1
        assert ks["stage.q5_finish"]["misses"] == 1
        compiles = CACHE.stats()["compiles"]
        assert bucket_rows(3800) == bucket_rows(4000)
        d2 = tpcds.gen_q5(rows=3800, stores=STORES, days=60, seed=9)
        out2 = C.run_q5(d2, STORES, 1 << 13)
        assert CACHE.stats()["compiles"] == compiles, \
            "second same-bucket fused query must compile nothing"
        ks = CACHE.stats()["kernels"]
        assert ks["stage.q5_partials"]["hits"] >= 1
        _assert_bytes(out2, tpcds.make_q5(
            STORES, join_capacity=1 << 13)(d2))

    def test_q3_single_stage_single_executable(self):
        CACHE.clear(reset_stats=True)
        d = tpcds.gen_q3(rows=5000, items=64, days=730, brands=8)
        C.run_q3(d, 10_957, years=3, brands=8, manufact=2)
        assert CACHE.stats()["kernels"]["stage.q3"]["misses"] == 1
        C.run_q3(d, 10_957, years=3, brands=8, manufact=2)
        assert CACHE.stats()["kernels"]["stage.q3"]["misses"] == 1
        assert CACHE.stats()["kernels"]["stage.q3"]["hits"] >= 1


# ------------------------------------------------- window + rollup


class TestWindowRollup:

    def test_q67_rollup_rank_golden(self):
        ncat, ncls = 6, 10
        d = tpcds.gen_q67(rows=5000, ncat=ncat, ncls=ncls)
        cat_s, cls_s, sum_s, rank_s, cnt_s, sum1, sumt = \
            C.run_q67(d, ncat, ncls)
        want_rows, want_sum1, want_tot = tpcds.oracle_q67(
            d, ncat, ncls)
        live = np.asarray(cnt_s) > 0
        got = list(zip(np.asarray(cat_s)[live].tolist(),
                       np.asarray(cls_s)[live].tolist(),
                       np.asarray(sum_s)[live].tolist(),
                       np.asarray(rank_s)[live].tolist()))
        assert got == want_rows
        assert np.asarray(sum1).tolist() == want_sum1
        assert int(sumt) == want_tot

    def test_cube_grouping_sets_golden(self):
        ncat, ncls = 5, 7
        d = tpcds.gen_q67(rows=4000, ncat=ncat, ncls=ncls, seed=3)
        outs = C.run_cube(d, ncat, ncls)
        for got, want in zip(outs, tpcds.oracle_cube(d, ncat, ncls)):
            got = np.asarray(got).tolist()
            want = want.tolist() if hasattr(want, "tolist") else want
            assert got == want

    def test_q89_window_sum_golden(self):
        stores, items = 4, 8
        d = tpcds.gen_q89(rows=5000, stores=stores, items=items)
        store_s, item_s, sales_s, tot_s, cnt_s = C.run_q89(
            d, stores, items)
        live = np.asarray(cnt_s) > 0
        got = list(zip(np.asarray(store_s)[live].tolist(),
                       np.asarray(item_s)[live].tolist(),
                       np.asarray(sales_s)[live].tolist(),
                       np.asarray(tot_s)[live].tolist(),
                       np.asarray(cnt_s)[live].tolist()))
        assert got == tpcds.oracle_q89(d, stores, items)

    def test_window_rank_ties_break_by_row(self):
        """Equal order keys rank by row index (stable) — the property
        the q67 presentation depends on."""
        plan = ir.StagePlan(
            "rank_ties",
            (ir.ScanBind("f", (ir.ColSpec("part"), ir.ColSpec("v")),
                         bucket=False),),
            (ir.WindowRank("rank", ir.Col("part"),
                           ir.Un("neg", ir.Col("v"))),),
            ("rank",))
        (rank,) = PC.compile_stage(plan).run({
            "f": (np.array([0, 0, 0, 1, 1], np.int64),
                  np.array([5, 9, 5, 3, 3], np.int64))})
        assert np.asarray(rank).tolist() == [1, 0, 2, 0, 1]


# ------------------------------------------------------------ mesh


@pytest.fixture
def mesh8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 (virtual) devices")
    return Mesh(np.array(devs[:8]), ("data",))


class TestMeshFused:

    def test_q5_fused_one_program_per_rank(self, mesh8):
        rows = 4096
        d = tpcds.gen_q5(rows=rows, stores=STORES, days=60)
        d = d._replace(r_date=d.r_date[:rows // 8 * 8],
                       r_store=d.r_store[:rows // 8 * 8],
                       r_amt=d.r_amt[:rows // 8 * 8],
                       r_loss=d.r_loss[:rows // 8 * 8])
        args = (d.s_date, d.s_store, d.s_price, d.s_profit,
                d.r_date, d.r_store, d.r_amt, d.r_loss,
                d.d_date, d.st_id)
        _assert_bytes(
            C.make_q5_multichip_fused(mesh8, STORES, 1 << 11)(*args),
            tpcds.make_q5_multichip(mesh8, STORES,
                                    join_capacity=1 << 11)(*args))

    def test_q72_fused_one_program_per_rank(self, mesh8):
        d = tpcds.gen_q72(cs_rows=2048, inv_rows=2048, items=ITEMS,
                          days=35)
        args = (d.cs_item, d.cs_date, d.cs_qty, d.inv_item,
                d.inv_date, d.inv_qty, d.item_id)
        _assert_bytes(
            C.make_q72_multichip_fused(mesh8, ITEMS, MAX_WEEK,
                                       1 << 16, week0=WEEK0)(*args),
            tpcds.make_q72_multichip(mesh8, ITEMS, MAX_WEEK,
                                     join_capacity=1 << 16,
                                     week0=WEEK0)(*args))


# ---------------------------------------------- distributed world=2


class TestDistributedFused:

    @pytest.fixture
    def crc_on(self):
        from spark_rapids_tpu.shuffle import kudo
        prior = kudo.set_crc_enabled(True)
        yield
        kudo.set_crc_enabled(prior)

    @pytest.mark.slow  # tier-1 time budget: dist-smoke runs the
    # fused runner (the default) across real processes every CI run
    def test_q5_world2_fused_byte_identical(self, tmp_path,
                                            crc_on):
        """Two in-process ranks over the real socket shuffle service:
        each rank runs ONE fused partials program, exchanges kudo
        tables, runs ONE fused finish program — bytes identical to the
        single-process hand pipeline."""
        from spark_rapids_tpu.distributed import runner as R
        from spark_rapids_tpu.distributed.service import ShuffleService
        params = dict(rows=512, join_capacity=1 << 11)
        addrs = [f"unix:{os.path.join(str(tmp_path), f'f{r}.sock')}"
                 for r in range(2)]
        svcs = [ShuffleService(r, 2, addrs).start() for r in range(2)]
        outs = [None, None]
        errs = [None, None]

        def work(r):
            try:
                outs[r] = R.run_dist_q5(params, transport=svcs[r])
            except Exception as e:  # noqa: BLE001
                errs[r] = e

        try:
            ts = [threading.Thread(target=work, args=(r,))
                  for r in range(2)]
            [t.start() for t in ts]
            [t.join(120) for t in ts]
        finally:
            for s in svcs:
                s.stop()
        assert errs == [None, None], errs
        ref = R.single_q5(dict(params, world=2))
        for r in range(2):
            for k in ("key", "sales", "rets", "profit"):
                assert outs[r][k].tobytes() == ref[k].tobytes(), \
                    (r, k)
            assert bool(outs[r]["overflow"]) == bool(ref["overflow"])


# ------------------------------------------------------ observability


class TestStageObservability:

    def test_counters_journal_and_report_table(self):
        from spark_rapids_tpu import observability as obs
        from spark_rapids_tpu.tools.metrics_report import (
            build_report, render_stage_table, stage_rows)
        obs.enable()
        d = tpcds.gen_q3(rows=3000, items=64, days=730, brands=8)
        C.run_q3(d, 10_957, years=3, brands=8, manufact=2)
        text = obs.expose_text()
        assert "srt_stage_fusion_total" in text
        events = [dict(r)
                  for r in obs.JOURNAL.records("stage_fusion")]
        assert any(e.get("stage") == "q3" for e in events)
        rows = stage_rows(events)
        assert any(r["stage"] == "q3" and r["fused"] >= 1
                   for r in rows)
        table = "\n".join(render_stage_table(events))
        assert "q3" in table
        report = build_report(events)
        assert any(r["stage"] == "q3" for r in report["stages"])


# ------------------------------------------------------- window slice


def _slice_plan(capacity):
    """Sum ``v`` over the rows of ``d`` in [lo, hi], read through a
    WindowSlice of ``capacity`` rows of the date-ordered input ``f``."""
    return ir.StagePlan(
        "t_window_slice",
        inputs=(ir.ScanBind("f", (ir.ColSpec("d"), ir.ColSpec("v"))),
                ir.ScanBind("q", (ir.ColSpec("lo"), ir.ColSpec("hi")),
                            bucket=False)),
        nodes=(
            ir.WindowSlice("z", "f", ir.Col("d"), ir.Col("lo"),
                           ir.Col("hi"), ("d", "v"), capacity),
            ir.Project("keep", ir.Bin("and", ir.Col("z.valid"), ir.Bin(
                "and", ir.Bin("ge", ir.Col("z.d"), ir.Col("lo")),
                ir.Bin("le", ir.Col("z.d"), ir.Col("hi"))))),
            ir.Project("total", ir.Un("sum", ir.Where(
                ir.Col("keep"), ir.Col("z.v"), ir.Lit(0, "int64")))),
        ),
        outputs=("total", "z.over"))


@pytest.mark.parametrize("lo,hi", [(0, 2), (3, 5), (6, 9), (9, 9),
                                   (-5, -1), (10, 20), (4, 3)])
def test_window_slice_reads_the_rows_of_its_range(lo, hi):
    """Sorted true rows with a pad tail: every range, the first rows,
    the last (a slice clamped back from the bucket's end), before and
    past every key and an empty one, sums as numpy does over the whole
    column, and ``over`` is False while the range fits the slice."""
    rng = np.random.default_rng((lo + 10) * 31 + hi + 10)
    d = np.sort(rng.integers(0, 10, 1000)).astype(np.int32)
    v = rng.integers(-1000, 1000, 1000).astype(np.int64)
    b = bucket_rows(len(d))
    cols = PC.Padded((np.concatenate([d, np.zeros(b - len(d), np.int32)]),
                      np.concatenate([v, np.full(b - len(v), 7)])), len(d))
    inside = (d >= lo) & (d <= hi)
    assert inside.sum() <= 512
    stage = PC.CompiledStage(_slice_plan(512))
    q = (np.int32(lo), np.int32(hi))
    total, over = stage.run({"f": cols, "q": q})
    assert int(total) == int(v[inside].sum()) and not bool(over)
    total, over = stage.run_unfused({"f": (d, v), "q": q})
    assert int(total) == int(v[inside].sum()) and not bool(over)
    if (lo, hi) == (3, 5):      # a slice one row short: flagged
        _total, over = PC.CompiledStage(_slice_plan(
            int(inside.sum()) - 1)).run({"f": cols, "q": q})
        assert bool(over)
