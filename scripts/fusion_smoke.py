"""Whole-stage fusion gate (`make fusion-smoke`, ISSUE 11
acceptance):

  * the fused q3/q5/q72 catalog pipelines must be byte-identical to
    the hand-fused single-jit oracles in models/tpcds;
  * each stage must compile exactly ONE executable (q3 is one stage;
    q5/q72 are partials + finish), and a second same-bucket query
    (different row count, same power-of-two bucket) must compile ZERO
    new executables;
  * the new window (q89) and rollup+rank (q67) stage-IR shapes must
    match their numpy oracles;
  * srt_stage_fusion_total and the metrics_report "stages" table
    must light up, and ``--json`` must carry a "stages" entry.

Exits non-zero on the first missing signal."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.pop("SPARK_RAPIDS_TPU_JIT_CACHE", None)  # gate runs cache ON
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402


def fail(msg: str) -> "NoReturn":  # noqa: F821
    print(f"fusion-smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def _bytes_equal(got, want) -> bool:
    return all(np.asarray(g).tobytes() == np.asarray(w).tobytes()
               for g, w in zip(got, want))


def main() -> int:
    from spark_rapids_tpu import observability as obs
    obs.enable()
    obs.reset()

    from spark_rapids_tpu.models import tpcds as T
    from spark_rapids_tpu.perf.jit_cache import CACHE, bucket_rows
    from spark_rapids_tpu.plan import catalog as C

    CACHE.clear(reset_stats=True)
    W0 = 11_000 // 7

    q5_rows, q3_rows, q72_rows = 8192, 8192, 4096
    d5 = T.gen_q5(rows=q5_rows, stores=32, days=60)
    d3 = T.gen_q3(rows=q3_rows, items=64, days=730, brands=8)
    d72 = T.gen_q72(cs_rows=q72_rows, inv_rows=q72_rows, items=64,
                    days=35)

    # ---- one executable per stage + byte identity -------------------
    runs = {
        "q5": (lambda d=d5: C.run_q5(d, 32, 1 << 15),
               lambda d=d5: T.make_q5(32, join_capacity=1 << 15)(d)),
        "q3": (lambda d=d3: C.run_q3(d, 10_957, years=3, brands=8,
                                     manufact=2),
               lambda d=d3: T.make_q3(10_957, years=3, brands=8,
                                      manufact=2)(d)),
        "q72": (lambda d=d72: C.run_q72(d, 64, 16, 1 << 19, week0=W0),
                lambda d=d72: T.make_q72(64, 16,
                                         join_capacity=1 << 19,
                                         week0=W0)(d)),
    }
    for name, (fused, oracle) in runs.items():
        if not _bytes_equal(fused(), oracle()):
            fail(f"fused {name} differs from the hand-fused oracle")
    expected = {"stage.q3": 1, "stage.q5_partials": 1,
                "stage.q5_finish": 1, "stage.q72_partials": 1,
                "stage.q72_finish": 1}
    ks = CACHE.stats()["kernels"]
    for kernel, want in expected.items():
        got = ks.get(kernel, {}).get("misses", 0)
        if got != want:
            fail(f"{kernel} compiled {got} executables, want exactly "
                 f"{want} (stats={ks})")
    if CACHE.stats()["compiles"] != len(expected):
        fail(f"stage compiles {CACHE.stats()['compiles']} != "
             f"{len(expected)} — something besides the stages "
             f"compiled, or a stage compiled twice")
    print(f"fusion-smoke: q3/q5/q72 byte-identical, one executable "
          f"per stage ({len(expected)} total)")

    # ---- second same-bucket query: ZERO new executables -------------
    compiles = CACHE.stats()["compiles"]
    for rows_a, rows_b in ((q5_rows, 7800), (q3_rows, 7600),
                           (q72_rows, 3900)):
        if bucket_rows(rows_a) != bucket_rows(rows_b):
            fail("smoke misconfigured: second batches left the bucket")
    C.run_q5(T.gen_q5(rows=7800, stores=32, days=60, seed=6), 32,
             1 << 15)
    C.run_q3(T.gen_q3(rows=7600, items=64, days=730, brands=8,
                      seed=4), 10_957, years=3, brands=8, manufact=2)
    C.run_q72(T.gen_q72(cs_rows=3900, inv_rows=3900, items=64,
                        days=35, seed=73), 64, 16, 1 << 19, week0=W0)
    if CACHE.stats()["compiles"] != compiles:
        fail(f"second same-bucket queries compiled "
             f"{CACHE.stats()['compiles'] - compiles} new "
             f"executable(s); stage reuse is broken")
    print("fusion-smoke: second same-bucket q3/q5/q72 compiled 0 new "
          "executables")

    # ---- window + rollup shapes vs numpy oracles --------------------
    d67 = T.gen_q67(rows=6000, ncat=6, ncls=10)
    cat_s, cls_s, sum_s, rank_s, cnt_s, sum1, sumt = C.run_q67(
        d67, 6, 10)
    want_rows, want_sum1, want_tot = T.oracle_q67(d67, 6, 10)
    live = np.asarray(cnt_s) > 0
    got_rows = list(zip(np.asarray(cat_s)[live].tolist(),
                        np.asarray(cls_s)[live].tolist(),
                        np.asarray(sum_s)[live].tolist(),
                        np.asarray(rank_s)[live].tolist()))
    if got_rows != want_rows or np.asarray(sum1).tolist() != want_sum1 \
            or int(sumt) != want_tot:
        fail("q67 rollup+rank shape differs from the numpy oracle")
    d89 = T.gen_q89(rows=6000, stores=4, items=8)
    store_s, item_s, sales_s, tot_s, cnt_s = C.run_q89(d89, 4, 8)
    live = np.asarray(cnt_s) > 0
    got = list(zip(np.asarray(store_s)[live].tolist(),
                   np.asarray(item_s)[live].tolist(),
                   np.asarray(sales_s)[live].tolist(),
                   np.asarray(tot_s)[live].tolist(),
                   np.asarray(cnt_s)[live].tolist()))
    if got != T.oracle_q89(d89, 4, 8):
        fail("q89 window-sum shape differs from the numpy oracle")
    print("fusion-smoke: q67 (rollup + rank) and q89 (window sum) "
          "match their numpy oracles")

    # ---- observability surface --------------------------------------
    text = obs.expose_text()
    if "srt_stage_fusion_total" not in text:
        fail("srt_stage_fusion_total missing from exposition")
    from spark_rapids_tpu.tools.metrics_report import (
        build_report, render_stage_table, stage_rows)
    events = [dict(r) for r in obs.JOURNAL.records("stage_fusion")]
    rows = stage_rows(events)
    if not any(r["stage"] == "q5_partials" and r["fused"] >= 1
               for r in rows):
        fail(f"stages table missing fused q5_partials rows: {rows}")
    if "stages" not in build_report(events):
        fail("metrics_report --json lost the 'stages' entry")
    for line in render_stage_table(events):
        print(line)

    print("fusion-smoke: OK (5 stage executables, 0 recompiles on "
          "same-bucket repeats)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
