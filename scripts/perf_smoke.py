"""Perf smoke gate (`make perf-smoke`, ISSUE 4 acceptance): a
two-batch fixed-width conversion over a 64-column schema must prove
the compile-cache contract —

  * batch 1 populates the cache (>=1 miss, each miss = one compile);
  * batch 2 (a different row count in the SAME power-of-two bucket)
    must be pure hits: ZERO new executables compiled, for to-rows,
    from-rows, and the row-hash kernels;
  * batch 2 wall time must not regress past a generous threshold
    (it skips every compile batch 1 paid for);
  * results must be byte-identical to the cache-disabled eager path;
  * the srt_jit_cache_* metrics and the metrics_report cache table
    must light up.

Exits non-zero on the first missing signal."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.pop("SPARK_RAPIDS_TPU_JIT_CACHE", None)   # gate runs cache ON
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402


def fail(msg: str) -> "NoReturn":  # noqa: F821
    print(f"perf-smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def make_table(rows: int, ncols: int = 64):
    from spark_rapids_tpu.columns import dtypes
    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.columns.table import Table

    rng = np.random.default_rng(11)
    cycle = [dtypes.INT64, dtypes.INT32, dtypes.FLOAT64, dtypes.FLOAT32,
             dtypes.INT16, dtypes.INT8, dtypes.BOOL8,
             dtypes.TIMESTAMP_MICROS]
    cols = []
    for i in range(ncols):
        dt = cycle[i % len(cycle)]
        if dt.kind == "float32":
            arr = rng.normal(size=rows).astype(np.float32)
        elif dt.kind == "float64":
            arr = rng.normal(size=rows)
        elif dt.kind == "bool8":
            arr = rng.integers(0, 2, rows).astype(np.uint8)
        else:
            info = np.iinfo(dt.np_dtype)
            arr = rng.integers(info.min // 2, info.max // 2, rows).astype(
                dt.np_dtype)
        validity = rng.integers(0, 2, rows) if i % 5 == 0 else None
        cols.append(Column.from_numpy(arr, validity=validity, dtype=dt))
    return Table(cols)


def main() -> int:
    from spark_rapids_tpu import observability as obs
    obs.enable()
    obs.reset()

    from spark_rapids_tpu.ops import murmur3_32
    from spark_rapids_tpu.ops import row_conversion as RC
    from spark_rapids_tpu.perf.jit_cache import CACHE, bucket_rows

    CACHE.clear(reset_stats=True)

    rows1, rows2 = 4096, 3500           # same power-of-two bucket
    if bucket_rows(rows1) != bucket_rows(rows2):
        fail("smoke misconfigured: batches landed in different buckets")
    t1m, t2m = make_table(rows1), make_table(rows2)
    schema = [c.dtype for c in t1m.columns]

    # ---- batch 1: populates the cache -------------------------------
    t0 = time.perf_counter()
    out1 = RC.convert_to_rows(t1m)
    back1 = RC.convert_from_rows(out1, schema)
    h1 = murmur3_32(t1m, 42)
    jax.block_until_ready((out1.children[0].data,
                           back1.columns[0].data, h1.data))
    batch1_s = time.perf_counter() - t0
    s1 = CACHE.stats()
    if s1["misses"] < 3:
        fail(f"batch 1 should miss for to_rows/from_rows/hash, "
             f"stats={s1}")
    if s1["compiles"] != s1["misses"]:
        fail(f"every miss must compile exactly one executable, "
             f"stats={s1}")

    # ---- batch 2: same bucket => pure hits, zero new compiles -------
    t0 = time.perf_counter()
    out2 = RC.convert_to_rows(t2m)
    back2 = RC.convert_from_rows(out2, schema)
    h2 = murmur3_32(t2m, 42)
    jax.block_until_ready((out2.children[0].data,
                           back2.columns[0].data, h2.data))
    batch2_s = time.perf_counter() - t0
    s2 = CACHE.stats()
    if s2["compiles"] != s1["compiles"]:
        fail(f"batch 2 compiled {s2['compiles'] - s1['compiles']} new "
             f"executable(s); same-bucket reuse is broken "
             f"(before={s1}, after={s2})")
    if s2["hits"] < s1["hits"] + 3:
        fail(f"batch 2 should hit for to_rows/from_rows/hash "
             f"(before={s1}, after={s2})")
    # generous wall threshold: batch 2 skips every compile batch 1
    # paid; 5s floor absorbs shared-CI noise on tiny batches
    threshold = max(5.0, batch1_s)
    if batch2_s > threshold:
        fail(f"batch 2 took {batch2_s:.2f}s > threshold "
             f"{threshold:.2f}s (batch 1 {batch1_s:.2f}s)")

    # ---- correctness vs the cache-disabled eager path ---------------
    os.environ["SPARK_RAPIDS_TPU_JIT_CACHE"] = "0"
    try:
        ref = RC.convert_to_rows(t2m)
        if not np.array_equal(np.asarray(ref.children[0].data),
                              np.asarray(out2.children[0].data)):
            fail("cached to_rows bytes differ from eager path")
        refh = murmur3_32(t2m, 42)
        if not np.array_equal(np.asarray(refh.data), np.asarray(h2.data)):
            fail("cached murmur3_32 differs from eager path")
    finally:
        os.environ.pop("SPARK_RAPIDS_TPU_JIT_CACHE", None)
    for orig, got in zip(t2m.columns, back2.columns):
        a, b = np.asarray(orig.data), np.asarray(got.data)
        if not np.array_equal(a, b):
            fail(f"from_rows round-trip mismatch on {orig.dtype!r}")

    # ---- observability surface --------------------------------------
    text = obs.expose_text()
    for needle in ("srt_jit_cache_hits_total",
                   "srt_jit_cache_misses_total", "srt_jit_compile_ns"):
        if needle not in text:
            fail(f"{needle} missing from Prometheus exposition")
    from spark_rapids_tpu.tools.metrics_report import (
        jit_cache_rows, render_jit_cache_table)
    snap = obs.METRICS.snapshot()
    rows = jit_cache_rows(snap)
    if not any(r["kernel"] == "row_conversion.to_rows" and r["hits"] >= 1
               for r in rows):
        fail(f"metrics_report cache table missing to_rows hits: {rows}")
    for line in render_jit_cache_table(snap):
        print(line)

    # ---- ISSUE 9: calibrated join path + zero-recompile batches -----
    import tempfile

    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.columns.table import Table
    from spark_rapids_tpu.ops import joins
    from spark_rapids_tpu.perf import calibrate

    calib_file = os.path.join(tempfile.mkdtemp(prefix="srt_smoke_"),
                              "calib.json")
    os.environ["SPARK_RAPIDS_TPU_CALIB_CACHE"] = calib_file
    calibrate.forget()
    rng = np.random.default_rng(17)
    n_l, keyspace = 1_000_000, 100_000
    lk = rng.integers(0, keyspace, n_l, dtype=np.int64)
    left = Table([Column.from_numpy(lk)])
    right = Table([Column.from_numpy(
        np.arange(keyspace, dtype=np.int64))])

    # (a) the 1e6-row join must EARN a measured, non-host-rank path
    li, ri = joins.sort_merge_inner_join(left, right)
    jax.block_until_ready((li, ri))
    snap = obs.METRICS.snapshot()
    jp = [tuple(s["labels"])
          for s in snap.get("srt_kernel_path_total", {}).get("series",
                                                             [])]
    picked = [p for op, p in jp if op == "join.inner"]
    if not picked:
        fail("join.inner recorded no kernel path")
    if picked[-1:] == ["host_rank"] and set(picked) == {"host_rank"}:
        fail(f"1e6-row join stayed on the host rank path: {picked}")
    if not os.path.exists(calib_file):
        fail("join calibration verdict was not persisted")

    # (b) device_hash second same-bucket batch: ZERO new executables
    os.environ["SPARK_RAPIDS_TPU_PATH_JOIN_INNER"] = "device_hash"
    try:
        lj1, rj1 = joins.sort_merge_inner_join(left, right)
        jax.block_until_ready((lj1, rj1))
        s3 = CACHE.stats()
        n_l2 = 950_000                      # same power-of-two bucket
        from spark_rapids_tpu.perf.jit_cache import bucket_rows as _br
        if _br(n_l2) != _br(n_l):
            fail("join smoke misconfigured: batches in different "
                 "buckets")
        left2 = Table([Column.from_numpy(lk[:n_l2])])
        lj2, rj2 = joins.sort_merge_inner_join(left2, right)
        jax.block_until_ready((lj2, rj2))
        s4 = CACHE.stats()
        if s4["compiles"] != s3["compiles"]:
            fail(f"second same-bucket join batch compiled "
                 f"{s4['compiles'] - s3['compiles']} new executable(s)")
        # byte-identity vs the host rank oracle
        lo, ro = joins._sort_merge_inner_join_host(left2, right)
        if not (np.array_equal(np.asarray(lj2), np.asarray(lo))
                and np.array_equal(np.asarray(rj2), np.asarray(ro))):
            fail("device_hash join differs from the host rank oracle")
    finally:
        os.environ.pop("SPARK_RAPIDS_TPU_PATH_JOIN_INNER", None)

    # (c) tokenizer batches compile nothing (pure numpy engine)
    from spark_rapids_tpu.ops import json_tokenizer as JT
    docs = ['{"a": %d, "b": "x%d"}' % (i, i) for i in range(20_000)]
    jcol = Column.from_strings(docs)
    s5 = CACHE.stats()
    out_a = JT.get_json_object_tokenized(jcol, "$.b")
    out_b = JT.get_json_object_tokenized(
        Column.from_strings(docs[:15_000]), "$.b")
    if CACHE.stats()["compiles"] != s5["compiles"]:
        fail("tokenizer batches must compile zero executables")
    if out_a.to_pylist()[7] != "x7" or out_b.to_pylist()[7] != "x7":
        fail("tokenizer smoke extraction wrong")

    # (e) ISSUE 11: second fused stage query compiles ZERO executables
    from spark_rapids_tpu.models import tpcds as T
    from spark_rapids_tpu.plan import catalog as PC
    d1 = T.gen_q5(rows=4000, stores=16, days=60)
    PC.run_q5(d1, 16, 1 << 13)
    s_f = CACHE.stats()
    d2 = T.gen_q5(rows=3600, stores=16, days=60, seed=8)
    out_f2 = PC.run_q5(d2, 16, 1 << 13)   # same row bucket
    if CACHE.stats()["compiles"] != s_f["compiles"]:
        fail(f"second fused q5 compiled "
             f"{CACHE.stats()['compiles'] - s_f['compiles']} new "
             f"executable(s); whole-stage reuse is broken")
    ref_f = T.make_q5(16, join_capacity=1 << 13)(d2)
    for g, w in zip(out_f2, ref_f):
        if np.asarray(g).tobytes() != np.asarray(w).tobytes():
            fail("fused q5 bytes differ from the hand-fused oracle")

    # (f) the kernel-path metric + report table light up
    text = obs.expose_text()
    if "srt_kernel_path_total" not in text:
        fail("srt_kernel_path_total missing from exposition")
    from spark_rapids_tpu.tools.metrics_report import \
        render_kernel_path_table
    for line in render_kernel_path_table(obs.METRICS.snapshot()):
        print(line)

    print(f"perf-smoke: OK (batch1 {batch1_s:.2f}s with "
          f"{s1['compiles']} compiles, batch2 {batch2_s:.2f}s with 0; "
          f"join path(s) {sorted(set(picked))}, second-bucket joins, "
          f"tokenizer AND fused q5 stages: 0 new executables)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
