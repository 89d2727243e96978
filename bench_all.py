"""Extended benchmark suite covering the BASELINE.json configs beyond the
headline row-conversion metric (bench.py remains the driver's single-line
entry):

  config 2: hash group-by aggregate on a 1e7-row int64/float64 table
  config 3: inner join on two large int64 tables
  config 4: string ops (get_json_object + parse_url + substring) on 1e6
            rows
  plus: murmur3/xxhash64 hash throughput, OOM state machine ops/sec
        (python vs native)

Writes BENCH_EXTRA.json and prints it.  One process, on
``jax.devices()[0]``: without a TPU it exits non-zero unless the caller
itself set ``JAX_PLATFORMS=cpu``, and the output names the platform,
device kind and device count it ran on.  Timed work ends in
``block_until_ready`` (or a host readback of the result).
"""

import json
import time

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402


def _path_snapshot():
    from spark_rapids_tpu import observability as obs
    fam = obs.METRICS.snapshot().get("srt_kernel_path_total", {})
    return {tuple(s["labels"]): s["value"] for s in fam.get("series", [])}


def _taken_path(op, before):
    """Calibrated engine(s) ``op`` actually ran since ``before`` (a
    _path_snapshot) — the bench table's path field is routing evidence
    read back from srt_kernel_path_total, not a hard-coded guess
    (ISSUE 9)."""
    grown = sorted({k[1] for k, v in _path_snapshot().items()
                    if k[0] == op and v > before.get(k, 0)})
    return "calibrated: " + "+".join(grown) if grown else "?"


def bench_groupby(n=10_000_000, groups=10_000):
    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.columns.table import Table
    from spark_rapids_tpu.ops import groupby as gb
    rng = np.random.default_rng(0)
    keys = Table([Column.from_numpy(
        rng.integers(0, groups, n, dtype=np.int64))])
    vals = Column.from_numpy(rng.normal(size=n))
    results = {}
    for label in ("cold", "warm"):  # cold includes eager-op compiles
        t0 = time.perf_counter()
        out = gb.groupby_aggregate(keys, [vals, vals],
                                   [gb.SUM, gb.COUNT])
        total = int(np.asarray(out.columns[2].data).sum())
        dt = time.perf_counter() - t0
        assert total == n
        results[label] = round(dt, 3)
    return {"rows": n, "groups": groups, "seconds": results,
            "warm_rows_per_sec_M": round(n / results["warm"] / 1e6, 1)}


def bench_join(n=10_000_000, keyspace=1_000_000):
    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.columns.table import Table
    from spark_rapids_tpu.ops import joins
    rng = np.random.default_rng(1)
    left = Table([Column.from_numpy(
        rng.integers(0, keyspace, n, dtype=np.int64))])
    right = Table([Column.from_numpy(
        np.arange(keyspace, dtype=np.int64))])
    results = {}
    for label in ("cold", "warm"):  # cold includes calibration+compiles
        before = _path_snapshot()
        t0 = time.perf_counter()
        li, ri = joins.sort_merge_inner_join(left, right)
        jax.block_until_ready((li, ri))
        dt = time.perf_counter() - t0
        pairs = int(li.shape[0])
        results[label] = round(dt, 3)
    path = _taken_path("join.inner", before)
    out = {"left_rows": n, "right_rows": keyspace, "pairs": pairs,
           "seconds": results, "path": path,
           "warm_rows_per_sec_M": round(n / results["warm"] / 1e6, 1)}

    # string-key variant (short keys: device-encodable)
    sl = Table([Column.from_strings(
        ["k%07d" % (i % keyspace) for i in range(n // 10)])])
    sr = Table([Column.from_strings(
        ["k%07d" % i for i in range(keyspace // 10)])])
    joins.sort_merge_inner_join(sl, sr)
    before = _path_snapshot()
    t0 = time.perf_counter()
    li, ri = joins.sort_merge_inner_join(sl, sr)
    jax.block_until_ready((li, ri))
    dt = time.perf_counter() - t0
    out["string_keys_1e6"] = {
        "left_rows": n // 10, "seconds": round(dt, 3),
        "warm_rows_per_sec_M": round(n / 10 / dt / 1e6, 2),
        "path": _taken_path("join.inner", before)}
    return out


def bench_strings(n=1_000_000):
    """All figures in k rows/sec; every entry names its code path."""
    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.ops import json_path, parse_uri
    from spark_rapids_tpu.ops.substring_index import substring_index

    def timed(fn, *args):
        fn(*args)                      # warm (compile)
        t0 = time.perf_counter()
        out = fn(*args)
        return out, time.perf_counter() - t0

    docs = [f'{{"user": {{"id": {i}, "name": "u{i}"}}, "n": {i % 97}}}'
            for i in range(n)]
    jcol = Column.from_strings(docs)
    before_json = _path_snapshot()
    out, dt_json = timed(json_path.get_json_object, jcol,
                         "$.user.name")
    assert out.to_pylist()[1] == "u1"

    urls = [f"https://host{i % 50}.example.com/p/{i}?k={i}&x=1"
            for i in range(n)]
    ucol = Column.from_strings(urls)
    # warm the compile on a SEPARATE column so the timed first-extract
    # below really pays the span analysis (the analysis memo is
    # per-column; timing a second call on the same column would measure
    # the cached regime — that's the next_3_components entry)
    parse_uri.parse_uri_to_host(Column.from_strings(urls))
    t0 = time.perf_counter()
    _hosts = parse_uri.parse_uri_to_host(ucol)
    dt_uri = time.perf_counter() - t0
    # subsequent components reuse the cached span analysis
    t0 = time.perf_counter()
    parse_uri.parse_uri_to_protocol(ucol)
    parse_uri.parse_uri_to_query(ucol)
    parse_uri.parse_uri_to_path(ucol)
    dt_uri_rest = time.perf_counter() - t0

    strs = Column.from_strings([f"a{i}.b{i}.c{i}" for i in range(n)])
    _sub, dt_sub = timed(substring_index, strs, ".", 2)
    return {
        "rows": n,
        "unit": "k_rows_per_sec",
        "get_json_object": {
            "k_rows_per_sec": round(n / dt_json / 1e3, 1),
            "path": _taken_path("get_json_object", before_json)},
        "parse_url_host_first": {
            "k_rows_per_sec": round(n / dt_uri / 1e3, 1),
            "path": "device analyze + materialize"},
        "parse_url_next_3_components": {
            "k_rows_per_sec": round(3 * n / dt_uri_rest / 1e3, 1),
            "path": "cached device analysis, materialize only"},
        "substring_index": {
            "k_rows_per_sec": round(n / dt_sub / 1e3, 1),
            "path": "device match scan + numpy gather (r4 fix)"},
    }


def bench_decoders(n=1_000_000):
    """protobuf / from_json / GBK — the four r3 host-loop families,
    now device/vectorized (r4).  k rows/sec, path-labeled."""
    import struct as _st

    from spark_rapids_tpu.columns import dtypes
    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.ops import protobuf as pb
    from spark_rapids_tpu.ops import json_utils as JU
    from spark_rapids_tpu.ops import strings_misc as SM

    def timed(fn, *args):
        fn(*args)
        t0 = time.perf_counter()
        fn(*args)
        return time.perf_counter() - t0

    def varint(v):
        out = b""
        v &= (1 << 64) - 1
        while True:
            b = v & 0x7F
            v >>= 7
            if v:
                out += bytes([b | 0x80])
            else:
                return out + bytes([b])

    msgs = [(b"\x08" + varint(i)                      # field 1 varint
             + b"\x12" + varint(8) + b"payload%d" % (i % 10)  # field 2
             + b"\x19" + _st.pack("<d", 1.5 * i))     # field 3 fixed64
            for i in range(n)]
    pcol = Column.from_strings(msgs)
    pfields = [pb.Field(1, dtypes.INT64, name="a"),
               pb.Field(2, dtypes.STRING, name="s"),
               pb.Field(3, dtypes.FLOAT64, encoding=pb.FIXED,
                        name="d")]
    dt_pb = timed(pb.decode_protobuf_to_struct, pcol, pfields)

    jdocs = [f'{{"a": {i}, "s": "u{i}", "d": {i}.5}}'
             for i in range(n)]
    jcol = Column.from_strings(jdocs)
    jfields = [("a", dtypes.INT64), ("s", dtypes.STRING),
               ("d", dtypes.FLOAT64)]
    before_fj = _path_snapshot()
    dt_fj = timed(JU.from_json_to_structs, jcol, jfields)

    gbk_rows = [("值%d中文" % i).encode("gbk") for i in range(n)]
    gcol = Column.from_strings(gbk_rows)
    dt_gbk = timed(SM.decode_to_utf8, gcol, "GBK", SM.REPLACE)

    rmdocs = [f'{{"id": {i}, "tag": "t{i % 9}", "ok": true}}'
              for i in range(n)]
    rmcol = Column.from_strings(rmdocs)
    before_rm = _path_snapshot()
    dt_rm = timed(JU.from_json_to_raw_map, rmcol)

    return {
        "rows": n,
        "from_json_raw_map": {
            "k_rows_per_sec": round(n / dt_rm / 1e3, 1),
            "path": _taken_path("from_json_raw_map", before_rm)},
        "protobuf_decode": {
            "k_rows_per_sec": round(n / dt_pb / 1e3, 1),
            "path": "device masked-scan (protobuf_device)"},
        "from_json_structs": {
            "k_rows_per_sec": round(n / dt_fj / 1e3, 1),
            "path": _taken_path("from_json_structs", before_fj)},
        "gbk_decode": {
            "k_rows_per_sec": round(n / dt_gbk / 1e3, 1),
            "path": "vectorized table decode (r4; was per-row codec)"},
    }


def bench_hash(n=10_000_000):
    import jax.numpy as jnp
    from spark_rapids_tpu.columns import dtypes
    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.ops import hash as H
    rng = np.random.default_rng(2)
    col = Column.from_numpy(rng.integers(-2**60, 2**60, n,
                                         dtype=np.int64))

    def step(salt):
        c = Column(dtypes.INT64, n, data=col.data + salt)
        h = H.murmur3_32([c], 42).data
        x = H.xxhash64([c]).data
        # return the hash arrays: jit outputs must be materialized
        return h, x, h[0].astype(jnp.int64) + salt

    stepj = jax.jit(step)
    jax.block_until_ready(stepj(jnp.int64(0)))      # compile + warm
    salt = jnp.int64(0)
    K = 20
    t0 = time.perf_counter()
    for _ in range(K):
        h, x, salt = stepj(salt)
    jax.block_until_ready((h, x, salt))
    dt = (time.perf_counter() - t0) / K
    return {"rows": n, "seconds_per_pass": round(dt, 4),
            "hash_rows_per_sec_M": round(n / dt / 1e6, 0),
            "note": "murmur3_32 + xxhash64 per pass, chained timing"}


def bench_oom_machine(ops=20_000):
    import threading
    results = {}
    for impl in ("python", "native"):
        if impl == "python":
            from spark_rapids_tpu.memory.resource import \
                LimitingMemoryResource
            from spark_rapids_tpu.memory.spark_resource_adaptor import \
                SparkResourceAdaptor
            a = SparkResourceAdaptor(LimitingMemoryResource(1 << 40))
        else:
            from spark_rapids_tpu.memory import native_adaptor
            if not native_adaptor.available():
                continue
            a = native_adaptor.NativeSparkResourceAdaptor(1 << 40)
        tid = threading.get_ident()
        a.start_dedicated_task_thread(tid, 1)
        t0 = time.perf_counter()
        for _ in range(ops):
            a.allocate(64)
            a.deallocate(64)
        dt = time.perf_counter() - t0
        a.task_done(1)
        a.shutdown()
        results[impl] = round(ops * 2 / dt / 1e3, 1)
    return {"alloc_dealloc_kops_per_sec": results}


def bench_tpcds(rows=2_000_000):
    """TPC-DS-shaped flagship pipelines (models/tpcds.py): per-query
    wall time for one fully-jitted scan->join->group->order program,
    warm (post-compile) timings."""
    from spark_rapids_tpu.models import tpcds
    out = {}

    d5 = tpcds.gen_q5(rows=rows, stores=64, days=120)
    q5 = tpcds.make_q5(64, join_capacity=1 << 19)
    t0 = time.perf_counter()
    res5 = q5(d5)
    jax.block_until_ready(res5)
    assert not bool(res5[-1]), "q5 bench overflowed its join capacity"
    out["q5_compile_plus_run_s"] = round(time.perf_counter() - t0, 3)
    t0 = time.perf_counter()
    jax.block_until_ready(q5(d5))
    warm = time.perf_counter() - t0
    out["q5_warm_s"] = round(warm, 4)
    out["q5_rows_per_s"] = round(rows / warm)

    q, p, n = tpcds.gen_q9(rows=rows)
    jax.block_until_ready(tpcds.run_q9(q, p, n))
    t0 = time.perf_counter()
    jax.block_until_ready(tpcds.run_q9(q, p, n))
    warm = time.perf_counter() - t0
    out["q9_warm_s"] = round(warm, 4)
    out["q9_rows_per_s"] = round(rows / warm)

    # fact-fact pair count ~ cs*inv/items: 250k*250k/16k ~ 3.8M < 2^22
    d72 = tpcds.gen_q72(cs_rows=rows // 8, inv_rows=rows // 8,
                        items=16384, days=70)
    q72 = tpcds.make_q72(16384, 16, join_capacity=1 << 22,
                         week0=11_000 // 7)
    res = q72(d72)
    jax.block_until_ready(res)
    assert not bool(res[-1]), "q72 bench overflowed its join capacity"
    t0 = time.perf_counter()
    jax.block_until_ready(q72(d72))
    warm = time.perf_counter() - t0
    out["q72_warm_s"] = round(warm, 4)
    out["q72_cs_rows_per_s"] = round(rows // 8 / warm)

    d3 = tpcds.gen_q3(rows=rows, items=1024, days=730, brands=64)
    q3 = tpcds.make_q3(10_957, years=3, brands=64, manufact=2)
    jax.block_until_ready(q3(d3))
    t0 = time.perf_counter()
    jax.block_until_ready(q3(d3))
    warm = time.perf_counter() - t0
    out["q3_warm_s"] = round(warm, 4)
    out["q3_rows_per_s"] = round(rows / warm)

    d7 = tpcds.gen_q7(rows=rows, items=1024)
    q7 = tpcds.make_q7(1024)
    jax.block_until_ready(q7(d7))
    t0 = time.perf_counter()
    jax.block_until_ready(q7(d7))
    warm = time.perf_counter() - t0
    out["q7_warm_s"] = round(warm, 4)
    out["q7_rows_per_s"] = round(rows / warm)
    return out


def main():
    # the path fields are read back from srt_kernel_path_total — the
    # registry must be on for the evidence to exist
    from bench import device_fields, require_device
    from spark_rapids_tpu import observability as obs
    from spark_rapids_tpu.perf.jit_cache import enable_persistent_cache
    enable_persistent_cache()
    dev = require_device()
    obs.enable()
    out = {
        **device_fields(dev),
        "measured": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "groupby_1e7": bench_groupby(),
        "join_1e7": bench_join(),
        "string_ops_1e6": bench_strings(),
        "decoders_1e6": bench_decoders(),
        "hash_1e7": bench_hash(),
        "oom_machine": bench_oom_machine(),
        "tpcds_2e6": bench_tpcds(),
    }
    with open("BENCH_EXTRA.json", "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
