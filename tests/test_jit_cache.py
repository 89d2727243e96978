"""Kernel compile cache + shape bucketing tests (ISSUE 4 tentpole:
perf/jit_cache.py and its row-conversion / hash / exchange wiring).

The load-bearing assertion is the recompile contract: a second
conversion with the same schema digest and a row count in the same
power-of-two bucket must perform ZERO new XLA compilations (tracked by
JitCache.stats()['compiles'] — every miss is exactly one
lower+compile; hits call a stored executable)."""

import os

import numpy as np
import pytest

from spark_rapids_tpu.columns import dtypes
from spark_rapids_tpu.columns.column import Column
from spark_rapids_tpu.columns.table import Table
from spark_rapids_tpu.ops import row_conversion as RC
from spark_rapids_tpu.perf.jit_cache import (CACHE, JitCache, bucket_rows,
                                             pad_axis0, schema_digest)

_CYCLE = [dtypes.INT64, dtypes.INT32, dtypes.FLOAT64, dtypes.FLOAT32,
          dtypes.INT16, dtypes.INT8, dtypes.BOOL8,
          dtypes.TIMESTAMP_MICROS]


def _wide_table(rows: int, ncols: int = 212, seed: int = 3) -> Table:
    """Bench-shaped wide table (212 mixed-width cols), every 7th column
    nullable."""
    rng = np.random.default_rng(seed)
    cols = []
    for i in range(ncols):
        dt = _CYCLE[i % len(_CYCLE)]
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
        validity = rng.integers(0, 2, rows) if i % 7 == 0 else None
        cols.append(Column.from_numpy(arr, validity=validity, dtype=dt))
    return Table(cols)


def _numpy_rows_reference(table: Table) -> np.ndarray:
    """Independent numpy assembly of the JCUDF bytes (fixed-width)."""
    starts, voff, fixed = RC.compute_layout([c.dtype for c in
                                             table.columns])
    rows = table.num_rows
    row_size = (fixed + 7) // 8 * 8
    out = np.zeros((rows, row_size), np.uint8)
    for c, st in zip(table.columns, starts):
        host = c.to_numpy()
        b = host.view(np.uint8).reshape(rows, host.dtype.itemsize)
        out[:, st:st + b.shape[1]] = b
    nb = (len(table.columns) + 7) // 8
    for i, c in enumerate(table.columns):
        bit = (np.ones(rows, np.uint8) if c.validity is None
               else np.asarray(c.validity).astype(np.uint8))
        out[:, voff + i // 8] |= (bit & 1) << (i % 8)
    return out


def _words_to_bytes(list_col: Column) -> np.ndarray:
    rows = list_col.length
    data = np.asarray(list_col.children[0].data)
    raw = data.view("<u4").tobytes() if data.dtype == np.uint32 \
        else data.tobytes()
    return np.frombuffer(raw, np.uint8)[:list_col.children[0].length] \
        .reshape(rows, -1)


# ------------------------------------------------------------ unit layer


def test_bucket_rows_power_of_two():
    assert bucket_rows(1) == 8
    assert bucket_rows(8) == 8
    assert bucket_rows(9) == 16
    assert bucket_rows(4096) == 4096
    assert bucket_rows(4097) == 8192
    assert bucket_rows(3500) == bucket_rows(4096)


def test_pad_axis0_shapes():
    import jax.numpy as jnp
    a = jnp.arange(10, dtype=jnp.int32)
    p = pad_axis0(a, 16)
    assert p.shape == (16,) and int(p[9]) == 9 and int(p[15]) == 0
    m = jnp.ones((3, 4), jnp.uint8)
    assert pad_axis0(m, 8).shape == (8, 4)
    assert pad_axis0(m, 3) is m


def test_schema_digest_discriminates():
    s1 = [dtypes.INT32, dtypes.INT64]
    assert schema_digest(s1) == schema_digest(list(s1))
    assert schema_digest(s1) != schema_digest([dtypes.INT64, dtypes.INT32])
    assert schema_digest(s1, (True, False)) != \
        schema_digest(s1, (False, False))
    assert schema_digest(s1, extra="a") != schema_digest(s1, extra="b")
    assert schema_digest([dtypes.decimal128(-2)]) != \
        schema_digest([dtypes.decimal128(-3)])


def test_lru_eviction_and_owner_identity():
    cache = JitCache(max_entries=2)
    built = []

    def builder(tag):
        def build():
            built.append(tag)
            return lambda: tag
        return build

    assert cache.get_or_build("k", "a", 8, builder("a"))() == "a"
    assert cache.get_or_build("k", "b", 8, builder("b"))() == "b"
    assert cache.get_or_build("k", "a", 8, builder("a2"))() == "a"  # hit
    assert cache.get_or_build("k", "c", 8, builder("c"))() == "c"
    # "b" was least recently used -> evicted; "a" survives
    assert cache.get_or_build("k", "a", 8, builder("a3"))() == "a"
    assert cache.get_or_build("k", "b", 8, builder("b2"))() == "b2"
    st = cache.stats()
    assert st["evictions"] >= 2 and built == ["a", "b", "c", "b2"]
    # owner identity: same key, different owner object -> rebuild
    o1, o2 = object(), object()
    cache2 = JitCache(max_entries=8)
    f1 = cache2.get_or_build("k", "d", 8, builder("o1"), owner=o1)
    f2 = cache2.get_or_build("k", "d", 8, builder("o2"), owner=o2)
    assert f1() == "o1" and f2() == "o2"
    assert cache2.get_or_build("k", "d", 8, builder("x"), owner=o2)() == \
        "o2"


def test_byte_budget_eviction():
    cache = JitCache(max_entries=100, max_bytes=100)

    def mk(tag):
        return lambda: (lambda: tag)

    cache.get_or_build("k", "a", 8, mk("a"), cost_bytes=60)
    cache.get_or_build("k", "b", 8, mk("b"), cost_bytes=60)
    st = cache.stats()
    assert st["entries"] == 1 and st["evictions"] == 1
    assert st["bytes"] <= 100


# ----------------------------------------------- recompile-count contract


def test_second_call_same_bucket_zero_compiles():
    t1 = _wide_table(200, ncols=24, seed=5)
    t2 = _wide_table(250, ncols=24, seed=6)       # same bucket (256)
    t3 = _wide_table(300, ncols=24, seed=7)       # different bucket (512)
    schema = [c.dtype for c in t1.columns]

    out1 = RC.convert_to_rows(t1)
    s1 = CACHE.stats()
    out2 = RC.convert_to_rows(t2)
    s2 = CACHE.stats()
    assert s2["compiles"] == s1["compiles"], \
        "same-bucket second call must not compile"
    assert s2["hits"] == s1["hits"] + 1
    out3 = RC.convert_to_rows(t3)
    s3 = CACHE.stats()
    assert s3["compiles"] == s2["compiles"] + 1, \
        "a new bucket compiles exactly once"

    RC.convert_from_rows(out1, schema)
    f1 = CACHE.stats()
    RC.convert_from_rows(out2, schema)
    f2 = CACHE.stats()
    assert f2["compiles"] == f1["compiles"]
    # from-rows is two executables: the transpose and the extraction
    assert f2["hits"] == f1["hits"] + 2
    del out3


def test_hash_cache_seed_does_not_recompile():
    from spark_rapids_tpu.ops import murmur3_32, xxhash64

    t = _wide_table(100, ncols=12, seed=9)
    h42 = murmur3_32(t, 42)
    s1 = CACHE.stats()
    h7 = murmur3_32(t, 7)                 # traced seed: same executable
    s2 = CACHE.stats()
    assert s2["compiles"] == s1["compiles"]
    assert not np.array_equal(np.asarray(h42.data), np.asarray(h7.data))
    # eager reference equality
    os.environ["SPARK_RAPIDS_TPU_JIT_CACHE"] = "0"
    try:
        ref42 = murmur3_32(t, 42)
        refx = xxhash64(t, 42)
    finally:
        os.environ.pop("SPARK_RAPIDS_TPU_JIT_CACHE", None)
    assert np.array_equal(np.asarray(h42.data), np.asarray(ref42.data))
    hx = xxhash64(t, 42)
    assert np.array_equal(np.asarray(hx.data), np.asarray(refx.data))


# -------------------------------------------------- wide-schema goldens


def test_wide_212col_golden_bytes_and_roundtrip():
    t = _wide_table(64)
    schema = [c.dtype for c in t.columns]
    rows_col = RC.convert_to_rows(t)
    got = _words_to_bytes(rows_col)
    ref = _numpy_rows_reference(t)
    assert got.shape == ref.shape
    assert np.array_equal(got, ref), "212-col bytes diverge from numpy"

    back = RC.convert_from_rows(rows_col, schema)
    for i, (orig, rec) in enumerate(zip(t.columns, back.columns)):
        assert orig.to_pylist() == rec.to_pylist(), f"col {i}"


def test_wide_cache_disabled_matches(monkeypatch):
    t = _wide_table(64, seed=13)
    cached = _words_to_bytes(RC.convert_to_rows(t))
    monkeypatch.setenv("SPARK_RAPIDS_TPU_JIT_CACHE", "0")
    eager = _words_to_bytes(RC.convert_to_rows(t))
    assert np.array_equal(cached, eager)
    back = RC.convert_from_rows(RC.convert_to_rows(t),
                                [c.dtype for c in t.columns])
    for orig, rec in zip(t.columns, back.columns):
        assert orig.to_pylist() == rec.to_pylist()


def test_validity_vectorized_matches_bitloop():
    """The packbits-style _validity_bytes must equal a per-bit
    reference, cache or no cache (satellite: the non-cached fallback
    must not regress on wide schemas)."""
    t = _wide_table(97, ncols=37, seed=21)
    got = np.asarray(RC._validity_bytes(t.columns))
    rows = t.num_rows
    nb = (len(t.columns) + 7) // 8
    ref = np.zeros((rows, nb), np.uint8)
    for ci, c in enumerate(t.columns):
        bit = (np.ones(rows, np.uint8) if c.validity is None
               else (np.asarray(c.validity) != 0).astype(np.uint8))
        ref[:, ci // 8] |= bit << (ci % 8)
    assert np.array_equal(got, ref)
    assert np.array_equal(np.asarray(RC._validity_byte_vector(
        t.columns, 1)), ref[:, 1])


def test_decimal_string_schema_roundtrip_cached():
    """Mixed schema exercises the dec128 limb class and the string
    (variable-width, uncached) path side by side."""
    d = Column.from_pylist([10**30, None, -5, 0], dtypes.decimal128(-2))
    s = Column.from_strings(["a", "bb", None, "dddd"])
    i = Column.from_pylist([1, None, 3, 4], dtypes.INT16)
    t = Table([d, s, i])
    rows_col = RC.convert_to_rows(t)
    back = RC.convert_from_rows(rows_col, [c.dtype for c in t.columns])
    assert back.columns[1].to_pylist() == ["a", "bb", None, "dddd"]
    assert back.columns[2].to_pylist() == [1, None, 3, 4]


# ------------------------------------------------- exchange step builders


def test_exchange_steps_ride_the_cache():
    from spark_rapids_tpu.parallel.exchange import with_capacity_retry

    calls = []

    def make_step(cap):
        calls.append(cap)
        return lambda x: (x * 2, np.zeros(1))     # never overflows

    run = with_capacity_retry(make_step, 8)
    base = CACHE.stats()["kernels"].get("exchange.step",
                                        {"hits": 0, "misses": 0})
    out, cap = run(3)
    assert out[0] == 6 and cap == 8
    out, cap = run(5)
    assert out[0] == 10 and cap == 8
    ks = CACHE.stats()["kernels"]["exchange.step"]
    assert ks["misses"] == base["misses"] + 1     # built once
    assert ks["hits"] >= base["hits"] + 1         # reused
    assert calls == [8]

    # a different factory at the same capacity must NOT reuse the entry
    def make_step2(cap):
        calls.append(-cap)
        return lambda x: (x * 3, np.zeros(1))

    run2 = with_capacity_retry(make_step2, 8)
    out, _ = run2(3)
    assert out[0] == 9
    assert -8 in calls


def test_exchange_steps_cache_disabled(monkeypatch):
    from spark_rapids_tpu.parallel.exchange import with_capacity_retry

    monkeypatch.setenv("SPARK_RAPIDS_TPU_JIT_CACHE", "0")
    calls = []

    def make_step(cap):
        calls.append(cap)
        return lambda x: (x + cap, np.zeros(1))

    run = with_capacity_retry(make_step, 4)
    assert run(1)[0][0] == 5
    assert run(2)[0][0] == 6
    assert calls == [4]                           # local dict still memoizes


# ------------------------------------------------------ metrics surface


def test_jit_cache_metrics_and_report():
    from spark_rapids_tpu import observability as obs
    from spark_rapids_tpu.tools.metrics_report import (
        jit_cache_rows, render_jit_cache_table)

    obs.enable()
    try:
        obs.METRICS.reset()
        t = _wide_table(100, ncols=8, seed=41)
        RC.convert_to_rows(t)
        RC.convert_to_rows(t)
        text = obs.expose_text()
        assert "srt_jit_cache_hits_total" in text
        snap = obs.METRICS.snapshot()
        rows = jit_cache_rows(snap)
        tor = [r for r in rows if r["kernel"] == "row_conversion.to_rows"]
        assert tor and tor[0]["hits"] >= 1
        assert 0.0 <= tor[0]["hit_rate"] <= 1.0
        table_lines = "\n".join(render_jit_cache_table(snap))
        assert "row_conversion.to_rows" in table_lines
    finally:
        obs.METRICS.reset()
        obs.disable()


def test_shim_stats_and_clear():
    import json

    from spark_rapids_tpu.shim import jni_api, jni_entry

    t = _wide_table(20, ncols=6, seed=51)
    RC.convert_to_rows(t)
    st = json.loads(jni_entry.jit_cache_stats())
    assert st["entries"] >= 1 and st["compiles"] >= 1
    dropped = jni_api.jit_cache_clear()
    assert dropped >= 1
    st2 = json.loads(jni_api.jit_cache_stats())
    assert st2["entries"] == 0
    assert st2["compiles"] >= st["compiles"]      # stats survive clear
    # a cleared cache recompiles once, then hits again
    RC.convert_to_rows(t)
    s1 = json.loads(jni_api.jit_cache_stats())
    RC.convert_to_rows(t)
    s2 = json.loads(jni_api.jit_cache_stats())
    assert s2["compiles"] == s1["compiles"]


def test_cache_disabled_env_is_dynamic(monkeypatch):
    t = _wide_table(16, ncols=4, seed=61)
    monkeypatch.setenv("SPARK_RAPIDS_TPU_JIT_CACHE", "0")
    before = CACHE.stats()
    out = RC.convert_to_rows(t)
    after = CACHE.stats()
    assert after["misses"] == before["misses"]    # cache untouched
    monkeypatch.delenv("SPARK_RAPIDS_TPU_JIT_CACHE")
    out2 = RC.convert_to_rows(t)
    assert np.array_equal(_words_to_bytes(out), _words_to_bytes(out2))


@pytest.mark.parametrize("rows", [1, 7, 8, 9])
def test_tiny_row_counts_pad_and_slice(rows):
    t = _wide_table(rows, ncols=9, seed=70 + rows)
    rows_col = RC.convert_to_rows(t)
    assert np.array_equal(_words_to_bytes(rows_col),
                          _numpy_rows_reference(t))
    back = RC.convert_from_rows(rows_col, [c.dtype for c in t.columns])
    for orig, rec in zip(t.columns, back.columns):
        assert orig.to_pylist() == rec.to_pylist()
