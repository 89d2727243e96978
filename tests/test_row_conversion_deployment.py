"""The row-conversion deployment (ISSUE 32): the program's
``convert_to_rows`` / ``convert_from_rows`` against the benchmark's
plain numpy reference (``benchmark/reference/jcudf_rows.py``, which
imports nothing of the program) on seeded tables of the upstream
benchmark's column cycle, with and without nulls; the one engine a
fixed-width schema has per direction, its span and counter, and the
gather path that rows of differing size keep."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu import observability as obs
from spark_rapids_tpu.columns import dtypes
from spark_rapids_tpu.columns.column import Column
from spark_rapids_tpu.columns.table import Table
from spark_rapids_tpu.ops import row_conversion as RC
from spark_rapids_tpu.perf import jit_cache as JC

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run as harness  # noqa: E402  (benchmark/run.py: loads by name)

REF = harness.load("reference", "jcudf_rows")
BY_KIND = {"int64": dtypes.INT64, "int32": dtypes.INT32,
           "float64": dtypes.FLOAT64, "float32": dtypes.FLOAT32,
           "int16": dtypes.INT16, "int8": dtypes.INT8,
           "bool8": dtypes.BOOL8,
           "timestamp_micros": dtypes.TIMESTAMP_MICROS}
DEC128 = np.dtype([("lo", "<u8"), ("hi", "<u8")])   # 16 bytes, as one field


def seeded(rows, columns, seed, nulls):
    """The reference's table for the seed, the program's Table of it,
    and per column the validity drawn (``None``: all valid)."""
    inputs = REF.make_inputs({"rows": rows}, {"columns": columns}, seed)
    rng = np.random.default_rng(seed + 1)
    valid = [rng.integers(0, 2, rows).astype(np.uint8) if nulls else None
             for _ in inputs["columns"]]
    table = Table([Column.from_numpy(a, validity=v, dtype=BY_KIND[k])
                   for k, a, v in zip(inputs["kinds"], inputs["columns"],
                                      valid)])
    return inputs, table, valid


def expected_rows(columns, valid):
    """The reference's row bytes (it draws no nulls) with the validity
    bit of every null cell cleared; a null's data bytes stay what the
    column buffer holds, as in the program."""
    want = REF.answer({"columns": columns}, None)["rows"].copy()
    voff = REF.layout([c.dtype.itemsize for c in columns])[1]
    for i, v in enumerate(valid):
        if v is not None:
            want[:, voff + i // 8] &= ~(
                (1 - v) << np.uint8(i % 8)).astype(np.uint8)
    return want


def host_rows(rows_col, rows):
    flat = np.asarray(rows_col.children[0].data).view(np.uint8)
    return flat[:rows_col.children[0].length].reshape(rows, -1)


def back_bytes(table):
    """The columns' buffers; a decimal's (rows, 4) limbs as one 16-byte
    field a row, the shape the reference compares."""
    out = [np.ascontiguousarray(np.asarray(c.data)) for c in table.columns]
    return [a.view(DEC128).reshape(-1) if a.ndim == 2 else a for a in out]


def numbers_of(rows_col, back, columns, valid):
    """The reference's comparison of one round trip's two outputs."""
    return REF.compare(
        {"rows": host_rows(rows_col, len(columns[0])),
         "columns": back_bytes(back)},
        {"rows": expected_rows(columns, valid), "columns": columns})


def check_round_trip(columns, table, valid):
    rows_col = RC.convert_to_rows(table)
    back = RC.convert_from_rows(rows_col, [c.dtype for c in table.columns])
    assert numbers_of(rows_col, back, columns, valid) == {
        "row_bytes_differing": 0, "column_bytes_differing": 0}
    for c, v in zip(back.columns, valid):
        check_validity(c, v)


def check_validity(c, v):
    """What from-rows' ``words`` engine hands back for a column drawn
    with the validity ``v``: no vector where no null was drawn (``v``
    is ``None``, or a draw of few rows came out all ones), else ``v``."""
    if v is None or v.all():
        assert c.validity is None and not c.has_validity
    else:
        assert c.validity.dtype == jnp.uint8
        np.testing.assert_array_equal(np.asarray(c.validity), v)


@pytest.mark.parametrize("nulls", [False, True],
                         ids=["all_valid", "nulls_in_every_column"])
@pytest.mark.parametrize("columns", [3, 8, 212])
@pytest.mark.parametrize("rows", [1, 7, 4096, 5000])
def test_round_trip_matches_the_plain_reference(rows, columns, nulls):
    """5,000 rows cross into the padded 8,192 bucket; 1 and 7 share
    the smallest; 4,096 is its own."""
    inputs, table, valid = seeded(rows, columns, 31 * rows + columns, nulls)
    check_round_trip(inputs["columns"], table, valid)


@pytest.mark.parametrize("nulls", [False, True], ids=["all_valid", "nulls"])
def test_decimal128_and_uint64_columns_round_trip(nulls):
    """Widths the upstream cycle lacks: a 16-byte field of four limbs
    (aligned to 16) and an unsigned 8-byte one whose top bit is set."""
    rows = 777
    rng = np.random.default_rng(128)
    limbs = rng.integers(-2 ** 31, 2 ** 31, (rows, 4)).astype(np.int32)
    u64 = rng.integers(0, 2 ** 64, rows, dtype=np.uint64)
    u64[0] = np.uint64(2 ** 64 - 1)
    i16 = rng.integers(-2 ** 15, 2 ** 15, rows).astype(np.int16)
    columns = [i16, limbs.view(DEC128).reshape(rows), u64,
               limbs[::-1].copy().view(DEC128).reshape(rows)]
    valid = [rng.integers(0, 2, rows).astype(np.uint8) if nulls else None
             for _ in columns]
    vd = [None if v is None else jnp.asarray(v) for v in valid]
    table = Table([
        Column.from_numpy(i16, validity=valid[0], dtype=dtypes.INT16),
        Column(dtypes.decimal128(-2), rows, data=jnp.asarray(limbs),
               validity=vd[1]),
        Column.from_numpy(u64, validity=valid[2], dtype=dtypes.UINT64),
        Column(dtypes.decimal128(-4), rows,
               data=jnp.asarray(limbs[::-1].copy()), validity=vd[3])])
    check_round_trip(columns, table, valid)


@pytest.mark.parametrize("seed", [1, 2_147_483_659])
def test_the_references_control_reads_above_zero(seed):
    inputs = REF.make_inputs({"rows": 4096}, {"columns": 212}, seed)
    want = REF.answer(inputs, None)
    broken = REF.compare(REF.control_answer(inputs, None), want)
    assert broken["row_bytes_differing"] > 0
    assert broken["column_bytes_differing"] > 0
    assert REF.compare(REF.answer(inputs, None), want) == {
        "row_bytes_differing": 0, "column_bytes_differing": 0}


def test_fixed_width_from_rows_executable_holds_no_gather(monkeypatch):
    """The structural guard: 2^20 rows of 212 columns came back in 34 s
    while every byte was fetched through a (rows, 1068) index matrix.
    Read from the executable the call itself runs, as the cache hands
    it over; a buffer of differing row sizes still compiles a gather
    (the guard can tell the two apart)."""
    seen = []
    real = JC.CACHE.get_or_build

    def spy(name, digest, bucket, build, **kw):
        ex = real(name, digest, bucket, build, **kw)
        seen.append((name, bucket, ex))
        return ex

    monkeypatch.setattr(JC.CACHE, "get_or_build", spy)
    inputs, table, _ = seeded(4096, 212, 5, False)
    schema = [c.dtype for c in table.columns]
    back = RC.convert_from_rows(RC.convert_to_rows(table), schema)
    assert [(n, b) for n, b, _ in seen] == [
        ("row_conversion.to_rows", 4096),
        ("row_conversion.from_rows.transpose", 4096),
        ("row_conversion.from_rows", 4096)]
    assert " transpose(" in seen[1][2].as_text()
    for _name, _bucket, ex in seen[1:]:
        assert " gather(" not in ex.as_text()
    assert len(back.columns) == 212
    region = jax.jit(lambda d, o: RC._gather_fixed_region(
        d, o, 1095, 4096 * 1096)).lower(
        jax.ShapeDtypeStruct((4096 * 274,), jnp.uint32),
        jax.ShapeDtypeStruct((4097,), jnp.int32)).compile().as_text()
    assert " gather(" in region


@pytest.fixture
def counting():
    prior = obs.is_enabled()
    obs.enable()
    obs.reset()
    yield
    obs.reset()
    (obs.enable if prior else obs.disable)()


def conversions():
    fam = obs.METRICS.snapshot().get("srt_row_conversion_total", {})
    return {tuple(s["labels"]): s["value"] for s in fam.get("series", [])}


def validity_outcomes():
    fam = obs.METRICS.snapshot().get("srt_from_rows_validity_total", {})
    return {s["labels"][0]: s["value"] for s in fam.get("series", [])}


def test_one_round_trip_counts_one_conversion_each_way_on_words(counting):
    inputs, table, _ = seeded(100, 8, 9, False)
    rows_col = RC.convert_to_rows(table)
    RC.convert_from_rows(rows_col, [c.dtype for c in table.columns])
    assert conversions() == {("to_rows", "words"): 1,
                             ("from_rows", "words"): 1}
    spans = {s["name"]: s for s in obs.TRACER.records()
             if s["name"] in ("to_rows", "from_rows")}
    assert set(spans) == {"to_rows", "from_rows"}
    row_size = REF.layout([c.dtype.itemsize for c in inputs["columns"]])[2]
    for name, s in spans.items():
        assert s["span_kind"] == "phase"
        want = {"rows": 100, "engine": "words", "bytes": 100 * row_size}
        if name == "from_rows":     # 8 values, validity words, all-valid
            want["results"] = 10
        assert s["attrs"] == want


def test_a_conversion_under_jit_records_nothing(counting):
    inputs, table, _ = seeded(64, 3, 4, False)
    schema = [c.dtype for c in table.columns]

    @jax.jit
    def trip(t):
        return RC.convert_from_rows(RC.convert_to_rows(t), schema)

    back = trip(table)
    assert conversions() == {}
    assert not [s for s in obs.TRACER.records()
                if s["name"] in ("to_rows", "from_rows")]
    for c, a in zip(back.columns, inputs["columns"]):
        assert np.asarray(c.data).tobytes() == a.tobytes()


def shuffled_rows(table, rng):
    """The rows of ``table`` in a buffer with a hole after each: the
    offsets are no longer 0, size, 2 * size, ..."""
    rows = table.num_rows
    rows_col = RC.convert_to_rows(table)
    tight = host_rows(rows_col, rows)
    size = tight.shape[1]
    gaps = rng.integers(0, 3, rows) * 8
    offs = np.concatenate([[0], np.cumsum(size + gaps)]).astype(np.int32)
    flat = np.zeros(int(offs[-1]), np.uint8)
    for r in range(rows):
        flat[offs[r]:offs[r] + size] = tight[r]
    return Column.make_list_from_parts(jnp.asarray(offs), jnp.asarray(flat))


def test_rows_of_differing_size_keep_the_gather_path_and_agree(counting):
    inputs, table, valid = seeded(300, 8, 12, True)
    schema = [c.dtype for c in table.columns]
    loose = shuffled_rows(table, np.random.default_rng(3))
    obs.reset()
    back = RC.convert_from_rows(loose, schema)
    assert conversions() == {("from_rows", "gather"): 1}
    gather_span = [s for s in obs.TRACER.records()
                   if s["name"] == "from_rows"][-1]
    assert "results" not in gather_span["attrs"]
    for c, a, v in zip(back.columns, inputs["columns"], valid):
        assert np.asarray(c.data).tobytes() == a.tobytes()
        # the gather keeps handing over a vector a column
        np.testing.assert_array_equal(np.asarray(c.validity), v)
    assert validity_outcomes() == {}


def test_uniform_rows_in_a_byte_buffer_from_elsewhere_take_words(counting):
    """A u8 buffer with offsets the program did not make: the offsets
    are read back and scanned once, the bytes packed to words inside
    the executable."""
    inputs, table, valid = seeded(300, 8, 13, True)
    schema = [c.dtype for c in table.columns]
    tight = host_rows(RC.convert_to_rows(table), 300)
    offs = np.arange(301, dtype=np.int32) * tight.shape[1]
    foreign = Column.make_list_from_parts(jnp.asarray(offs),
                                          jnp.asarray(tight.reshape(-1)))
    obs.reset()
    back = RC.convert_from_rows(foreign, schema)
    again = RC.convert_from_rows(foreign, schema)
    assert conversions() == {("from_rows", "words"): 2}
    for t in (back, again):
        for c, a, v in zip(t.columns, inputs["columns"], valid):
            assert np.asarray(c.data).tobytes() == a.tobytes()
            check_validity(c, v)


def test_without_the_executable_cache_the_engine_is_the_same(
        counting, monkeypatch):
    monkeypatch.setenv("SPARK_RAPIDS_TPU_JIT_CACHE", "0")
    inputs, table, valid = seeded(50, 8, 14, True)
    check_round_trip(inputs["columns"], table, valid)
    assert conversions() == {("to_rows", "words"): 1,
                             ("from_rows", "words"): 1}


@pytest.mark.parametrize("columns,engine", [(8, "pallas"), (212, "pallas"),
                                            (599, "pallas"), (600, "words"),
                                            (1000, "words")])
def test_on_a_tpu_the_tile_kernel_takes_the_rows_its_vmem_holds(
        counting, monkeypatch, columns, engine):
    """The Pallas tile grows with the row, so on a TPU
    the engine is a property of the schema: the tile kernel while a
    1,024-row tile fits the kernel's VMEM (599 cycled columns), XLA's
    word assembly for wider rows, where the chip's compiler would
    refuse the kernel (tests/test_tpu_compile.py).  Off the chip the
    kernel runs interpreted here; either way the bytes are the
    reference's."""
    import functools

    from spark_rapids_tpu.ops import row_assembly_pallas as RP

    monkeypatch.setattr(RC.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(RP, "assemble_rows_pallas", functools.partial(
        RP.assemble_rows_pallas, interpret=True))
    inputs, table, valid = seeded(40, columns, 21, True)
    check_round_trip(inputs["columns"], table, valid)
    assert conversions() == {("to_rows", engine): 1,
                             ("from_rows", "words"): 1}


# ------------- from-rows' validity is deferred (ISSUE 37): the extract
# executable hands over the validity words and one all-valid word, and
# a column's vector is made only where the row buffer holds a null


@pytest.fixture
def readbacks(monkeypatch):
    """Counts the device-to-host reads of a table's all-valid word."""
    seen = []
    real = RC._read_all_valid
    monkeypatch.setattr(RC, "_read_all_valid",
                        lambda a: seen.append(a.shape) or real(a))
    return seen


@pytest.fixture
def built(monkeypatch):
    """Names of the executables the compile cache is asked for."""
    seen = []
    real = JC.CACHE.get_or_build

    def spy(name, *a, **kw):
        seen.append(name)
        return real(name, *a, **kw)

    monkeypatch.setattr(JC.CACHE, "get_or_build", spy)
    return seen


def with_nulls_in(rows, columns, seed, null_columns):
    """``seeded`` with nulls in the columns listed alone; every such
    column has a null (the last row) and, from two rows on, a valid
    row (the first)."""
    inputs, table, valid = seeded(rows, columns, seed, False)
    rng = np.random.default_rng(seed + 2)
    for ci in null_columns:
        v = rng.integers(0, 2, rows).astype(np.uint8)
        v[0], v[-1] = 1, 0              # one row: the null wins
        valid[ci] = v
        c = table.columns[ci]
        table.columns[ci] = Column(c.dtype, rows, data=c.data,
                                   validity=jnp.asarray(v))
    return inputs, table, valid


VALIDITY_VECTOR = "row_conversion.from_rows.validity"


@pytest.mark.parametrize("null_columns", ["none", "one", "three", "all"])
@pytest.mark.parametrize("columns,validity_bytes", [(1, 1), (9, 2),
                                                    (212, 27)])
@pytest.mark.parametrize("rows", [1, 300, 4096, 5000])
def test_from_rows_defers_validity_and_makes_only_the_vectors_of_nulls(
        counting, readbacks, built, rows, columns, validity_bytes,
        null_columns):
    """1, 9 and 212 columns hold their validity in 1, 2 and 27 bytes
    (one word, one word, seven); 300 and 5,000 rows are neither a
    multiple of 128 nor a power of two, so their buckets (512, 8,192)
    hold pad rows, which are no nulls; 1 row shares the smallest."""
    picked = {"none": [], "one": [columns - 1],
              "three": sorted({0, columns // 2, columns - 1}),
              "all": list(range(columns))}[null_columns]
    inputs, table, valid = with_nulls_in(
        rows, columns, 37 * rows + columns, picked)
    schema = [c.dtype for c in table.columns]
    assert (columns + 7) // 8 == validity_bytes
    rows_col = RC.convert_to_rows(table)
    back = RC.convert_from_rows(rows_col, schema)
    jax.block_until_ready([c.data for c in back.columns])
    # the call, and waiting on the values, read nothing back and
    # resolve nothing
    assert readbacks == [] and validity_outcomes() == {}
    span = [s for s in obs.TRACER.records() if s["name"] == "from_rows"][-1]
    assert span["attrs"]["results"] == columns + 2
    for c, v in zip(back.columns, valid):
        check_validity(c, v)
        check_validity(c, v)            # a second read: the same answer
        assert c.null_count() == (0 if v is None else int(rows - v.sum()))
        np.testing.assert_array_equal(
            np.asarray(c.valid_mask()),
            np.ones(rows, bool) if v is None else v.astype(bool))
    # one readback a table, of one u32 a validity word of the row;
    # every column counted once, a vector made only where a null is
    assert readbacks == [((validity_bytes + 3) // 4,)]
    want = {"absent": columns - len(picked), "materialized": len(picked)}
    assert validity_outcomes() == {k: n for k, n in want.items() if n}
    assert (VALIDITY_VECTOR in built) == bool(picked)
    assert built.count(VALIDITY_VECTOR) == len(picked)
    # and back to rows: byte for byte what came in
    again = RC.convert_to_rows(back)
    assert np.array_equal(host_rows(again, rows), host_rows(rows_col, rows))
    assert numbers_of(rows_col, back, inputs["columns"], valid) == {
        "row_bytes_differing": 0, "column_bytes_differing": 0}


@pytest.mark.parametrize("schema,bits", [
    ([dtypes.INT8], [8]),                       # validity at byte 1
    ([dtypes.INT16, dtypes.INT8], [24, 25]),    # at byte 3 of word 0
    ([dtypes.INT32, dtypes.INT8] + [dtypes.BOOL8] * 29,
     [16 + i for i in range(31)])])             # bytes 34-37: words 8, 9
def test_validity_bytes_that_share_a_word_with_fields(counting, schema, bits):
    """The validity bytes start where the last field ends, so the first
    validity word may hold field bytes below them and the bytes may
    straddle two words."""
    starts, voff, _ = RC.compute_layout(schema)
    vw0, vw1, bit0 = RC._validity_bits(len(schema), voff)
    assert [bit0 + ci for ci in range(len(schema))] == bits
    assert vw0 == voff // 4
    assert vw1 == (voff + (len(schema) + 7) // 8 - 1) // 4 + 1
    rows = 77
    rng = np.random.default_rng(len(schema))
    valid = [rng.integers(0, 2, rows).astype(np.uint8) if ci % 2 == 0
             else None for ci in range(len(schema))]
    for v in valid[::2]:
        v[:2] = 0, 1
    table = Table([Column.from_numpy(
        rng.integers(0, 2, rows).astype(dt.np_dtype), validity=v, dtype=dt)
        for dt, v in zip(schema, valid)])
    back = RC.convert_from_rows(RC.convert_to_rows(table), schema)
    for c, a, v in zip(back.columns, table.columns, valid):
        assert np.asarray(c.data).tobytes() == np.asarray(a.data).tobytes()
        check_validity(c, v)


def test_rows_from_elsewhere_get_their_validity_from_the_buffer(counting):
    """No side channel from ``convert_to_rows``: a u8 buffer written on
    the host, with the spare bits of the last validity byte and the
    row's tail bytes set to anything, answers from its own bits."""
    inputs, table, valid = with_nulls_in(200, 9, 41, [4])
    schema = [c.dtype for c in table.columns]
    tight = host_rows(RC.convert_to_rows(table), 200).copy()
    voff = REF.layout([c.dtype.itemsize for c in inputs["columns"]])[1]
    tight[::2, voff + 1] |= 0xFE        # bits of columns that are none
    tight[1::2, voff + 1] &= 0x01
    tight[:, voff + 2:] = np.random.default_rng(5).integers(
        0, 256, tight[:, voff + 2:].shape)
    offs = np.arange(201, dtype=np.int32) * tight.shape[1]
    foreign = Column.make_list_from_parts(jnp.asarray(offs),
                                          jnp.asarray(tight.reshape(-1)))
    back = RC.convert_from_rows(foreign, schema)
    for c, a, v in zip(back.columns, inputs["columns"], valid):
        assert np.asarray(c.data).tobytes() == a.tobytes()
        check_validity(c, v)
    # a null written into the buffer after the program made it is seen
    tight[17, voff] &= ~np.uint8(1 << 2)
    edited = Column.make_list_from_parts(jnp.asarray(offs),
                                         jnp.asarray(tight.reshape(-1)))
    v2 = np.ones(200, np.uint8)
    v2[17] = 0
    back = RC.convert_from_rows(edited, schema)
    check_validity(back.columns[2], v2)
    check_validity(back.columns[3], None)


@pytest.mark.parametrize("null_columns", [[], [1, 7]])
def test_a_from_rows_table_flattens_and_goes_through_jit(
        counting, readbacks, null_columns):
    inputs, table, valid = with_nulls_in(300, 9, 43, null_columns)
    schema = [c.dtype for c in table.columns]
    back = RC.convert_from_rows(RC.convert_to_rows(table), schema)
    leaves, tree = jax.tree_util.tree_flatten(back)
    assert len(readbacks) == 1
    # values, and a vector for the two columns with nulls alone
    assert len(leaves) == 9 + len(null_columns)
    assert tree == jax.tree_util.tree_structure(table)
    rebuilt = jax.tree_util.tree_unflatten(tree, leaves)
    for c, v in zip(rebuilt.columns, valid):
        check_validity(c, v)

    @jax.jit
    def nulls_and_rows(t):
        return (sum(jnp.sum(~c.valid_mask()) for c in t.columns),
                RC.convert_to_rows(t).children[0].data)

    fresh = RC.convert_from_rows(RC.convert_to_rows(table), schema)
    n, words = nulls_and_rows(fresh)
    assert int(n) == sum(int(300 - v.sum()) for v in valid if v is not None)
    assert np.array_equal(
        np.asarray(words), np.asarray(nulls_and_rows(table)[1]))
    assert len(readbacks) == 2          # one a table


def test_two_threads_resolve_a_table_once(counting, readbacks):
    import threading

    inputs, table, valid = with_nulls_in(1000, 212, 47, [0, 100, 211])
    schema = [c.dtype for c in table.columns]
    back = RC.convert_from_rows(RC.convert_to_rows(table), schema)
    start = threading.Barrier(4)
    got, errors = [], []

    def read():
        try:
            start.wait()
            got.append([c.validity for c in back.columns])
        except Exception as e:          # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=read) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors and len(got) == 4
    for seen in got:
        for a, b in zip(seen, got[0]):
            assert a is b               # one vector a column, shared
    assert len(readbacks) == 1
    assert validity_outcomes() == {"absent": 209, "materialized": 3}
    for c, v in zip(back.columns, valid):
        check_validity(c, v)


def test_a_validity_assigned_to_a_column_is_read_back_as_it_is():
    from spark_rapids_tpu.columns.column import DeferredValidity

    v = jnp.asarray(np.array([1, 0, 1], np.uint8))
    c = Column(dtypes.INT32, 3, data=jnp.arange(3, dtype=jnp.int32))
    assert c.validity is None and not c.has_validity
    c.validity = v
    assert c.validity is v and c.has_validity and c.null_count() == 1
    c.validity = None
    assert c.validity is None and c.null_count() == 0

    class Once(DeferredValidity):
        calls = 0

        def resolve(self):
            Once.calls += 1
            return v

    c = Column(dtypes.INT32, 3, data=c.data, validity=Once())
    assert c.data is not None and Once.calls == 0
    assert c.validity is v and c.validity is v and Once.calls == 1
    leaves = jax.tree_util.tree_leaves(c)
    assert len(leaves) == 2 and leaves[1] is v


# ------------- the cell's binding: a program off the default engines
# is refused (benchmark/ops/rowconv_roundtrip_default.py, ISSUE 32)

CELL = "rowconv-fixed-212x1m-roundtrip"
REFUSAL = "no engine counter"


def rehearse(trace=0):
    """One run of the cell at toy size, in this process."""
    import argparse

    return harness.run_cell(argparse.Namespace(
        workload=CELL, seed=2_147_483_659, seconds=0.3, trace=trace,
        size="toy"))


def counter_hidden(monkeypatch):
    """The registry of a program that predates the counter."""
    monkeypatch.delitem(obs.METRICS._families, "srt_row_conversion_total")


@pytest.mark.skipif(os.environ.get("JAX_PLATFORMS") != "cpu",
                    reason="a rehearsal: set JAX_PLATFORMS=cpu")
class TestTheCellRefusesAProgramOffItsEngines:
    def test_without_the_counter_build_raises_before_any_array(
            self, monkeypatch):
        made = []
        real = Column.from_numpy
        monkeypatch.setattr(
            Column, "from_numpy",
            staticmethod(lambda *a, **kw: made.append(1) or real(*a, **kw)))
        binding = harness.load("ops", "rowconv_roundtrip_default")
        inputs = REF.make_inputs({"rows": 8}, {"columns": 212}, 1)
        with monkeypatch.context() as hidden:
            counter_hidden(hidden)
            with pytest.raises(RuntimeError, match=REFUSAL):
                binding.build(inputs)
            with pytest.raises(RuntimeError, match=REFUSAL):
                rehearse()
        assert not made
        assert binding.build(inputs)["rows"] == 8 and len(made) == 212

    @pytest.mark.parametrize("trace", [0, 1])
    def test_without_the_counter_the_command_ends_non_zero_at_once(
            self, trace):
        """The parent commit under this PR's benchmark files, as a
        process: non-zero, nothing on the result line, the reason the
        last line of standard error."""
        import subprocess

        hide = ("import runpy, sys; sys.path.insert(0, %r); "
                "from spark_rapids_tpu import observability as obs; "
                "del obs.METRICS._families['srt_row_conversion_total']; "
                "sys.argv = ['run.py'] + sys.argv[1:]; "
                "runpy.run_path(%r, run_name='__main__')"
                % (ROOT, os.path.join(BENCH, "run.py")))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        out = subprocess.run(
            [sys.executable, "-c", hide, "--workload", CELL, "--size",
             "toy", "--seconds", "1", "--seed", "1", "--trace", str(trace)],
            env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
        assert out.returncode not in (0, 2), out.stderr[-2000:]
        last = out.stderr.strip().splitlines()[-1]
        assert last.startswith("RuntimeError: " + REFUSAL), last
        assert "29.8 s a round trip" in last and "PERF.md" in last
        assert '"correct"' not in out.stdout
        assert '"phase": "setup"' not in out.stdout

    def test_from_rows_on_the_gather_raises_after_the_warm_round_trip(
            self, monkeypatch):
        monkeypatch.setattr(RC, "_uniform_row_offsets",
                            lambda *a, **kw: False)
        with pytest.raises(RuntimeError,
                           match="after the warm round trip.*"
                                 "from_rows on gather"):
            rehearse()

    def test_a_gather_inside_the_window_raises_when_outputs_are_read(
            self, monkeypatch):
        """The warm round trip on the named engines, a later one not:
        the check over the window finds it."""
        real, calls = RC._uniform_row_offsets, []

        def uniform_once(*a, **kw):
            calls.append(1)
            return real(*a, **kw) if len(calls) == 1 else False

        monkeypatch.setattr(RC, "_uniform_row_offsets", uniform_once)
        with pytest.raises(RuntimeError,
                           match="over the window.*from_rows on gather"):
            rehearse()
        assert len(calls) > 1

    def test_counters_switched_off_cannot_show_the_engine(
            self, monkeypatch):
        monkeypatch.setattr(obs, "enable", lambda: None)
        prior = obs.is_enabled()
        obs.disable()
        try:
            with pytest.raises(RuntimeError, match="counted no conversion"):
                rehearse()
        finally:
            (obs.enable if prior else obs.disable)()

    def test_the_tree_as_it_is_reads_correct(self):
        """Untraced (the suite's profiler sessions live in
        tests/test_query_timeline.py, which reads the cell's traced
        line): the end-to-end names, and the per-layer names the
        manifest lists for the cell."""
        code, result = rehearse()
        assert code == 0 and result["correct"] is True
        assert result["failed"] == 0 and result["attempted"] > 1
        assert result["compared"]["answers_missing"]["value"] == 0
        assert result["compared"]["row_bytes_differing"]["value"] == 0
        assert result["compared"]["column_bytes_differing"]["value"] == 0
        assert set(result["metrics"]) == {"setup_s", "rows_per_s",
                                          "query_ms.p50"}
        manifest = harness.load_json(ROOT, "BENCHMARK.json")
        # a direction's time may carry a ``.hostpaced`` suffix or not:
        # the benchmark names it, this test holds it to the quantity
        listed = {m["name"].removesuffix(".hostpaced")
                  for m in harness.Cell(
                      manifest, CELL, 1, "toy").metrics(
                          manifest, "per_layer")}
        assert listed == {
            "to_rows_ms", "from_rows_ms",
            "to_rows_roofline", "from_rows_roofline",
            "rowconv_dispatch_ms", "hbm_roofline", "device_idle_pct",
            "window_compiles", "setup_compile_s", "setup_compile_misses"}

    def test_the_configuration_sets_no_engine_variable(self):
        config = harness.load_json(
            BENCH, "configs", "rowconv-upstream-212col-1m.json")
        assert config["environment"] == {"SPARK_RAPIDS_TPU_CALIB_CACHE": ""}
        assert config["must_be_unset"] == [
            "SPARK_RAPIDS_TPU_PALLAS_ROWCONV"]
        assert config["assumed"]["engines"]["tpu"] == {
            "to_rows": "pallas", "from_rows": "words"}
        assert "gather" not in json.dumps(config["assumed"]["engines"])
