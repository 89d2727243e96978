"""Rehearsal compiles for a described TPU v5e (no chip attached).

The chip's own compiler is installed in the sandbox and compiles for a
topology that is described, not attached (``on-chip-measurement`` guide,
section 2).  These tests compile the main path's jitted programs at the
sizes ``chip_smoke.py`` runs on the chip — TPC-DS SF10 facts in the 2^25
row bucket with join capacity 2^23, row conversion at 212 columns x 2^19
rows — and at the benchmark's 2^20-row row-conversion cell, so that what
the chip's compiler refuses costs no chip time.
Nothing runs here: a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every xdist
worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

FACT_BUCKET = 1 << 25        # bucket_rows(28_800_991)
RETURNS_BUCKET = 1 << 22     # bucket_rows(28_800_991 // 8)
JOIN_CAPACITY = 1 << 23
ROWCONV_ROWS = 1 << 19
ROWCONV_CELL_ROWS = 1 << 20    # rowconv-fixed-212x1m-roundtrip
ROWCONV_COLS = 212

I32, I64, U32 = jnp.int32, jnp.int64, jnp.uint32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever stops the describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shaped(topo):
    """shape -> ShapeDtypeStruct placed on the first described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


Q5_FACT_DTYPES = (I32, I32, I64, I64)    # date, store, amount a, amount b
HBM_GIB = 14.0                           # one v5e chip: 16 GB, with room


def _device_gib(compiled) -> float:
    """Arguments + outputs + temporaries of one compiled program."""
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes) / 2 ** 30


# ------------------------------------------------------------ served path


def test_q5_kernel_compiles_at_sf10(shaped):
    from spark_rapids_tpu.models import tpcds
    kernel = tpcds._q5_kernel(8, JOIN_CAPACITY, lambda x: x, lambda b: b)
    args = ([shaped((FACT_BUCKET,), dt) for dt in Q5_FACT_DTYPES]
            + [shaped((RETURNS_BUCKET,), dt) for dt in Q5_FACT_DTYPES]
            + [shaped((14,), I32), shaped((8,), I32)])
    compiled = jax.jit(kernel).lower(*args).compile()
    assert _device_gib(compiled) < HBM_GIB


def _stage_args(stage, shaped, columns_by_input):
    """The fused stage executable's flat argument list, as
    CompiledStage._bind_args builds it: every input's (shape, dtype)
    columns, then one int32 row count per bucketed input."""
    cols, nvalid = [], []
    for inp in stage.plan.inputs:
        columns = columns_by_input[inp.name]
        assert len(columns) == len(inp.columns)
        cols += [shaped(shape, dt) for shape, dt in columns]
        if inp.bucket:
            nvalid.append(shaped((), I32))
    return cols + nvalid


def _vectors(rows, dtypes):
    return [((rows,), dt) for dt in dtypes]


def test_q5_fused_stage_compiles_at_sf10(shaped):
    from spark_rapids_tpu.plan import catalog
    from spark_rapids_tpu.plan.compiler import compile_stage
    stage = compile_stage(catalog.q5_partials_plan(8, JOIN_CAPACITY))
    args = _stage_args(stage, shaped, {
        "s": _vectors(FACT_BUCKET, Q5_FACT_DTYPES),
        "r": _vectors(RETURNS_BUCKET, Q5_FACT_DTYPES),
        "d": _vectors(16, (I32,)),
    })
    compiled = jax.jit(stage._fused_callable()).lower(*args).compile()
    assert _device_gib(compiled) < HBM_GIB
    finish = compile_stage(catalog.q5_finish_plan(8))
    args = _stage_args(finish, shaped, {
        "xchg": _vectors(8, (I64, I64, I64, I64)) + [((), jnp.bool_)],
        "dims": _vectors(8, (I32,)),
    })
    jax.jit(finish._fused_callable()).lower(*args).compile()


Q3_DATE_ROWS, Q3_ITEM_ROWS = 730, 102_000   # the benchmark's dims


def _q3_lookup_ops(text, scopes):
    """(products, gathers) among the compiled operations under the
    ``srt/...`` scopes that hold q3's dim lookups."""
    lines = [ln for ln in text.splitlines()
             if any(f"/{s}/" in ln for s in scopes)]
    return ([ln for ln in lines if " convolution(" in ln],
            [ln for ln in lines if " gather(" in ln])


def _assert_q3_dims_are_looked_up_by_products(text, scopes):
    """The date dim's two tables are one product (one ``convolution``
    in the chunk loop's body); the item dim's two are another while
    ``ops/dense_lookup``'s bound admits 102,000 rows x 8 limbs, else
    two gathers; no gather is left for a table under the bound."""
    from spark_rapids_tpu.ops import dense_lookup as dl
    assert dl.engine((I32, I32), Q3_DATE_ROWS) == "dense"
    items_dense = dl.engine((I32, I32), Q3_ITEM_ROWS) == "dense"
    products, gathers = _q3_lookup_ops(text, scopes)
    assert len(products) == (2 if items_dense else 1), products
    assert len(gathers) == (0 if items_dense else 2), gathers
    assert not any(f"s32[{Q3_DATE_ROWS}]" in ln for ln in gathers)


def test_q3_fused_stage_compiles_at_sf10(shaped):
    """The fused q3 stage on the 2^25 bucket with the benchmark's dims
    (730 days, 102,000 items, 2 x 1,000 groups): the dim lookups are
    products under the scope of the node that reads each index first."""
    from spark_rapids_tpu.plan import catalog
    from spark_rapids_tpu.plan.compiler import compile_stage
    stage = compile_stage(catalog.q3_plan(10_957, 2, 1000, 3))
    args = _stage_args(stage, shaped, {
        "s": _vectors(FACT_BUCKET, (I32, I32, I64)),
        "dims": (_vectors(Q3_DATE_ROWS, (I32, I32))
                 + _vectors(Q3_ITEM_ROWS, (I32, I32))),
    })
    compiled = jax.jit(stage._fused_callable()).lower(*args).compile()
    assert _device_gib(compiled) < HBM_GIB
    _assert_q3_dims_are_looked_up_by_products(
        compiled.as_text(), ("srt/q3/year_idx", "srt/q3/keep",
                             "srt/q3/brand"))


def test_q3_kernel_compiles_to_matrix_products_at_sf10(shaped):
    """The hand-written q3 at the benchmark's cardinalities (28,800,991
    rows, 102,000 items, 2 x 1,000 groups): the chip's compiler makes
    both segment sums and the dim lookups matrix products and leaves
    no scatter."""
    from spark_rapids_tpu.models import tpcds
    kernel = tpcds._q3_kernel(10_957, 2, 1000, 3, 11, 100, lambda x: x)
    args = [shaped(shape, dt) for shape, dt in
            _vectors(28_800_991, (I32, I32, I64))
            + _vectors(Q3_DATE_ROWS, (I32, I32))
            + _vectors(Q3_ITEM_ROWS, (I32, I32))]
    compiled = jax.jit(kernel).lower(*args).compile()
    assert _device_gib(compiled) < HBM_GIB
    text = compiled.as_text()
    products = [ln for ln in text.splitlines()
                if "convolution(" in ln and "srt/q3/segment_sum" in ln]
    assert len(products) == 2 and "scatter" not in text
    _assert_q3_dims_are_looked_up_by_products(
        text, ("srt/q3/dim_gather",))


def test_q9_compiles_at_sf10_with_f64_divide(shaped):
    """q9 divides in float64 on the device; the chip has no f64 unit,
    so this is the compile that says whether XLA emulates or refuses."""
    from spark_rapids_tpu.models import tpcds
    compiled = tpcds._run_q9_jit.lower(
        shaped((FACT_BUCKET,), I32), shaped((FACT_BUCKET,), I64),
        shaped((FACT_BUCKET,), I64)).compile()
    assert "f64" in compiled.as_text()


# --------------------------------------------------------- row conversion


@pytest.fixture(scope="module")
def rowconv():
    """The bench_impl.py schema (212 cycled fixed-width columns) and
    its JCUDF layout."""
    from spark_rapids_tpu.columns import dtypes
    from spark_rapids_tpu.ops import row_conversion as RC
    cycle = [dtypes.INT64, dtypes.INT32, dtypes.FLOAT64, dtypes.FLOAT32,
             dtypes.INT16, dtypes.INT8, dtypes.BOOL8,
             dtypes.TIMESTAMP_MICROS]
    schema = [cycle[i % len(cycle)] for i in range(ROWCONV_COLS)]
    starts, voff, fixed = RC.compute_layout(schema)
    row_size = (fixed + 7) // 8 * 8
    assert (row_size, row_size // 4) == (1096, 274)
    return schema, starts, voff, row_size


def _column_structs(schema, shaped, rows):
    """One (rows,) operand per column, in the dtype the column really
    carries on the device (FLOAT64 is raw u64 bits)."""
    import numpy as np

    from spark_rapids_tpu.columns.column import Column

    def struct(dt):
        if dt.kind == "decimal128":         # four int32 limbs a value
            return shaped((rows, 4), jnp.int32)
        return shaped((rows,), Column.from_numpy(
            np.zeros(1, dt.np_dtype), dtype=dt).data.dtype)

    return tuple(struct(dt) for dt in schema)


def _columns(schema, datas, rows):
    from spark_rapids_tpu.columns.column import Column
    return [Column(dt, rows, data=d, validity=None)
            for dt, d in zip(schema, datas)]


def test_rowconv_stack_path_compiles(shaped, rowconv):
    from spark_rapids_tpu.ops import row_conversion as RC
    schema, starts, voff, row_size = rowconv

    def to_rows(datas):
        return RC._assemble_fixed_words(
            _columns(schema, datas, ROWCONV_ROWS), starts, voff, row_size)

    compiled = jax.jit(to_rows).lower(
        _column_structs(schema, shaped, ROWCONV_ROWS)).compile()
    assert _device_gib(compiled) < HBM_GIB


def test_rowconv_from_rows_compiles_at_the_cell_size(shaped, rowconv):
    """The two from-rows executables of the benchmark's cell: 2^20 rows
    of 212 columns as one flat word buffer transposed to (274, 8192,
    128) blocks, then every field a static slice of a block, the seven
    validity blocks as they are and their AND over the rows: 214
    results, where a validity vector a column made 424 (ISSUE 37)."""
    from spark_rapids_tpu.ops import row_conversion as RC
    schema, starts, voff, row_size = rowconv
    n_words = row_size // 4

    def transpose(flat):
        return RC._transpose_row_words(flat, ROWCONV_CELL_ROWS, row_size)

    def extract(blocks):
        return RC._extract_fixed_words(blocks, schema, starts, voff)

    first = jax.jit(transpose).lower(shaped(
        (ROWCONV_CELL_ROWS * n_words,), U32)).compile()
    second = jax.jit(extract).lower(shaped(
        (n_words, ROWCONV_CELL_ROWS // 128, 128), U32)).compile()
    for compiled in (first, second):
        assert _device_gib(compiled) < HBM_GIB
        assert " gather(" not in compiled.as_text()
    # the slices read the blocks in place: no temporaries to speak of
    assert second.memory_analysis().temp_size_in_bytes < 2 ** 26
    results = jax.tree_util.tree_leaves(second.out_info)
    assert len(results) == len(schema) + 2
    vals, vwords, all_valid = second.out_info
    assert len(vals) == len(schema)
    assert (vwords.shape, vwords.dtype) == (
        (7, ROWCONV_CELL_ROWS // 128, 128), U32)
    assert (all_valid.shape, all_valid.dtype) == ((7,), U32)


def test_rowconv_validity_vector_compiles_at_the_cell_size(shaped):
    """The executable that makes one column's validity vector out of
    the kept validity words, which a column with a null alone asks for:
    the column's bit is an operand, so one serves all 212."""
    from spark_rapids_tpu.ops import row_conversion as RC

    compiled = jax.jit(RC._column_validity).lower(
        shaped((7, ROWCONV_CELL_ROWS // 128, 128), U32),
        shaped((), I32)).compile()
    assert _device_gib(compiled) < HBM_GIB
    assert " gather(" not in compiled.as_text()
    (out,) = jax.tree_util.tree_leaves(compiled.out_info)
    assert (out.shape, out.dtype) == ((ROWCONV_CELL_ROWS,), jnp.uint8)


# The Pallas to-rows kernel is a TPU's engine for fixed-width schemas
# (ops/row_conversion._to_rows).  As first written the chip's compiler
# refused it: i64 block indices under x64, 512-row 1-D blocks against
# XLA's T(1024) layout, and a tile orientation that asked Mosaic for one
# relayout per word (VMEM exhausted at 274 words after a 57 s compile).
# The from-rows tile kernel went with PR 32; the string paste is opt-in
# and not brought up.


def _cycled(n):
    from spark_rapids_tpu.columns import dtypes
    cycle = [dtypes.INT64, dtypes.INT32, dtypes.FLOAT64, dtypes.FLOAT32,
             dtypes.INT16, dtypes.INT8, dtypes.BOOL8,
             dtypes.TIMESTAMP_MICROS]
    return [cycle[i % len(cycle)] for i in range(n)]


def _all_of(name, n):
    from spark_rapids_tpu.columns import dtypes
    dt = dtypes.decimal128(-2) if name == "decimal128" \
        else getattr(dtypes, name)
    return [dt] * n


def _with_decimals(n):
    from spark_rapids_tpu.columns import dtypes
    return [dtypes.decimal128(-2) if i % 11 == 10 else dt
            for i, dt in enumerate(_cycled(n))]


def _layout(schema):
    from spark_rapids_tpu.ops import row_conversion as RC
    starts, voff, fixed = RC.compute_layout(schema)
    return starts, voff, (fixed + 7) // 8 * 8


def _pallas_to_rows(schema, shaped, rows, nullable):
    """The tile kernel of ``schema`` compiled for the chip as
    ``_to_rows_fixed_cached`` builds it."""
    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.ops import row_assembly_pallas as RP
    from spark_rapids_tpu.ops import row_conversion as RC
    starts, voff, row_size = _layout(schema)

    def to_rows(datas, valids):
        cols = [Column(dt, rows, data=d, validity=v)
                for dt, d, v in zip(schema, datas, valids)]
        inputs, plan = RC.build_plan(cols, starts, voff, row_size // 4)
        return RP.assemble_rows_pallas(inputs, plan, rows, row_size // 4,
                                       interpret=False)

    valids = tuple(shaped((rows,), jnp.uint8) if nullable else None
                   for _ in schema)
    return jax.jit(to_rows).lower(
        _column_structs(schema, shaped, rows), valids).compile()


# what ``row_conversion._tile_fits`` admits the chip's compiler takes:
# the cell, the small buckets, and the widest schema of each family
# that the rule lets through (the tile's VMEM does not depend on the
# row count, so those compile at 2^18 rows)
PALLAS_ADMITTED = [
    ("cell_all_valid", lambda: _cycled(212), ROWCONV_CELL_ROWS, False),
    ("cell_nullable", lambda: _cycled(212), ROWCONV_CELL_ROWS, True),
    ("three_columns_bucket_8", lambda: _cycled(3), 8, True),
    ("three_columns_bucket_512", lambda: _cycled(3), 512, False),
    ("decimal128_among_40", lambda: _with_decimals(40), 1 << 18, True),
    ("widest_cycled_599", lambda: _cycled(599), 1 << 18, False),
    ("widest_int8_1514", lambda: _all_of("INT8", 1514), 1 << 18, False),
    ("widest_int16_1052", lambda: _all_of("INT16", 1052), 1 << 18, False),
    ("widest_int64_427", lambda: _all_of("INT64", 427), 1 << 18, False),
    ("widest_decimal128_218", lambda: _all_of("decimal128", 218),
     1 << 18, True),
]


@pytest.mark.parametrize("make,rows,nullable",
                         [c[1:] for c in PALLAS_ADMITTED],
                         ids=[c[0] for c in PALLAS_ADMITTED])
def test_pallas_to_rows_compiles_where_the_rule_admits(shaped, make, rows,
                                                       nullable):
    from spark_rapids_tpu.ops import row_conversion as RC
    schema = make()
    assert RC._tile_fits(schema, _layout(schema)[2])
    compiled = _pallas_to_rows(schema, shaped, rows, nullable)
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_gib(compiled) < HBM_GIB


def test_widest_admitted_schemas_are_the_widest():
    """One column more and the rule sends each family to the word
    path: the cases above are its boundary."""
    from spark_rapids_tpu.ops import row_conversion as RC
    for wider in (_cycled(600), _all_of("INT8", 1515),
                  _all_of("INT16", 1053), _all_of("INT64", 428),
                  _all_of("decimal128", 219)):
        assert not RC._tile_fits(wider, _layout(wider)[2])


def test_mosaic_refuses_a_row_the_rule_refuses(shaped):
    """800 cycled columns are 1,026 words a row: the tile no longer
    fits the kernel's VMEM, which is why such rows are the word
    path's.  (700 columns still compile; the rule gives way at 600.)"""
    from spark_rapids_tpu.ops import row_conversion as RC
    schema = _cycled(800)
    assert not RC._tile_fits(schema, _layout(schema)[2])
    with pytest.raises(Exception, match="vmem"):
        _pallas_to_rows(schema, shaped, 1 << 18, False)


def test_thousand_columns_compile_on_the_word_path(shaped):
    """A schema five times the cell's width (5,128 B a row): to rows by
    XLA's word assembly, from rows by the transposed word slices, both
    under the chip's memory at 2^18 rows (1.3 GB of rows) and without a
    gather."""
    from spark_rapids_tpu.ops import row_conversion as RC
    schema, rows = _cycled(1000), 1 << 18
    starts, voff, row_size = _layout(schema)
    assert not RC._tile_fits(schema, row_size)

    def to_rows(datas):
        return RC._assemble_fixed_words(_columns(schema, datas, rows),
                                        starts, voff, row_size)

    def transpose(flat):
        return RC._transpose_row_words(flat, rows, row_size)

    def extract(blocks):
        return RC._extract_fixed_words(blocks, schema, starts, voff)

    n_words = row_size // 4
    for fn, arg in ((to_rows, _column_structs(schema, shaped, rows)),
                    (transpose, shaped((rows * n_words,), U32)),
                    (extract, shaped((n_words, rows // 128, 128), U32))):
        compiled = jax.jit(fn).lower(arg).compile()
        assert _device_gib(compiled) < HBM_GIB
        assert " gather(" not in compiled.as_text()
    assert len(jax.tree_util.tree_leaves(compiled.out_info)) == 1000 + 2


@pytest.mark.xfail(strict=True, reason=(
    "not brought up: jnp.take_along_axis over u8 inside the kernel "
    "raises 'NotImplementedError: 64-bit types are not supported' "
    "while lowering for Mosaic under x64"))
def test_pallas_string_paste_compiles(shaped):
    from spark_rapids_tpu.ops import row_assembly_pallas as RP

    def paste(mat, chars, vstart, lens):
        return RP.paste_strings_pallas(mat, chars, vstart, lens,
                                       interpret=False)

    rows = 1 << 16
    compiled = jax.jit(paste).lower(
        shaped((rows, 256), jnp.uint8), shaped((rows, 64), jnp.uint8),
        shaped((rows,), I32), shaped((rows,), I32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -------------------------------------------------------------- four chips


def test_q5_multichip_compiles_for_four_chips(topo):
    """The mesh q5 on the described 2x2: one all-reduce for the group
    table, and the facts split four ways (per-device argument bytes are
    a quarter of the single-chip program's)."""
    from spark_rapids_tpu.models import tpcds
    mesh = Mesh(topo.devices, ("data",))
    shard = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    rows, r_rows = 28_800_992, 3_600_124      # SF10, padded to 4 | rows

    def struct(n, dt, sharding):
        return jax.ShapeDtypeStruct((n,), dt, sharding=sharding)

    args = ([struct(rows, dt, shard) for dt in Q5_FACT_DTYPES]
            + [struct(r_rows, dt, shard) for dt in Q5_FACT_DTYPES]
            + [struct(14, I32, rep), struct(8, I32, rep)])
    q5 = tpcds.make_q5_multichip(mesh, 8, JOIN_CAPACITY)
    # the wrapper runs the jitted shard_map under a span + retry
    # driver; lower the jitted program itself
    compiled = q5.__wrapped__.lower(*args).compile()
    text = compiled.as_text()
    assert "all-reduce" in text
    fact_bytes = (rows + r_rows) * 24
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert fact_bytes / 4 <= per_device <= fact_bytes / 4 * 1.05
