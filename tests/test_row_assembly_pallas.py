"""The Pallas tile kernels of row conversion against the XLA paths they
replace (interpret mode on the CPU): the to-rows row assembly, which is
a TPU's engine for fixed-width schemas, and the string-payload paste
(opt-in, not brought up on the chip).  The from-rows tile kernel and
the tests of the environment switch went with PR 32."""

import numpy as np
import pytest

from spark_rapids_tpu.columns import dtypes
from spark_rapids_tpu.columns.column import Column
from spark_rapids_tpu.columns.table import Table
from spark_rapids_tpu.ops import row_conversion as RC
from spark_rapids_tpu.ops.row_assembly_pallas import assemble_rows_pallas

CYCLE = [dtypes.INT64, dtypes.INT32, dtypes.FLOAT64, dtypes.FLOAT32,
         dtypes.INT16, dtypes.INT8, dtypes.BOOL8, dtypes.TIMESTAMP_MICROS]


def _make_cols(rng, rows, ncols, with_nulls=True, with_dec=False):
    cols = []
    for i in range(ncols):
        dt = CYCLE[i % len(CYCLE)]
        if with_dec and i % 11 == 10:
            c = Column.from_pylist(
                [int.from_bytes(rng.bytes(12), "little", signed=True)
                 for _ in range(rows)],
                dtypes.decimal128(-2))
        else:
            if dt.kind == "float32":
                arr = rng.normal(size=rows).astype(np.float32)
            elif dt.kind == "float64":
                arr = rng.normal(size=rows)
            elif dt.kind == "bool8":
                arr = rng.integers(0, 2, rows).astype(np.uint8)
            else:
                info = np.iinfo(dt.np_dtype)
                arr = rng.integers(info.min // 2, info.max // 2,
                                   rows).astype(dt.np_dtype)
            c = Column.from_numpy(arr, dtype=dt)
        if with_nulls and i % 3 == 0:
            c = Column(c.dtype, c.length, data=c.data,
                       validity=np.asarray(rng.integers(0, 2, rows),
                                           np.uint8),
                       offsets=c.offsets, children=c.children)
        cols.append(c)
    return cols


@pytest.mark.parametrize("rows,ncols,br", [
    (1000, 20, 256),      # ragged edge block
    (512, 212, 128),      # bench-shape schema
    (7, 3, 512),          # rows < block
    (256, 12, 256),       # exact single block
])
def test_pallas_assembly_matches_reference(rows, ncols, br):
    rng = np.random.default_rng(rows + ncols)
    cols = _make_cols(rng, rows, ncols, with_dec=(ncols == 12))
    starts, voff, fixed = RC.compute_layout([c.dtype for c in cols])
    row_size = (fixed + 7) // 8 * 8
    ref = np.asarray(RC._assemble_fixed_words(cols, starts, voff,
                                              row_size))
    inputs, plan = RC.build_plan(cols, starts, voff, row_size // 4)
    got = np.asarray(assemble_rows_pallas(
        inputs, plan, rows, row_size // 4, block_rows=br,
        interpret=True))
    np.testing.assert_array_equal(ref, got)


# --------------------------------------- string payload tiling (r5)


def test_pallas_string_paste_matches_scatter():
    """The VMEM gather paste must reproduce _masked_row_scatter."""
    import jax.numpy as jnp

    from spark_rapids_tpu.ops.row_assembly_pallas import \
        paste_strings_pallas

    rng = np.random.default_rng(5)
    rows, max_row, pad = 100, 64, 16
    mat = rng.integers(0, 256, (rows, max_row)).astype(np.uint8)
    chars = rng.integers(97, 123, (rows, pad)).astype(np.uint8)
    lens = rng.integers(0, pad + 1, rows).astype(np.int32)
    vstart = rng.integers(0, max_row - pad, rows).astype(np.int32)
    j = np.arange(pad, dtype=np.int32)
    dest = vstart[:, None] + j[None, :]
    m = j[None, :] < lens[:, None]
    ref = np.asarray(RC._masked_row_scatter(
        jnp.asarray(mat), jnp.asarray(dest), jnp.asarray(chars),
        jnp.asarray(m)))
    got = np.asarray(paste_strings_pallas(
        jnp.asarray(mat), jnp.asarray(chars), jnp.asarray(vstart),
        jnp.asarray(lens), interpret=True))
    np.testing.assert_array_equal(ref, got)


def test_pallas_string_to_rows_env_opt_in(monkeypatch):
    """convert_to_rows with string columns routes the payload paste
    through the tile kernel under the env flag, byte-identically."""
    strs = ["alpha", "", None, "bee", "sea", "longer-string-here"] * 20
    cols = [Column.from_pylist(list(range(120)), dtypes.INT64),
            Column.from_strings(strs)]
    t = Table(cols)
    base = RC.convert_to_rows(t)
    monkeypatch.setenv("SPARK_RAPIDS_TPU_PALLAS_ROWCONV", "1")
    via = RC.convert_to_rows(t)
    assert np.array_equal(np.asarray(base.children[0].data),
                          np.asarray(via.children[0].data))
    assert np.array_equal(np.asarray(base.offsets),
                          np.asarray(via.offsets))
