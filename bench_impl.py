"""Headline benchmark: JCUDF row<->columnar conversion throughput.

Mirrors the reference harness shape (benchmarks/row_conversion.cpp:27-60:
2^N rows x 212 columns of cycled fixed-width dtypes, to-rows and from-rows).
vs_baseline compares against a single-thread numpy host implementation of
the same byte assembly — the CPU path a Spark executor would otherwise run.
"""

import time

import jax
import numpy as np

jax.config.update("jax_enable_x64", True)


def _make_table(rows: int, ncols: int):
    from spark_rapids_tpu.columns import dtypes
    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.columns.table import Table

    rng = np.random.default_rng(7)
    cycle = [dtypes.INT64, dtypes.INT32, dtypes.FLOAT64, dtypes.FLOAT32,
             dtypes.INT16, dtypes.INT8, dtypes.BOOL8, dtypes.TIMESTAMP_MICROS]
    cols = []
    for i in range(ncols):
        dt = cycle[i % len(cycle)]
        if dt.kind in ("float32",):
            arr = rng.normal(size=rows).astype(np.float32)
        elif dt.kind in ("float64",):
            arr = rng.normal(size=rows)
        elif dt.kind == "bool8":
            arr = rng.integers(0, 2, rows).astype(np.uint8)
        else:
            info = np.iinfo(dt.np_dtype)
            arr = rng.integers(info.min // 2, info.max // 2, rows).astype(
                dt.np_dtype)
        cols.append(Column.from_numpy(arr, dtype=dt))
    return Table(cols)


def _numpy_to_rows_reference(table, layout):
    """Single-thread numpy host assembly of the same JCUDF bytes."""
    starts, voff, fixed = layout
    rows = table.num_rows
    row_size = (fixed + 7) // 8 * 8
    out = np.zeros((rows, row_size), np.uint8)
    for c, st in zip(table.columns, starts):
        host = c.to_numpy()
        # decimal128 is four limbs a value, and a chip hands a 2-D
        # array back in its own strides
        b = np.ascontiguousarray(host).view(np.uint8).reshape(rows, -1)
        out[:, st:st + b.shape[1]] = b
    nb = (len(table.columns) + 7) // 8
    v = np.full((rows, nb), 0, np.uint8)
    for i, c in enumerate(table.columns):
        bit = (np.ones(rows, np.uint8) if c.validity is None
               else np.asarray(c.validity))
        v[:, i // 8] |= bit << (i % 8)
    out[:, voff:voff + nb] = v
    return out


def run():
    from spark_rapids_tpu.ops import row_conversion as RC

    rows = 1 << 19
    ncols = 212
    table = _make_table(rows, ncols)
    layout = RC.compute_layout([c.dtype for c in table.columns])
    row_size = (layout[2] + 7) // 8 * 8
    total_bytes = rows * row_size

    # Time a chained window that ends in block_until_ready: K conversions
    # linked through a data dependency (salt_{i+1} is derived from
    # iteration i's output), so no two can overlap and none can be elided.
    import jax.numpy as jnp
    from spark_rapids_tpu.columns.column import Column as _C
    from spark_rapids_tpu.columns.table import Table as _T

    def step(t, salt):
        c0 = t.columns[0]
        salted = _C(c0.dtype, c0.length, data=c0.data + salt,
                    validity=c0.validity)
        rows_col = RC.convert_to_rows(_T([salted] + t.columns[1:]))
        data = rows_col.children[0].data
        # the buffer is RETURNED from jit: XLA must materialize it fully
        # (a reduction-only salt lets XLA push the sum through the stack
        # and skip the writes; an element-only salt risks slicing).  The
        # cheap chained salt serializes iterations.
        new_salt = data[0].astype(jnp.int64) + salt
        return data, new_salt

    step_j = jax.jit(step)
    buf, salt = step_j(table, jnp.int64(0))
    jax.block_until_ready((buf, salt))    # warm: compile + first run

    iters = 30
    t0 = time.perf_counter()
    for _ in range(iters):
        buf, salt = step_j(table, salt)   # chained: serialized on device
    jax.block_until_ready((buf, salt))
    dt_dev = (time.perf_counter() - t0) / iters
    gbps = total_bytes / dt_dev / 1e9

    # numpy host baseline (single pass; it's deterministic)
    t0 = time.perf_counter()
    _numpy_to_rows_reference(table, layout)
    dt_np = time.perf_counter() - t0
    gbps_np = total_bytes / dt_np / 1e9

    return {
        "metric": "jcudf_to_rows_212cols_524288rows",
        "value": round(gbps, 3),
        "unit": "GB/s",
        "vs_baseline": round(gbps / gbps_np, 3),
    }


if __name__ == "__main__":
    import json
    print(json.dumps(run()))
