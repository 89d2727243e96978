"""Binding of the ``op_loop`` driver to the program's row conversion:
``convert_to_rows`` then ``convert_from_rows`` on a table resident on
the device, called as a caller calls them (eager, through
perf/jit_cache; no outer jit)."""

import time

import numpy as np


def build(inputs):
    """The program's Table from the reference's numpy columns."""
    import jax

    from spark_rapids_tpu.columns import dtypes
    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.columns.table import Table

    by_kind = {"int64": dtypes.INT64, "int32": dtypes.INT32,
               "float64": dtypes.FLOAT64, "float32": dtypes.FLOAT32,
               "int16": dtypes.INT16, "int8": dtypes.INT8,
               "bool8": dtypes.BOOL8,
               "timestamp_micros": dtypes.TIMESTAMP_MICROS}
    table = Table([Column.from_numpy(arr, dtype=by_kind[k])
                   for k, arr in zip(inputs["kinds"], inputs["columns"])])
    jax.block_until_ready([c.data for c in table.columns])
    return {"table": table, "schema": [c.dtype for c in table.columns],
            "rows": table.num_rows}


def rows_per_op(state):
    return 2 * state["rows"]          # each direction counted


def run(state, annotate):
    """One round trip; returns (outputs, {span: seconds})."""
    import jax

    from spark_rapids_tpu.ops import row_conversion as RC

    t0 = time.perf_counter()
    with annotate("to_rows"):
        rows_col = RC.convert_to_rows(state["table"])
        jax.block_until_ready(rows_col.children[0].data)
    t1 = time.perf_counter()
    with annotate("from_rows"):
        back = RC.convert_from_rows(rows_col, state["schema"])
        jax.block_until_ready([c.data for c in back.columns])
    t2 = time.perf_counter()
    return (rows_col, back), {"to_rows": t1 - t0, "from_rows": t2 - t1}


def produced(state, outputs):
    """The outputs as host bytes, in the reference's form."""
    rows_col, back = outputs
    words = np.asarray(rows_col.children[0].data)
    flat = words.view(np.uint8)
    rows = state["rows"]
    return {"rows": flat.reshape(rows, flat.size // max(rows, 1)),
            "columns": [c.to_numpy() for c in back.columns]}
