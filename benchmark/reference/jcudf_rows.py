"""Plain numpy reference for the JCUDF row format over the upstream
benchmark's table (spark-rapids-jni benchmarks/row_conversion.cpp:
fixed-width columns of 8 cycled dtypes): the table from a seed, the row
layout, the bytes of every row, and the columns a round trip must give
back.  Imports nothing of the program."""

import numpy as np

# (kind, numpy dtype) in the upstream cycle's order
CYCLE = (("int64", np.int64), ("int32", np.int32), ("float64", np.float64),
         ("float32", np.float32), ("int16", np.int16), ("int8", np.int8),
         ("bool8", np.uint8), ("timestamp_micros", np.int64))
ROW_ALIGNMENT = 8
LIMITS = {"row_bytes_differing": 0, "column_bytes_differing": 0}


def kinds(params):
    n = int(params["columns"])
    return [CYCLE[i % len(CYCLE)] for i in range(n)]


def make_inputs(sizes, params, data_seed):
    """One numpy array per column, all valid, drawn from the seed."""
    rows = int(sizes["rows"])
    rng = np.random.default_rng(data_seed)
    cols = []
    for kind, dt in kinds(params):
        if kind == "float32":
            arr = rng.normal(size=rows).astype(np.float32)
        elif kind == "float64":
            arr = rng.normal(size=rows)
        elif kind == "bool8":
            arr = rng.integers(0, 2, rows).astype(np.uint8)
        else:
            info = np.iinfo(dt)
            arr = rng.integers(info.min // 2, info.max // 2, rows).astype(dt)
        cols.append(arr)
    return {"kinds": [k for k, _ in kinds(params)], "columns": cols}


def layout(widths):
    """(starts, validity_offset, row_size): each column aligned to its
    own width, one validity bit per column after the last, the row
    padded to 8 bytes (JCUDF, row_conversion.cu
    compute_column_information)."""
    starts, size = [], 0
    for w in widths:
        size = (size + w - 1) // w * w
        starts.append(size)
        size += w
    validity_offset = size
    size += (len(widths) + 7) // 8
    return starts, validity_offset, (
        size + ROW_ALIGNMENT - 1) // ROW_ALIGNMENT * ROW_ALIGNMENT


def _assemble(cols):
    rows = len(cols[0])
    starts, voff, row_size = layout([c.dtype.itemsize for c in cols])
    out = np.zeros((rows, row_size), np.uint8)
    for c, st in zip(cols, starts):
        w = c.dtype.itemsize
        out[:, st:st + w] = c.view(np.uint8).reshape(rows, w)
    for i in range(len(cols)):       # every column valid
        out[:, voff + i // 8] |= np.uint8(1 << (i % 8))
    return out


def answer(inputs, params):
    return {"rows": _assemble(inputs["columns"]),
            "columns": inputs["columns"]}


def control_answer(inputs, params):
    """The guarantee broken: float64 columns carried as float32, the
    widest float a TPU holds natively, so neither the row bytes nor the
    columns back are identical."""
    cols = [c.astype(np.float32).astype(np.float64)
            if c.dtype == np.float64 else c for c in inputs["columns"]]
    return {"rows": _assemble(cols), "columns": cols}


def compare(got, want):
    g, w = got["rows"], want["rows"]
    if g.shape != w.shape:
        row_bad = max(g.size, w.size)
    else:
        row_bad = int(np.count_nonzero(g != w))
    col_bad = abs(len(got["columns"]) - len(want["columns"]))
    for a, b in zip(got["columns"], want["columns"]):
        a, b = np.ascontiguousarray(a), np.ascontiguousarray(b)
        if a.dtype.itemsize != b.dtype.itemsize or a.shape != b.shape:
            col_bad += max(a.nbytes, b.nbytes)
        else:
            col_bad += int(np.count_nonzero(
                a.view(np.uint8) != b.view(np.uint8)))
    return {"row_bytes_differing": row_bad,
            "column_bytes_differing": col_bad}


def min_bytes(sizes, params):
    """A round trip reads the columnar table and writes the rows, then
    reads the rows and writes the columns: each once, each way."""
    rows = int(sizes["rows"])
    widths = [np.dtype(dt).itemsize for _k, dt in kinds(params)]
    columnar = rows * sum(widths)
    row_bytes = rows * layout(widths)[2]
    return 2 * (columnar + row_bytes)
