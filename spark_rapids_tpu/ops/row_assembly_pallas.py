"""Single-pass Pallas row-assembly kernel for JCUDF conversion.

The default `_assemble_fixed_words` path (row_conversion.py) composes
each output u32 word as an OR of shifted column vectors and relies on
XLA's `jnp.stack(words, axis=1)` to materialize the (rows, W) matrix;
the stack's strided stores pass through HBM.

This kernel instead builds each (BLOCK_ROWS, W) tile in VMEM: column
blocks stream in once in their NATIVE widths (u8/u16/u32 — the narrow
converts and shifts happen in-register), the word vectors stack along
sublanes and ONE aligned transpose in VMEM turns them into the row
tile, which is stored once.  The only pre-pass is splitting 8-byte
columns into u32 lo/hi halves (TPU vectors are 32-bit; see
docs/tpu_design.md §2 for why (rows, 2) u32 bitcasts are not safe on
the TPU's tiling).

Reference counterpart: row_conversion.cu:591 copy_to_rows (shared-memory
tiled memcpy); the TPU shape is word-composition, not memcpy.

Both directions live here (r5): `assemble_rows_pallas` builds row
tiles (copy_to_rows), `disassemble_rows_pallas` streams the packed row
matrix through VMEM once and slices every column field out in-register
(copy_from_rows), and `paste_strings_pallas` gathers string payloads
into row tiles (the string variants, row_conversion.cu:71-73) instead
of scattering across the whole HBM matrix.

Opt-in: set SPARK_RAPIDS_TPU_PALLAS_ROWCONV=1 (row_conversion routes
to-rows, from-rows, and the string paste through these kernels), or
call directly.  `interpret=True` runs anywhere (tests use the CPU
backend).  On a chip to-rows and from-rows compile for Mosaic and match
the stack path byte for byte (chip_smoke.py; device throughput: not
measured); `paste_strings_pallas` is NOT brought up — its
take_along_axis does not lower for Mosaic under x64
(tests/test_tpu_compile.py) — and a kernel selected on a chip raises
what the compiler raises, it never gives way to the stack path.
"""

from __future__ import annotations

from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from spark_rapids_tpu.columns.column import Column

_U32 = jnp.uint32
# block index maps must return int32: under x64 a python 0 traces as
# an i64 constant, which Mosaic refuses beside the i32 grid index
_ZERO = np.int32(0)


def _lane_pad(n_words: int) -> int:
    """Row width rounded up to whole 128-lane tiles: a 2-D transpose
    inside a kernel wants both dimensions aligned."""
    return -(-n_words // 128) * 128


def assemble_rows_pallas(inputs: Sequence[jnp.ndarray],
                         plan: Sequence[Tuple[int, int]],
                         rows: int, n_words: int,
                         block_rows: int = 1024,
                         interpret: bool = False) -> jnp.ndarray:
    """Run the tile kernel; returns flat packed u32 LE words
    (rows * n_words,), same contract as _assemble_fixed_words."""
    import jax.experimental.pallas as pl

    br = min(block_rows, max(8, rows))
    wpad = _lane_pad(n_words)

    def kernel(*refs):
        out_ref = refs[-1]
        words = [None] * wpad
        for r, (w, sh) in zip(refs[:-1], plan):
            v = r[:]
            if v.dtype != _U32:
                v = v.astype(_U32)
            if sh:
                v = v << _U32(sh)
            words[w] = v if words[w] is None else (words[w] | v)
        zeros = jnp.zeros((br,), _U32)
        # each (br,) word vector is one sublane row of the (wpad, br)
        # stack; ONE aligned transpose turns it into the row tile
        # (stacking along axis 1 asks Mosaic for a relayout per word:
        # VMEM exhausted at 274 words)
        out_ref[:, :] = jnp.stack([w if w is not None else zeros
                                   for w in words], axis=0).T

    grid = (pl.cdiv(rows, br),)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br,), lambda i: (i,)) for _ in inputs],
        # the block is lane-padded past the row width; Pallas masks
        # the out-of-bounds columns of an edge block
        out_specs=pl.BlockSpec((br, wpad), lambda i: (i, _ZERO)),
        out_shape=jax.ShapeDtypeStruct((rows, n_words), _U32),
        interpret=interpret,
    )(*inputs)
    return out.reshape(-1)


def assemble_fixed_words_pallas(cols, starts, validity_offset, row_size,
                                block_rows: int = 1024,
                                interpret: bool = False) -> jnp.ndarray:
    """Drop-in replacement for row_conversion._assemble_fixed_words.

    Routes through the process compile cache (perf/jit_cache.py) when
    enabled: column operands pad to the power-of-two row bucket,
    build_plan + the tile kernel trace once per (schema digest, bucket)
    and later batches in the same bucket reuse the executable."""
    from spark_rapids_tpu.ops.row_conversion import build_plan
    from spark_rapids_tpu.perf import jit_cache as _jc

    rows = cols[0].length
    n_words = row_size // 4
    traced = any(isinstance(c.data, jax.core.Tracer) for c in cols)
    if not _jc.cache_enabled() or rows == 0 or traced:
        inputs, plan = build_plan(cols, starts, validity_offset, n_words)
        return assemble_rows_pallas(inputs, plan, rows, n_words,
                                    block_rows=block_rows,
                                    interpret=interpret)

    from spark_rapids_tpu.columns.column import Column as _Col
    nullable = tuple(c.validity is not None for c in cols)
    schema_t = tuple(c.dtype for c in cols)
    starts_t = tuple(starts)
    digest = _jc.schema_digest(
        schema_t, nullable,
        extra=f"pallas_to:{row_size}:{block_rows}:{int(interpret)}")
    bucket = _jc.bucket_rows(rows)
    datas = tuple(_jc.pad_axis0(c.data, bucket) for c in cols)
    valids = tuple(None if c.validity is None
                   else _jc.pad_axis0(c.validity, bucket) for c in cols)

    def kernel(datas, valids):
        kcols = [_Col(dt, bucket, data=d, validity=v)
                 for dt, d, v in zip(schema_t, datas, valids)]
        inputs, plan = build_plan(kcols, starts_t, validity_offset,
                                  n_words)
        return assemble_rows_pallas(inputs, plan, bucket, n_words,
                                    block_rows=block_rows,
                                    interpret=interpret)

    out = _jc.CACHE.cached_call("pallas.to_rows", digest, kernel,
                                (datas, valids), bucket=bucket,
                                donate_argnums=(0,))
    return out[: rows * n_words]


# ------------------------------------------------- from-rows direction


def disassemble_rows_pallas(words: jnp.ndarray,
                            extract_plan: Sequence[Tuple[int, int, int]],
                            block_rows: int = 1024,
                            interpret: bool = False):
    """Inverse tile kernel (row_conversion.cu:591 copy_from_rows
    counterpart): the (rows, W) packed word matrix streams through
    VMEM once per row tile and every extraction — (word, shift, nbits)
    — slices its field out in-register.  Returns one (rows,) u32 array
    per plan entry.

    One HBM read of the row matrix feeds ALL column extractions (the
    default gather path reads the byte buffer once per column)."""
    import jax.experimental.pallas as pl

    rows, n_words = words.shape
    br = min(block_rows, max(8, rows))

    def kernel(in_ref, *out_refs):
        # one aligned transpose, then every field is a sublane row:
        # the lane extract tile[:, w] costs Mosaic a relayout each and
        # compile time grew quadratically with the field count
        words = in_ref[:, :].T
        for ref, (w, sh, nbits) in zip(out_refs, extract_plan):
            v = words[w, :]
            if sh:
                v = v >> _U32(sh)
            if nbits < 32:
                v = v & _U32((1 << nbits) - 1)
            ref[:] = v

    grid = (pl.cdiv(rows, br),)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br, _lane_pad(n_words)),
                               lambda i: (i, _ZERO))],
        out_specs=[pl.BlockSpec((br,), lambda i: (i,))
                   for _ in extract_plan],
        out_shape=[jax.ShapeDtypeStruct((rows,), _U32)
                   for _ in extract_plan],
        interpret=interpret,
    )(words)
    return outs


def build_extract_plan(schema, starts, validity_offset, n_words):
    """Per-logical-field (word, shift, nbits) extraction entries for
    a fixed-width JCUDF schema + per-column validity entries.  Field
    coordinates come from row_conversion.field_word_slots — the SAME
    layout source the assembly direction consumes."""
    from spark_rapids_tpu.ops.row_conversion import field_word_slots

    plan: List[Tuple[int, int, int]] = []
    col_entries: List[List[int]] = []
    for dt, st in zip(schema, starts):
        entries = []
        for slot in field_word_slots(dt, st):
            entries.append(len(plan))
            plan.append(slot)
        col_entries.append(entries)
    valid_entries: List[int] = []
    for ci in range(len(schema)):
        off = validity_offset + ci // 8
        valid_entries.append(len(plan))
        plan.append((off // 4, (off % 4) * 8 + (ci % 8), 1))
    assert all(w < n_words for w, _sh, _nb in plan)
    return plan, col_entries, valid_entries


def convert_from_rows_pallas(list_col: Column, schema,
                             block_rows: int = 1024,
                             interpret: bool = False):
    """Fixed-width-schema from-rows over the tile kernel; returns a
    Table matching row_conversion.convert_from_rows bit-for-bit.
    Requires uniform row sizes (fixed-width schemas have them)."""
    from spark_rapids_tpu.columns.dtypes import Kind
    from spark_rapids_tpu.columns.table import Table
    from spark_rapids_tpu.ops.row_conversion import (
        _col_byte_size, compute_layout, _round_up, JCUDF_ROW_ALIGNMENT)

    rows = list_col.length
    starts, validity_offset, fixed_size = compute_layout(schema)
    row_size = _round_up(fixed_size, JCUDF_ROW_ALIGNMENT)
    n_words = row_size // 4
    child = list_col.children[0]
    words = child.data
    assert words.dtype == _U32, "packed u32 word buffer expected"
    if int(words.size) != rows * n_words:
        raise ValueError(
            f"row buffer holds {int(words.size)} words, schema needs "
            f"{rows}x{n_words} uniform rows")
    mat = words.reshape(rows, n_words)
    plan, col_entries, valid_entries = build_extract_plan(
        schema, starts, validity_offset, n_words)
    from spark_rapids_tpu.perf import jit_cache as _jc
    if (_jc.cache_enabled() and rows > 0
            and not isinstance(mat, jax.core.Tracer)):
        # bucketed + compile-cached tile disassembly: pad the row
        # matrix (padded rows decode to garbage sliced off below)
        bucket = _jc.bucket_rows(rows)
        mat_p = _jc.pad_axis0(mat, bucket)
        digest = _jc.schema_digest(
            schema,
            extra=f"pallas_from:{row_size}:{block_rows}:{int(interpret)}")

        def kernel(mat_p):
            return tuple(disassemble_rows_pallas(
                mat_p, plan, block_rows=block_rows, interpret=interpret))

        pieces_b = _jc.CACHE.cached_call(
            "pallas.from_rows", digest, kernel, (mat_p,),
            bucket=bucket, donate_argnums=(0,))
        pieces = [p[:rows] for p in pieces_b]
    else:
        pieces = disassemble_rows_pallas(mat, plan,
                                         block_rows=block_rows,
                                         interpret=interpret)
    out_cols = []
    for ci, dt in enumerate(schema):
        es = [pieces[e] for e in col_entries[ci]]
        kind = dt.kind
        size = _col_byte_size(dt)
        if kind == Kind.DECIMAL128:
            data = lax.bitcast_convert_type(
                jnp.stack(es, axis=1), jnp.int32)
        elif size == 8:
            u = (es[0].astype(jnp.uint64)
                 | (es[1].astype(jnp.uint64) << jnp.uint64(32)))
            # FLOAT64 stays raw-bits u64 (columns convention)
            data = (u if kind == Kind.FLOAT64
                    else lax.bitcast_convert_type(
                        u, jnp.dtype(dt.np_dtype)))
        elif size == 4:
            data = lax.bitcast_convert_type(es[0],
                                            jnp.dtype(dt.np_dtype))
        elif size == 2:
            data = lax.bitcast_convert_type(
                es[0].astype(jnp.uint16), jnp.dtype(dt.np_dtype))
        else:
            data = lax.bitcast_convert_type(
                es[0].astype(jnp.uint8), jnp.dtype(dt.np_dtype))
        valid = pieces[valid_entries[ci]].astype(jnp.uint8)
        out_cols.append(Column(dt, rows, data=data, validity=valid))
    return Table(out_cols)


# ------------------------------------------- string payload tiling


def paste_strings_pallas(mat: jnp.ndarray, chars: jnp.ndarray,
                         vstart: jnp.ndarray, lens: jnp.ndarray,
                         block_rows: int = 1024,
                         interpret: bool = False) -> jnp.ndarray:
    """Tile-resident string-payload paste for the variable-width
    to-rows path (row_conversion.cu:71-73 string copy counterpart):
    for each output byte position p of a row tile, the value is
    chars[r, p - vstart[r]] when p falls in the row's payload span,
    else the existing fixed-section byte.  The gather happens in VMEM
    per tile — the XLA fallback (_masked_row_scatter) materializes a
    scatter over the whole (rows, max_row) matrix in HBM."""
    import jax.experimental.pallas as pl

    rows, max_row = mat.shape
    pad = chars.shape[1]
    br = min(block_rows, max(8, rows))

    def kernel(mat_ref, ch_ref, vs_ref, ln_ref, out_ref):
        base = mat_ref[:, :]
        ch = ch_ref[:, :]
        vs = vs_ref[:]
        ln = ln_ref[:]
        p = lax.broadcasted_iota(jnp.int32, (br, max_row), 1)
        src = p - vs[:, None]
        in_span = (src >= 0) & (src < ln[:, None]) & (src < pad)
        gathered = jnp.take_along_axis(
            ch, jnp.minimum(jnp.maximum(src, 0), pad - 1), axis=1)
        out_ref[:, :] = jnp.where(in_span, gathered, base)

    grid = (pl.cdiv(rows, br),)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br, max_row), lambda i: (i, _ZERO)),
                  pl.BlockSpec((br, pad), lambda i: (i, _ZERO)),
                  pl.BlockSpec((br,), lambda i: (i,)),
                  pl.BlockSpec((br,), lambda i: (i,))],
        out_specs=pl.BlockSpec((br, max_row), lambda i: (i, _ZERO)),
        out_shape=jax.ShapeDtypeStruct((rows, max_row), mat.dtype),
        interpret=interpret,
    )(mat, chars, vstart.astype(jnp.int32), lens.astype(jnp.int32))
