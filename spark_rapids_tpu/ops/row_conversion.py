"""Row <-> columnar conversion in the JCUDF row format.

Reference: src/main/cpp/src/row_conversion.cu (format spec in
RowConversion.java:67-137 javadoc and compute_column_information
row_conversion.cu:1367-1405):

  * fixed-width section: columns in order, each aligned to its byte size
    (strings/lists store a 4-byte-aligned (offset-in-row, length) uint32
    pair); then validity — one bit per column (1 = valid), byte-aligned;
    then variable-width payloads; row length rounded up to 8 bytes
    (JCUDF_ROW_ALIGNMENT).
  * output is a LIST<INT8> column: row i = bytes[offsets[i]:offsets[i+1]].

TPU-first design: the reference uses square shared-memory tiles with
memcpy_async to balance row/column coalescing (row_conversion.cu:109-126).
On TPU the same job is done by XLA fusion: each row word is an OR of
shifted (rows,) column vectors fused into one concat write
(_assemble_fixed_words).  Validity packs ALL columns in one vectorized
packbits-style scatter-add instead of a per-byte python loop — that
loop was the historical compile blow-up; with it gone a 212-column
schema lowers+compiles in about a second.  The **width-grouped** class
machinery (_grouped_fixed_bytes: columns of equal byte width stacked
into one (rows, n_cols_of_width) matrix per width class, one byte-lane
expansion each) builds the variable-width fixed section; for the
fixed-width word path the measured truth on this backend is that
per-column fusion beats materialized class matrices by 4-20x, so the
word path keeps per-column pieces and the class path stays for
byte-matrix consumers.  FLOAT64 columns already carry uint64 raw bits
(columns/column.py) so no f64 bitcasts are ever needed; float32
bitcasts to u32 lanes (TPU-supported).

The eager graph is additionally routed through the process-wide kernel
compile cache (spark_rapids_tpu/perf/jit_cache.py): fixed-width
conversions compile once per (schema digest, power-of-two row bucket)
and every later batch in the same bucket reuses the executable with
zero XLA compilation.  SPARK_RAPIDS_TPU_JIT_CACHE=0 runs the same
graphs uncached.

Fixed-width schemas read no environment variable and have one engine
per direction and backend: to-rows composes row words (on a TPU in the
Pallas tile kernel of ops/row_assembly_pallas.py, elsewhere by XLA:
_assemble_fixed_words), from-rows transposes the word matrix once
(_transpose_row_words) and slices them (_extract_fixed_words: every
field a static slice, no gather) whenever the buffer holds uniform
rows, which is read from the buffer; rows of differing size and schemas with
strings keep the byte gather (_gather_fixed_region).  Eager calls
record the spans ``to_rows`` / ``from_rows`` and
srt_row_conversion_total{direction,engine}.  From-rows' word slices
leave the validity as the row words it is: the executable hands over
the validity words and their AND over the rows, the table's columns
share them (_RowsValidity), and a column's (rows,) vector is made when
the column is first asked and the buffer holds a null for it; else its
validity is None (srt_from_rows_validity_total{outcome}).

Variable-width rows are assembled per-row padded then compacted by a
gather keyed on searchsorted(row_offsets) — vectorized, no per-row loops.
"""

from __future__ import annotations

import os
import threading
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from spark_rapids_tpu.columns import dtypes
from spark_rapids_tpu.columns.column import Column, DeferredValidity
from spark_rapids_tpu.columns.dtypes import DType, Kind
from spark_rapids_tpu.columns.table import Table

JCUDF_ROW_ALIGNMENT = 8

_U8 = jnp.uint8
_U32 = jnp.uint32
_U64 = jnp.uint64
_I32 = jnp.int32


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _col_byte_size(dt: DType) -> int:
    if dt.is_string:
        return 8  # (offset, length) uint32 pair
    if dt.kind == Kind.DECIMAL128:
        return 16
    return dt.size_bytes


def _col_alignment(dt: DType) -> int:
    return 4 if dt.is_string else _col_byte_size(dt)


def compute_layout(schema: Sequence[DType]):
    """Per-column start offsets + fixed-section/validity sizes.
    Mirrors compute_column_information (row_conversion.cu:1367)."""
    starts: List[int] = []
    size = 0
    for dt in schema:
        size = _round_up(size, _col_alignment(dt))
        starts.append(size)
        size += _col_byte_size(dt)
    validity_offset = size
    size += (len(schema) + 7) // 8
    return starts, validity_offset, size  # size = fixed + validity bytes


# --------------------------------------------------- width-grouped assembly


def _to_unsigned(mat: jnp.ndarray) -> jnp.ndarray:
    """Same-width unsigned view of an integer/float matrix (bitcast —
    never a value conversion)."""
    dt = mat.dtype
    if dt in (jnp.uint8, jnp.uint16, jnp.uint32, jnp.uint64):
        return mat
    if dt == jnp.float32:
        return lax.bitcast_convert_type(mat, _U32)
    if dt == jnp.float64:
        return lax.bitcast_convert_type(mat, _U64)
    target = {1: jnp.uint8, 2: jnp.uint16, 4: _U32, 8: _U64}[dt.itemsize]
    return lax.bitcast_convert_type(mat, target)


def _le_byte_matrix(mat: jnp.ndarray, w: int) -> jnp.ndarray:
    """(rows, m) unsigned width-w matrix -> (rows, m*w) uint8
    little-endian byte lanes — one shift/mask over the whole class."""
    if w == 1:
        return mat.astype(_U8)
    shifts = (8 * jnp.arange(w, dtype=_I32)).astype(mat.dtype)
    b = ((mat[:, :, None] >> shifts[None, None, :])
         & mat.dtype.type(0xFF)).astype(_U8)
    return b.reshape(mat.shape[0], mat.shape[1] * w)


def _validity_bytes(cols: Sequence[Column]) -> jnp.ndarray:
    """(rows, ceil(ncols/8)) uint8; bit c%8 of byte c//8 set = col c
    valid.  Vectorized packbits: always-valid columns fold into one
    host-side constant byte vector; the nullable columns stack into a
    single (rows, m) matrix, scale by their bit weights, and scatter-add
    into the byte lanes in one op — no per-byte python loop."""
    rows = cols[0].length
    nbytes = (len(cols) + 7) // 8
    base = np.zeros((nbytes,), np.uint8)
    arrs, byte_idx, weights = [], [], []
    for ci, c in enumerate(cols):
        if c.validity is None:
            base[ci // 8] |= np.uint8(1 << (ci % 8))
        else:
            arrs.append((c.validity != 0).astype(_U8))
            byte_idx.append(ci // 8)
            weights.append(1 << (ci % 8))
    out = jnp.broadcast_to(jnp.asarray(base)[None, :], (rows, nbytes))
    if arrs:
        vm = jnp.stack(arrs, axis=1) * \
            jnp.asarray(np.array(weights, np.uint8))[None, :]
        acc = jnp.zeros((rows, nbytes), _U8).at[
            :, jnp.asarray(np.array(byte_idx, np.int32))].add(vm)
        out = out | acc
    return out


def _validity_byte_vector(cols: Sequence[Column], b: int) -> jnp.ndarray:
    """(rows,) uint8 validity byte b (bit i = col 8b+i valid).  Kept for
    callers that want one byte; packs all bytes vectorized and slices —
    use _validity_bytes directly when you need more than one."""
    return _validity_bytes(cols)[:, b]


def _grouped_fixed_bytes(cols: Sequence[Column], starts: Sequence[int],
                         validity_offset: int, out_width: int,
                         var_pairs: Optional[Sequence[Tuple]] = None
                         ) -> jnp.ndarray:
    """(rows, out_width) uint8 fixed section via width-grouped assembly.

    Columns are grouped by native buffer dtype; each group becomes one
    stacked matrix and one byte-lane expansion (O(width classes) heavy
    ops).  Per-column byte runs are then cheap static slices of their
    class byte matrix, concatenated in layout order with zero-fill for
    alignment gaps — compile-light data movement, no per-column math.
    String columns contribute their (offset-in-row, length) u32 pairs
    from ``var_pairs``; DECIMAL128 contributes its four u32 limbs."""
    rows = cols[0].length
    groups: dict = {}          # key -> {"w": int, "arrs": [...]}
    placement = []             # per column: (key, first_piece, n_pieces)
    vp = 0
    for c, st in zip(cols, starts):
        if c.dtype.is_string:
            vstart, lens = var_pairs[vp]
            vp += 1
            g = groups.setdefault("u32", {"w": 4, "arrs": []})
            placement.append(("u32", len(g["arrs"]), 2))
            g["arrs"].extend([vstart.astype(_U32), lens.astype(_U32)])
        elif c.dtype.kind == Kind.DECIMAL128:
            g = groups.setdefault("dec128", {"w": 4, "arrs": []})
            placement.append(("dec128", len(g["arrs"]), 4))
            g["arrs"].append(c.data)   # (rows, 4) int32 limbs
        else:
            key = str(c.data.dtype)
            g = groups.setdefault(
                key, {"w": c.data.dtype.itemsize, "arrs": []})
            placement.append((key, len(g["arrs"]), 1))
            g["arrs"].append(c.data)

    class_bytes = {}
    for key, g in groups.items():
        if key == "dec128":
            mat = jnp.concatenate(g["arrs"], axis=1)   # (rows, 4k) i32
        else:
            mat = jnp.stack(g["arrs"], axis=1)
        class_bytes[key] = _le_byte_matrix(_to_unsigned(mat), g["w"])

    pieces = []
    pos = 0
    for (key, p0, np_), c, st in zip(placement, cols, starts):
        if st > pos:
            pieces.append(jnp.zeros((rows, st - pos), _U8))
        w = groups[key]["w"]
        if key == "dec128":
            # placement counts (rows,4) limb matrices; 16 bytes each
            pieces.append(class_bytes[key][:, p0 * 16:(p0 + 1) * 16])
            pos = st + 16
        else:
            pieces.append(class_bytes[key][:, p0 * w:(p0 + np_) * w])
            pos = st + np_ * w
    if validity_offset > pos:
        pieces.append(jnp.zeros((rows, validity_offset - pos), _U8))
    pieces.append(_validity_bytes(cols))
    pos = validity_offset + (len(cols) + 7) // 8
    if out_width > pos:
        pieces.append(jnp.zeros((rows, out_width - pos), _U8))
    return jnp.concatenate(pieces, axis=1)


def _assemble_fixed_words(cols, starts, validity_offset,
                          row_size) -> jnp.ndarray:
    """Word-oriented row assembly: compose each 4-byte word of the row
    from (rows,) u32 vectors and stack them into the (rows, W) matrix.
    XLA fuses every per-column bitcast/shift straight into the single
    concat write, so the data moves HBM->HBM exactly once — measured
    4-20x faster than materializing per-width-class matrices on this
    backend (class matrices force extra full-size passes that defeat
    the fusion).  The graph stays O(columns) in op COUNT but each op is
    trivial data movement; the historical compile blow-up came from the
    per-byte python validity stacking, which _validity_bytes now packs
    in one vectorized scatter-add (a 212-column schema lowers+compiles
    in ~1 s).  Recompiles across batch sizes are absorbed by the
    compile cache (perf/jit_cache.py row bucketing).  Returns flat
    packed u32 LE words."""
    rows = cols[0].length
    n_words = row_size // 4
    inputs, plan = build_plan(cols, starts, validity_offset, n_words)
    contribs = {}
    for arr, (w, sh) in zip(inputs, plan):
        u = arr if arr.dtype == _U32 else arr.astype(_U32)
        if sh:
            u = u << _U32(sh)
        contribs.setdefault(w, []).append(u)
    zeros = None
    words = []
    for w in range(n_words):
        if w in contribs:
            acc = contribs[w][0]
            for u in contribs[w][1:]:
                acc = acc | u
            words.append(acc)
        else:
            if zeros is None:
                zeros = jnp.zeros((rows,), _U32)
            words.append(zeros)
    mat = jnp.stack(words, axis=1)         # (rows, W) directly
    return mat.reshape(-1)                  # packed u32 LE words


def field_word_slots(dt: DType, st: int):
    """[(word_index, shift_bits, nbits)] for the value pieces of one
    fixed-width field at byte offset `st` — THE single source of the
    JCUDF word layout.  Consumed by build_plan (assembly: piece arrays
    zip with these coordinates) and by _extract_fixed_words (from-rows
    slices the same coordinates), so the two directions cannot
    drift."""
    w = st // 4
    size = _col_byte_size(dt)
    if dt.kind == Kind.DECIMAL128:
        return [(w + k, 0, 32) for k in range(4)]
    if size == 8:
        return [(w, 0, 32), (w + 1, 0, 32)]
    if size == 4:
        return [(w, 0, 32)]
    return [(w, (st % 4) * 8, size * 8)]


def build_plan(cols: Sequence[Column], starts: Sequence[int],
               validity_offset: int, n_words: int):
    """(inputs, plan): one (rows,) array per word contribution in its
    native width (u8/u16/u32; 8-byte columns split into u32 lo/hi —
    (rows, 2) u32 bitcasts are not tile-safe on this backend, see
    docs/tpu_design.md §2), and the (word_index, left_shift_bits) each
    lands at.  Word coordinates come from field_word_slots (the shared
    layout source); this function supplies the matching piece arrays."""
    inputs = []
    plan = []

    def add(arrs, slots):
        assert len(arrs) == len(slots)
        for arr, (word, shift, _nbits) in zip(arrs, slots):
            inputs.append(arr)
            plan.append((word, shift))

    for c, st in zip(cols, starts):
        kind = c.dtype.kind
        d = c.data
        slots = field_word_slots(c.dtype, st)
        if kind == Kind.FLOAT32:
            arrs = [lax.bitcast_convert_type(d, _U32)]
        elif kind == Kind.DECIMAL128:
            u = lax.bitcast_convert_type(d, _U32)
            arrs = [u[:, k] for k in range(4)]
        elif _col_byte_size(c.dtype) == 8:
            u = (d if d.dtype == jnp.uint64
                 else d.astype(jnp.int64).astype(_U64))
            arrs = [(u & _U64(0xFFFFFFFF)).astype(_U32),
                    (u >> _U64(32)).astype(_U32)]
        elif _col_byte_size(c.dtype) == 4:
            arrs = [lax.bitcast_convert_type(d.astype(_I32), _U32)]
        else:
            size = _col_byte_size(c.dtype)
            native = jnp.uint8 if size == 1 else jnp.uint16
            arrs = [d if d.dtype == native
                    else lax.bitcast_convert_type(
                        d.astype(jnp.int16 if size == 2 else jnp.int8),
                        native)]
        add(arrs, slots)

    # validity: packed once vectorized, sliced per byte
    packed = _validity_bytes(cols)
    for b in range((len(cols) + 7) // 8):
        off = validity_offset + b
        inputs.append(packed[:, b])
        plan.append((off // 4, (off % 4) * 8))

    assert all(w < n_words for w, _ in plan)
    return inputs, plan


# -------------------------------------------------------------- to-rows


def _is_traced(cols: Sequence[Column]) -> bool:
    return any(isinstance(c.data, jax.core.Tracer) for c in cols
               if c.data is not None)


def _pad_rows(arr: jnp.ndarray, bucket: int, fill: int = 0) -> jnp.ndarray:
    """``arr`` padded with ``fill`` along axis 0 to ``bucket`` rows;
    ``arr`` itself where it already has them.  Neither direction
    donates its operands (no result has an operand's shape, so a
    donation frees nothing sooner), so the caller's buffer needs no
    protecting copy: at 212 columns x 2^20 rows those copies were 63 of
    to-rows' 84 ms (PERF.md, Findings, PR 32)."""
    n = int(arr.shape[0])
    if n == bucket:
        return arr
    return jnp.pad(arr, [(0, bucket - n)] + [(0, 0)] * (arr.ndim - 1),
                   constant_values=fill)


def _to_rows_fixed_cached(cols, schema, starts, validity_offset,
                          row_size, rows, engine: str) -> jnp.ndarray:
    """Fixed-width to-rows through the process compile cache: operands
    pad to the power-of-two row bucket, the assembly compiles once per
    (engine, schema digest, bucket), and the padded tail rows are
    sliced off.  ``engine``: ``pallas`` builds the row tiles in VMEM
    (ops/row_assembly_pallas.py, a TPU's engine), ``words`` is the XLA
    word path."""
    from spark_rapids_tpu.perf import jit_cache as _jc

    nullable = tuple(c.validity is not None for c in cols)
    digest = _jc.schema_digest(schema, nullable,
                               extra=f"to_rows:{row_size}")
    bucket = _jc.bucket_rows(rows)
    datas = tuple(_pad_rows(c.data, bucket) for c in cols)
    valids = tuple(None if c.validity is None
                   else _pad_rows(c.validity, bucket) for c in cols)
    schema_t = tuple(schema)
    starts_t = tuple(starts)

    def kernel(datas, valids):
        kcols = [Column(dt, bucket, data=d, validity=v)
                 for dt, d, v in zip(schema_t, datas, valids)]
        if engine == "pallas":
            from spark_rapids_tpu.ops.row_assembly_pallas import \
                assemble_rows_pallas
            inputs, plan = build_plan(kcols, starts_t, validity_offset,
                                      row_size // 4)
            return assemble_rows_pallas(inputs, plan, bucket,
                                        row_size // 4)
        return _assemble_fixed_words(kcols, starts_t, validity_offset,
                                     row_size)

    words = _jc.CACHE.cached_call(
        "pallas.to_rows" if engine == "pallas"
        else "row_conversion.to_rows", digest, kernel, (datas, valids),
        bucket=bucket)
    if bucket == rows:
        return words
    return words[: rows * (row_size // 4)]


def _tile_fits(schema, row_size: int) -> bool:
    """Whether the Pallas to-rows tile of this schema fits the chip's
    VMEM (row_assembly_pallas.tile_fits_vmem)."""
    from spark_rapids_tpu.ops.row_assembly_pallas import tile_fits_vmem

    narrow = sum(1 for dt in schema if _col_byte_size(dt) < 4)
    return tile_fits_vmem(row_size, narrow + (len(schema) + 7) // 8)


def _note(span, direction: str, engine: str, rows: int,
          nbytes: int) -> None:
    """One eager conversion on ``engine`` into the open ``span`` and
    the srt_row_conversion_total counter."""
    from spark_rapids_tpu import observability as _obs

    span.set_attr("rows", int(rows))
    span.set_attr("bytes", int(nbytes))
    span.set_attr("engine", engine)
    _obs.record_row_conversion(direction, engine)


def convert_to_rows(table: Table) -> Column:
    """Table -> LIST<INT8> column of JCUDF rows (RowConversion.convertToRows,
    RowConversionJni.cpp).  Fixed-width and string columns.  An eager
    call is the timeline span ``to_rows`` (it ends where the call
    returns, at the enqueue: the host's share of the conversion); under
    a jit trace nothing is recorded."""
    from spark_rapids_tpu import observability as _obs

    if not table.columns:
        raise ValueError("cannot convert empty table")
    if _is_traced(table.columns):
        return _to_rows(table, True)[0]
    with _obs.TRACER.start_span("to_rows", kind="phase") as span:
        out, engine = _to_rows(table, False)
        _note(span, "to_rows", engine, table.num_rows,
              out.children[0].length)
    return out


def _to_rows(table: Table, traced: bool) -> Tuple[Column, str]:
    """(rows column, engine): ``words`` and ``pallas`` compose each row
    word from column vectors (fixed-width schemas; XLA, or on a TPU the
    tile kernel); ``gather`` pads every row to the longest and compacts
    by a gather (schemas with strings)."""
    from spark_rapids_tpu.perf import jit_cache as _jc

    cols = table.columns
    rows = table.num_rows
    schema = [c.dtype for c in cols]
    starts, validity_offset, fixed_size = compute_layout(schema)

    str_cols = [c for c in cols if c.dtype.is_string]
    if not str_cols:
        row_size = _round_up(fixed_size, JCUDF_ROW_ALIGNMENT)
        # one engine a backend, chosen from chip runs (PERF.md,
        # Findings, PR 32): on a TPU the Pallas tile kernel for every
        # row its tile holds in VMEM, elsewhere (and for wider rows,
        # under a jit trace, and without the executable cache) the XLA
        # word path, which is also the kernel's reference
        engine = "words"
        if _jc.cache_enabled() and rows > 0 and not traced:
            if jax.default_backend() == "tpu" and _tile_fits(schema,
                                                             row_size):
                engine = "pallas"
            data = _to_rows_fixed_cached(cols, schema, starts,
                                         validity_offset, row_size, rows,
                                         engine)
        else:
            data = _assemble_fixed_words(cols, starts, validity_offset,
                                         row_size)
        offsets = jnp.arange(rows + 1, dtype=_I32) * _I32(row_size)
        out = Column.make_list_from_parts(offsets, data,
                                          nbytes=rows * row_size)
        _note_uniform(out.offsets, row_size)
        return out, engine

    # variable-width path
    str_lens = [c.string_lengths() for c in str_cols]
    var_total = sum(str_lens)
    row_sizes = ((jnp.full((rows,), fixed_size, _I32) + var_total
                  + _I32(JCUDF_ROW_ALIGNMENT - 1))
                 // JCUDF_ROW_ALIGNMENT * JCUDF_ROW_ALIGNMENT)
    offsets = jnp.concatenate([jnp.zeros((1,), _I32),
                               jnp.cumsum(row_sizes).astype(_I32)])
    # per-row (offset-in-row, length) pairs for each string column
    var_starts = []
    off = jnp.full((rows,), fixed_size, _I32)
    for lens in str_lens:
        var_starts.append(off)
        off = off + lens
    max_row = int(np.asarray(row_sizes).max()) if rows else 0
    mat = _grouped_fixed_bytes(cols, starts, validity_offset, max_row,
                               var_pairs=list(zip(var_starts, str_lens)))
    # paste string payloads into the padded matrix
    use_pallas_paste = (
        os.environ.get("SPARK_RAPIDS_TPU_PALLAS_ROWCONV") == "1"
        and rows > 0)
    for c, vstart, lens in zip(str_cols, var_starts, str_lens):
        pad = max(1, c.max_string_length())
        chars, _ = c.to_padded_chars(pad_to=pad)
        if use_pallas_paste:
            # VMEM tile gather (row_assembly_pallas.py) instead of a
            # whole-matrix HBM scatter; interpret mode on CPU
            from spark_rapids_tpu.ops.row_assembly_pallas import \
                paste_strings_pallas
            mat = paste_strings_pallas(
                mat, chars, vstart, lens,
                interpret=jax.default_backend() == "cpu")
            continue
        # scatter chars into mat[r, vstart[r]+j]
        j = jnp.arange(pad, dtype=_I32)
        dest = vstart[:, None] + j[None, :]
        m = j[None, :] < lens[:, None]
        mat = _masked_row_scatter(mat, dest, chars, m)
    flat = _compact(mat, offsets, row_sizes)
    return Column.make_list_from_parts(offsets, flat), "gather"


def _masked_row_scatter(mat, dest, src, mask):
    """mat[r, dest[r,j]] = src[r,j] where mask — via one-hot-free gather:
    build an index map from output position back to source position."""
    rows, width = mat.shape
    pad = dest.shape[1]
    # scatter via jnp at: vectorized scatter is fine on TPU through XLA
    r = jnp.broadcast_to(jnp.arange(rows, dtype=_I32)[:, None], dest.shape)
    dest_c = jnp.where(mask, dest, width)  # out-of-range drops
    return mat.at[r.reshape(-1), dest_c.reshape(-1)].set(
        src.reshape(-1), mode="drop")


def _compact(mat: jnp.ndarray, offsets: jnp.ndarray,
             row_sizes: jnp.ndarray) -> jnp.ndarray:
    """(rows, maxP) padded matrix -> flat uint8 using per-row sizes."""
    total = int(np.asarray(offsets)[-1])
    i = jnp.arange(total, dtype=_I32)
    r = jnp.searchsorted(offsets, i, side="right").astype(_I32) - 1
    p = i - offsets[r]
    return mat[r, p]


# ------------------------------------------------------------ from-rows


def _bytes_to_values(raw: jnp.ndarray, dt: DType) -> jnp.ndarray:
    """(rows, size) uint8 LE bytes -> (rows,) natural-dtype values (or
    (rows,4) int32 limbs for decimal128)."""
    kind = dt.kind
    if kind == Kind.DECIMAL128:
        b = raw.astype(_U32)
        limbs = (b[:, 0::4] | (b[:, 1::4] << _U32(8))
                 | (b[:, 2::4] << _U32(16)) | (b[:, 3::4] << _U32(24)))
        return limbs.astype(jnp.int32)
    n = raw.shape[1]
    if n == 8:
        u = jnp.zeros(raw.shape[:1], _U64)
        for k in range(8):
            u = u | (raw[:, k].astype(_U64) << _U64(8 * k))
        if kind == Kind.FLOAT64 or dt.np_dtype == np.dtype(np.uint64):
            return u  # raw-bits / unsigned representation
        return u.astype(jnp.int64)
    u = jnp.zeros(raw.shape[:1], _U32)
    for k in range(n):
        u = u | (raw[:, k].astype(_U32) << _U32(8 * k))
    if kind == Kind.FLOAT32:
        return lax.bitcast_convert_type(u, jnp.float32)
    if n < 4 and dt.np_dtype.kind == "i":  # sign-extend from the top
        u = u << _U32(8 * (4 - n))
        s = u.astype(jnp.int32) >> _I32(8 * (4 - n))
        return s.astype(dt.np_dtype)
    return u.astype(jnp.int32) if dt.np_dtype == np.dtype(np.int32) else \
        u.astype(dt.np_dtype)


def _gather_fixed_region(data, offs, fixed_size: int, nbytes_total: int):
    """ONE clipped gather of every row's fixed+validity section —
    (rows, fixed_size) uint8.  The retired path gathered per column
    (O(columns) gathers, each with its own (rows, size) index matrix);
    all column decodes now slice this single region."""
    from spark_rapids_tpu.columns import bytesview

    idx = offs[:-1][:, None] + jnp.arange(fixed_size, dtype=_I32)[None, :]
    idx = jnp.clip(idx, 0, max(nbytes_total - 1, 0))
    return bytesview.byte_gather(data, idx)


def _decode_validity(region: jnp.ndarray, schema, validity_offset: int):
    """(rows, ncols) uint8 validity bits in one vectorized op."""
    n = len(schema)
    bidx = np.array([validity_offset + ci // 8 for ci in range(n)],
                    np.int32)
    shifts = np.array([ci % 8 for ci in range(n)], np.uint8)
    return ((region[:, bidx] >> jnp.asarray(shifts)[None, :])
            & _U8(1)).astype(jnp.uint8)


# Whether an offsets array is 0, row_size, 2 * row_size, ...: known
# per array object.  convert_to_rows notes it for the offsets it makes
# (true by construction); for offsets from elsewhere the first
# from-rows call reads them back and scans them (a synchronous
# device-to-host copy and an O(rows) pass) and later calls on the same
# array find the verdict.  Keyed by id() with a weakref guard: the
# finalizer drops the entry when the array dies, so a recycled id can
# never resurrect a stale verdict.
_UNIFORM_VERDICTS: dict = {}


def _note_uniform(offs, row_size: int, verdict: bool = True) -> None:
    import weakref

    if isinstance(offs, jax.core.Tracer):
        return
    key = id(offs)
    try:
        ref = weakref.ref(offs,
                          lambda _r: _UNIFORM_VERDICTS.pop(key, None))
    except TypeError:
        return
    if len(_UNIFORM_VERDICTS) > 512:
        _UNIFORM_VERDICTS.clear()
    _UNIFORM_VERDICTS[key] = (ref, row_size, verdict)


def _uniform_row_offsets(offs, rows: int, row_size: int,
                         nbytes_total: int) -> bool:
    """True when the list column holds exactly rows x row_size uniform
    rows (what fixed-width convert_to_rows produces) — the shape the
    word-slice from-rows kernel requires."""
    if int(nbytes_total) != rows * row_size:
        return False
    ent = _UNIFORM_VERDICTS.get(id(offs))
    if ent is not None:
        ref, rs, verdict = ent
        if ref() is offs and rs == row_size:
            return verdict
    o = np.asarray(offs)
    verdict = bool(o[0] == 0 and np.all(np.diff(o) == row_size))
    _note_uniform(offs, row_size, verdict)
    return verdict


def _transpose_row_words(data: jnp.ndarray, rows: int,
                         row_size: int) -> jnp.ndarray:
    """Flat buffer of ``rows`` uniform rows (packed u32 words as
    convert_to_rows emits them, or u8, packed here once) -> the row
    words transposed, (row_size // 4, rows // 128, 128) u32: word w of
    every row is the block [w], contiguous in the TPU's (8, 128)
    tiling.  An executable of its own (_from_rows_fixed_cached): as the
    result of a program the blocks have that layout for certain; inside
    one program with its consumers XLA folds the transpose into their
    reads, and each field then reads a column of the untransposed
    matrix (300-400 GB of traffic at 212 columns x 2^20 rows), while as
    rows of one (words, rows) matrix eight words share every tile and
    each field's read costs eight (PERF.md, Findings, PR 32)."""
    if data.dtype != _U32:      # little-endian bytes -> words
        b = data.astype(_U32)
        data = (b[0::4] | (b[1::4] << _U32(8))
                | (b[2::4] << _U32(16)) | (b[3::4] << _U32(24)))
    lane = 128 if rows % 128 == 0 else rows
    return jnp.transpose(
        data.reshape(rows // lane, lane, row_size // 4), (2, 0, 1))


def _validity_bits(ncols: int, validity_offset: int):
    """(first validity word, one past the last, bit of column 0): where
    a row's validity bits lie among its u32 words.  Column ci's bit is
    ``bit0 + ci`` counted from bit 0 of the first validity word: word
    ``bit >> 5`` of the kept words, bit ``bit & 31`` of that word (the
    validity bytes start wherever the last field ends, so the first
    word may hold field bytes below them)."""
    vw0 = validity_offset // 4
    vw1 = (validity_offset + (ncols + 7) // 8 - 1) // 4 + 1
    return vw0, vw1, (validity_offset % 4) * 8


def _extract_fixed_words(blocks: jnp.ndarray, schema, starts,
                         validity_offset: int):
    """Transposed row words (_transpose_row_words) -> (values, validity
    words, all-valid word): the inverse of _assemble_fixed_words, with
    the validity left as the row words it is.  Every field is a static
    slice of word vectors (field_word_slots: the layout the assembly
    writes) with a shift, a mask and a bitcast: no gather, no index
    matrix.  Values come back in the dtypes the columns carry (FLOAT64
    and uint64 as raw u64 bits, DECIMAL128 as (rows, 4) int32 limbs).
    The validity words are the blocks that hold the validity bytes
    (_validity_bits), sliced and not decoded; the all-valid word is the
    AND over all rows of each of them, so a column has a null exactly
    where its bit there is 0 (the caller pads the buffer with ones, so
    a bucket's pad rows read as valid).  ``len(schema) + 2`` arrays,
    where a validity vector a column made twice ``len(schema)``: the
    host pays for every result buffer of an executable before it
    enqueues it (PERF.md, Findings, PR 37)."""
    rows = blocks.shape[1] * blocks.shape[2]

    def word(w):
        return blocks[w].reshape(rows)

    vals = []
    for dt, st in zip(schema, starts):
        slots = field_word_slots(dt, st)
        w, shift, nbits = slots[0]
        kind = dt.kind
        if kind == Kind.DECIMAL128:
            v = lax.bitcast_convert_type(
                blocks[w:w + 4].reshape(4, rows).T, _I32)
        elif len(slots) == 2:
            u = (word(w).astype(_U64)
                 | (word(w + 1).astype(_U64) << _U64(32)))
            raw = (kind == Kind.FLOAT64
                   or dt.np_dtype == np.dtype(np.uint64))
            v = u if raw else u.astype(jnp.int64)
        elif nbits == 32:
            v = lax.bitcast_convert_type(
                word(w), jnp.float32 if kind == Kind.FLOAT32
                else jnp.dtype(dt.np_dtype))
        elif dt.np_dtype.kind == "i":   # sign-extend from the top
            up = word(w) << _U32(32 - nbits - shift)
            v = (lax.bitcast_convert_type(up, _I32)
                 >> _I32(32 - nbits)).astype(dt.np_dtype)
        else:
            v = ((word(w) >> _U32(shift))
                 & _U32((1 << nbits) - 1)).astype(dt.np_dtype)
        vals.append(v)
    vw0, vw1, _ = _validity_bits(len(schema), validity_offset)
    vwords = blocks[vw0:vw1]
    all_valid = lax.reduce(vwords, np.uint32(0xFFFFFFFF), lax.bitwise_and,
                           (1, 2))
    return tuple(vals), vwords, all_valid


def _column_validity(vwords: jnp.ndarray, bit) -> jnp.ndarray:
    """(bucket,) uint8 validity vector of the column whose bit is
    ``bit`` (_validity_bits; an int32 operand, so one executable a
    bucket serves every column) out of the kept validity words."""
    w = lax.dynamic_index_in_dim(vwords, bit >> 5, 0, keepdims=False)
    return ((w >> (bit & 31).astype(_U32)) & _U32(1)).astype(_U8).reshape(-1)


def _read_all_valid(all_valid: jnp.ndarray) -> np.ndarray:
    """The all-valid word on the host: the one device-to-host read a
    table's validity costs (a few bytes, and a wait for ``extract``)."""
    return np.asarray(all_valid)


class _RowsValidity:
    """What from-rows' ``words`` engine keeps of a table's validity,
    shared by the table's columns: the validity words as they lie in
    the transposed row blocks, the all-valid word, and column 0's bit
    (_extract_fixed_words, _validity_bits).  The first column asked
    reads the all-valid word back for all of them; a column whose bit
    is set in every row has no vector made (None), any other gets its
    (rows,) uint8 vector from one executable a bucket.  The answer is
    what the row buffer holds, whoever wrote it."""

    __slots__ = ("words", "all_valid", "bit0", "rows", "lock", "host")

    def __init__(self, words, all_valid, bit0: int, rows: int):
        self.words = words
        self.all_valid = all_valid
        self.bit0 = bit0
        self.rows = rows
        self.lock = threading.Lock()
        self.host = None

    def column(self, ci: int) -> Optional[jnp.ndarray]:
        """Caller holds ``lock``."""
        from spark_rapids_tpu import observability as _obs
        from spark_rapids_tpu.perf import jit_cache as _jc

        if self.host is None:
            self.host = _read_all_valid(self.all_valid)
        bit = self.bit0 + ci
        if (int(self.host[bit >> 5]) >> (bit & 31)) & 1:
            _obs.record_from_rows_validity("absent")
            return None
        bucket = int(self.words.shape[1] * self.words.shape[2])
        args = (self.words, np.int32(bit))
        if _jc.cache_enabled():
            v = _jc.CACHE.cached_call(
                "row_conversion.from_rows.validity",
                str(int(self.words.shape[0])), _column_validity, args,
                bucket=bucket)
        else:
            v = _column_validity(*args)
        _obs.record_from_rows_validity("materialized")
        return v if bucket == self.rows else v[:self.rows]


class _DeferredColumnValidity(DeferredValidity):
    """Column ``ci``'s share of a _RowsValidity: resolved once, under
    the table's lock, whichever threads ask."""

    __slots__ = ("table", "ci", "value")

    def __init__(self, table: _RowsValidity, ci: int):
        self.table = table
        self.ci = ci
        self.value = None

    def resolve(self) -> Optional[jnp.ndarray]:
        table = self.table
        if table is not None:
            with table.lock:
                if self.table is not None:
                    self.value = table.column(self.ci)
                    self.table = None   # the kept words go with the last
        return self.value


def _from_rows_fixed_cached(list_col: Column, schema, starts,
                            validity_offset: int,
                            row_size: int) -> Tuple[Table, int]:
    """Uniform fixed-width from-rows through the compile cache, as two
    executables: _transpose_row_words per (row size, bucket, buffer
    packing), whatever the schema, and _extract_fixed_words per (schema
    digest, bucket).  Returns the table and the number of arrays the
    second handed over.  The columns' validity is deferred
    (_RowsValidity): nothing is read back here, and the buffer is
    padded to the bucket with ones, so that a pad row is no null."""
    from spark_rapids_tpu.perf import jit_cache as _jc

    rows = list_col.length
    data = list_col.children[0].data
    packed = data.dtype == _U32
    bucket = _jc.bucket_rows(rows)
    data = _pad_rows(data, bucket * (row_size // 4 if packed else row_size),
                     fill=0xFFFFFFFF if packed else 0xFF)
    schema_t = tuple(schema)
    starts_t = tuple(starts)

    def transpose(data):
        return _transpose_row_words(data, bucket, row_size)

    def extract(blocks):
        return _extract_fixed_words(blocks, schema_t, starts_t,
                                    validity_offset)

    if _jc.cache_enabled():
        blocks = _jc.CACHE.cached_call(
            "row_conversion.from_rows.transpose",
            _jc.schema_digest(
                (), extra=f"{row_size}:{'u32' if packed else 'u8'}"),
            transpose, (data,), bucket=bucket)
        vals, vwords, all_valid = _jc.CACHE.cached_call(
            "row_conversion.from_rows",
            _jc.schema_digest(schema, extra=f"from_rows:{row_size}"),
            extract, (blocks,), bucket=bucket)
    else:
        vals, vwords, all_valid = extract(transpose(data))
    if bucket != rows:
        vals = [v[:rows] for v in vals]
    validity = _RowsValidity(
        vwords, all_valid,
        _validity_bits(len(schema_t), validity_offset)[2], rows)
    return Table([Column(dt, rows, data=v,
                         validity=_DeferredColumnValidity(validity, ci))
                  for ci, (dt, v) in enumerate(zip(schema, vals))]), \
        len(vals) + 2


def convert_from_rows(list_col: Column, schema: Sequence[DType]) -> Table:
    """LIST<INT8> of JCUDF rows -> Table (RowConversion.convertFromRows).
    An eager call is the timeline span ``from_rows`` (it ends where the
    call returns, at the enqueue; on the ``words`` engine the attribute
    ``results`` is the number of arrays its second executable handed
    over); under a jit trace nothing is recorded.

    On the ``words`` engine the columns come back with their validity
    deferred (columns/column.py): the call reads nothing back, the
    first read of any column's ``validity`` reads one word a validity
    word of the row back for the whole table, and only a column that
    holds a null in this buffer has a vector made; every other reads
    ``None``.  srt_from_rows_validity_total{outcome} counts the columns
    either way.  The ``gather`` engine returns a vector a column."""
    from spark_rapids_tpu import observability as _obs

    child = list_col.children[0]
    if isinstance(child.data, jax.core.Tracer) or \
            isinstance(list_col.offsets, jax.core.Tracer):
        return _from_rows(list_col, schema, True)[0]
    with _obs.TRACER.start_span("from_rows", kind="phase") as span:
        out, engine, results = _from_rows(list_col, schema, False)
        _note(span, "from_rows", engine, list_col.length, child.length)
        if results is not None:
            span.set_attr("results", results)
    return out


def _from_rows(list_col: Column, schema: Sequence[DType],
               traced: bool) -> Tuple[Table, str, Optional[int]]:
    """(table, engine, results of the engine's last executable):
    ``words`` slices every field out of the row words (fixed-width
    schema, uniform rows: what the buffer is, not an option) and defers
    the validity; ``gather`` fetches every row's fixed section by byte
    index, op by op, so it has no such count (rows of differing size,
    schemas with strings, a jit trace)."""
    from spark_rapids_tpu.columns import bytesview

    rows = list_col.length
    starts, validity_offset, fixed_size = compute_layout(schema)
    has_strings = any(dt.is_string for dt in schema)
    row_size = _round_up(fixed_size, JCUDF_ROW_ALIGNMENT)
    child = list_col.children[0]
    data = child.data  # flat byte buffer (u8 or packed u32 words)
    offs = list_col.offsets
    nbytes_total = child.length

    if (rows > 0 and not has_strings and not traced
            and _uniform_row_offsets(offs, rows, row_size, nbytes_total)):
        table, results = _from_rows_fixed_cached(
            list_col, schema, starts, validity_offset, row_size)
        return table, "words", results

    # eager width-grouped decode: one region gather + static slices
    region = _gather_fixed_region(data, offs, fixed_size, nbytes_total)
    valid_all = _decode_validity(region, schema, validity_offset)
    out_cols: List[Column] = []
    for ci, dt in enumerate(schema):
        st = starts[ci]
        valid = valid_all[:, ci]
        if dt.is_string:
            in_row_off = _bytes_to_values(region[:, st:st + 4],
                                          dtypes.INT32)
            lens = _bytes_to_values(region[:, st + 4:st + 8],
                                    dtypes.INT32)
            str_offsets = jnp.concatenate(
                [jnp.zeros((1,), _I32), jnp.cumsum(lens).astype(_I32)])
            pad = int(np.asarray(lens).max()) if rows else 0
            pad = max(pad, 1)
            j = jnp.arange(pad, dtype=_I32)
            src = offs[:-1][:, None] + in_row_off[:, None] + j[None, :]
            src = jnp.clip(src, 0, max(nbytes_total - 1, 0))
            chars2d = jnp.where(j[None, :] < lens[:, None],
                                bytesview.byte_gather(data, src), _U8(0))
            flat = _compact(chars2d, str_offsets, lens)
            out_cols.append(Column(dtypes.STRING, rows, data=flat,
                                   validity=valid, offsets=str_offsets))
        else:
            vals = _bytes_to_values(
                region[:, st:st + _col_byte_size(dt)], dt)
            out_cols.append(Column(dt, rows, data=vals, validity=valid))
    return Table(out_cols), "gather", None
