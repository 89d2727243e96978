"""Single-pass Pallas row-assembly kernel for JCUDF to-rows.

The XLA word path (`row_conversion._assemble_fixed_words`) composes
each output u32 word as an OR of shifted column vectors and leaves the
(rows, W) matrix to `jnp.stack`: a concatenate, a pad-and-add and a
transposing copy, each a pass through HBM.  This kernel builds each
(BLOCK_ROWS, W) tile in VMEM instead: column blocks stream in once in
their NATIVE widths (u8/u16/u32 — the narrow converts and shifts happen
in-register), the word vectors stack along sublanes and ONE aligned
transpose in VMEM turns them into the row tile, which is stored once.
The only pre-pass is splitting 8-byte columns into u32 lo/hi halves
(TPU vectors are 32-bit; see docs/tpu_design.md §2 for why (rows, 2)
u32 bitcasts are not safe on the TPU's tiling).

Reference counterpart: row_conversion.cu:591 copy_to_rows (shared-memory
tiled memcpy); the TPU shape is word-composition, not memcpy.

It is the to-rows engine of fixed-width schemas **on a TPU**
(`row_conversion._to_rows`; engine `pallas`), chosen from chip runs at
212 columns x 2^20 rows on one v5e: 14.79 ms against the XLA word
path's 21.54 ms, bytes identical (PERF.md, Findings, PR 32).  Every
other backend, a row too wide for a tile in VMEM (`tile_fits_vmem`) and
a call under a jit trace run the XLA word path, which is also the
reference the kernel is tested against
(`interpret=True` runs the kernel anywhere; tests use the CPU backend).
The from-rows tile kernel that lived here went with PR 32 (288.9 ms
against the word slices' 23.9 ms: its 636 one-word results and the
eager post-processing of every column cost more than any kernel could
win back).

`paste_strings_pallas` gathers string payloads into row tiles (the
string variants, row_conversion.cu:71-73) instead of scattering across
the whole HBM matrix; opt-in by SPARK_RAPIDS_TPU_PALLAS_ROWCONV=1 and
NOT brought up on the chip — its take_along_axis does not lower for
Mosaic under x64 (tests/test_tpu_compile.py, strict xfail); selected
on a chip it raises what the compiler raises.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_U32 = jnp.uint32
# block index maps must return int32: under x64 a python 0 traces as
# an i64 constant, which Mosaic refuses beside the i32 grid index
_ZERO = np.int32(0)


def _lane_pad(n_words: int) -> int:
    """Row width rounded up to whole 128-lane tiles: a 2-D transpose
    inside a kernel wants both dimensions aligned."""
    return -(-n_words // 128) * 128


# A column vector reaches the kernel in XLA's T(1024) layout, so a row
# block cannot be shorter than 1024 rows, and the tile grows with the
# row: what decides whether the kernel can run is the row's width.
BLOCK_ROWS = 1024
# of the 16 MiB of VMEM that Mosaic scopes to one kernel on a v5e
VMEM_BUDGET = 14 << 20


def tile_fits_vmem(row_size: int, n_narrow: int) -> bool:
    """Whether one BLOCK_ROWS tile of ``row_size``-byte rows stays
    under VMEM_BUDGET: the lane-padded output tile and the operand
    blocks (the row's own bytes), both double-buffered, and the 1- and
    2-byte operands (``n_narrow``: narrow columns and validity bytes)
    widened to u32.  Reckoned high on purpose: the chip's compiler took
    every shape this admits and refused the cycled schema from 800
    columns (1,026 words) and 3,000 INT8 columns, which read 19.0 and
    26.8 MiB here (tests/test_tpu_compile.py).  A wider row is the XLA
    word path's (row_conversion._to_rows): a property of the schema,
    not an option."""
    need = BLOCK_ROWS * (2 * 4 * _lane_pad(row_size // 4)
                         + 2 * row_size + 4 * n_narrow)
    return need <= VMEM_BUDGET


def assemble_rows_pallas(inputs: Sequence[jnp.ndarray],
                         plan: Sequence[Tuple[int, int]],
                         rows: int, n_words: int,
                         block_rows: int = BLOCK_ROWS,
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
