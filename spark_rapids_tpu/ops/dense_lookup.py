"""Exact dense-dimension lookup on the matrix unit.

``table[idx]`` lowers to a gather, which a TPU runs one element after
another (7-9 ns a row on a v5e: 226-289 ms over 2^25 rows, for tables
of 3 KB and 408 KB that fit in VMEM many times over).  The same
lookup is a product of a one-hot matrix with the table, the mirror
image of ``ops/segment_sum``, and that runs on the matrix unit --
exactly, when the table goes in as 8-bit limbs:

  * a limb (0..255) and a one-hot entry (0/1) are exact in bfloat16;
  * every index is brought inside ``[0, table_len)`` first, so each
    row's one-hot has exactly one 1: the float32 product is that one
    limb plus zeros, exact with no summation at all;
  * the limbs recombine with shifts in an unsigned integer of the
    table's own width, which is then read as the table's dtype: the
    bits that went in come out.

The one-hot is factored, ``idx = hi * lo_n + lo``: the limb table
``[lo_n * L, hi_n]`` times ``one_hot(hi)`` ``[hi_n, chunk]`` gives, for
every row of the chunk, the ``lo_n * L`` limbs that share its ``hi``;
a masked sum over ``lo_n`` picks the row's own.  All tables that share
an index stack their limbs (``L`` is their total), so the one-hots of
an index are built once.

What decides the engine is what the code sees in its input, on every
backend alike (:func:`engine`): integer or boolean 1-D tables with
``table_len * L`` up to ``DENSE_MAX_TABLE_LIMBS`` take the dense
product; floating-point tables (a float's bits could be limb-split,
but no caller has one), tables past the bound (the product grows with
``rows * table_len``, the gather hardly at all), tables that are not
1-D and indices that are not 1-D integers stay on ``table[idx]``.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from spark_rapids_tpu.ops.segment_sum import (_LIMB_BITS, _limbs,
                                              _n_limbs)

# Set from a chip sweep at 2^25 int32 rows on a v5e (PERF.md, PR 33;
# scripts/dense_lookup_sweep.py), dense against gather, one int32
# table / two on one index: 17 / 19 ms against 227 / 477 at 730 rows,
# 134 / 203 against 289 / 540 at 2^16, 179 / 307 against 290 / 539 at
# 102,000, 414 / 772 against 289 / 758 at 2^18.  The product runs
# near the matrix unit's peak and grows with ``table_len * L``, the
# gather only with the number of tables, so past 102,000 x 8 limbs,
# the largest size at which dense won, the gather is ahead.
DENSE_MAX_TABLE_LIMBS = 102_000 * 8

# rows of one product: 2^14 and 2^18 read no faster at the date dim
# (36 / 106 ms for one table where 2^16 read 36; 22.5 / 22.6 for two
# where it read 21)
_CHUNK_ROWS = 1 << 16
# one-hot and product elements built per chunk, at most: a long table
# shortens the chunk instead of growing the operands past 128 MB
_CHUNK_ELEMS = 1 << 25


def engine(dtypes: Sequence, table_len: int) -> str:
    """``"dense"`` or ``"gather"``: the path :func:`lookup` takes for
    1-D tables of ``table_len`` rows, one of ``dtypes`` each, that
    share one 1-D integer index.  Static per executable."""
    dts = [np.dtype(d) for d in dtypes]
    if not dts or any(d.kind not in "biu" for d in dts):
        return "gather"
    total = sum(_n_limbs(d) for d in dts)
    if 1 <= table_len * total <= DENSE_MAX_TABLE_LIMBS:
        return "dense"
    return "gather"


def _products(tables: Sequence, idx) -> list:
    """The positions of the tables that go dense, one list per
    product: the integer and boolean 1-D tables together if that is
    under the bound, else each that is under it alone."""
    if idx.ndim != 1 or idx.dtype.kind not in "iu":
        return []
    flat = [k for k, t in enumerate(tables)
            if t.ndim == 1 and t.dtype.kind in "biu"]
    lens = {tables[k].shape[0] for k in flat}
    if len(lens) > 1:
        raise ValueError(
            f"lookup wants tables of one length, got {sorted(lens)}")
    if not flat:
        return []
    if engine([tables[k].dtype for k in flat], *lens) == "dense":
        return [flat]
    return [[k] for k in flat
            if engine((tables[k].dtype,), *lens) == "dense"]


def engines(tables: Sequence, idx) -> Tuple[str, ...]:
    """The engine of each of ``tables`` under ``idx``, from shapes and
    dtypes alone (arrays or ``jax.ShapeDtypeStruct``s)."""
    dense = {k for group in _products(tables, idx) for k in group}
    return tuple("dense" if k in dense else "gather"
                 for k in range(len(tables)))


def lookup(tables: Sequence, idx) -> tuple:
    """``tuple(t[idx] for t in tables)`` for tables of one length:
    same dtypes, and bit for bit what ``t[idx]`` gives for every
    index (a negative index wraps once, what is still outside is
    clamped)."""
    from spark_rapids_tpu import observability as _obs

    tables = tuple(jnp.asarray(t) for t in tables)
    # a python index stays one for ``t[idx]``: a static slice
    raw, idx = idx, jnp.asarray(idx)
    out = [None] * len(tables)
    for group in _products(tables, idx):
        for k, v in zip(group, _dense([tables[k] for k in group], idx)):
            out[k] = v
    for k, t in enumerate(tables):
        # traced once per executable: the counter says how many
        # lookups of each engine were built, not how often they ran
        _obs.record_dense_lookup("gather" if out[k] is None else "dense")
        if out[k] is None:
            out[k] = t[raw]
    return tuple(out)


def _split(table_len: int, limbs: int):
    """``(hi_n, lo_bits)`` with ``hi_n << lo_bits >= table_len``:
    ``lo_n = 1 << lo_bits`` near the fourth root of ``table_len *
    limbs``, which is where the chip ran fastest at both q3 dims, for
    one int32 table and for two: 8 at 730 rows (against 2, 4, 16, 32,
    64, 128) and 32 at 102,000 (against 8, 16, 64, 128; PERF.md, PR
    33).  A short table wants few product rows ``lo_n * limbs`` (the
    masked pick reads them all), a long one few one-hot rows."""
    lo_bits = int(round(np.log2(table_len * limbs) / 4))
    lo_bits = min(lo_bits, int(table_len - 1).bit_length())
    return -(-table_len >> lo_bits), lo_bits


def _inside(idx, table_len: int):
    """``idx`` as the int32 position ``t[idx]`` reads: jnp wraps a
    negative index once, in the index's own width, narrows to int32,
    and XLA's gather clamps what is still outside."""
    if idx.dtype.kind == "i":
        if idx.dtype.itemsize < 4:
            idx = idx.astype(jnp.int32)
        idx = jnp.where(idx < 0, idx + table_len, idx)
    return jnp.clip(idx.astype(jnp.int32), 0, table_len - 1)


def _from_limbs(limbs, dtype):
    """The inverse of ``segment_sum._limbs``: ``[L, n]`` int32 limbs,
    least significant first, as ``n`` values of ``dtype``."""
    dt = np.dtype(dtype)
    if dt.kind == "b":
        return limbs[0] != 0
    u = limbs.astype(jnp.uint32)
    words = [sum(u[w * 4 + k] << (_LIMB_BITS * k)
                 for k in range(min(dt.itemsize, 4)))
             for w in range(-(-dt.itemsize // 4))]
    if dt.itemsize == 8:
        bits = words[0].astype(jnp.uint64) | (
            words[1].astype(jnp.uint64) << 32)
    else:
        bits = words[0].astype(np.dtype(f"uint{8 * dt.itemsize}"))
    return lax.bitcast_convert_type(bits, dt)


def _dense(tables, idx, *, lo_bits=None):
    n = idx.shape[0]
    table_len = tables[0].shape[0]
    limbs = jnp.concatenate([_limbs(t) for t in tables])
    n_limbs = limbs.shape[0]
    if lo_bits is None:
        hi_n, lo_bits = _split(table_len, n_limbs)
    else:
        hi_n = -(-table_len >> lo_bits)
    lo_n = 1 << lo_bits
    # [lo_n * L, hi_n]: row (lo, l), column hi holds limb l of entry
    # hi * lo_n + lo
    table = jnp.pad(limbs, ((0, 0), (0, hi_n * lo_n - table_len)))
    table = (table.reshape(n_limbs, hi_n, lo_n).transpose(2, 0, 1)
             .reshape(lo_n * n_limbs, hi_n).astype(jnp.bfloat16))

    chunk = max(1024, min(
        _CHUNK_ROWS, -(-n // 1024) * 1024,
        _CHUNK_ELEMS // (hi_n + lo_n * n_limbs) // 1024 * 1024))
    n_chunks = -(-n // chunk)
    pos = jnp.pad(_inside(idx, table_len), (0, n_chunks * chunk - n))

    hi_iota = lax.broadcasted_iota(jnp.int32, (hi_n, chunk), 0)
    lo_iota = lax.broadcasted_iota(jnp.int32, (lo_n, 1, chunk), 0)
    starts = np.cumsum([0] + [_n_limbs(t.dtype) for t in tables])

    def one_chunk(_, i):
        hi = i >> lo_bits
        lo = i & (lo_n - 1)
        a = (hi[None, :] == hi_iota).astype(jnp.bfloat16)
        # one non-zero term a row: exact
        part = lax.dot_general(
            table, a, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        part = part.reshape(lo_n, n_limbs, chunk)
        mine = jnp.sum(jnp.where(lo[None, None, :] == lo_iota,
                                 part, 0.0), axis=0).astype(jnp.int32)
        return None, tuple(
            _from_limbs(mine[s:e], t.dtype)
            for t, s, e in zip(tables, starts[:-1], starts[1:]))

    # no carry, so nothing to pcast inside shard_map: the outputs vary
    # over the axes the index does
    _, out = lax.scan(one_chunk, None, pos.reshape(n_chunks, chunk))
    return tuple(o.reshape(n_chunks * chunk)[:n] for o in out)
