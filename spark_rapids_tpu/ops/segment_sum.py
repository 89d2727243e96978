"""Exact dense-key segment sum on the matrix unit.

``jax.ops.segment_sum`` lowers to a scatter-add, which a TPU runs one
row after another (91 ns a row for int64 on a v5e: 3.05 s over 2^25
rows).  The same sum is a product of a one-hot matrix with the values,
and that runs on the matrix unit — exactly, when the values go in as
8-bit limbs:

  * a limb (0..255) and a one-hot entry (0/1) are exact in bfloat16;
  * a chunk holds at most 2^16 rows, so every float32 partial sum is
    an integer at most 255 * 65,536 < 2^24: exact in any order;
  * each chunk's partial is cast to an integer and added to an
    integer carry; the limbs recombine as ``sum_k carry[:, k] << 8k``
    in wrapping arithmetic of the value's own width, so negative
    values (two's complement) and overflowing sums come out as the
    wrapping scatter-add gave them, bit for bit.

The one-hot is factored, ``id = hi * lo_n + lo``: ``one_hot(hi)``
``[hi_n, chunk]`` against ``one_hot(lo) * limbs`` ``[lo_n * L, chunk]``,
which builds about ``2 * sqrt(num_segments * L)`` one-hot elements a
row where the plain form builds ``num_segments``.

What decides the engine is what the code sees in its input, on every
backend alike: integer or boolean values and ``num_segments`` up to
``DENSE_MAX_SEGMENTS`` take the dense path; floating-point values (a
float cannot be limb-split) and larger ``num_segments`` (the product
grows with ``rows * num_segments``, the scatter hardly at all) take
the scatter-add.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# Set from a chip sweep at 2^25 int64 rows on a v5e (PERF.md, PR 28;
# scripts/segment_sum_sweep.py): dense 14 / 22 / 64 / 204 / 792 ms at
# 8 / 2,000 / 2^14 / 2^16 / 2^18 groups against the scatter's 2.7 /
# 3.3 / 4.0 / 4.3 / 4.5 s.  Past 2^16 the product itself is the cost
# and grows with num_segments (3.9x from 2^16 to 2^18), so the paths
# would meet near 2^20: the bound is the largest size measured.
DENSE_MAX_SEGMENTS = 1 << 18

# 255 * 2^16 < 2^24: the float32 partial sums of one chunk are exact
_CHUNK_ROWS = 1 << 16
_LIMB_BITS = 8
# one-hot elements built per chunk, at most: a large num_segments
# shortens the chunk instead of growing the operands past 64 MB
_CHUNK_ELEMS = 1 << 25
# what booleans are counted into
_COUNT_DTYPE = np.int64


def engine(dtype, num_segments: int) -> str:
    """``"dense"`` or ``"scatter"``: the path :func:`segment_sum` takes
    for values of ``dtype``.  Static per executable."""
    dt = np.dtype(dtype)
    if dt.kind in "biu" and 1 <= num_segments <= DENSE_MAX_SEGMENTS:
        return "dense"
    return "scatter"


def segment_sum(values, ids, num_segments: int):
    """``jax.ops.segment_sum(values, ids, num_segments)`` for 1-D
    ``values``: same dtype, same wrapping sums, ids outside
    ``[0, num_segments)`` dropped.  Boolean values are counted, into
    the default integer dtype (``jax.ops.segment_sum`` refuses them).
    """
    from spark_rapids_tpu import observability as _obs

    values = jnp.asarray(values)
    ids = jnp.asarray(ids)
    which = engine(values.dtype, num_segments)
    # traced once per executable: the counter says how many segment
    # sums of each engine were built, not how often they ran
    _obs.record_segment_sum(which)
    if which == "scatter":
        if values.dtype == jnp.bool_:
            values = values.astype(_COUNT_DTYPE)
        return jax.ops.segment_sum(values, ids,
                                   num_segments=num_segments)
    if values.ndim != 1 or ids.shape != values.shape:
        raise ValueError(
            f"segment_sum wants 1-D values and ids of one shape, got "
            f"{values.shape} and {ids.shape}")
    return _dense(values, ids, num_segments)


def _split(num_segments: int, limbs: int):
    """``(hi_n, lo_bits)`` with ``hi_n << lo_bits >= num_segments``:
    ``lo_n = 1 << lo_bits`` near ``sqrt(num_segments / limbs)``, which
    is where ``hi_n + lo_n * limbs`` one-hot elements a row is least
    (and where the chip ran fastest: 16 for int64 and 32 for counts
    into 2,000 groups, against 4, 8, 32, 64 and 8, 16, 64, 128;
    PERF.md, PR 28)."""
    lo_bits = max(int(round(math.log2(num_segments / limbs) / 2)), 0)
    return -(-num_segments >> lo_bits), lo_bits


def _n_limbs(dtype) -> int:
    """How many limbs :func:`_limbs` splits a value of ``dtype`` into."""
    dt = np.dtype(dtype)
    return 1 if dt.kind == "b" else dt.itemsize


def _limbs(values):
    """``[L, n]`` float32 limbs (0..255) of the unsigned view of
    ``values``, least significant first.  64-bit values are taken as
    two 32-bit halves: the chip has no 64-bit lanes."""
    if values.dtype == jnp.bool_:
        return values.astype(jnp.float32)[None, :]
    width = values.dtype.itemsize
    if width == 8:
        words = [values.astype(jnp.uint32),
                 (values >> 32).astype(jnp.uint32)]
    else:
        words = [values.astype(jnp.uint32)]
    out = []
    for w in words:
        for k in range(min(width, 4)):
            out.append((w >> (_LIMB_BITS * k)) & 0xFF)
    return jnp.stack(out).astype(jnp.float32)


def _dense(values, ids, num_segments: int):
    n = values.shape[0]
    counted = values.dtype == jnp.bool_
    out_dtype = (jax.dtypes.canonicalize_dtype(_COUNT_DTYPE) if counted
                 else values.dtype)
    n_limbs = _n_limbs(values.dtype)
    hi_n, lo_bits = _split(num_segments, n_limbs)
    lo_n = 1 << lo_bits
    # the carry wraps in the result's own width (modulo 2^32 or 2^64)
    acc = jnp.uint64 if np.dtype(out_dtype).itemsize == 8 else jnp.uint32

    chunk = max(1024, min(
        _CHUNK_ROWS, -(-n // 1024) * 1024,
        _CHUNK_ELEMS // (hi_n + lo_n * n_limbs) // 1024 * 1024))
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n

    inside = (ids >= 0) & (ids < num_segments)
    ids = jnp.where(inside, ids, -1).astype(jnp.int32)
    if pad:
        ids = jnp.pad(ids, (0, pad), constant_values=-1)
        values = jnp.pad(values, (0, pad))

    hi_iota = lax.broadcasted_iota(jnp.int32, (hi_n, chunk), 0)
    lo_iota = lax.broadcasted_iota(jnp.int32, (lo_n, 1, chunk), 0)

    def one_chunk(carry, xs):
        v, i = xs
        # a dropped id (-1) has hi = -1: it matches no row of `a`
        hi = i >> lo_bits
        lo = i & (lo_n - 1)
        a = (hi[None, :] == hi_iota).astype(jnp.bfloat16)
        b = jnp.where(lo[None, None, :] == lo_iota,
                      _limbs(v)[None, :, :], 0.0)
        b = b.astype(jnp.bfloat16).reshape(lo_n * n_limbs, chunk)
        part = lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return carry + part.astype(jnp.int32).astype(acc), None

    carry = jnp.zeros((hi_n, lo_n * n_limbs), acc)
    # inside shard_map the carry varies over the axes its inputs do
    varying = tuple(jax.typeof(values).vma | jax.typeof(ids).vma)
    if varying:
        carry = lax.pcast(carry, varying, to="varying")
    carry, _ = lax.scan(
        one_chunk, carry,
        (values.reshape(n_chunks, chunk), ids.reshape(n_chunks, chunk)))
    per_limb = carry.reshape(hi_n * lo_n, n_limbs)[:num_segments]
    shifts = jnp.arange(n_limbs, dtype=acc) * _LIMB_BITS
    total = jnp.sum(per_limb << shifts[None, :], axis=1, dtype=acc)
    return total.astype(out_dtype)
