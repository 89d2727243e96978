"""Fully-jittable fixed-capacity inner join.

The eager joins in ops/joins.py (reference join_primitives.hpp) produce
exact variable-size index pairs at the eager boundary.  This module is
the *device* counterpart for use INSIDE jit/shard_map — the piece a
distributed join needs so the whole partition→exchange→join step
compiles to one XLA program: static shapes, a caller-chosen pair
capacity, and a true pair count so overflow is detectable (the same
fixed-capacity-plus-true-count contract as parallel/exchange.py).

TPU-first shape: a merge, not a search.  Keys are total-order integer
ranks (callers canonicalize floats/strings first, as ops/joins does).
One sort of both sides' keys puts every left row right behind the
valid right rows equal to it, so its run ``[lo, hi)`` of matches is
the count of valid right rows ahead of it (a cumsum) and that count at
its key's first row (a cummax).  A second sort hands the bounds back
in left-row order beside the right rows in key order.  Pair slot j
maps back to its left row by a scatter-max of each row at its first
slot and a cummax over the slots.  Sorts and scans only: no
data-dependent loops, no dynamic shapes, no rounds of dependent
gathers — a binary search's round into HBM costs several times a
sort's pass per element on a TPU (PERF.md, section 5).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp
from jax import lax


class JoinPairs(NamedTuple):
    left_indices: jnp.ndarray   # (capacity,) int32 into the left table
    right_indices: jnp.ndarray  # (capacity,) int32 into the right table
    valid: jnp.ndarray          # (capacity,) bool — slot holds a pair
    total: jnp.ndarray          # () int64 TRUE pair count (may exceed
    #                               capacity: caller must retry bigger)


def merge_run_bounds(left_keys: jnp.ndarray, right_keys: jnp.ndarray,
                     right_valid: jnp.ndarray | None = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Each left key's run among the valid right keys, by merge.

    Returns ``(lo, hi, r_order)``: ``r_order`` (nr,) int32 lists the
    valid right rows in (key, row) order, then the invalid ones, and
    ``r_order[lo[i]:hi[i]]`` (int32 bounds) are the valid right rows
    whose key equals ``left_keys[i]``, in row order.  Rows with
    ``right_valid`` False match nothing, whatever their key."""
    nl, nr = left_keys.shape[0], right_keys.shape[0]
    n = nl + nr
    keys = jnp.concatenate([right_keys.astype(jnp.int64),
                            left_keys.astype(jnp.int64)])
    # unique tags: valid right rows 0..nr-1, left rows nr..n-1, invalid
    # right rows n..n+nr-1 — so at equal keys a left row has every valid
    # right row of its key ahead of it and no invalid one
    r_row = lax.iota(jnp.int32, nr)
    if right_valid is not None:
        r_row = jnp.where(right_valid, r_row, n + r_row)
    tag = jnp.concatenate([r_row, nr + lax.iota(jnp.int32, nl)])
    skey, stag = lax.sort((keys, tag), num_keys=2)

    is_vr = stag < nr
    is_left = (stag >= nr) & (stag < n)
    vr = is_vr.astype(jnp.int32)
    hi = lax.cumsum(vr) - vr            # valid right rows ahead
    # lo is hi at the key's first row; hi never falls, so a cummax
    # carries it along the run (the first row compares with itself and
    # is no start, which changes nothing: hi is 0 there)
    prev = jnp.concatenate([skey[:1], skey[:-1]])
    lo = lax.cummax(jnp.where(skey != prev, hi, 0))

    # left rows first in row order, then the valid right rows in key
    # order, then the invalid ones
    order = jnp.where(is_left, stag - nr,
                      jnp.where(is_vr, nl + lax.iota(jnp.int32, n),
                                nl + stag))
    row = jnp.where(stag >= n, stag - n, stag)
    _, a, b = lax.sort((order, jnp.where(is_left, lo, row), hi),
                       num_keys=1)
    return a[:nl], b[:nl], a[nl:]


def inner_join_device(left_keys: jnp.ndarray, right_keys: jnp.ndarray,
                      capacity: int,
                      left_valid: jnp.ndarray | None = None,
                      right_valid: jnp.ndarray | None = None
                      ) -> JoinPairs:
    """Jittable inner join on integer key arrays (join_primitives.hpp
    sort_merge_inner_join contract, device-resident).  Pairs fill the
    slots in left-row order, each left row's matches in right-row
    order, and stop at ``capacity``; empty slots are 0 and not valid.
    Rows with valid=False never match (NULL-inequality semantics;
    encode null-equals by mapping nulls to a shared sentinel key AND a
    dedicated validity column upstream, as ops/joins._key_ids does)."""
    nl = left_keys.shape[0]
    nr = right_keys.shape[0]
    if nl == 0 or nr == 0:
        z = jnp.zeros(capacity, jnp.int32)
        return JoinPairs(z, z, jnp.zeros(capacity, jnp.bool_),
                         jnp.int64(0))

    lo, hi, r_order = merge_run_bounds(left_keys, right_keys, right_valid)
    # pair accounting is int64: two 64k-row sides sharing one key are
    # 2^32 pairs, which would wrap int32 and defeat overflow detection
    counts = (hi - lo).astype(jnp.int64)
    if left_valid is not None:
        counts = jnp.where(left_valid, counts, 0)

    offs = jnp.cumsum(counts) - counts          # exclusive prefix sum
    total = offs[-1] + counts[-1]

    # slot map: pair slot j -> left row i with offs[i] <= j < offs[i+1].
    # Each row with pairs marks its first slot; the cummax carries it
    # over the row's run (rows without pairs share a neighbour's offset
    # and mark nothing)
    first = jnp.where((counts > 0) & (offs < capacity), offs,
                      capacity).astype(jnp.int32)
    i = lax.cummax(jnp.zeros(capacity, jnp.int32).at[first].max(
        lax.iota(jnp.int32, nl), mode="drop"))
    j = jnp.arange(capacity, dtype=jnp.int64)
    k = j - offs[i]
    valid = (j < total) & (k < counts[i])
    r_pos = jnp.clip(lo[i] + k, 0, nr - 1)
    right_idx = r_order[r_pos]
    return JoinPairs(jnp.where(valid, i, 0),
                     jnp.where(valid, right_idx, 0),
                     valid, total)
