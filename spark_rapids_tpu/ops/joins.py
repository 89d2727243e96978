"""Join primitives (reference join_primitives.hpp/.cu, JoinPrimitives.java):
sort_merge_inner_join / hash_inner_join -> (left_indices, right_indices)
gather maps, plus the index transforms make_left_outer / make_full_outer /
make_semi / make_anti / get_matched_rows and conditional pair filtering.

TPU-first design (SURVEY.md §7.4): sort-based equality matching — TPUs
have no device hash tables, but argsort/segment ops vectorize well.  Keys
are reduced to per-column total-order rank arrays (floats via the raw-bit
total-order transform, strings via host ordinal ranking for now), combined
lexicographically, and matched by group: both sides' rows are bucketed by
canonical key id, and the inner join emits the per-group cross products.
Pair expansion sizes are data-dependent, so the expansion happens at the
eager boundary (host offsets + device gathers) — the budgeted-chunk
device path is future work.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columns import dtypes
from spark_rapids_tpu.columns.column import Column
from spark_rapids_tpu.columns.dtypes import Kind
from spark_rapids_tpu.columns.table import Table
from spark_rapids_tpu.utils import floats, native

_I32 = jnp.int32

NULL_EQUAL = "EQUAL"
NULL_UNEQUAL = "UNEQUAL"


def _mask_of(col: Column) -> np.ndarray:
    return (np.ones(col.length, bool) if col.validity is None
            else np.asarray(col.validity).astype(bool))


def _string_buf(col: Column) -> np.ndarray:
    return (np.asarray(col.data) if col.data is not None
            else np.zeros(0, np.uint8))


_STRING_RANK_WORDS_BUDGET = 256 << 20   # packed-word matrix byte cap


def _string_ranks(chars: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    """Dense lexicographic ranks of an Arrow string buffer — native C++
    kernel when available (utils/native.py), packed-word vectorized
    ranking otherwise (ISSUE 9 satellite: the per-row
    ``chars[o[i]:o[i+1]].tobytes()`` python loop was a big slice of the
    11.2s host join)."""
    offsets = np.asarray(offsets, dtype=np.int64)
    ranks = native.rank_strings(chars, offsets)
    if ranks is not None:
        return ranks
    n = len(offsets) - 1
    if n <= 0:
        return np.zeros(0, np.int64)
    lens = np.diff(offsets)
    maxlen = int(lens.max()) if n else 0
    k = max(1, (maxlen + 7) // 8)
    idx_dt = np.int32 if len(chars) < 2**31 else np.int64
    # budget the whole transient, not just the u8 word matrix: the
    # (n, k*8) gather-index matrix below is idx_dt-sized and dominates
    if n * k * 8 * (1 + np.dtype(idx_dt).itemsize) > \
            _STRING_RANK_WORDS_BUDGET:
        # pathological width: the dense matrices would dwarf the
        # data; keep the exact per-row path for this rare shape
        vals = np.array([chars[offsets[i]:offsets[i + 1]].tobytes()
                         for i in range(n)], dtype=object)
        _, inv = np.unique(vals, return_inverse=True)
        return inv.astype(np.int64)
    # big-endian packed u64 words: zero pad preserves byte order, the
    # length column restores shorter-before-longer on equal prefixes
    # (and keeps "a" != "a\x00" injective)
    padded = np.zeros((n, k * 8), np.uint8)
    if len(chars):
        width = np.arange(k * 8, dtype=idx_dt)[None, :]
        idx = offsets[:-1, None].astype(idx_dt) + width
        valid = width < lens[:, None]
        np.minimum(idx, idx_dt(len(chars) - 1), out=idx)
        padded = chars[idx] * valid
    words = np.ascontiguousarray(padded).view(
        np.dtype(">u8")).astype(np.uint64).reshape(n, k)
    cols = [words[:, i] for i in range(k)]
    cols.append(lens.astype(np.uint64))
    ids, _, _ = group_ids_from_ranks(cols)
    return ids.astype(np.int64)


def _column_rank_host(col: Column) -> Tuple[np.ndarray, np.ndarray]:
    """(rank int64 array, null mask) — ranks order rows like the column's
    natural ordering; nulls get rank -1."""
    kind = col.dtype.kind
    mask = _mask_of(col)
    if kind == Kind.STRING:
        rank = _string_ranks(_string_buf(col), np.asarray(col.offsets))
    elif kind == Kind.DECIMAL128:
        _, inv = np.unique(_raw_values(col), return_inverse=True)
        rank = inv.astype(np.int64)
    elif kind == Kind.FLOAT64:
        rank = np.asarray(floats.total_order_key(col.data))
    elif kind == Kind.FLOAT32:
        import jax.numpy as _j
        from jax import lax
        bits = np.asarray(lax.bitcast_convert_type(col.data, _j.uint32))
        flipped = np.where(bits >> 31 != 0, ~bits,
                           bits | np.uint32(1 << 31)).astype(np.int64)
        rank = flipped
    else:
        rank = np.asarray(col.to_numpy()).astype(np.int64, copy=False)
    rank = np.where(mask, rank, 0)
    return rank, mask


def group_ids_from_ranks(rank_cols):
    """(ids, first_index_per_group, ngroups) from per-column rank arrays.
    Single column uses the fast 1-D np.unique; multi-column avoids the
    slow np.unique(axis=0) structured path via lexsort + adjacent-diff."""
    n = len(rank_cols[0]) if rank_cols else 0
    if n == 0:
        return (np.zeros(0, np.int64), np.zeros(0, np.int64), 0)
    if len(rank_cols) == 1:
        uniq, first_idx, ids = np.unique(
            rank_cols[0], return_index=True, return_inverse=True)
        return ids.astype(np.int64), first_idx, len(uniq)
    order = np.lexsort(tuple(reversed(rank_cols)))
    diff = np.zeros(n, bool)
    for c in rank_cols:
        cs = c[order]
        diff[1:] |= cs[1:] != cs[:-1]
    gid_sorted = np.cumsum(diff)  # 0-based after subtracting below
    ids = np.empty(n, np.int64)
    ids[order] = gid_sorted
    ngroups = int(gid_sorted[-1]) + 1
    # stable lexsort: the first sorted element of each group is its
    # earliest original occurrence (np.unique return_index semantics)
    starts = np.concatenate([[0], np.nonzero(diff)[0]])
    first_idx = order[starts]
    return ids, first_idx, ngroups


def _key_ids(left: Table, right: Table, compare_nulls: str):
    """Canonical group id per row of left and right (equal keys <=> equal
    id), plus per-row key-validity (any null key under UNEQUAL = no
    match)."""
    nl, nr = left.num_rows, right.num_rows
    cols = list(zip(left.columns, right.columns))
    ranks = []
    valid_l = np.ones(nl, bool)
    valid_r = np.ones(nr, bool)
    for lc, rc in cols:
        if lc.dtype.kind != rc.dtype.kind:
            raise ValueError("join key dtypes must match")
        if lc.dtype.kind == Kind.STRING:
            # joint ranking over the concatenated Arrow buffers (native
            # C++ rank kernel when available); int64 offsets so the
            # combined buffers may exceed 2^31 bytes
            lm, rm = _mask_of(lc), _mask_of(rc)
            lchars, rchars = _string_buf(lc), _string_buf(rc)
            loffs = np.asarray(lc.offsets).astype(np.int64)
            roffs = np.asarray(rc.offsets).astype(np.int64)
            chars = np.concatenate([lchars, rchars])
            offsets = np.concatenate([loffs, roffs[1:] + len(lchars)])
            inv = _string_ranks(chars, offsets)
            lr, rr = inv[:nl], inv[nl:]
        elif lc.dtype.kind == Kind.DECIMAL128:
            lm, rm = _mask_of(lc), _mask_of(rc)
            lvals, rvals = _raw_values(lc), _raw_values(rc)
            _, inv = np.unique(np.concatenate([lvals, rvals]),
                               return_inverse=True)
            lr, rr = inv[:nl].astype(np.int64), inv[nl:].astype(np.int64)
        else:
            lr, lm = _column_rank_host(lc)
            rr, rm = _column_rank_host(rc)
        # null encoding WITHOUT sentinel values (a sentinel collides with
        # legal ranks like INT64_MIN): the mask itself becomes an extra
        # key column, and null rows zero their value column
        ranks.append((lm.astype(np.int64), rm.astype(np.int64)))
        ranks.append((np.where(lm, lr, np.int64(0)),
                      np.where(rm, rr, np.int64(0))))
        if compare_nulls == NULL_UNEQUAL:
            valid_l &= lm
            valid_r &= rm
    combined = [np.concatenate([a, b]) for a, b in ranks]
    if combined and len(combined[0]):
        ids, _, _ = group_ids_from_ranks(combined)
    else:
        ids = np.zeros(nl + nr, np.int64)
    return ids[:nl], ids[nl:], valid_l, valid_r


def _raw_values(col: Column) -> np.ndarray:
    kind = col.dtype.kind
    if kind == Kind.DECIMAL128:
        limbs = np.asarray(col.data).astype(np.uint32).astype(object)
        vals = (limbs[:, 0] + (limbs[:, 1] << 32) + (limbs[:, 2] << 64)
                + (limbs[:, 3] << 96))
        return np.where(vals >= (1 << 127), vals - (1 << 128), vals)
    raise AssertionError


def _device_key_kind_ok(c: Column) -> bool:
    """Can this column be a device join/group-by key?  Fixed-width and
    decimal128 always; strings up to the word-sort width cap."""
    kind = c.dtype.kind
    if kind in _DEVICE_RANK_KINDS or kind == Kind.DECIMAL128:
        return True
    if kind == Kind.STRING:
        return c.max_string_length() <= DEVICE_STR_KEY_MAX_LEN
    return False


# dtypes whose rank is a pure device transform (no host readback):
# everything fixed-width except decimal128 (multi-word device encoding
# via _decimal_words) and strings (packed-word device encoding via
# _string_words, host native-rank fallback beyond the width cap)
_DEVICE_RANK_KINDS = frozenset({
    Kind.BOOL8, Kind.INT8, Kind.INT16, Kind.INT32, Kind.INT64,
    Kind.UINT8, Kind.UINT16, Kind.UINT32, Kind.UINT64,
    Kind.FLOAT32, Kind.FLOAT64, Kind.TIMESTAMP_DAYS,
    Kind.TIMESTAMP_MICROS, Kind.DECIMAL32, Kind.DECIMAL64})


def _device_rank(col: Column) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(int64 equality-rank, bool mask) computed entirely on device.
    Ranks are injective per value (sufficient for equality joins);
    float ranks also order correctly (total-order bit transform)."""
    from jax import lax

    kind = col.dtype.kind
    if kind == Kind.FLOAT64:
        r = floats.total_order_key(col.data)   # data carries raw bits
    elif kind == Kind.FLOAT32:
        bits = lax.bitcast_convert_type(col.data, jnp.uint32)
        r = jnp.where(bits >> 31 != 0, ~bits,
                      bits | jnp.uint32(1 << 31)).astype(jnp.int64)
    else:
        r = col.data.astype(jnp.int64)  # uint64 wraps but stays injective
    mask = (jnp.ones(col.length, jnp.bool_) if col.validity is None
            else jnp.asarray(col.validity).astype(jnp.bool_))
    return r, mask


# Longest string key that still goes through the device word-sort path
# (comparator width = ceil(maxlen/8)+1 columns per key; beyond this the
# host rank path wins)
DEVICE_STR_KEY_MAX_LEN = 256


def _string_words(col: Column, pad_to: int) -> List[jnp.ndarray]:
    """Exact string equality keys as packed big-endian u64 word columns
    plus the byte length (padding zeros alone would conflate "a" and
    "a\\x00" — the length column restores injectivity).  Entirely on
    device; the joint pad width makes both join sides comparable."""
    chars, lens = col.to_padded_chars(pad_to=max(pad_to, 1))
    rows, L = chars.shape
    k = (L + 7) // 8
    padded = jnp.concatenate(
        [chars, jnp.zeros((rows, k * 8 - L), jnp.uint8)], axis=1)
    bytes_ = padded.reshape(rows, k, 8).astype(jnp.uint64)
    shifts = jnp.asarray(
        np.arange(56, -8, -8, dtype=np.uint64))      # big-endian
    words = (bytes_ << shifts[None, None, :]).sum(
        axis=2, dtype=jnp.uint64)
    out = [words[:, i].astype(jnp.int64) for i in range(k)]
    out.append(lens.astype(jnp.int64))
    return out


def _decimal_words(col: Column) -> List[jnp.ndarray]:
    """decimal128 equality keys: the (n, 4) int32 limb matrix packed
    into two u64 word columns (equality-injective; order irrelevant for
    join/group-by ids)."""
    limbs = col.data.astype(jnp.uint32).astype(jnp.uint64)
    lo = limbs[:, 0] | (limbs[:, 1] << jnp.uint64(32))
    hi = limbs[:, 2] | (limbs[:, 3] << jnp.uint64(32))
    return [lo.astype(jnp.int64), hi.astype(jnp.int64)]


def _device_equality_cols(col: Column, pad_to: int = 0
                          ) -> Optional[List[jnp.ndarray]]:
    """Device int64 equality-key columns for one column, or None when
    the kind has no device path.  Multi-column encodings (strings,
    decimal128) are fine: the sorted-gid core takes any column list."""
    kind = col.dtype.kind
    if kind in _DEVICE_RANK_KINDS:
        r, _ = _device_rank(col)
        return [r]
    if kind == Kind.STRING:
        return _string_words(col, pad_to)
    if kind == Kind.DECIMAL128:
        return _decimal_words(col)
    return None


def _device_key_columns(columns) -> list:
    """int64 equality-key columns for the sorted-gid core.  Nullable
    columns (validity present — a static pytree property) contribute a
    mask column before their value columns: the sentinel-free null
    encoding shared by joins and group-by (a sentinel value would
    collide with legal ranks like INT64_MIN).  All-valid columns skip
    the mask, keeping the sort comparator as narrow as possible —
    comparator width is what drives XLA sort compile/runtime cost."""
    cols = []
    for c in columns:
        pad = c.max_string_length() if c.dtype.kind == Kind.STRING \
            else 0
        vals = _device_equality_cols(c, pad)
        if vals is None:
            raise ValueError(f"no device key path for {c.dtype}")
        if c.validity is not None:
            m = c.validity.astype(jnp.bool_)
            cols.append(m.astype(jnp.int64))
            cols.extend(jnp.where(m, v, jnp.int64(0)) for v in vals)
        else:
            cols.extend(vals)
    return cols


def _sorted_gid_core(cols):
    """(order, gid_sorted): stable sort over the key columns plus
    adjacent-diff group numbering.  Shared device core for join key ids
    and group-by ids.  Uses lax.sort directly: the iota as the final
    sort key gives deterministic (stable) ordering, and the co-sorted
    key columns come back from the same sort — no post-sort gathers."""
    from jax import lax

    n = cols[0].shape[0]
    iota = lax.iota(jnp.int32, n)
    sorted_all = lax.sort(tuple(cols) + (iota,), num_keys=len(cols) + 1)
    order = sorted_all[-1]
    diff = jnp.zeros(n, jnp.bool_)
    for cs in sorted_all[:-1]:
        diff = diff.at[1:].set(diff[1:] | (cs[1:] != cs[:-1]))
    gid_sorted = jnp.cumsum(diff.astype(jnp.int64))
    return order, gid_sorted


def _sort_merge_inner_join_device(left: Table, right: Table,
                                  compare_nulls: str):
    """Device fast path: ranks, joint ids, run search, and pair
    expansion are one XLA program each; only the true pair count crosses
    to the host (to size the output)."""
    from spark_rapids_tpu.ops.device_join import inner_join_device

    nl, nr = left.num_rows, right.num_rows
    if nl == 0 or nr == 0 or not left.columns:
        return (jnp.zeros(0, jnp.int32), jnp.zeros(0, jnp.int32))

    lid, rid, lval, rval = _device_ids(left, right, compare_nulls)
    total = int(_device_join_total(lid, rid, lval, rval))
    if total == 0:
        return (jnp.zeros(0, jnp.int32), jnp.zeros(0, jnp.int32))
    cap = 1 << (total - 1).bit_length()   # pow2-bucketed: few recompiles
    pairs = _device_join_pairs(lid, rid, lval, rval, cap)
    # with capacity >= total the first `total` slots are exactly the
    # valid pairs, in (left row, right sorted-run) order — identical to
    # the host path's layout
    return pairs.left_indices[:total], pairs.right_indices[:total]


# module-level jitted helpers: jax.jit caches on function identity, so
# these compile once per (shape, static arg) instead of once per call
from functools import partial as _partial  # noqa: E402


@jax.jit
def _ids_from_cols_jit(cols):
    order, gid_sorted = _sorted_gid_core(list(cols))
    n = cols[0].shape[0]
    return jnp.zeros(n, jnp.int64).at[order].set(gid_sorted)


def _col_mask(c: Column) -> jnp.ndarray:
    return (jnp.ones(c.length, jnp.bool_) if c.validity is None
            else c.validity.astype(jnp.bool_))


def _device_ids(left: Table, right: Table, compare_nulls: str):
    """Per-row equality ids over the joined key columns.  Eager key
    prep (string pad widths are data-dependent) + one jitted sorted-gid
    program.  The join core only needs an injective int64 key (it sorts
    and merges), so a single all-valid fixed-width key column IS its
    own id — no sort at all; multi-column encodings (strings as packed
    words + length, decimal128 as limb words) and nullable keys pay for
    the sorted-gid pass."""
    nl, nr = left.num_rows, right.num_rows
    key_cols = []
    vl = jnp.ones(nl, jnp.bool_)
    vr = jnp.ones(nr, jnp.bool_)
    for lc, rc in zip(left.columns, right.columns):
        pad = (max(lc.max_string_length(), rc.max_string_length())
               if lc.dtype.kind == Kind.STRING else 0)
        lvals = _device_equality_cols(lc, pad)
        rvals = _device_equality_cols(rc, pad)
        nullable = lc.validity is not None or rc.validity is not None
        if nullable or compare_nulls == NULL_UNEQUAL:
            lm, rm = _col_mask(lc), _col_mask(rc)
        if nullable:
            key_cols.append(jnp.concatenate([lm, rm]).astype(jnp.int64))
            key_cols.extend(
                jnp.concatenate([jnp.where(lm, lv, jnp.int64(0)),
                                 jnp.where(rm, rv, jnp.int64(0))])
                for lv, rv in zip(lvals, rvals))
        else:
            key_cols.extend(jnp.concatenate([lv, rv])
                            for lv, rv in zip(lvals, rvals))
        if compare_nulls == NULL_UNEQUAL:
            vl &= lm
            vr &= rm
    if len(key_cols) == 1:
        ids = key_cols[0]
    else:
        ids = _ids_from_cols_jit(tuple(key_cols))
    return ids[:nl], ids[nl:], vl, vr


@jax.jit
def _device_join_total(lid, rid, lval, rval):
    """Count-only half of inner_join_device: the same merged run
    bounds (no slot map, no pair expansion)."""
    from spark_rapids_tpu.ops.device_join import merge_run_bounds

    lo, hi, _ = merge_run_bounds(lid, rid, rval)
    counts = jnp.where(lval, hi - lo, 0).astype(jnp.int64)
    return jnp.sum(counts)


@_partial(jax.jit, static_argnames=("capacity",))
def _device_join_pairs(lid, rid, lval, rval, capacity: int):
    from spark_rapids_tpu.ops.device_join import inner_join_device

    return inner_join_device(lid, rid, capacity, lval, rval)


# rows (max side) at or above this count earn a measured path pick;
# below it the static default is cheaper than timing anything
JOIN_CALIBRATE_MIN_ROWS = 1 << 15

JOIN_PATHS = ("host_rank", "host_hash", "device_sort", "device_hash")


def _host_hash_inner_join(left_keys: Table, right_keys: Table,
                          compare_nulls: str):
    from spark_rapids_tpu.ops import hash_join as HJ
    lwords, rwords, vl, vr, _extra = HJ.join_key_words(
        left_keys, right_keys, compare_nulls)
    li, ri = HJ.host_hash_join(
        [np.asarray(w) for w in lwords], [np.asarray(w) for w in rwords],
        np.asarray(vl), np.asarray(vr))
    return jnp.asarray(li), jnp.asarray(ri)


def _device_hash_inner_join(left_keys: Table, right_keys: Table,
                            compare_nulls: str):
    from spark_rapids_tpu.ops import hash_join as HJ
    lwords, rwords, vl, vr, extra = HJ.join_key_words(
        left_keys, right_keys, compare_nulls)
    return HJ.device_hash_join(lwords, rwords, vl, vr, extra)


def _join_engines():
    """Name -> engine map, resolved lazily (the host rank oracle is
    defined below this router in file order).  Dict order is the
    calibration measurement order: expected-fast engines first, the
    rank oracle LAST, so a slow oracle that trips the calibration
    budget can only lose to already-measured candidates, never win by
    starving them (perf/calibrate.pick_path's budget discipline)."""
    return {
        "host_hash": _host_hash_inner_join,
        "device_sort": _sort_merge_inner_join_device,
        "device_hash": _device_hash_inner_join,
        "host_rank": _sort_merge_inner_join_host,
    }


def _join_sample(table: Table, rows: int) -> Table:
    if table.num_rows <= rows:
        return table
    from spark_rapids_tpu.ops.copying import slice_table
    return slice_table(table, 0, rows)


def sort_merge_inner_join(left_keys: Table, right_keys: Table,
                          compare_nulls: str = NULL_EQUAL
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(left_indices, right_indices) gather maps of matching row pairs
    (join_primitives.hpp:64).  Pair order: grouped by key, row-order
    within group — identical across every engine.

    Engine choice is a MEASUREMENT, not a backend gate (ISSUE 9): for
    large inputs the per-(schema digest, backend) calibrator times the
    host rank oracle, the numpy bucket hash join, and the two device
    engines on a sample (left side capped, right side full — the build
    side's cache behavior is what separates the engines) and caches
    the verdict.  Small inputs keep the static default (device on
    accelerators, host elsewhere); operators can pin a path with
    SPARK_RAPIDS_TPU_PATH_JOIN_INNER=<engine> or force the legacy
    device gate with SPARK_RAPIDS_TPU_FORCE_DEVICE_JOIN=1."""
    import os

    from spark_rapids_tpu import observability as _obs

    nl, nr = left_keys.num_rows, right_keys.num_rows
    rows = max(nl, nr)
    # both sides must have a device key encoding AND per-column kinds
    # must match (a mismatch falls through to the host path's
    # ValueError); very long string keys rank better on the host
    device_ok = (
        len(left_keys.columns) == len(right_keys.columns)
        and all(lc.dtype.kind == rc.dtype.kind
                and _device_key_kind_ok(lc) and _device_key_kind_ok(rc)
                for lc, rc in zip(left_keys.columns, right_keys.columns)))
    on_accel = jax.default_backend() != "cpu"
    force_device = os.environ.get(
        "SPARK_RAPIDS_TPU_FORCE_DEVICE_JOIN") == "1"

    engines = _join_engines()
    path = None
    if not device_ok or not left_keys.columns:
        path = "host_rank"
    elif force_device:
        path = "device_sort"
    else:
        from spark_rapids_tpu.perf import calibrate
        pin = calibrate.pinned_path("join.inner")
        if pin is not None and pin in engines:
            path = pin
        elif rows < JOIN_CALIBRATE_MIN_ROWS:
            path = "device_sort" if on_accel else "host_rank"
        else:
            from spark_rapids_tpu.perf.jit_cache import schema_digest
            # BOTH sides' schemas and size classes key the verdict
            # (calibrate.operands_digest): the winning engine flips
            # with how much of the build side stays cache-resident,
            # and a probe side that changed size class must not reuse
            # a verdict measured at another scale
            nulls = [lc.validity is not None or rc.validity is not None
                     for lc, rc in zip(left_keys.columns,
                                       right_keys.columns)]
            digest = calibrate.operands_digest(
                [(schema_digest([c.dtype for c in left_keys.columns],
                                nulls), nl),
                 (schema_digest([c.dtype for c in right_keys.columns],
                                nulls), nr)],
                extra=f"join:{compare_nulls}")
            # the build side is bounded too: its size CLASS stays in
            # the digest above, but timing 4 engines x 2 runs over an
            # unbounded build side would stall the first query for
            # minutes (and trip the lifeguard deadline) — a 2^20-row
            # build is enough to separate the engines
            sl = _join_sample(left_keys, 1 << 18)
            sr = _join_sample(right_keys, 1 << 20)
            candidates = {
                name: (lambda fn=fn: fn(sl, sr, compare_nulls))
                for name, fn in engines.items()}
            path = calibrate.pick_path(
                "join.inner", digest, candidates,
                default="device_sort" if on_accel else "host_hash")
            if path not in engines:
                path = "host_rank"
    _obs.record_kernel_path("join.inner", path, rows)
    return engines[path](left_keys, right_keys, compare_nulls)


def _sort_merge_inner_join_host(left_keys: Table, right_keys: Table,
                                compare_nulls: str = NULL_EQUAL
                                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Host rank path (all dtypes incl. strings/decimal128/nested) —
    also the executable oracle for the device path's differential
    tests."""
    lid, rid, lval, rval = _key_ids(left_keys, right_keys, compare_nulls)
    nl = left_keys.num_rows
    # bucket right rows by id
    order_r = np.argsort(rid, kind="stable")
    rid_sorted = rid[order_r]
    # for each left row, locate its id-run in the sorted right side
    starts = np.searchsorted(rid_sorted, lid, side="left")
    ends = np.searchsorted(rid_sorted, lid, side="right")
    counts = ends - starts
    lrows = np.arange(nl)
    if compare_nulls == NULL_UNEQUAL:
        counts = np.where(lval, counts, 0)
    # drop right rows that are invalid under UNEQUAL: since any null key
    # made the whole row invalid, exclude them from the runs
    if compare_nulls == NULL_UNEQUAL and not rval.all():
        keep = rval[order_r]
        # recompute runs against only valid rows
        order_r = order_r[keep]
        rid_sorted = rid[order_r]
        starts = np.searchsorted(rid_sorted, lid, side="left")
        ends = np.searchsorted(rid_sorted, lid, side="right")
        counts = np.where(lval, ends - starts, 0)
    total = int(counts.sum())
    left_out = np.repeat(lrows, counts)
    offs = np.zeros(nl + 1, np.int64)
    np.cumsum(counts, out=offs[1:])
    pos = np.arange(total) - offs[left_out]
    right_out = order_r[starts[left_out] + pos]
    return (jnp.asarray(left_out.astype(np.int32)),
            jnp.asarray(right_out.astype(np.int32)))


def hash_inner_join(left_keys: Table, right_keys: Table,
                    compare_nulls: str = NULL_EQUAL):
    """Same contract as the reference hash_inner_join
    (join_primitives.hpp:87).  Since ISSUE 9 the shared router really
    does own hash-keyed engines (ops/hash_join.py: xxhash64 group ids
    over the word encoding, bucket-table host core / fixed-capacity
    device core), so both entries converge on the calibrated pick."""
    return sort_merge_inner_join(left_keys, right_keys, compare_nulls)


def filter_join_pairs(left_indices: jnp.ndarray,
                      right_indices: jnp.ndarray,
                      predicate: jnp.ndarray):
    """Keep pairs where predicate (bool per pair) holds
    (join_primitives.hpp conditional filtering — the AST predicate is
    evaluated by the caller over gathered pair columns)."""
    keep = np.asarray(predicate).astype(bool)
    li = np.asarray(left_indices)[keep]
    ri = np.asarray(right_indices)[keep]
    return jnp.asarray(li), jnp.asarray(ri)


def make_left_outer(left_indices, right_indices, left_num_rows: int):
    """Add unmatched left rows with right index -1 (null sentinel,
    join_primitives.hpp:145)."""
    li = np.asarray(left_indices)
    ri = np.asarray(right_indices)
    matched = np.zeros(left_num_rows, bool)
    matched[li] = True
    missing = np.nonzero(~matched)[0].astype(li.dtype)
    out_l = np.concatenate([li, missing])
    out_r = np.concatenate([ri, np.full(missing.shape, -1, ri.dtype)])
    return jnp.asarray(out_l), jnp.asarray(out_r)


def make_full_outer(left_indices, right_indices, left_num_rows: int,
                    right_num_rows: int):
    """Unmatched rows from both sides with -1 sentinels
    (join_primitives.hpp:169)."""
    li = np.asarray(left_indices)
    ri = np.asarray(right_indices)
    lmatched = np.zeros(left_num_rows, bool)
    lmatched[li] = True
    rmatched = np.zeros(right_num_rows, bool)
    rmatched[ri] = True
    lmiss = np.nonzero(~lmatched)[0].astype(li.dtype)
    rmiss = np.nonzero(~rmatched)[0].astype(ri.dtype)
    out_l = np.concatenate([li, lmiss, np.full(rmiss.shape, -1, li.dtype)])
    out_r = np.concatenate([ri, np.full(lmiss.shape, -1, ri.dtype), rmiss])
    return jnp.asarray(out_l), jnp.asarray(out_r)


def make_semi(left_indices, left_num_rows: int):
    """Distinct left rows with >=1 match (join_primitives.hpp:194)."""
    li = np.asarray(left_indices)
    matched = np.zeros(left_num_rows, bool)
    matched[li] = True
    return jnp.asarray(np.nonzero(matched)[0].astype(np.int32))


def make_anti(left_indices, left_num_rows: int):
    """Left rows with no match (join_primitives.hpp:213)."""
    li = np.asarray(left_indices)
    matched = np.zeros(left_num_rows, bool)
    matched[li] = True
    return jnp.asarray(np.nonzero(~matched)[0].astype(np.int32))


def get_matched_rows(indices, num_rows: int) -> Column:
    """BOOL8 column marking rows present in the gather map
    (join_primitives.hpp:237)."""
    idx = np.asarray(indices)
    matched = np.zeros(num_rows, bool)
    matched[idx[idx >= 0]] = True
    return Column(dtypes.BOOL8, num_rows,
                  data=jnp.asarray(matched.astype(np.uint8)))
