"""Hash-keyed inner join engines (ISSUE 9 tentpole).

The general join used to run a double-argsort rank core: every key
column of BOTH sides was jointly ranked (np.unique / lax.sort over the
concatenated sides) before the run search could even start — O((nl+nr)
log(nl+nr)) comparator work for what is an equality-only problem.
This module replaces that core with the classic hash-join shape:

  * keys reduce to the existing device word encoding
    (ops/joins._device_equality_cols: fixed-width ranks, packed string
    words + length, decimal128 limb words, sentinel-free null masks);
  * one xxhash64 pass over the word columns assigns a 64-bit group id
    per row (ops/hash.py mixing primitives — the short-input xxhash64
    schedule, extended past 32 bytes by chaining 8-byte updates), AOT
    compiled through perf/jit_cache with power-of-two row buckets and
    operand donation;
  * only the RIGHT side is organized (bucket table / sort) — the probe
    is a gather, so the big side never pays comparator work;
  * candidate pairs are verified by exact word comparison — hash
    quality affects SPEED only, never correctness.

Three engines share that skeleton:

``host`` (numpy)
    A direct-address bucket table: ``slot = hash & (m-1)`` with m a
    power of two at load factor <= 1/4, right rows counting-sorted by
    slot, probes resolved with O(1) gathers — no binary search (the
    cache-hostile searchsorted is what made the old host path crawl at
    0.9M rows/s).  When the single key column is an integer rank whose
    value span fits a small table, the identity function IS a perfect
    hash: ``slot = key - min`` with zero collisions and no verify pass
    (``direct`` sub-path).

``device`` (XLA)
    The same hash ids drive ops/device_join.inner_join_device (sort-
    merge run expansion) inside ONE compiled program per
    (schema digest, row buckets, capacity): fixed-capacity pair slots
    with a true count, equality verification fused into the program,
    and the pair capacity doubling under the SAME
    exchange.with_capacity_retry discipline the shuffle uses.

Pair order is identical across engines and to the host rank oracle:
grouped by left row (ascending), right indices ascending within each
group — the differential tests in tests/test_device_join_paths.py
pin this byte-for-byte.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from spark_rapids_tpu.columns.column import Column
from spark_rapids_tpu.columns.table import Table

_I32 = jnp.int32
_I64 = jnp.int64
_U64 = jnp.uint64

JOIN_HASH_SEED = 42

# perfect-hash (direct-address) table budget: the key span must fit a
# table no larger than this many slots AND no larger than a small
# multiple of the data (a sparse 2^40 keyspace must not allocate 2^40
# counters)
DIRECT_MAX_SLOTS = 1 << 23
DIRECT_SPAN_FACTOR = 4


# --------------------------------------------------------------- key prep

def join_key_words(left: Table, right: Table, compare_nulls: str):
    """Per-side device word columns + validity for the join keys.

    Mirrors ops/joins._device_ids exactly: nullable key columns (on
    EITHER side — pytree symmetry) contribute a mask word followed by
    their zeroed value words (sentinel-free null encoding), and
    NULL_UNEQUAL rows with any null key become invalid.  Returns
    (lwords, rwords, lvalid, rvalid, digest_extra) with words as int64
    jnp arrays; raises ValueError when a key kind has no device word
    encoding (caller falls back to the host rank path)."""
    from spark_rapids_tpu.ops import joins as J

    nl, nr = left.num_rows, right.num_rows
    lwords: List[jnp.ndarray] = []
    rwords: List[jnp.ndarray] = []
    vl = jnp.ones(nl, jnp.bool_)
    vr = jnp.ones(nr, jnp.bool_)
    shape = []
    for lc, rc in zip(left.columns, right.columns):
        if lc.dtype.kind != rc.dtype.kind:
            raise ValueError("join key dtypes must match")
        from spark_rapids_tpu.columns.dtypes import Kind
        pad = (max(lc.max_string_length(), rc.max_string_length())
               if lc.dtype.kind == Kind.STRING else 0)
        lvals = J._device_equality_cols(lc, pad)
        rvals = J._device_equality_cols(rc, pad)
        if lvals is None or rvals is None:
            raise ValueError(f"no device key path for {lc.dtype}")
        nullable = lc.validity is not None or rc.validity is not None
        if nullable or compare_nulls == J.NULL_UNEQUAL:
            lm, rm = J._col_mask(lc), J._col_mask(rc)
        if nullable:
            lwords.append(lm.astype(jnp.int64))
            rwords.append(rm.astype(jnp.int64))
            lwords.extend(jnp.where(lm, v, jnp.int64(0)) for v in lvals)
            rwords.extend(jnp.where(rm, v, jnp.int64(0)) for v in rvals)
        else:
            lwords.extend(lvals)
            rwords.extend(rvals)
        if compare_nulls == J.NULL_UNEQUAL:
            vl = vl & lm
            vr = vr & rm
        shape.append(f"{lc.dtype.kind}:{len(lvals)}:{int(nullable)}")
    extra = f"{compare_nulls}|{';'.join(shape)}"
    return lwords, rwords, vl, vr, extra


# ------------------------------------------------------------- key hashes

def _hash_words_program(*words):
    """xxhash64 of the concatenated 8-byte words, one lane per row —
    the short-input schedule from ops/hash.py (seed + P5 + length, an
    _xx_update8 per word, avalanche finalize), chained past the 32-byte
    stripe threshold.  Internal group ids only: NOT the Spark row-hash
    contract (ops/hash.xxhash64 keeps that)."""
    from spark_rapids_tpu.ops.hash import (_XXP5, _xx_finalize,
                                           _xx_update8)
    rows = words[0].shape[0]
    h = jnp.full((rows,), np.uint64(JOIN_HASH_SEED), _U64)
    h = h + _XXP5 + _U64(8 * len(words))
    for w in words:
        h = _xx_update8(h, lax.bitcast_convert_type(w, _U64))
    return _xx_finalize(h).astype(_I64)


def key_hashes(words: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """(rows,) int64 xxhash64 group ids for a word-column list, AOT
    compiled through the process jit cache under power-of-two row
    buckets (zero recompiles on same-bucket batches) with operand
    donation on backends that honor it."""
    from spark_rapids_tpu.perf.jit_cache import (CACHE, bucket_rows,
                                                 pad_axis0)
    rows = int(words[0].shape[0])
    if rows == 0:
        return jnp.zeros(0, _I64)
    if not CACHE.enabled():
        return jax.jit(_hash_words_program)(*words)[:rows]
    bucket = bucket_rows(rows)
    padded = tuple(pad_axis0(w.astype(_I64), bucket) for w in words)
    out = CACHE.cached_call(
        "join.keyhash", f"w{len(words)}", _hash_words_program, padded,
        bucket=bucket,
        donate_argnums=tuple(range(len(padded))))
    return out[:rows]


# ------------------------------------------------------------ host engine

def _expand_runs(starts: np.ndarray, counts: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """(left_out, positions) for per-left-row candidate runs: left row
    i contributes counts[i] consecutive positions starts[i]..  One
    np.repeat of the fused (start - exclusive_offset) adjustment plus
    an arange keeps the temporaries to two total-sized arrays."""
    nl = len(counts)
    total = int(counts.sum())
    idx_dtype = np.int32 if total < 2**31 and nl < 2**31 else np.int64
    left_out = np.repeat(np.arange(nl, dtype=idx_dtype), counts)
    ends = np.cumsum(counts, dtype=np.int64)
    adj = starts.astype(np.int64) - (ends - counts)
    pos = np.repeat(adj, counts) + np.arange(total, dtype=np.int64)
    return left_out, pos


def _host_join_from_slots(lslot, rslot, m, lcount_mask, verify,
                          rcounts=None) -> Tuple[np.ndarray, np.ndarray]:
    """Shared bucket-table core: build over right slots, probe with
    left slots, expand runs, then ``verify(left_out, cand)`` filters
    candidate pairs to true matches (None skips the pass — perfect
    hash).  ``rcounts`` is the caller's already-computed
    ``np.bincount(rslot, minlength=m)`` when it has one.  Returns
    (left_out, right_out_in_filtered_space)."""
    nr = len(rslot)
    order_r = np.argsort(rslot, kind="stable")
    if order_r.dtype != np.int32 and nr < 2**31:
        order_r = order_r.astype(np.int32)
    bcount = (np.bincount(rslot, minlength=m) if rcounts is None
              else rcounts)
    bstart = np.zeros(m + 1, np.int64)
    np.cumsum(bcount, out=bstart[1:])
    if nr < 2**31:
        bcount = bcount.astype(np.int32)
        bstart32 = bstart[:-1].astype(np.int32)
    else:  # pragma: no cover - >2^31-row build side
        bstart32 = bstart[:-1]
    starts = bstart32[lslot]
    counts = bcount[lslot]
    if lcount_mask is not None:
        counts = np.where(lcount_mask, counts, 0)
    left_out, pos = _expand_runs(starts, counts)
    cand = order_r[pos]
    if verify is not None:
        eq = verify(left_out, cand)
        if not eq.all():
            left_out = left_out[eq]
            cand = cand[eq]
    return left_out, cand


def host_hash_join(lwords, rwords, lvalid, rvalid
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """numpy bucket-table hash join over host word columns.

    lwords/rwords: list of (rows,) int64 numpy arrays (the device word
    encoding pulled to host — zero-copy on the CPU backend).
    lvalid/rvalid: bool masks (NULL_UNEQUAL exclusion).  Returns int32
    (left_indices, right_indices) in oracle order."""
    nl = len(lwords[0]) if lwords else 0
    nr = len(rwords[0]) if rwords else 0
    empty = (np.zeros(0, np.int32), np.zeros(0, np.int32))
    if nl == 0 or nr == 0 or not lwords:
        return empty

    ridx = None
    if not rvalid.all():
        ridx = np.nonzero(rvalid)[0].astype(np.int32)
        rwords = [w[ridx] for w in rwords]
        nr = len(ridx)
        if nr == 0:
            return empty
    lmask = None if lvalid.all() else lvalid

    # ---- perfect-hash fast path: one integer word, small value span
    if len(lwords) == 1:
        lo = int(rwords[0].min())
        hi = int(rwords[0].max())
        span = hi - lo + 1
        if span <= min(DIRECT_MAX_SLOTS,
                       max(1 << 16, DIRECT_SPAN_FACTOR * (nl + nr))):
            lk = lwords[0]
            rk0 = rwords[0] - lo if lo else rwords[0]
            bcount = np.bincount(rk0, minlength=span)
            if int(bcount.max()) <= 1:
                # unique build keys (the PK-FK join): the probe is ONE
                # gather through a dense lookup — no run expansion, no
                # sort, the fewest full-size passes this box's memory
                # bus allows
                lookup = np.full(span, -1, np.int32)
                lookup[rk0] = np.arange(nr, dtype=np.int32)
                if int(lk.min()) >= lo and int(lk.max()) <= hi:
                    cand = lookup[lk - lo if lo else lk]
                else:
                    inr = (lk >= lo) & (lk <= hi)
                    cand = lookup[np.where(inr, lk - lo, 0)]
                    cand = np.where(inr, cand, np.int32(-1))
                ok = cand >= 0
                if lmask is not None:
                    ok &= lmask
                if ok.all():
                    left_out = np.arange(nl, dtype=np.int32)
                    right_out = cand
                else:
                    left_out = np.nonzero(ok)[0].astype(np.int32,
                                                        copy=False)
                    right_out = cand[left_out]
                if ridx is not None:
                    right_out = ridx[right_out]
                return left_out, right_out
            inr = (lk >= lo) & (lk <= hi)
            if lmask is not None:
                inr &= lmask
            lslot = np.where(inr, lk - lo, 0)
            left_out, cand = _host_join_from_slots(
                lslot, rk0, span, inr, None, rcounts=bcount)
            right_out = cand if ridx is None else ridx[cand]
            return (left_out.astype(np.int32, copy=False),
                    right_out.astype(np.int32, copy=False))

    # ---- general path: xxhash64 bucket table + exact verify
    lh = np.asarray(key_hashes([jnp.asarray(w) for w in lwords])) \
        .view(np.uint64)
    rh = np.asarray(key_hashes([jnp.asarray(w) for w in rwords])) \
        .view(np.uint64)
    m = 1 << min(max(4, int(nr - 1).bit_length() + 2), 26)
    mask = np.uint64(m - 1)
    lslot = (lh & mask).astype(np.int64)
    rslot = (rh & mask).astype(np.int64)

    def verify(left_out, cand):
        eq = np.ones(len(left_out), bool)
        for lw, rw in zip(lwords, rwords):
            eq &= lw[left_out] == rw[cand]
        return eq

    left_out, cand = _host_join_from_slots(lslot, rslot, m, lmask,
                                           verify)
    right_out = cand if ridx is None else ridx[cand]
    return (left_out.astype(np.int32, copy=False),
            right_out.astype(np.int32, copy=False))


# ---------------------------------------------------------- device engine

@functools.lru_cache(maxsize=64)
def _device_step_factory(k: int, nlb: int, nrb: int, digest: str):
    """Capacity-parameterized factory for the fused hash-join program,
    memoized so repeated same-shape joins present the SAME factory
    object to with_capacity_retry (one jit-cache owner, steady-state
    cache hits)."""
    from spark_rapids_tpu.perf.jit_cache import CACHE, pad_axis0

    def make_step(capacity: int):
        def program(lh, rh, lv, rv, *words):
            from spark_rapids_tpu.ops.device_join import \
                inner_join_device
            lws, rws = words[:k], words[k:]
            pairs = inner_join_device(lh, rh, capacity, lv, rv)
            eq = pairs.valid
            for i in range(k):
                eq = eq & (lws[i][pairs.left_indices]
                           == rws[i][pairs.right_indices])
            overflow = pairs.total > capacity
            return (pairs.left_indices, pairs.right_indices, eq,
                    pairs.total, overflow)

        program_jit = jax.jit(program)   # cache-disabled fallback

        def run(lh, rh, lv, rv, lwords, rwords):
            # pad fresh per attempt: donated buffers must be throwaway
            # (a doubled-capacity retry re-reads the same logical args)
            args = (pad_axis0(lh, nlb), pad_axis0(rh, nrb),
                    pad_axis0(lv, nlb), pad_axis0(rv, nrb),
                    *[pad_axis0(w, nlb) for w in lwords],
                    *[pad_axis0(w, nrb) for w in rwords])
            if not CACHE.enabled():
                return program_jit(*args)
            return CACHE.cached_call(
                "join.hash_pairs",
                f"{digest}|k{k}|r{nrb}|c{capacity}", program, args,
                bucket=nlb,
                donate_argnums=tuple(range(len(args))))

        return run

    return make_step


# pair-capacity memo per (digest, bucket) shape: a steady workload
# whose joins fan out (dup keys, null-equal clusters) must not re-learn
# the budget by doubling from scratch on every batch
_LEARNED_CAPACITY: dict = {}


def device_hash_join(lwords, rwords, lvalid, rvalid, digest_extra: str,
                     initial_capacity: Optional[int] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Device-resident hash join: xxhash64 ids + fixed-capacity pair
    expansion (ops/device_join) + fused equality verify, AOT through
    the jit cache, capacity learned by the exchange retry driver.
    Returns int32 (left_indices, right_indices) in oracle order."""
    from spark_rapids_tpu.parallel.exchange import with_capacity_retry
    from spark_rapids_tpu.perf.jit_cache import bucket_rows

    nl = int(lwords[0].shape[0]) if lwords else 0
    nr = int(rwords[0].shape[0]) if rwords else 0
    if nl == 0 or nr == 0 or not lwords:
        return (jnp.zeros(0, _I32), jnp.zeros(0, _I32))
    lh = key_hashes(lwords)
    rh = key_hashes(rwords)
    nlb, nrb = bucket_rows(nl), bucket_rows(nr)
    k = len(lwords)
    cap_key = (digest_extra, k, nlb, nrb)
    cap0 = (int(initial_capacity) if initial_capacity
            else max(1 << max(4, nl.bit_length()),
                     _LEARNED_CAPACITY.get(cap_key, 0)))
    make_step = _device_step_factory(k, nlb, nrb, digest_extra)
    run = with_capacity_retry(make_step, cap0, overflow_index=-1,
                              max_doublings=20)
    (li, ri, eq, total, _of), cap_used = run(
        lh, rh, lvalid.astype(jnp.bool_), rvalid.astype(jnp.bool_),
        [w.astype(_I64) for w in lwords],
        [w.astype(_I64) for w in rwords])
    if len(_LEARNED_CAPACITY) > 256:     # bounded memo
        _LEARNED_CAPACITY.clear()
    _LEARNED_CAPACITY[cap_key] = int(cap_used)
    # eager compaction: collisions are ~never, so eq usually equals the
    # valid prefix and the nonzero is one pass over a bitmask
    eqn = np.asarray(eq)
    tot = int(total)
    if tot and bool(eqn[:tot].all()):
        return li[:tot], ri[:tot]
    keep = np.nonzero(eqn)[0]
    return (jnp.asarray(np.asarray(li)[keep]),
            jnp.asarray(np.asarray(ri)[keep]))
