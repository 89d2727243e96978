"""Chip sweep of the device join probe (``ops/device_join.py``): the
sort-merge ``inner_join_device`` against the binary-search form it
replaced (three ``jnp.searchsorted`` scans, kept here as ``search_join``
for the comparison), at the probe shapes of the shipped plans and at
small left sides into 2^23 right rows, where the search's
n_l * log2(n_r) dependent gathers undercut two sorts of n_l + n_r.
PERF.md, section 5 and Open questions, hold its table.

Per shape, the median over ``--reps`` jitted calls, each ended by
``block_until_ready``, of ``search`` (the old form), ``merge`` (the
probe) and ``merge_bounds`` (its run bounds alone, ``merge_run_bounds``;
the rest of ``merge`` is the slot map and the pair gathers); both
forms' four outputs are compared on the device and must be identical.
Prints one JSON line per reading and writes them to
``chiprun_out/join_probe_sweep.jsonl``.  Needs the chip:

    python scripts/join_probe_sweep.py [--shapes NAME ...] [--reps N]

Rehearse on the CPU at toy size with ``--scale`` (every row count
divided by it): ``JAX_PLATFORMS=cpu python scripts/join_probe_sweep.py
--scale 256 --reps 2``.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

import spark_rapids_tpu  # noqa: E402,F401  (x64 on)
from spark_rapids_tpu.ops.device_join import (  # noqa: E402
    JoinPairs, inner_join_device, merge_run_bounds)

# name -> (left rows, valid left rows, right rows, valid right rows,
#          key kind, capacity).  "unique": the right keys are distinct
# 37-bit (order, item) keys and each valid left key is a distinct one
# of them (q5's web returns into web_sales); "dates": q5's store
# channel, a fact's date keys over 60 days into a 14-day date window;
# "dup": q72's catalog_sales into inventory on 16,384 items
SHAPES = {
    "q5_web_2e20x2e23": (1 << 20, 719_217, 1 << 23, 7_197_566,
                         "unique", 1 << 20),
    "q5_store_2e25x14": (1 << 25, 28_800_991, 14, 14, "dates", 1 << 23),
    "q72_250kx250k": (250_000, 250_000, 250_000, 250_000, "dup",
                      1 << 22),
    "left_2e10x2e23": (1 << 10, 1 << 10, 1 << 23, 7_197_566, "unique",
                       1 << 10),
    "left_2e13x2e23": (1 << 13, 1 << 13, 1 << 23, 7_197_566, "unique",
                       1 << 13),
    "left_2e16x2e23": (1 << 16, 1 << 16, 1 << 23, 7_197_566, "unique",
                       1 << 16),
}


def search_join(left_keys, right_keys, capacity, left_valid, right_valid):
    """The binary-search probe as it stood before the merge form: the
    right side sorted, ``lo``/``hi`` by two searchsorteds, the slot map
    by a third over the offsets."""
    nl, nr = left_keys.shape[0], right_keys.shape[0]
    lk = left_keys.astype(jnp.int64)
    r_sortkey = jnp.where(right_valid, right_keys.astype(jnp.int64),
                          jnp.int64(2**63 - 1))
    _, rk_sorted, r_order = lax.sort(
        ((~right_valid).astype(jnp.int32), r_sortkey,
         lax.iota(jnp.int32, nr)), num_keys=3)
    n_valid_r = jnp.sum(right_valid.astype(jnp.int32))
    lo = jnp.minimum(jnp.searchsorted(rk_sorted, lk, side="left"),
                     n_valid_r)
    hi = jnp.minimum(jnp.searchsorted(rk_sorted, lk, side="right"),
                     n_valid_r)
    counts = jnp.where(left_valid, hi - lo, 0).astype(jnp.int64)
    offs = jnp.cumsum(counts) - counts
    total = offs[-1] + counts[-1]
    j = jnp.arange(capacity, dtype=jnp.int64)
    i = jnp.searchsorted(offs, j, side="right").astype(jnp.int32) - 1
    i = jnp.clip(i, 0, nl - 1)
    k = j - offs[i]
    valid = (j < total) & (k < counts[i])
    r_pos = jnp.clip(lo[i] + k, 0, nr - 1)
    right_idx = r_order[r_pos].astype(jnp.int32)
    return JoinPairs(jnp.where(valid, i, 0).astype(jnp.int32),
                     jnp.where(valid, right_idx, 0), valid, total)


def make_inputs(seed, nl, nl_valid, nr, nr_valid, kind):
    """Keys and validity masks, made on the device from ``seed``."""
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    lval = jnp.arange(nl) < nl_valid
    rval = jnp.arange(nr) < nr_valid
    if kind == "unique":
        # an odd multiplier is a bijection mod 2^37: distinct keys
        rk = (jnp.arange(nr, dtype=jnp.int64) * 0x9E3779B1) & ((1 << 37) - 1)
        pick = jax.random.permutation(ka, nr_valid)[:nl]
        lk = rk[pick]
    elif kind == "dates":
        lk = jax.random.randint(ka, (nl,), 11_000, 11_060, jnp.int64)
        rk = 11_040 + jnp.arange(nr, dtype=jnp.int64)
    else:
        lk = jax.random.randint(ka, (nl,), 0, 16_384, jnp.int64)
        rk = jax.random.randint(kb, (nr,), 0, 16_384, jnp.int64)
    lk = jnp.where(lval, lk, -1)            # the buckets' pad sentinels
    rk = jnp.where(rval, rk, -2)
    return lk, rk, lval, rval


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES),
                    choices=list(SHAPES))
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--scale", type=int, default=1)
    ap.add_argument("--seed", type=int, default=3900000017)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/join_probe_sweep.jsonl", "w")

    def emit(**kw):
        kw.update(platform=dev.platform, device_kind=dev.device_kind,
                  reps=args.reps, scale=args.scale)
        print(json.dumps(kw), flush=True)
        out.write(json.dumps(kw) + "\n")
        out.flush()

    for name in args.shapes:
        nl, nlv, nr, nrv, kind, cap = (
            max(1, v // args.scale) if isinstance(v, int) else v
            for v in SHAPES[name])
        lk, rk, lval, rval = make_inputs(args.seed, nl, nlv, nr, nrv, kind)
        forms = {
            "search": jax.jit(lambda a, b, c, d, cap=cap:
                              search_join(a, b, cap, c, d)),
            "merge": jax.jit(lambda a, b, c, d, cap=cap:
                             inner_join_device(a, b, cap, c, d)),
            "merge_bounds": jax.jit(lambda a, b, c, d:
                                    merge_run_bounds(a, b, d)),
        }
        results, reading = {}, {}
        for form, fn in forms.items():
            t0 = time.perf_counter()
            results[form] = jax.block_until_ready(fn(lk, rk, lval, rval))
            first = time.perf_counter() - t0
            times = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(lk, rk, lval, rval))
                times.append(time.perf_counter() - t0)
            reading[form + "_ms"] = statistics.median(times) * 1e3
            reading[form + "_first_s"] = first
        same = all(bool(jnp.array_equal(x, y)) for x, y in
                   zip(results["search"], results["merge"]))
        emit(shape=name, left_rows=nl, right_rows=nr, capacity=cap,
             total=int(results["merge"].total), identical=same,
             **{k: round(v, 4) for k, v in reading.items()})
        del results
        if not same:
            raise SystemExit(f"{name}: the two forms differ")
    out.close()


if __name__ == "__main__":
    main()
