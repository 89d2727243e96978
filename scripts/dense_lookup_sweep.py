"""Chip sweep behind ``ops/dense_lookup.DENSE_MAX_TABLE_LIMBS``: the
dense one-hot lookup against ``table[idx]`` over ``--rows`` int32
indices, for each table length, one int32 table and two that share the
index; every dense answer is compared with the gather's (indices
outside the table among them).  ``--splits`` also times other
``lo_n`` than the code's own at the two q3 dims, and the item dim's
size tries
one stacked ``[n, 2]`` gather, for the issue that packs the tables
that stay on the gather.  Prints one JSON line per reading and writes
them to ``chiprun_out/dense_lookup_sweep.jsonl``.  Needs the chip:

    python scripts/dense_lookup_sweep.py [--rows N] [--splits]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from segment_sum_sweep import _time  # noqa: E402  (its sibling's clock)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 25)
    ap.add_argument("--lens", type=int, nargs="*",
                    default=[730, 1 << 12, 1 << 14, 1 << 16, 102_000,
                             1 << 18])
    ap.add_argument("--splits", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import spark_rapids_tpu  # noqa: F401  (x64 on)
    from spark_rapids_tpu.ops import dense_lookup as dl

    dev = jax.devices()[0]
    lines = []
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/dense_lookup_sweep.jsonl", "w")

    def emit(**kw):
        kw.update(platform=dev.platform, device_kind=dev.device_kind,
                  rows=args.rows)
        lines.append(kw)
        print(json.dumps(kw), flush=True)
        out.write(json.dumps(kw) + "\n")
        out.flush()

    rng = np.random.default_rng(33)
    for T in args.lens:
        idx = rng.integers(-1, T + 1, args.rows).astype(np.int32)
        edge = np.array([np.iinfo(np.int32).min, np.iinfo(np.int32).max,
                         -T, -T - 1, T, 2 * T], np.int32)
        idx[:min(edge.size, args.rows)] = edge[:args.rows]
        idx = jnp.asarray(idx)
        tables = tuple(jnp.asarray(rng.integers(
            -(1 << 31), 1 << 31, T).astype(np.int32)) for _ in range(2))
        for n_tables in (1, 2):
            tabs = tables[:n_tables]
            gather = jax.jit(lambda i, *ts: tuple(t[i] for t in ts))
            want, first, walls = _time(gather, idx, *tabs, reps=2)
            emit(engine="gather", table_len=T, n_tables=n_tables,
                 first_s=first, walls_s=walls)
            own = dl._split(T, 4 * n_tables)[1]
            tried = [own]
            if args.splits and T in (730, 102_000):
                tried += [b for b in range(1, 8)
                          if b != own and (T >> b) <= (1 << 14)]
            for lo_bits in tried:
                dense = jax.jit(lambda i, *ts, b=lo_bits: dl._dense(
                    list(ts), i, lo_bits=b))
                got, first, walls = _time(dense, idx, *tabs)
                emit(engine="dense", table_len=T, n_tables=n_tables,
                     table_limbs=T * 4 * n_tables, lo_n=1 << lo_bits,
                     own=lo_bits == own, first_s=first, walls_s=walls,
                     equal=all(bool(jnp.array_equal(g, w))
                               for g, w in zip(got, want)))
        if T != 102_000:
            continue
        # sizes the issue that packs the tables that stay on the
        # gather; at 2^25 rows the chip's compiler refuses it (the
        # [n, 2] result is laid out in (8, 128) tiles, 64x its size)
        stacked = jax.jit(lambda i, a, b: jnp.stack([a, b], axis=1)[i])
        try:
            got, first, walls = _time(stacked, idx, *tables, reps=2)
        except jax.errors.JaxRuntimeError as e:
            emit(engine="gather_stacked", table_len=T, n_tables=2,
                 error=str(e).splitlines()[0][:200])
            continue
        emit(engine="gather_stacked", table_len=T, n_tables=2,
             first_s=first, walls_s=walls,
             equal=all(bool(jnp.array_equal(got[:, k], w))
                       for k, w in enumerate(want)))
    return 0 if all(ln.get("equal", True) for ln in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
