"""Chip sweep behind ``ops/segment_sum.DENSE_MAX_SEGMENTS``: the dense
one-hot segment sum against the scatter-add over ``--rows`` int64 rows,
for each ``num_segments``; every dense answer is compared with the
scatter's.  Prints one JSON line per reading and writes them to
``chiprun_out/segment_sum_sweep.jsonl``.  Needs the chip:

    python scripts/segment_sum_sweep.py [--rows N]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _time(fn, *args, reps=3):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        walls.append(time.perf_counter() - t0)
    return out, first, sorted(walls)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 25)
    ap.add_argument("--segments", type=int, nargs="*",
                    default=[8, 2000, 1 << 14, 1 << 16, 1 << 18])
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import spark_rapids_tpu  # noqa: F401  (x64 on)
    from spark_rapids_tpu.ops import segment_sum as ss

    dev = jax.devices()[0]
    lines = []

    def emit(**kw):
        kw.update(platform=dev.platform, device_kind=dev.device_kind,
                  rows=args.rows)
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    rng = np.random.default_rng(28)
    price = jnp.asarray(rng.integers(-(1 << 40), 1 << 40, args.rows))
    keep = jnp.asarray(rng.integers(0, 2, args.rows).astype(bool))
    for S in args.segments:
        ids = jnp.asarray(rng.integers(-1, S + 1, args.rows)
                          .astype(np.int32))
        scatter = jax.jit(lambda v, i, S=S: jax.ops.segment_sum(
            v, i, num_segments=S))
        want, first, walls = _time(scatter, price, ids, reps=2)
        emit(engine="scatter", dtype="int64", num_segments=S,
             first_s=first, walls_s=walls)
        cases = [("int64", price, want)]
        if S == 2000:
            cases.append(("bool", keep, jax.block_until_ready(
                scatter(keep.astype(jnp.int64), ids))))
        for name, v, ref in cases:
            dense = jax.jit(lambda v, i, S=S: ss._dense(v, i, S))
            got, first, walls = _time(dense, v, ids)
            emit(engine="dense", dtype=name, num_segments=S,
                 lo_n=1 << ss._split(S, 1 if name == "bool" else 8)[1],
                 first_s=first, walls_s=walls,
                 equal=bool(jnp.array_equal(got, ref)))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/segment_sum_sweep.jsonl", "w") as f:
        for ln in lines:
            f.write(json.dumps(ln) + "\n")
    return 0 if all(ln.get("equal", True) for ln in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
