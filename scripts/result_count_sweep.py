"""Chip sweep of what one result buffer of an executable costs the host
(ISSUE 37; PERF.md, Findings, PR 37): one executable of the process
compile cache over a ``(words, rows // 128, 128)`` u32 operand, the
shape from-rows' ``extract`` reads at 212 columns x 2^20 rows, that
returns N slices of it, for each N of ``--results``.  Per N the median
over ``--reps`` calls of the host time from the call to its return (the
enqueue), of the time until ``block_until_ready`` returns, and of the
time to drop the results.  Kind ``row`` returns ``(rows,)`` u32
vectors (4 MiB each at the default size: the device has work a result),
kind ``tiny`` ``(128,)`` ones (the host's handling alone).  It is
what "stack the values" is sized from.  Prints one JSON line per
reading and writes them to ``chiprun_out/result_count_sweep.jsonl``.
Needs the chip:

    python scripts/result_count_sweep.py [--rows N] [--results N ...]
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 20)
    ap.add_argument("--words", type=int, default=274)
    ap.add_argument("--results", type=int, nargs="*",
                    default=[27, 53, 106, 212, 424, 848])
    ap.add_argument("--kinds", nargs="*", default=["row", "tiny"],
                    choices=["row", "tiny"])
    ap.add_argument("--reps", type=int, default=50)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import spark_rapids_tpu  # noqa: F401  (x64 on)
    from spark_rapids_tpu.perf import jit_cache as jc

    dev = jax.devices()[0]
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/result_count_sweep.jsonl", "w")

    def emit(**kw):
        kw.update(platform=dev.platform, device_kind=dev.device_kind,
                  rows=args.rows, words=args.words, reps=args.reps)
        print(json.dumps(kw), flush=True)
        out.write(json.dumps(kw) + "\n")
        out.flush()

    rows, words = args.rows, args.words
    blocks = jax.random.bits(jax.random.PRNGKey(37),
                             (words, rows // 128, 128), jnp.uint32)
    jax.block_until_ready(blocks)

    def med_ms(xs):
        return statistics.median(xs) * 1e3

    for kind in args.kinds:
        for n in args.results:
            def slices(b, n=n, kind=kind):
                # every result differs from every other: XLA merges none
                if kind == "tiny":
                    return tuple(b[i % words, i // words] for i in range(n))
                return tuple(b[i % words].reshape(rows)
                             >> jnp.uint32(i // words) for i in range(n))

            def call():
                return jc.CACHE.cached_call(
                    "result_count_sweep." + kind, str(n), slices,
                    (blocks,), bucket=rows)

            t0 = time.perf_counter()
            jax.block_until_ready(call())
            first = time.perf_counter() - t0
            host, ready, free = [], [], []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                res = call()
                t1 = time.perf_counter()
                jax.block_until_ready(res)
                t2 = time.perf_counter()
                del res
                t3 = time.perf_counter()
                host.append(t1 - t0)
                ready.append(t2 - t0)
                free.append(t3 - t2)
            emit(kind=kind, results=n, first_s=first,
                 host_ms=med_ms(host), ready_ms=med_ms(ready),
                 free_ms=med_ms(free),
                 host_us_per_result=med_ms(host) * 1e3 / n,
                 host_ms_min=min(host) * 1e3, host_ms_max=max(host) * 1e3)
    out.close()


if __name__ == "__main__":
    main()
