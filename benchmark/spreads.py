#!/usr/bin/env python3
"""Runs of one cell, one after another, and the spreads a bound is set
from.  For a ``benchmark`` PR; the driver's check never calls it.

    python3 benchmark/spreads.py --workload <cell> --seeds 1,2,3 [--sets 2]
        [--seconds 51] [--trace-seed 4] [--out chiprun_out/<cell>.jsonl]

Every run is a process of its own (``run.py``; this parent never
touches JAX, so the chip is the child's).  ``--sets 2`` runs the seeds
twice over, as the check's two sets do.  Each result line goes to
``--out`` with its seed and set; the table at the end gives, per
end-to-end metric, the median and both spreads over the untraced runs
(PERF.md, section 2): ``iqr``, the interquartile distance over the
median (``statistics.quantiles(n=4)``); and ``range1``, the range with
the run farthest from the median left out, over the median (the
driver's); each taken per set, the wider one.  The rule gives the
bound: the larger of ``5 x iqr`` and ``2 x range1``, never under 0.01,
rounded up to two digits.  A run that fails or is not ``correct`` ends
the call.

    python3 benchmark/spreads.py --read chiprun_out/<cell>.jsonl
"""

import argparse
import json
import math
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def iqr_share(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def range1_share(values):
    med = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - med))[:-1]
    return (max(kept) - min(kept)) / med


def bound_by_rule(iqr, range1):
    return max(0.01, math.ceil(max(5 * iqr, 2 * range1) * 100 - 1e-9) / 100)


def table(lines):
    """{metric: {median, iqr, range1, bound, n}} over the untraced,
    warm lines (a set's spread is over that set's runs)."""
    runs = [r for r in lines if not r["trace"] and not r.get("cold")]
    out = {}
    for name in sorted({m for r in runs for m in r["metrics"]}):
        by_set = {}
        for r in runs:
            if name in r["metrics"]:
                by_set.setdefault(r["set"], []).append(
                    r["metrics"][name]["value"])
        every = [v for vs in by_set.values() for v in vs]
        if len(every) < 3:
            continue
        sets = [vs for vs in by_set.values() if len(vs) > 2] or [every]
        iqr = max(iqr_share(vs) for vs in sets)
        range1 = max(range1_share(vs) for vs in sets)
        out[name] = {"n": len(every), "median": statistics.median(every),
                     "set_medians": [statistics.median(vs)
                                     for vs in by_set.values()],
                     "iqr": iqr, "range1": range1,
                     "bound": bound_by_rule(iqr, range1)}
    return out


def one_run(args, seed, trace):
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           args.workload, "--seed", str(seed), "--seconds",
           str(args.seconds), "--trace", str(trace), "--size", args.size]
    done = subprocess.run(cmd, stdout=subprocess.PIPE, text=True)
    said = done.stdout.strip().splitlines() or [""]
    if done.returncode != 0 or not said[-1].startswith("{"):
        sys.exit("spreads.py: %s exited with %d" % (" ".join(cmd),
                                                    done.returncode))
    # run.py's earlier line on the window (each operation's wall) is
    # kept beside the result: it is where a far-off run shows its cause
    window = [x for x in said[:-1] if '"phase": "window"' in x]
    return dict(json.loads(said[-1]),
                window=json.loads(window[-1]) if window else None)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace-seed", type=int)
    ap.add_argument("--size", choices=("full", "toy"), default="full")
    ap.add_argument("--out")
    ap.add_argument("--read", help="only the table of a file of lines")
    args = ap.parse_args(argv)
    if args.read:
        with open(args.read) as f:
            lines = [json.loads(x) for x in f if x.strip()]
        print(json.dumps(table(lines), indent=1))
        return 0
    out = args.out or os.path.join("chiprun_out", args.workload + ".jsonl")
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    plan = [(int(s), 0, k) for k in range(args.sets)
            for s in args.seeds.split(",") if s]
    if args.trace_seed is not None:   # first, so that it is the run
        plan.insert(0, (args.trace_seed, 1, -1))    # that compiles
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(HERE), ".jax_cache")
    compiles = not (os.path.isdir(cache) and os.listdir(cache))
    lines = []
    for i, (seed, trace, k) in enumerate(plan):
        line = dict(one_run(args, seed, trace), seed=seed, trace=trace,
                    set=k, workload=args.workload, cold=(compiles and i == 0))
        lines.append(line)
        with open(out, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps({k: line[k] for k in (
            "seed", "set", "trace", "correct", "failed", "attempted",
            "metrics", "compared")}), flush=True)
        if not line["correct"]:
            sys.exit("spreads.py: seed %d is not correct" % seed)
    print(json.dumps(table(lines), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
