#!/usr/bin/env python3
"""The control of a cell's comparison, at the cell's own size: the
reference put in the program's place and computed with one stated
guarantee broken (``control_answer`` of the cell's reference).  It has
to come out as not correct on every seed.  Host numpy only; the
benchmark's own runs never run it.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--size toy]
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402


def control_numbers(cell, data_seed):
    ref, params = cell.reference, cell.traffic["params"]
    inputs = ref.make_inputs(cell.sizes, params, data_seed)
    want = ref.answer(inputs, params)
    sound = ref.compare(ref.answer(inputs, params), want)
    broken = ref.compare(ref.control_answer(inputs, params), want)
    return sound, broken


def fails(numbers, limits):
    return [k for k, v in numbers.items() if v > limits[k]]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--size", choices=("full", "toy"), default="full")
    args = ap.parse_args(argv)
    manifest = harness.with_pending(
        harness.load_json(harness.ROOT, "BENCHMARK.json"), args.workload)
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.Cell(manifest, args.workload, seed, args.size)
        sound, broken = control_numbers(cell, seed % (2 ** 31 - 1))
        failed = fails(broken, cell.reference.LIMITS)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "reference_against_itself": sound,
                          "control": broken,
                          "limits": cell.reference.LIMITS,
                          "control_fails": failed}), flush=True)
        ok = ok and bool(failed) and not fails(sound, cell.reference.LIMITS)
    print("control comes out not correct on every seed: %s" % ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
