"""Plain numpy reference for the catalog's ``tpcds_q9``: five quantity
buckets, each a count and two float64 averages of exact int64 sums.
Imports nothing of the program."""

import math

import numpy as np

BUCKETS = ((1, 20), (21, 40), (41, 60), (61, 80), (81, 100))
# counts exact; the averages are float64 divides (see PERF.md for the
# readings this limit stands between)
LIMITS = {"counts_differing": 0, "averages_not_finite": 0,
          "avg_rel_err": 1e-10}


def query_params(sizes, params, data_seed):
    return {"rows": int(sizes["rows"]), "seed": int(data_seed), **params}


def make_inputs(sizes, params, data_seed):
    rows = sizes["rows"]
    rng = np.random.default_rng(data_seed)
    return dict(
        quantity=rng.integers(1, 101, rows).astype(np.int32),
        price=rng.integers(100, 30_000, rows).astype(np.int64),
        profit=rng.integers(-5_000, 20_000, rows).astype(np.int64),
    )


def _answer(d, acc, div):
    out = []
    for lo, hi in BUCKETS:
        m = (d["quantity"] >= lo) & (d["quantity"] <= hi)
        c = int(m.sum())
        sp = d["price"][m].astype(acc).sum(dtype=acc)
        sn = d["profit"][m].astype(acc).sum(dtype=acc)
        out.append([c, float(div(sp) / div(max(c, 1))),
                    float(div(sn) / div(max(c, 1)))])
    return {"rows": out}


def answer(inputs, params):
    return _answer(inputs, np.int64, np.float64)


def control_answer(inputs, params):
    """The guarantee broken: float32 averages in place of float64."""
    return _answer(inputs, np.int64, np.float32)


def from_served(result):
    return {"rows": [list(r) for r in result]}


def compare(got, want):
    g, w = got["rows"], want["rows"]
    bad = abs(len(g) - len(w))
    err, not_finite = 0.0, 0
    for a, b in zip(g, w):
        if len(a) != 3:
            bad += 1
            continue
        bad += int(a[0] != b[0])
        for x, y in zip(a[1:], b[1:]):
            if not math.isfinite(x):     # max() would drop a NaN
                not_finite += 1
                continue
            err = max(err, abs(x - y) / max(abs(y), 1e-300))
    return {"counts_differing": bad, "averages_not_finite": not_finite,
            "avg_rel_err": err}


def min_bytes(sizes, params):
    """quantity i32 + price i64 + profit i64 per fact row."""
    return int(sizes["rows"]) * (4 + 8 + 8)
