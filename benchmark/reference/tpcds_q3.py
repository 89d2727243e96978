"""Plain numpy reference for the catalog's ``tpcds_q3_fused`` and its
hand-fused twin ``tpcds_q3``: the data a query's parameters define (a
copy of the draw order the catalog documents: date, item, price, then
the two dense dims) and the q3-shape answer over it.  The cardinalities
(fact rows, item rows, brand ids) are the configuration's ``sizes``;
the traffic's ``params`` hold only the query's own substitution
(``manufact``).  Imports nothing of the program."""

import numpy as np

# what the program's runner fixes (the configuration file names both as
# departures from its source): a two-year date dim, eight manufacturers
BASE, DAYS, YEARS, MONTH, MANUFACTURERS = 10_957, 730, 2, 11, 8
LIMIT = 100
SENTINEL32 = 2 ** 31 - 1
LIMITS = {"values_differing": 0}


def query_params(sizes, params, data_seed):
    return {"rows": int(sizes["rows"]), "items": int(sizes["items"]),
            "brands": int(sizes["brands"]), "seed": int(data_seed),
            **params}


def make_inputs(sizes, params, data_seed):
    rows, items, brands = sizes["rows"], sizes["items"], sizes["brands"]
    rng = np.random.default_rng(data_seed)
    day_idx = np.arange(DAYS)
    return dict(
        s_date=rng.integers(BASE, BASE + DAYS, rows).astype(np.int32),
        s_item=rng.integers(0, items, rows).astype(np.int32),
        s_price=rng.integers(100, 50_000, rows).astype(np.int64),
        d_moy=((day_idx // 30) % 12 + 1).astype(np.int32),
        d_year=(2000 + day_idx // 365).astype(np.int32),
        i_brand=rng.integers(0, brands, items).astype(np.int32),
        i_manufact=rng.integers(0, MANUFACTURERS, items).astype(np.int32),
        brands=brands,
    )


def _answer(d, params, acc, amounts=lambda a: a):
    """(year, brand) sums through the dense dims, ORDER BY year, sum
    DESC, brand LIMIT 100; then the count of rows kept.  ``acc`` is the
    type the sums are accumulated in, ``amounts`` what is done to each
    amount before it is added."""
    brands = d["brands"]
    di = d["s_date"] - BASE
    year_idx = d["d_year"][di] - d["d_year"][0]
    keep = ((d["d_moy"][di] == MONTH)
            & (d["i_manufact"][d["s_item"]] == params["manufact"])
            & (year_idx >= 0) & (year_idx < YEARS))
    gid = year_idx[keep] * brands + d["i_brand"][d["s_item"][keep]]
    n_groups = YEARS * brands
    sums = np.zeros(n_groups, acc)
    np.add.at(sums, gid, amounts(d["s_price"][keep]).astype(acc))
    cnts = np.bincount(gid, minlength=n_groups)
    y0 = int(d["d_year"][0])
    live = sorted((g // brands + y0, -int(sums[g]), g % brands)
                  for g in range(n_groups) if cnts[g] > 0)
    return {"rows": [[y, b, -negs] for y, negs, b in live[:LIMIT]],
            "total": int(cnts.sum())}


def answer(inputs, params):
    return _answer(inputs, params, np.int64)


def _to_bfloat16(a):
    """Round to nearest even onto bfloat16's eight bits of mantissa."""
    bits = a.astype(np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32)


def control_answer(inputs, params):
    """The guarantee "exact int64 sums" broken the way that tempts on
    this chip: the segment sum as a one-hot product on the matrix unit
    at default precision, amounts in bfloat16, sums in float32.
    (float32 sums of whole amounts alone stay exact at SF10's
    cardinalities: a group's sum is under 2^24.)"""
    return _answer(inputs, params, np.float32, _to_bfloat16)


def from_served(result):
    """The served rows in the reference's form: dead output slots (the
    year sentinel) are padding, the last row is the kept-row count."""
    body, tail = result[:-1], result[-1]
    return {"rows": [list(r) for r in body if r[0] != SENTINEL32],
            "total": tail[0] if len(tail) == 1 else None}


def compare(got, want):
    g, w = got["rows"], want["rows"]
    bad = abs(len(g) - len(w)) * 3
    for a, b in zip(g, w):
        bad += sum(1 for x, y in zip(a, b) if x != y) + abs(len(a) - len(b))
    bad += int(got["total"] != want["total"])
    return {"values_differing": bad}


def min_bytes(sizes, params):
    """Bytes one query must read: s_date i32 + s_item i32 + s_price
    i64 per fact row (the dims are kilobytes)."""
    return int(sizes["rows"]) * (4 + 4 + 8)
