"""Plain numpy reference for the catalog's ``tpcds_q5_channels``: TPC-DS
query template 5 over one database made from a seed.  Imports nothing
of the program.

The database is the catalog's (a copy of the draw order
``models/tpcds.py:gen_q5_db`` documents): date_dim one row a day from
1900-01-02, each outlet dim's business ids drawn as a permutation (two
surrogate keys an id for store and web_site), then the six facts
column by column.  The query is the template's equations:

* ssr / csr / wsr: a channel's sales UNION ALL its returns, joined to
  date_dim on the date key with ``d_date BETWEEN SALES_DATE AND
  SALES_DATE + 14 days`` and to the outlet dim on its key, grouped by
  the outlet's business id; web returns take the site of their sale
  (``web_returns LEFT OUTER JOIN web_sales ON (item, order)``, whose
  unmatched rows the join to web_site drops);
* the three channels UNION ALL as (channel, id, sales, returns,
  profit - loss), ``GROUP BY ROLLUP(channel, id)``, ``ORDER BY channel,
  id`` NULLS FIRST, ``LIMIT _LIMIT``.

_LIMIT is the template's 100 unless the traffic's ``params`` give a
``limit``.  The cell's traffic gives one past the rollup's 12,076 rows
at SF10, so ``compare`` sees every channel's every id: under LIMIT 100
the rows are the grand total, the catalog subtotal and 98 catalog
pages, and the store and web channels reach the comparison only
through the grand total's sums, which a return paired with the wrong
sale leaves as they are.

Ids are the business ids' dictionary ids, which sort as the id strings
do (the program serves them so); channels are their names.  Sums are
int64.  The one database built is held, so the check after a window
builds it once for all its substitutions.
"""

import datetime

import numpy as np

LIMIT = 100
LIMITS = {"values_differing": 0}
CHANNELS = ("catalog channel", "store channel", "web channel")
NULL_CODE, DEAD_CODE = -1, 2 ** 31 - 1
TABLES = ("store_sales", "store_returns", "catalog_sales",
          "catalog_returns", "web_sales", "web_returns", "date_dim",
          "store", "catalog_page", "web_site", "item")
FIRST_DATE, FIRST_DATE_SK = datetime.date(1900, 1, 2), 2_415_022
SALE_FIRST, SALE_LAST = datetime.date(1998, 1, 2), datetime.date(2002, 12, 31)
RETURN_LAG = (1, 91)
WEB_LINES = 12
WINDOW_DAYS = 15

_HELD = {}


def query_params(sizes, params, data_seed):
    """SALES_DATE from the query's seed: a day of August 1-30 in
    1998-2002 (the template's substitution as remembered; qualification
    2000-08-23); the database is the configuration's."""
    rng = np.random.default_rng(data_seed)
    year = 1998 + int(rng.integers(0, 5))
    day = 1 + int(rng.integers(0, 30))
    return {"sales_date": "%d-08-%02d" % (year, day),
            "db_seed": int(sizes["db_seed"]),
            "sizes": {t: int(sizes[t]) for t in TABLES}, **params}


def _sk(day):
    return FIRST_DATE_SK + (day - FIRST_DATE).days


def database(sizes, seed):
    """The database of ``sizes`` and ``seed``, built once and held."""
    key = (tuple(sorted(sizes.items())), int(seed))
    if key not in _HELD:
        _HELD.clear()
        _HELD[key] = _build(sizes, seed)
    return _HELD[key]


def _build(sizes, seed):
    rng = np.random.default_rng(seed)
    i32, i64 = np.int32, np.int64
    n_dates = sizes["date_dim"]
    db = {"date_dim": (
        np.arange(FIRST_DATE_SK, FIRST_DATE_SK + n_dates, dtype=i64),
        np.datetime64(FIRST_DATE) + np.arange(n_dates))}
    for dim, per in (("store", 2), ("catalog_page", 1), ("web_site", 2)):
        n = sizes[dim]
        ids = rng.permutation(-(-n // per)).astype(i32)
        db[dim] = (np.arange(1, n + 1), ids[np.arange(n) // per])
    sale = (_sk(SALE_FIRST), _sk(SALE_LAST) + 1)

    def sales(n, outlets):
        return (rng.integers(*sale, n, dtype=i32),
                rng.integers(1, outlets + 1, n, dtype=i32),
                rng.integers(0, 10_000_000, n, dtype=i64),
                rng.integers(-5_000_000, 5_000_000, n, dtype=i64))

    def returns(n, outlets):
        date = rng.integers(*sale, n, dtype=i32)
        date = date + rng.integers(*RETURN_LAG, n, dtype=i32)
        return (date, rng.integers(1, outlets + 1, n, dtype=i32),
                rng.integers(0, 10_000_000, n, dtype=i64),
                rng.integers(0, 5_000_000, n, dtype=i64))

    db["store_sales"] = sales(sizes["store_sales"], sizes["store"])
    db["store_returns"] = returns(sizes["store_returns"], sizes["store"])
    db["catalog_sales"] = sales(sizes["catalog_sales"],
                                sizes["catalog_page"])
    db["catalog_returns"] = returns(sizes["catalog_returns"],
                                    sizes["catalog_page"])
    n_ws, items = sizes["web_sales"], sizes["item"]
    date, site, price, profit = sales(n_ws, sizes["web_site"])
    first = rng.integers(0, items, -(-n_ws // WEB_LINES), dtype=i64)
    row = np.arange(n_ws)
    item = (first[row // WEB_LINES] + row % WEB_LINES) % items + 1
    order = row // WEB_LINES + 1
    db["web_sales"] = (date, site, price, profit, item, order)
    n_wr = sizes["web_returns"]
    pick = rng.choice(n_ws, n_wr, replace=False)
    wr_date = date[pick] + rng.integers(*RETURN_LAG, n_wr, dtype=i32)
    db["web_returns"] = (wr_date, item[pick], order[pick],
                         rng.integers(0, 10_000_000, n_wr, dtype=i64),
                         rng.integers(0, 5_000_000, n_wr, dtype=i64))
    return db


def make_inputs(sizes, params, data_seed):
    q = query_params(sizes, params, data_seed)
    return {"db": database(q["sizes"], q["db_seed"]),
            "sales_date": q["sales_date"],
            "limit": int(q.get("limit", LIMIT))}


def _join_dim(outlet, dim):
    """``outlet = dim key``: the rows that find their outlet, and its
    business id."""
    keys, ids = dim
    order = np.argsort(keys)
    at = np.clip(np.searchsorted(keys[order], outlet), 0, len(keys) - 1)
    found = keys[order][at] == outlet
    return found, ids[order][at]


def _web_sale_of(returns, sales):
    """``wr LEFT OUTER JOIN ws ON (item, order)``: per return, whether
    its sale is there and the sale's row."""
    ws_key = sales[5].astype(np.int64) * 2 ** 32 + sales[4]
    wr_key = returns[2].astype(np.int64) * 2 ** 32 + returns[1]
    order = np.argsort(ws_key, kind="stable")
    at = np.clip(np.searchsorted(ws_key[order], wr_key), 0,
                 len(ws_key) - 1)
    return ws_key[order][at] == wr_key, order[at]


def _answer(inputs, acc, amounts=lambda a: a):
    """The query, sums accumulated in ``acc`` after ``amounts`` is done
    to each amount."""
    db = inputs["db"]
    lo = np.datetime64(inputs["sales_date"])
    d_sk, d_date = db["date_dim"]
    window = d_sk[(d_date >= lo)
                  & (d_date <= lo + np.timedelta64(WINDOW_DAYS - 1, "D"))]
    out = []
    for name, (sold, ret, dim) in zip(CHANNELS, (
            ("catalog_sales", "catalog_returns", "catalog_page"),
            ("store_sales", "store_returns", "store"),
            ("web_sales", "web_returns", "web_site"))):
        n_ids = int(db[dim][1].max()) + 1
        sums = np.zeros((3, n_ids), acc)       # sales, returns, profit
        rows = np.zeros(n_ids, np.int64)
        s, r = ([c[np.isin(fact[0], window)] for c in fact]
                for fact in (db[sold], db[ret]))
        if ret == "web_returns":
            matched, sale = _web_sale_of(r, db[sold])
            r = [c[matched] for c in r]
            r_cols = (db[sold][1][sale[matched]], r[3], r[4])
        else:
            r_cols = (r[1], r[2], r[3])
        for (outlet, amt_a, amt_b), value_rows, sign in (
                ((s[1], s[2], s[3]), (0, 2), 1), (r_cols, (1, 2), -1)):
            found, ids = _join_dim(outlet, db[dim])
            gid = ids[found]
            np.add.at(sums[value_rows[0]], gid,
                      amounts(amt_a[found]).astype(acc))
            np.add.at(sums[value_rows[1]], gid,
                      (sign * amounts(amt_b[found])).astype(acc))
            rows += np.bincount(gid, minlength=n_ids)
        live = [i for i in range(n_ids) if rows[i] > 0]
        out += [(name, i, sums[0][i], sums[1][i], sums[2][i])
                for i in live]
        if live:
            out.append((name, None) + tuple(
                sums[k][live].sum(dtype=acc) for k in range(3)))
    if out:
        by_id = [o for o in out if o[1] is not None]
        out.append((None, None) + tuple(
            np.sum([o[k] for o in by_id], dtype=acc) for k in (2, 3, 4)))
    out.sort(key=lambda o: (o[0] is not None, o[0] or "",
                            o[1] is not None, o[1] or 0))
    return {"rows": [[o[0], o[1]] + [v.item() for v in o[2:]]
                     for o in out[:inputs["limit"]]]}


def answer(inputs, params):
    return _answer(inputs, np.int64)


def _to_bfloat16(a):
    """Round to nearest even onto bfloat16's eight bits of mantissa."""
    bits = a.astype(np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32)


def control_answer(inputs, params):
    """The guarantee "exact int64 sums" broken the way that tempts on
    this chip: each amount rounded to bfloat16 and summed in float32
    (the segment sum as a one-hot product at default precision).  A
    store id sums some 4.6 k rows of up to 10^7 cents, far past
    2^24."""
    return _answer(inputs, np.float32, _to_bfloat16)


def from_served(result):
    """The served rows in the reference's form: channel codes as their
    names, NULL_CODE as None, slots past the live rows dropped."""
    out = []
    for ch, i, sales, rets, profit in result:
        if ch == DEAD_CODE:
            continue
        out.append([None if ch == NULL_CODE else CHANNELS[ch],
                    None if i == NULL_CODE else i, sales, rets, profit])
    return {"rows": out}


def compare(got, want):
    g, w = got["rows"], want["rows"]
    bad = abs(len(g) - len(w)) * 5
    for a, b in zip(g, w):
        bad += sum(1 for x, y in zip(a, b) if x != y) + abs(len(a) - len(b))
    return {"values_differing": bad}


def min_bytes(sizes, params):
    """Bytes one query must read: date key, outlet key and two int64
    amounts of every fact row but web_returns' (which holds no outlet:
    its site comes from the sale), item and order number of every
    web_sales and web_returns row.  The dims are kilobytes."""
    facts = ("store_sales", "store_returns", "catalog_sales",
             "catalog_returns", "web_sales")
    return (24 * sum(int(sizes[t]) for t in facts)
            + 28 * int(sizes["web_returns"])
            + 8 * int(sizes["web_sales"]))
