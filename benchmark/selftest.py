#!/usr/bin/env python3
"""Self-check of the yardstick's arithmetic: the trace reducer on a
hand-made interval list, the bytes functions against ISSUE 26's
figures, the order statistics.  No JAX, no program.

    python3 benchmark/selftest.py        (also collected by pytest)
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from lib import stats, trace as T  # noqa: E402
import run as harness  # noqa: E402

MS = 1_000_000


def test_union_and_gaps():
    merged = T.union([(0, 10), (5, 10), (30, 5), (32, 1), (50, 0)])
    assert merged == [(0, 15), (30, 35)]
    assert T.busy_seconds(merged) == 20 / 1e9
    assert T.gaps(merged, 0, 40) == [(15, 30), (35, 40)]
    assert T.clip(merged, 10, 32) == [(10, 15), (30, 32)]


def test_reduce_idle_share_and_labels():
    # one device: busy 0-200 ms and 600-1000 ms of a 0-1000 ms query,
    # then a second operation busy 1100-1200 ms of 1050-1250 ms
    dev = [("fusion.1", 0, 200 * MS), ("sort.2", 600 * MS, 400 * MS),
           ("fusion.1", 1100 * MS, 100 * MS),
           ("outside", 5000 * MS, 100 * MS)]
    spans = [("query:q3", 0, 1000 * MS), ("to_rows", 1050 * MS, 200 * MS)]
    r = T.reduce_trace([dev], spans)
    assert abs(r["window_s"] - 1.25) < 1e-12
    assert abs(r["busy_s"] - 0.7) < 1e-12
    assert r["device_ops"][:2] == [["sort.2", 0.4], ["fusion.1", 0.3]]
    assert all(name != "outside" for name, _s in r["device_ops"])
    assert r["idle_gaps"][0] == ["query:q3", 0.4]
    labels = {name for name, _s in r["idle_gaps"]}
    assert labels == {"query:q3", "to_rows"}
    assert T.label_gap((2000 * MS, 2100 * MS), spans) == T.BETWEEN
    idle_pct = 100 * (1 - r["busy_s"] / r["window_s"])
    assert abs(idle_pct - 44.0) < 1e-9


def test_reduce_averages_devices_and_handles_nothing():
    a = [("x", 0, 100 * MS)]
    b = [("x", 0, 50 * MS)]
    r = T.reduce_trace([a, b], [("op", 0, 100 * MS)])
    assert abs(r["busy_s"] - 0.075) < 1e-12 and r["devices"] == 2
    assert T.reduce_trace([], []) is None
    assert T.reduce_trace([[]], [("op", 0, 1)]) is None


def test_bytes_against_the_issue():
    q3 = harness.load("reference", "tpcds_q3")
    q9 = harness.load("reference", "tpcds_q9")
    rc = harness.load("reference", "jcudf_rows")
    sf10 = {"rows": 28_800_991}
    assert round(q3.min_bytes(sf10, {}) / 1e6, 1) == 460.8
    assert round(q9.min_bytes(sf10, {}) / 1e6, 1) == 576.0
    assert round(rc.min_bytes({"rows": 1 << 19}, {"columns": 212})
                 / 1e6) == 2156
    widths = [dt().itemsize for _k, dt in rc.kinds({"columns": 212})]
    assert sum(widths) == 960 and rc.layout(widths)[2] == 1096


def test_percentile():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile([5], 95) == 5
    assert stats.percentile([], 50) is None
    assert abs(stats.percentile(range(1, 11), 95) - 9.55) < 1e-12


if __name__ == "__main__":
    for name, fn in sorted(globals().items()):
        if name.startswith("test_"):
            fn()
            print("ok", name)
