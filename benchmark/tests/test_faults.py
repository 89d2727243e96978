"""A whole run with the timed path broken underneath reads
``correct: false``; the same run unbroken reads true.  CPU rehearsal at
toy size (JAX_PLATFORMS=cpu), which is the one way past the harness's
look for a chip.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import argparse
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import run as harness  # noqa: E402

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS") != "cpu",
    reason="a rehearsal: set JAX_PLATFORMS=cpu")


def drive(workload, trace=0):
    """One whole run at toy size.  A cell that BENCHMARK.json does not
    hold is driven from its manifest entries in ``pending/``
    (``run.py:with_pending``)."""
    args = argparse.Namespace(workload=workload, seed=2_147_483_659,
                              seconds=0.3, trace=trace, size="toy")
    code, result = harness.run_cell(args)
    assert code == 0
    return result


# ---- the served path: faults planted in the catalog runner


def answer_altered(monkeypatch):
    import spark_rapids_tpu.models as models
    real = models._rows

    def rows(*arrays):
        out = real(*arrays)
        if out and len(out[0]) == 3:
            out[0][2] += 1            # one sum, off by one
        return out
    monkeypatch.setattr(models, "_rows", rows)


def half_left_out(monkeypatch):
    from spark_rapids_tpu.models import tpcds
    real = tpcds.gen_q3

    def gen(**kw):
        d = real(**kw)
        half = kw["rows"] // 2
        return d._replace(s_date=d.s_date[:half], s_item=d.s_item[:half],
                          s_price=d.s_price[:half])
    monkeypatch.setattr(tpcds, "gen_q3", gen)


def answer_never_comes(monkeypatch):
    import spark_rapids_tpu.models as models
    real, calls = models._rows, []

    def rows(*arrays):
        calls.append(1)
        if len(calls) > 1:            # the warm-up query still answers
            raise RuntimeError("planted")
        return real(*arrays)
    monkeypatch.setattr(models, "_rows", rows)


def average_not_a_number(monkeypatch):
    import spark_rapids_tpu.models as models
    real = models._rows

    def rows(*arrays):
        out = real(*arrays)
        if out and len(out[0]) == 3 and isinstance(out[0][1], float):
            out[0][1] = float("nan")  # a broken f64 divide
        return out
    monkeypatch.setattr(models, "_rows", rows)


Q3_CELLS = ["sf10-q3-streams4", "sf10-q3-handfused-streams4",
            "sf10-q3-streams2", "sf10-q3-handfused-streams2"]


@pytest.mark.parametrize("cell", Q3_CELLS)
@pytest.mark.parametrize("fault", [answer_altered, half_left_out])
def test_served_fault_reads_false(monkeypatch, fault, cell):
    fault(monkeypatch)
    result = drive(cell)
    assert result["correct"] is False
    assert result["compared"]["values_differing"]["value"] > 0


@pytest.mark.parametrize("cell", Q3_CELLS)
def test_served_answer_that_never_comes_reads_false(monkeypatch, cell):
    answer_never_comes(monkeypatch)
    result = drive(cell)
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] > 0
    assert result["compared"]["answers_missing"]["value"] > 0


@pytest.mark.parametrize("cell", Q3_CELLS)
def test_served_sound_reads_true(cell):
    result = drive(cell, trace=1)
    assert result["correct"] is True
    assert result["compared"]["answers_compared"] == result["attempted"]
    assert {"query_tail_ms", "host_ingest_ms", "window_compiles"} <= set(
        result["metrics"])


def test_q9_average_that_is_no_number_reads_false(monkeypatch):
    average_not_a_number(monkeypatch)
    result = drive("sf10-q9-streams2")
    assert result["correct"] is False
    assert result["compared"]["averages_not_finite"]["value"] > 0


def test_q9_sound_reads_true():
    result = drive("sf10-q9-streams2")
    assert result["correct"] is True
    assert {"rows_per_s.hostgen", "query_ms.p50.hostgen"} <= set(
        result["metrics"])


# ---- row conversion: faults planted in the conversion itself


def to_rows_altered(monkeypatch):
    from spark_rapids_tpu.ops import row_conversion as RC
    real = RC._to_rows_fixed_cached

    def altered(*a, **kw):
        data = real(*a, **kw)
        return data.at[0].set(data[0] ^ 1)      # one bit of one row
    monkeypatch.setattr(RC, "_to_rows_fixed_cached", altered)


def from_rows_altered(monkeypatch):
    from spark_rapids_tpu.ops import row_conversion as RC
    real = RC.convert_from_rows

    def altered(list_col, schema):
        table = real(list_col, schema)
        c = table.columns[1]
        c.data = c.data.at[0].add(1)            # one value of one column
        return table
    monkeypatch.setattr(RC, "convert_from_rows", altered)


def half_rows_left_out(monkeypatch):
    from spark_rapids_tpu.columns.table import Table
    from spark_rapids_tpu.ops import row_conversion as RC
    real = RC.convert_from_rows

    def half(list_col, schema):
        table = real(list_col, schema)
        n = table.num_rows // 2
        return Table([type(c)(c.dtype, n, data=c.data[:n], validity=None)
                      for c in table.columns])
    monkeypatch.setattr(RC, "convert_from_rows", half)


@pytest.mark.parametrize("fault,number", [
    (to_rows_altered, "row_bytes_differing"),
    (from_rows_altered, "column_bytes_differing"),
    (half_rows_left_out, "column_bytes_differing")])
def test_rowconv_fault_reads_false(monkeypatch, fault, number):
    fault(monkeypatch)
    result = drive("rowconv-212x512k-roundtrip")
    assert result["correct"] is False
    assert result["compared"][number]["value"] > 0


def test_rowconv_sound_reads_true():
    result = drive("rowconv-212x512k-roundtrip", trace=1)
    assert result["correct"] is True
    assert {"to_rows_ms", "from_rows_ms"} <= set(result["metrics"])
