"""ops/segment_sum (PR 28): the dense one-hot segment sum against
``np.add.at`` in the value's own width, the engine choice, and the
structural guard that the two q3 paths lower to products, not
scatters."""

import jax
import numpy as np
import pytest

from spark_rapids_tpu import observability as obs
from spark_rapids_tpu.models import tpcds
from spark_rapids_tpu.ops import segment_sum as ss
from spark_rapids_tpu.plan import catalog as C
from spark_rapids_tpu.plan import compiler as PC

BOUND = ss.DENSE_MAX_SEGMENTS
I64 = np.iinfo(np.int64)


def _oracle(values, ids, num_segments):
    """The wrapping scatter-add in numpy; booleans are counted."""
    values = np.asarray(values)
    ids = np.asarray(ids)
    if values.dtype == np.bool_:
        values = values.astype(np.int64)
    want = np.zeros(num_segments, values.dtype)
    ok = (ids >= 0) & (ids < num_segments)
    with np.errstate(over="ignore"):
        np.add.at(want, ids[ok], values[ok])
    return want


def _values(rng, dtype, n):
    if dtype == np.bool_:
        return rng.integers(0, 2, n).astype(bool)
    info = np.iinfo(dtype)
    # the whole range: negative values and sums that wrap
    return rng.integers(info.min, info.max, n, dtype=dtype,
                        endpoint=True)


def _check(values, ids, num_segments):
    got = jax.jit(ss.segment_sum, static_argnums=2)(
        values, ids, num_segments)
    want = _oracle(values, ids, num_segments)
    assert got.dtype == want.dtype
    assert np.asarray(got).tobytes() == want.tobytes()


@pytest.mark.parametrize("rows", [1, 65_535, 65_537, 200_001])
@pytest.mark.parametrize("dtype", [np.bool_, np.int32, np.int64])
def test_dense_matches_numpy_rows_not_a_multiple_of_the_chunk(
        dtype, rows):
    rng = np.random.default_rng(rows)
    _check(_values(rng, dtype, rows),
           rng.integers(0, 37, rows).astype(np.int32), 37)


@pytest.mark.parametrize("num_segments", [1, 8, 2_000, BOUND, BOUND + 1])
@pytest.mark.parametrize("dtype", [np.bool_, np.int32, np.int64])
def test_matches_numpy_over_num_segments(dtype, num_segments):
    rows = 3_001
    rng = np.random.default_rng(num_segments)
    assert ss.engine(dtype, num_segments) == (
        "dense" if num_segments <= BOUND else "scatter")
    _check(_values(rng, dtype, rows),
           rng.integers(0, num_segments, rows).astype(np.int32),
           num_segments)


@pytest.mark.parametrize("ids_dtype", [np.int32, np.int64])
def test_ids_out_of_range_are_dropped(ids_dtype):
    rng = np.random.default_rng(5)
    rows = 70_000
    ids = rng.integers(-40, 2_040, rows).astype(ids_dtype)
    ids[:3] = [-1, 2_000, np.iinfo(ids_dtype).max]
    assert ((ids < 0) | (ids >= 2_000)).sum() > 1_000
    _check(_values(rng, np.int64, rows), ids, 2_000)


@pytest.mark.parametrize("edge", [I64.min, I64.max, -1])
def test_int64_extremes_wrap_as_numpy_does(edge):
    rows = 66_000
    values = np.full(rows, edge, np.int64)
    ids = (np.arange(rows) % 3).astype(np.int32)
    _check(values, ids, 3)


@pytest.mark.parametrize("dtype", [np.int32, np.int64, np.uint32])
def test_a_chunk_of_limb_255_in_one_group(dtype):
    """The 2^24 edge: every row of a full chunk carries 255 in every
    limb and lands in one group, so each float32 partial sum is
    255 * 65,536 = 16,711,680, the largest the path can make."""
    rows = 2 * 65_536
    values = np.full(rows, -1, np.int64).astype(dtype)
    _check(values, np.full(rows, 7, np.int32), 8)


def test_counted_booleans_all_in_one_group():
    rows = 65_536 + 9
    _check(np.ones(rows, bool), np.zeros(rows, np.int32), 2_000)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_float_values_take_the_scatter(dtype):
    rng = np.random.default_rng(9)
    values = rng.standard_normal(5_000).astype(dtype)
    ids = rng.integers(-2, 12, 5_000).astype(np.int32)
    assert ss.engine(dtype, 10) == "scatter"
    got = jax.jit(ss.segment_sum, static_argnums=2)(values, ids, 10)
    want = jax.jit(lambda v, i: jax.ops.segment_sum(
        v, i, num_segments=10))(values, ids)
    assert got.dtype == want.dtype
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    assert "scatter" in jax.jit(ss.segment_sum, static_argnums=2).lower(
        values, ids, 10).as_text()


def test_the_split_covers_every_segment():
    for limbs in (1, 4, 8):
        for n in (1, 2, 7, 8, 2_000, 4_097, BOUND):
            hi_n, lo_bits = ss._split(n, limbs)
            assert (hi_n - 1) << lo_bits < n <= hi_n << lo_bits


def test_shapes_that_do_not_match_are_refused():
    with pytest.raises(ValueError):
        ss.segment_sum(np.zeros((4, 2), np.int64),
                       np.zeros((4, 2), np.int32), 3)
    with pytest.raises(ValueError):
        ss.segment_sum(np.zeros(4, np.int64), np.zeros(5, np.int32), 3)


# ------------------------------------------------- the structural guard


def _segment_sum_lines(text, scope):
    """The operations of a compiled program whose ``op_name`` lies
    under one ``srt/...`` scope."""
    return [ln for ln in text.splitlines() if f"/{scope}/" in ln]


def _built():
    series = obs.SEGMENT_SUM.snapshot()["series"]
    return {e: sum(x["value"] for x in series if x["labels"] == [e])
            for e in ("dense", "scatter")}


@pytest.fixture
def counting():
    """Metrics and profiling on for one test; yields a reader of the
    segment sums built since the test began, by engine."""
    prior_m, prior_p = obs.is_enabled(), obs.is_profiling_enabled()
    obs.enable()
    obs.enable_profiling()
    before = _built()
    yield lambda: {e: n - before[e] for e, n in _built().items()}
    if not prior_p:
        obs.disable_profiling()
    if not prior_m:
        obs.disable()


def test_q3_hand_fused_lowers_to_products(counting):
    d = tpcds.gen_q3(rows=5_000, items=64, days=730, brands=8)
    kernel = tpcds._q3_kernel(10_957, 3, 8, 2, 11, 100, lambda x: x)
    text = jax.jit(kernel).lower(*d).compile().as_text()
    assert counting() == {"dense": 2, "scatter": 0}
    assert "scatter" not in text
    assert any("dot_general" in ln for ln in
               _segment_sum_lines(text, "srt/q3/segment_sum"))


def test_q3_fused_stage_lowers_to_products(counting):
    d = tpcds.gen_q3(rows=5_000, items=64, days=730, brands=8)
    # a plan of its own (another month), so this test's compile is the
    # one that traces it whatever ran before in the process
    st = PC.compile_stage(C.q3_plan(10_957, 3, 8, 2, month=10))
    inputs = {"s": (d.s_date, d.s_item, d.s_price),
              "dims": (d.d_moy, d.d_year, d.i_brand, d.i_manufact)}
    args, _parts, _bucket = st._bind_args(inputs)
    text = jax.jit(st._fused_callable()).lower(*args).compile().as_text()
    assert counting() == {"dense": 2, "scatter": 0}
    assert "scatter" not in text
    for node in ("sums0", "cnts0"):
        assert any("dot_general" in ln for ln in
                   _segment_sum_lines(text, f"srt/q3/{node}")), node

    sess = obs.PROFILER.begin("q3-guard", query="q3")
    st.run(inputs)
    (stage,) = obs.PROFILER.end(sess)["stages"]
    engines = {n["outs"][0]: n.get("engine")
               for n in stage["nodes"] if n["kind"] == "SegmentSum"}
    assert engines == {"sums0": "dense", "cnts0": "dense"}
