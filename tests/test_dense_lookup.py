"""ops/dense_lookup (PR 33): the dense one-hot lookup against numpy's
``t[idx]`` (and against jnp's, which says what an index outside the
table reads), the engine choice, and the structural guard that the two
q3 paths lower their dim lookups to products, not gathers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu import observability as obs
from spark_rapids_tpu.models import tpcds
from spark_rapids_tpu.ops import dense_lookup as dl
from spark_rapids_tpu.plan import catalog as C
from spark_rapids_tpu.plan import compiler as PC
from spark_rapids_tpu.plan import ir

BOUND = dl.DENSE_MAX_TABLE_LIMBS
DTYPES = [np.bool_, np.int32, np.int64]


def _oracle(table, idx):
    """numpy's ``table[idx]`` after jnp's rule for an index: a negative
    one wraps once, in the index's own width, the result is narrowed to
    int32, and what is still outside is clamped."""
    n = table.shape[0]
    idx = np.asarray(idx)
    with np.errstate(over="ignore"):
        pos = np.where(idx < 0, idx + idx.dtype.type(n), idx)
    return table[np.clip(pos.astype(np.int32), 0, n - 1)]


def _table(rng, dtype, n):
    if dtype == np.bool_:
        return rng.integers(0, 2, n).astype(bool)
    info = np.iinfo(dtype)
    t = rng.integers(info.min, info.max, n, dtype=dtype, endpoint=True)
    edges = np.array([info.min, info.max, info.max, 0], dtype)
    if info.min:
        edges[2] = -1
    t[:min(4, n)] = edges[:n]
    return t


def _indices(rng, dtype, n_rows, table_len):
    """In range, negative, past the end, and the dtype's extremes."""
    idx = rng.integers(-2 * table_len - 2, 2 * table_len + 2,
                       n_rows).astype(dtype)
    info = np.iinfo(dtype)
    edges = np.array([info.min, info.max, -1, table_len, -table_len,
                      -table_len - 1, info.min + 1, info.max - 1], dtype)
    idx[:min(edges.size, n_rows)] = edges[:n_rows]
    return idx


def _check(tables, idx, want_engine="dense"):
    tables = tuple(jnp.asarray(t) for t in tables)
    assert dl.engines(tables, jnp.asarray(idx)) == (
        (want_engine,) * len(tables))
    got = jax.jit(dl.lookup)(tables, idx)
    assert len(got) == len(tables)
    for g, t in zip(got, tables):
        want = _oracle(np.asarray(t), idx)
        assert g.dtype == want.dtype and g.shape == want.shape
        assert np.asarray(g).tobytes() == want.tobytes()


@pytest.mark.parametrize("idx_dtype", [np.int32, np.int64])
@pytest.mark.parametrize("table_len", [1, 7, 730])
def test_the_oracle_is_what_jnp_indexing_gives(table_len, idx_dtype):
    """The rule is read off jnp, not assumed: wrap once, clamp."""
    rng = np.random.default_rng(table_len)
    table = _table(rng, np.int32, table_len)
    idx = _indices(rng, idx_dtype, 4_000, table_len)
    got = jax.jit(lambda t, i: t[i])(table, idx)
    assert np.asarray(got).tobytes() == _oracle(table, idx).tobytes()


@pytest.mark.parametrize("rows", [1, 65_535, 65_537, 200_001])
@pytest.mark.parametrize("dtype", DTYPES)
def test_dense_matches_numpy_rows_not_a_multiple_of_the_chunk(
        dtype, rows):
    rng = np.random.default_rng(rows)
    _check((_table(rng, dtype, 37),),
           _indices(rng, np.int32, rows, 37))


@pytest.mark.parametrize("table_len", [1, 730, 102_000])
@pytest.mark.parametrize("dtype", DTYPES)
def test_one_table_matches_numpy_over_table_lengths(dtype, table_len):
    rng = np.random.default_rng(table_len)
    _check((_table(rng, dtype, table_len),),
           _indices(rng, np.int32, 3_001, table_len))


@pytest.mark.parametrize("idx_dtype", [np.int32, np.int64])
@pytest.mark.parametrize("table_len", [1, 730, 102_000])
def test_three_tables_share_one_index(table_len, idx_dtype):
    rng = np.random.default_rng(table_len + 1)
    _check([_table(rng, dt, table_len) for dt in DTYPES],
           _indices(rng, idx_dtype, 3_001, table_len))


@pytest.mark.parametrize("over", [0, 1])
@pytest.mark.parametrize("dtype", DTYPES)
def test_the_bound_and_one_past_it(dtype, over):
    """``table_len * limbs`` at the bound goes dense, one more row of
    table takes the gather; both read what numpy reads."""
    limbs = dl._n_limbs(dtype)
    table_len = BOUND // limbs + over
    rng = np.random.default_rng(over)
    want = "dense" if table_len * limbs <= BOUND else "gather"
    assert dl.engine((dtype,), table_len) == want
    _check((_table(rng, dtype, table_len),),
           _indices(rng, np.int32, 1_500, table_len), want)


def test_a_set_past_the_bound_goes_dense_table_by_table():
    table_len = BOUND // 8
    rng = np.random.default_rng(8)
    tables = [_table(rng, dt, table_len) for dt in (np.int64, np.int32)]
    assert dl.engine([t.dtype for t in tables], table_len) == "gather"
    _check(tables, _indices(rng, np.int32, 1_200, table_len))


@pytest.mark.parametrize("dtype", [np.int8, np.int16, np.uint8,
                                   np.uint16, np.uint32, np.uint64])
def test_other_integer_widths(dtype):
    rng = np.random.default_rng(16)
    _check((_table(rng, dtype, 300), _table(rng, np.int32, 300)),
           _indices(rng, np.int32, 5_000, 300))


@pytest.mark.parametrize("idx_dtype", [np.int8, np.int16, np.uint8,
                                       np.uint32])
def test_other_index_widths(idx_dtype):
    """A narrow or unsigned index inside the table; jnp itself wraps a
    narrow index in its own width, which the helper does not copy."""
    rng = np.random.default_rng(17)
    table = _table(rng, np.int64, 100)
    idx = rng.integers(0, 100, 3_000).astype(idx_dtype)
    got = jax.jit(dl.lookup)((table,), idx)[0]
    assert np.asarray(got).tobytes() == table[idx].tobytes()


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_float_tables_take_the_gather(dtype):
    rng = np.random.default_rng(9)
    table = rng.standard_normal(730).astype(dtype)
    ints = _table(rng, np.int32, 730)
    idx = _indices(rng, np.int32, 5_000, 730)
    assert dl.engine((dtype,), 730) == "gather"
    assert dl.engines((jnp.asarray(table), jnp.asarray(ints)),
                      jnp.asarray(idx)) == ("gather", "dense")
    lowered = jax.jit(dl.lookup).lower((table,), idx)
    assert "gather" in lowered.as_text()
    assert "dot_general" not in lowered.as_text()
    got_f, got_i = jax.jit(dl.lookup)((table, ints), idx)
    assert got_f.dtype == dtype
    assert np.asarray(got_f).tobytes() == _oracle(table, idx).tobytes()
    assert np.asarray(got_i).tobytes() == _oracle(ints, idx).tobytes()


def test_tables_that_are_not_1d_take_the_gather():
    rng = np.random.default_rng(10)
    table = rng.integers(0, 99, (40, 3)).astype(np.int32)
    idx = _indices(rng, np.int32, 500, 40)
    assert dl.engines((jnp.asarray(table),), jnp.asarray(idx)) == (
        "gather",)
    got = jax.jit(dl.lookup)((table,), idx)[0]
    assert np.asarray(got).tobytes() == _oracle(table, idx).tobytes()


@pytest.mark.parametrize("idx", [0, -1, np.int32(5)])
def test_a_scalar_index_takes_the_gather(idx):
    table = np.arange(10, 20, dtype=np.int32)
    assert dl.engines((jnp.asarray(table),), jnp.asarray(idx)) == (
        "gather",)
    got = jax.jit(lambda t: dl.lookup((t,), idx)[0])(table)
    assert got.shape == () and int(got) == int(table[idx])


def test_a_2d_index_takes_the_gather():
    table = np.arange(10, 20, dtype=np.int32)
    idx = np.arange(6, dtype=np.int32).reshape(2, 3)
    got = jax.jit(dl.lookup)((table,), idx)[0]
    assert np.array_equal(np.asarray(got), table[idx])


def test_tables_of_differing_length_are_refused():
    with pytest.raises(ValueError):
        dl.lookup((np.zeros(4, np.int32), np.zeros(5, np.int32)),
                  np.zeros(3, np.int32))


def test_no_rows():
    got = jax.jit(dl.lookup)((np.arange(5, dtype=np.int64),),
                             np.zeros(0, np.int32))[0]
    assert got.shape == (0,) and got.dtype == np.int64


def test_the_split_covers_every_entry():
    for limbs in (1, 4, 8, 13):
        for n in (1, 2, 7, 730, 4_097, 102_000, BOUND // limbs):
            hi_n, lo_bits = dl._split(n, limbs)
            assert (hi_n - 1) << lo_bits < n <= hi_n << lo_bits


def test_limbs_and_back():
    from spark_rapids_tpu.ops.segment_sum import _limbs
    rng = np.random.default_rng(3)
    for dtype in DTYPES + [np.int16, np.uint64]:
        t = _table(rng, dtype, 500)
        back = dl._from_limbs(_limbs(jnp.asarray(t)).astype(jnp.int32),
                              dtype)
        assert back.dtype == t.dtype
        assert np.asarray(back).tobytes() == t.tobytes()


def _built():
    series = obs.DENSE_LOOKUP.snapshot()["series"]
    return {e: sum(x["value"] for x in series if x["labels"] == [e])
            for e in ("dense", "gather")}


@pytest.fixture
def counting():
    """Metrics and profiling on for one test; yields a reader of the
    lookups built since the test began, by engine."""
    prior_m, prior_p = obs.is_enabled(), obs.is_profiling_enabled()
    obs.enable()
    obs.enable_profiling()
    before = _built()
    yield lambda: {e: n - before[e] for e, n in _built().items()}
    if not prior_p:
        obs.disable_profiling()
    if not prior_m:
        obs.disable()


def test_the_counter_counts_tables_per_engine_at_trace_time(counting):
    f = jax.jit(dl.lookup)
    ints = (np.zeros(9, np.int32), np.zeros(9, np.int64))
    idx = np.zeros(4, np.int32)
    f(ints, idx)
    f(ints, idx)          # cached: not traced again
    f((np.zeros(9, np.float32), np.zeros(9, bool)), idx)
    assert counting() == {"dense": 3, "gather": 1}


# ------------------------------------------------- the structural guard


def _scope_lines(text, scope):
    """The operations of a compiled program whose ``op_name`` lies
    under one ``srt/...`` scope."""
    return [ln for ln in text.splitlines() if f"/{scope}/" in ln]


def _gathers(text):
    return [ln for ln in text.splitlines() if " gather(" in ln]


def _products(text, scope):
    return [ln for ln in _scope_lines(text, scope)
            if " dot(" in ln or " convolution(" in ln]


Q3_DIMS = dict(rows=5_000, items=64, days=730, brands=8)


def _q3_stage(month):
    """A q3 stage of the test's own (by its month), so the test's
    compile is the one that traces it whatever ran before."""
    d = tpcds.gen_q3(**Q3_DIMS)
    st = PC.compile_stage(C.q3_plan(10_957, 3, 8, 2, month=month))
    return st, {"s": (d.s_date, d.s_item, d.s_price),
                "dims": (d.d_moy, d.d_year, d.i_brand, d.i_manufact)}


def test_q3_hand_fused_looks_its_dims_up_by_products(counting):
    """Two products (the date dim's tables on one index, the item
    dim's on the other), no row gather, and the counter reads what the
    bound implies: four dense tables."""
    d = tpcds.gen_q3(**Q3_DIMS)
    kernel = tpcds._q3_kernel(10_957, 3, 8, 2, 11, 100, lambda x: x)
    text = jax.jit(kernel).lower(*d).compile().as_text()
    assert counting() == {"dense": 4, "gather": 0}
    assert not _gathers(text)
    products = _products(text, "srt/q3/dim_gather")
    assert len(products) == 2, products


def test_q3_fused_stage_looks_its_dims_up_by_products(counting):
    """The stage path: the same two products, under the scope of the
    first node that reads each index; the scalar ``d_year[0]`` (twice
    in the plan) is the gather engine's and a static slice."""
    st, inputs = _q3_stage(month=9)
    args, _parts, _bucket = st._bind_args(inputs)
    text = jax.jit(st._fused_callable()).lower(*args).compile().as_text()
    assert counting() == {"dense": 4, "gather": 1}
    assert not _gathers(text)
    for node in ("year_idx", "keep"):
        products = _products(text, f"srt/q3/{node}")
        assert len(products) == 1, (node, products)


def test_the_profile_record_names_each_lookups_engine(counting):
    """Every ``Idx``-bearing Project of the q3 stage says how its
    lookups run, as the SegmentSum nodes say theirs."""
    st, inputs = _q3_stage(month=8)
    sess = obs.PROFILER.begin("q3-lookups", query="q3")
    st.run(inputs)
    (stage,) = obs.PROFILER.end(sess)["stages"]
    engines = {n["outs"][0]: n["engine"] for n in stage["nodes"]
               if n["kind"] == "Project" and "engine" in n}
    assert engines == {
        "year_idx": "dense+gather",       # d_year[di] and d_year[0]
        "keep": "dense",            # d_moy[di], i_manufact[s_item]
        "brand": "dense",
        "yrs": "gather"}                  # d_year[0] alone


def test_stage_lookups_on_one_index_share_their_one_hots():
    """Four ``Idx`` on two indices become two products, whatever node
    they stand in; a lookup whose table is longer than the bound
    admits stays a gather in the same stage."""
    plan = ir.StagePlan(
        name="lk",
        inputs=(ir.ScanBind("f", (ir.ColSpec("i"), ir.ColSpec("j"))),
                ir.ScanBind("dims", (ir.ColSpec("a"), ir.ColSpec("b"),
                                     ir.ColSpec("c"), ir.ColSpec("big")),
                            bucket=False)),
        nodes=(
            ir.Project("x", ir.Bin("add", ir.Idx(ir.Col("a"),
                                                 ir.Col("i")),
                                   ir.Idx(ir.Col("c"), ir.Col("j")))),
            ir.Project("y", ir.Idx(ir.Col("b"), ir.Col("i"))),
            ir.Project("z", ir.Idx(ir.Col("big"), ir.Col("j"))),
            ir.Project("w", ir.Idx(ir.Col("a"), ir.Col("i"))),
        ),
        outputs=("x", "y", "z", "w"))
    rng = np.random.default_rng(4)
    n = 5_000
    a, b = _table(rng, np.int32, 50), _table(rng, np.int64, 50)
    c = _table(rng, np.int32, 60)
    big = _table(rng, np.bool_, BOUND + 1)
    i = rng.integers(0, 50, n).astype(np.int32)
    j = rng.integers(0, 60, n).astype(np.int32)
    st = PC.compile_stage(plan)
    inputs = {"f": (i, j), "dims": (a, b, c, big)}
    args, _parts, _bucket = st._bind_args(inputs)
    text = jax.jit(st._fused_callable()).lower(*args).as_text()
    assert text.count("stablehlo.dot_general") == 2
    assert text.count('"stablehlo.gather"') == 1
    x, y, z, w = st.run_unfused(inputs)
    with np.errstate(over="ignore"):
        assert np.array_equal(np.asarray(x), a[i] + c[j])
    assert np.array_equal(np.asarray(y), b[i])
    assert np.array_equal(np.asarray(z), big[j])
    assert np.array_equal(np.asarray(w), a[i])
    assert st._engines == {"x": "dense", "y": "dense", "z": "gather",
                           "w": "dense"}
