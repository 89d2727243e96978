"""Jittable fixed-capacity join + distributed shuffle-join tests
(device_join.py, models/distributed_join.py) against brute-force
oracles on the 8-device CPU mesh."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from spark_rapids_tpu.models.distributed_join import make_distributed_join
from spark_rapids_tpu.ops.device_join import inner_join_device


def _oracle(lk, rk, lval, rval):
    return sorted((i, j) for i in range(len(lk)) for j in range(len(rk))
                  if lval[i] and rval[j] and lk[i] == rk[j])


def test_inner_join_device_vs_oracle():
    rng = np.random.default_rng(5)
    jfn = jax.jit(lambda a, b, c, d: inner_join_device(a, b, 4096, c, d))
    for trial in range(8):
        nl, nr = rng.integers(1, 200, 2)
        lk = rng.integers(0, 40, nl)
        rk = rng.integers(0, 40, nr)
        lval = rng.random(nl) < 0.9
        rval = rng.random(nr) < 0.9
        want = _oracle(lk, rk, lval, rval)
        out = jfn(jnp.asarray(lk), jnp.asarray(rk), jnp.asarray(lval),
                  jnp.asarray(rval))
        v = np.asarray(out.valid)
        got = sorted(zip(np.asarray(out.left_indices)[v].tolist(),
                         np.asarray(out.right_indices)[v].tolist()))
        assert int(out.total) == len(want)
        assert got == want


def test_inner_join_device_edges():
    # capacity overflow: true total reported, slots saturate
    out = inner_join_device(jnp.zeros(50, jnp.int64),
                            jnp.zeros(50, jnp.int64), 64)
    assert int(out.total) == 2500 and int(out.valid.sum()) == 64
    # empty sides
    out = inner_join_device(jnp.zeros(0, jnp.int64),
                            jnp.zeros(5, jnp.int64), 16)
    assert int(out.total) == 0 and not bool(out.valid.any())
    # INT64_MAX keys still join (sentinel-free invalid encoding)
    big = jnp.asarray([2**63 - 1, 1], jnp.int64)
    out = inner_join_device(big, big, 16)
    assert int(out.total) == 2
    # ...but an INVALID row with INT64_MAX key does not
    out = inner_join_device(big, big, 16,
                            right_valid=jnp.asarray([False, True]))
    assert int(out.total) == 1


INT64_MAX = 2**63 - 1


def _contract(lk, rk, lval, rval, capacity):
    """The probe's contract as plain numpy: pairs in left-row order,
    each run in right-row order, cut at ``capacity``; empty slots 0."""
    li, ri = [], []
    for i in range(len(lk)):
        if lval[i]:
            js = np.nonzero(rval & (rk == lk[i]))[0]
            li += [i] * len(js)
            ri += js.tolist()
    m = min(len(li), capacity)
    out_l = np.zeros(capacity, np.int32)
    out_r = np.zeros(capacity, np.int32)
    out_l[:m], out_r[:m] = li[:m], ri[:m]
    return out_l, out_r, np.arange(capacity) < m, len(li)


def _case(name):
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    if name == "duplicates_both_sides":
        lk, rk = rng.integers(0, 8, 60), rng.integers(0, 8, 90)
        lval, rval, cap = np.ones(60, bool), np.ones(90, bool), 1024
    elif name == "unique_right_q5_shape":
        # 48 returns into 384 sales (n_r = 8 n_l) on 37-bit keys, both
        # padded to a bucket with side-specific sentinels masked off
        sales = rng.choice(1 << 37, 384, replace=False)
        lk = np.concatenate([rng.choice(sales[:360], 48, replace=False),
                             np.full(16, -1)])
        rk = np.concatenate([sales[:360], np.full(24, -2)])
        lval = np.arange(64) < 48
        rval = np.arange(384) < 360
        cap = 64
    elif name == "invalid_rows_each_side":
        lk, rk = rng.integers(0, 30, 100), rng.integers(0, 30, 140)
        lval, rval = rng.random(100) < 0.7, rng.random(140) < 0.6
        cap = 512
    elif name == "int64_max_valid_and_invalid":
        lk = rng.choice([INT64_MAX, -2**63, 0, 5], 40)
        rk = rng.choice([INT64_MAX, -2**63, 0, 7], 50)
        lval, rval = rng.random(40) < 0.8, rng.random(50) < 0.7
        cap = 1024
    elif name == "total_over_capacity":
        lk, rk = rng.integers(0, 3, 70), rng.integers(0, 3, 80)
        lval, rval = rng.random(70) < 0.9, rng.random(80) < 0.9
        cap = 100
    elif name == "empty_left":
        lk, rk = np.zeros(0, np.int64), rng.integers(0, 4, 9)
        lval, rval, cap = np.zeros(0, bool), np.ones(9, bool), 16
    else:                                   # empty_right
        lk, rk = rng.integers(0, 4, 9), np.zeros(0, np.int64)
        lval, rval, cap = np.ones(9, bool), np.zeros(0, bool), 16
    return lk.astype(np.int64), rk.astype(np.int64), lval, rval, cap


CASES = ("duplicates_both_sides", "unique_right_q5_shape",
         "invalid_rows_each_side", "int64_max_valid_and_invalid",
         "total_over_capacity", "empty_left", "empty_right")


@pytest.mark.parametrize("name", CASES)
def test_inner_join_device_contract_bit_identical(name):
    lk, rk, lval, rval, cap = _case(name)
    want = _contract(lk, rk, lval, rval, cap)
    out = jax.jit(lambda a, b, c, d: inner_join_device(a, b, cap, c, d))(
        jnp.asarray(lk), jnp.asarray(rk), jnp.asarray(lval),
        jnp.asarray(rval))
    assert np.asarray(out.left_indices).dtype == np.int32
    assert np.asarray(out.right_indices).dtype == np.int32
    assert np.array_equal(np.asarray(out.left_indices), want[0])
    assert np.array_equal(np.asarray(out.right_indices), want[1])
    assert np.array_equal(np.asarray(out.valid), want[2])
    assert out.total.dtype == jnp.int64 and int(out.total) == want[3]
    if name == "total_over_capacity":
        assert want[3] > cap


@pytest.mark.parametrize("name", CASES)
def test_device_join_total_contract(name):
    from spark_rapids_tpu.ops.joins import _device_join_total

    lk, rk, lval, rval, cap = _case(name)
    got = _device_join_total(jnp.asarray(lk), jnp.asarray(rk),
                             jnp.asarray(lval), jnp.asarray(rval))
    assert got.dtype == jnp.int64
    assert int(got) == _contract(lk, rk, lval, rval, cap)[3]


def test_inner_join_device_has_no_loop():
    """The probe is sorts and scans: no binary search's loop of
    dependent gathers is left in the program."""
    def primitives(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from primitives(sub)

    jaxpr = jax.make_jaxpr(
        lambda a, b, c, d: inner_join_device(a, b, 1 << 12, c, d))(
        jnp.zeros(1 << 12, jnp.int64), jnp.zeros(1 << 15, jnp.int64),
        jnp.ones(1 << 12, jnp.bool_), jnp.ones(1 << 15, jnp.bool_))
    names = set(primitives(jaxpr.jaxpr))
    assert "sort" in names
    assert not names & {"while", "scan"}


@pytest.fixture(scope="module")
def mesh8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    return Mesh(np.array(devs[:8]), ("x",))


def test_distributed_join_exact(mesh8):
    rng = np.random.default_rng(11)
    NL = NR = 512
    lk = rng.integers(0, 300, NL).astype(np.int64)
    rk = rng.integers(0, 300, NR).astype(np.int64)
    lv = rng.integers(0, 1000, NL).astype(np.int64)
    rv = rng.integers(0, 1000, NR).astype(np.int64)
    step = make_distributed_join(mesh8, exch_cap=64, pair_cap=2048)
    k, olv, orv, valid, totals, ovf = step(
        jnp.asarray(lk), jnp.asarray(lv), jnp.asarray(rk),
        jnp.asarray(rv))
    assert not bool(np.asarray(ovf).any())
    v = np.asarray(valid).reshape(-1)
    got = sorted(zip(np.asarray(k).reshape(-1)[v].tolist(),
                     np.asarray(olv).reshape(-1)[v].tolist(),
                     np.asarray(orv).reshape(-1)[v].tolist()))
    want = sorted((int(a), int(b), int(c))
                  for a, b in zip(lk, lv)
                  for a2, c in zip(rk, rv) if a == a2)
    assert got == want


def test_distributed_join_overflow_flag(mesh8):
    rng = np.random.default_rng(12)
    lk = rng.integers(0, 10, 256).astype(np.int64)
    vals = np.arange(256, dtype=np.int64)
    step = make_distributed_join(mesh8, exch_cap=2, pair_cap=8)
    *_, ovf = step(jnp.asarray(lk), jnp.asarray(vals), jnp.asarray(lk),
                   jnp.asarray(vals))
    assert bool(np.asarray(ovf).any())


def test_inner_join_device_no_int32_wrap():
    """2^32 true pairs must not wrap the pair accounting to 0 (which
    would silently defeat overflow detection)."""
    n = 1 << 16
    k = jnp.zeros(n, jnp.int64)
    out = inner_join_device(k, k, 16)
    assert int(out.total) == 1 << 32
    assert int(out.valid.sum()) == 16


def test_distributed_join_auto_retry(mesh8):
    """The centralized capacity retry (with_capacity_retry) must grow a
    deliberately-too-small budget until the join is complete and exact."""
    from spark_rapids_tpu.models.distributed_join import \
        make_distributed_join_auto

    rng = np.random.default_rng(13)
    NL = NR = 256
    lk = rng.integers(0, 8, NL).astype(np.int64)    # heavy skew
    rk = rng.integers(0, 8, NR).astype(np.int64)
    lv = np.arange(NL, dtype=np.int64)
    rv = np.arange(NR, dtype=np.int64) + 1000
    run = make_distributed_join_auto(mesh8, exch_cap=2, pair_cap=4,
                                    max_doublings=12)
    (k, olv, orv, valid, _totals, ovf), (cap_used, _pc) = run(
        jnp.asarray(lk), jnp.asarray(lv), jnp.asarray(rk),
        jnp.asarray(rv))
    assert cap_used > 2                      # budget actually grew
    assert not bool(np.asarray(ovf).any())
    v = np.asarray(valid).reshape(-1)
    got = sorted(zip(np.asarray(k).reshape(-1)[v].tolist(),
                     np.asarray(olv).reshape(-1)[v].tolist(),
                     np.asarray(orv).reshape(-1)[v].tolist()))
    want = sorted((int(a), int(b), int(c))
                  for a, b in zip(lk, lv)
                  for a2, c in zip(rk, rv) if a == a2)
    assert got == want


def test_capacity_retry_ceiling():
    from spark_rapids_tpu.parallel.exchange import (CapacityExceeded,
                                                    with_capacity_retry)

    def make_step(cap):
        return lambda: (np.array([True]),)   # always overflows

    run = with_capacity_retry(make_step, 2, max_doublings=3)
    with pytest.raises(CapacityExceeded):
        run()
