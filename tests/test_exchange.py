"""ICI exchange tests on the 8-device virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_tpu.parallel import exchange as ex


def _mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]), ("data",))


def test_build_padded_sends():
    part = jnp.array([2, 0, 2, 1, 2, 0], jnp.int32)
    vals = jnp.array([20, 0, 21, 10, 22, 1], jnp.int64)
    sends, counts = ex.build_padded_sends([vals], part, 4, 3)
    np.testing.assert_array_equal(np.asarray(counts), [2, 1, 3, 0])
    s = np.asarray(sends[0])
    assert sorted(s[0, :2].tolist()) == [0, 1]
    assert s[1, 0] == 10
    assert sorted(s[2].tolist()) == [20, 21, 22]


def test_exchange_all_rows_arrive():
    n = 8
    mesh = _mesh(n)
    rows_per = 32
    cap = 16

    def local(keys, vals):
        part = (keys % n).astype(jnp.int32)
        (rk, rv), valid, total, send_counts = ex.exchange(
            [keys, vals], part, "data", n, cap)
        return rk, rv, valid, total[None], send_counts

    f = jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(P("data"), P("data")),
        out_specs=(P("data"),) * 5))

    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(0, 1000, n * rows_per, dtype=np.int64))
    vals = jnp.arange(n * rows_per, dtype=np.int64)
    sharding = NamedSharding(mesh, P("data"))
    keys = jax.device_put(keys, sharding)
    vals = jax.device_put(vals, sharding)
    rk, rv, valid, total, send_counts = f(keys, vals)
    # no destination overflowed the capacity budget
    assert (np.asarray(send_counts) <= cap).all()

    rk = np.asarray(rk).reshape(n, -1)
    rv = np.asarray(rv).reshape(n, -1)
    valid = np.asarray(valid).reshape(n, -1)
    # every row arrives exactly once, on the right device
    got_vals = []
    for d in range(n):
        kd = rk[d][valid[d]]
        vd = rv[d][valid[d]]
        assert ((kd % n) == d).all(), "row landed on wrong partition"
        got_vals.extend(vd.tolist())
    assert sorted(got_vals) == list(range(n * rows_per))


def test_exchange_overflow_clips_counts():
    n = 8
    mesh = _mesh(n)
    cap = 2  # deliberately too small: all keys hash to partition 0

    def local(keys):
        part = jnp.zeros_like(keys, jnp.int32)
        (rk,), valid, total, send_counts = ex.exchange(
            [keys], part, "data", n, cap)
        return rk, valid, total[None], send_counts

    f = jax.jit(shard_map(local, mesh=mesh, in_specs=(P("data"),),
                          out_specs=(P("data"),) * 4))
    keys = jax.device_put(jnp.arange(n * 8, dtype=jnp.int64),
                          NamedSharding(mesh, P("data")))
    rk, valid, total, send_counts = f(keys)
    total = np.asarray(total).reshape(n)
    # overflow IS detectable: senders report true counts > capacity
    assert (np.asarray(send_counts).reshape(n, n)[:, 0] == 8).all()
    # device 0 received clipped capacity from each sender; others nothing
    assert total[0] == n * cap
    assert (total[1:] == 0).all()


def _scatter_layout(arrays, part, n_parts, capacity, counting):
    """The layout ``build_padded_sends`` made before it sorted: each
    row's rank among the rows bound for its destination (a one-hot
    cumsum where the shard was small, a stable argsort otherwise),
    then one scatter a column into the padded slots."""
    pi = part.astype(jnp.int32)
    rows = int(pi.shape[0])
    if counting:
        onehot = pi[:, None] == jnp.arange(n_parts, dtype=jnp.int32)[None, :]
        rank = jnp.take_along_axis(
            jnp.cumsum(onehot.astype(jnp.int32), axis=0),
            jnp.clip(pi, 0, n_parts - 1)[:, None], axis=1)[:, 0] - 1
        counts = jnp.sum(onehot, axis=0, dtype=jnp.int32)
    else:
        order = jnp.argsort(pi)
        p_sorted = pi[order]
        counts = jnp.bincount(pi, length=n_parts).astype(jnp.int32)
        starts = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)])
        rank_sorted = jnp.arange(rows, dtype=jnp.int32) - starts[
            jnp.clip(p_sorted, 0, n_parts - 1)]
        rank = jnp.zeros(rows, jnp.int32).at[order].set(rank_sorted)
    slot = jnp.where(rank < capacity, rank, capacity)
    sends = []
    for a in arrays:
        buf = jnp.zeros((n_parts, capacity) + a.shape[1:], a.dtype)
        sends.append(buf.at[pi, slot].set(a, mode="drop"))
    return sends, counts


@pytest.mark.parametrize("counting", [True, False])
@pytest.mark.parametrize("rows,n_parts,capacity", [
    (1, 4, 1), (300, 4, 128), (300, 4, 40), (4096, 8, 512),
    (5000, 3, 2048)])
def test_sort_and_slice_layout_is_the_scatter_layout(rows, n_parts,
                                                      capacity, counting):
    """Slots and counts byte for byte as the scatter layout made them,
    with destinations over capacity (rows dropped, counts true) and
    rows sent nowhere (destination ``n_parts``: a bucket's pad)."""
    rng = np.random.default_rng(rows + n_parts + capacity)
    part = rng.integers(0, n_parts, rows, dtype=np.int32)
    part[rng.random(rows) < 0.2] = n_parts
    part = jnp.asarray(part)
    arrays = [jnp.asarray(rng.integers(-2 ** 40, 2 ** 40, rows)),
              jnp.asarray(rng.integers(0, 9, rows, dtype=np.int32)),
              jnp.asarray(rng.normal(size=(rows, 3)).astype(np.float32))]
    got, got_counts = ex.build_padded_sends(arrays, part, n_parts, capacity)
    want, want_counts = _scatter_layout(arrays, part, n_parts, capacity,
                                        counting)
    np.testing.assert_array_equal(np.asarray(got_counts),
                                  np.asarray(want_counts))
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()


def test_sort_and_slice_layout_has_no_scatter():
    arrays = [jnp.zeros(1 << 12, jnp.int64), jnp.zeros(1 << 12, jnp.int32)]
    part = jnp.zeros(1 << 12, jnp.int32)
    jaxpr = str(jax.make_jaxpr(
        lambda a, b, p: ex.build_padded_sends([a, b], p, 4, 1 << 10))(
        *arrays, part))
    assert "scatter" not in jaxpr and "sort" in jaxpr
