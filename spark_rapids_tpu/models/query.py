"""Flagship query pipelines — the "model" of this framework (BASELINE.json
configs: join + group-by aggregate shapes from TPC-DS q5/q9/q72).

Two forms:
  * simple_star_join_agg: eager composition of the real op kernels
    (hash join -> gather -> group-by aggregate) — the single-chip
    end-to-end slice.
  * distributed_hash_aggregate: the multi-chip step — murmur hash
    partitioning + all-to-all ICI exchange + on-device bucketed partial
    aggregation, all inside one jitted shard_map (the analog of the
    reference's executor-parallel shuffle+agg, SURVEY.md §2.2 checklist).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_tpu import observability as _obs
from spark_rapids_tpu.columns import dtypes
from spark_rapids_tpu.columns.column import Column
from spark_rapids_tpu.columns.table import Table
from spark_rapids_tpu.ops import copying, groupby, joins
from spark_rapids_tpu.ops import hash as H
from spark_rapids_tpu.parallel import exchange as ex


def simple_star_join_agg(fact: Table, dim: Table,
                         fact_key: int = 0, fact_value: int = 1,
                         dim_key: int = 0, dim_attr: int = 1) -> Table:
    """SELECT d.attr, sum(f.value), count(*) FROM fact f JOIN dim d
    ON f.key = d.key GROUP BY d.attr — the minimum end-to-end slice."""
    from spark_rapids_tpu.robustness import retry as _retry

    def _run():
        li, ri = joins.hash_inner_join(
            Table([fact.columns[fact_key]]),
            Table([dim.columns[dim_key]]))
        value = copying.gather(fact.columns[fact_value], li)
        attr = copying.gather(dim.columns[dim_attr], ri)
        return groupby.groupby_aggregate(
            Table([attr], names=["attr"]), [value, value],
            [groupby.SUM, groupby.COUNT])

    # query-root span: the eagerly composed op kernels below each open
    # child op spans under it, so a trace export shows the whole query
    # as one tree; the retry driver recomputes the (pure) composition
    # on a mid-query OOM
    with _obs.TRACER.span("simple_star_join_agg", kind="query"):
        return _retry.with_retry(_run, name="simple_star_join_agg")


def make_distributed_hash_aggregate(mesh: Mesh, n_parts: int,
                                    num_buckets: int, capacity: int):
    """Jitted multi-chip step: per-shard murmur partition -> all-to-all ->
    per-device bucketed sums/counts.  Returns (step_fn, sharding).

    The returned step takes (keys int64 shard, vals float32 shard) and
    yields per-device (bucket_sums, bucket_counts, send_counts) — callers
    check max(send_counts) <= capacity per the exchange contract."""

    def local(keys, vals):
        h = H.murmur3_32(
            [Column(dtypes.INT64, keys.shape[0], data=keys)], 42).data
        part = (h.astype(jnp.uint32) % jnp.uint32(n_parts)).astype(
            jnp.int32)
        (rk, rv), valid, _total, send_counts = ex.exchange(
            [keys, vals], part, "data", n_parts, capacity)
        bucket = (rk.astype(jnp.uint64)
                  % jnp.uint64(num_buckets)).astype(jnp.int32)
        bucket = jnp.where(valid, bucket, num_buckets)  # dropped lane
        sums = jax.ops.segment_sum(
            jnp.where(valid, rv, 0.0), bucket, num_buckets + 1)
        counts = jax.ops.segment_sum(
            valid.astype(jnp.int32), bucket, num_buckets + 1)
        return sums[:num_buckets], counts[:num_buckets], send_counts

    jitted = jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data"), P("data"))))

    from spark_rapids_tpu.robustness import retry as _retry

    def step(keys, vals):
        # stage-level span around the jitted multi-chip step (the
        # exchange itself runs inside XLA; the span brackets dispatch);
        # retry driver: a mid-dispatch OOM re-runs the pure step
        with _obs.TRACER.span("distributed_hash_aggregate",
                              kind="stage"):
            return _retry.with_retry(
                jitted, keys, vals, name="distributed_hash_aggregate")

    return step, NamedSharding(mesh, P("data"))
