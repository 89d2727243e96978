"""Distributed TPC-DS worker: q5/q72 promoted to real processes
(ISSUE 10 tentpole).

Execution plan per rank (the process-per-shard harness; mesh.py may
additionally form a jax.distributed mesh, but table movement ALWAYS
rides the shuffle service — that is the contract under test):

  1. scan      — every rank regenerates the seeded dataset and takes
                 its row shard (deterministic, no data files needed);
  2. partials  — the map side runs as ONE fused stage executable
                 through the stage IR (plan/catalog — ISSUE 11), AOT
                 in the process compile cache, under
                 ``exchange.with_capacity_retry`` (overflow doubles
                 the join budget, same as every other
                 capacity-bounded pipeline);
  3. reduce-scatter — the partial group table is sliced into
                 rank-owned chunks, each chunk shipped to its owner as
                 kudo tables over the socket shuffle
                 (partition -> kudo write -> transport -> kudo merge);
                 owners sum their received chunks (exact int64 — any
                 arrival order is byte-identical);
  4. allgather — owners re-share their summed chunks; every rank
                 reassembles the GLOBAL group table;
  5. finish    — the reduce side is the matching fused finish stage
                 (ONE executable again — a rank runs exactly one
                 program between kudo exchanges); the output bytes
                 are identical to the single-process pipeline's
                 (``single_q5`` / ``single_q72``, the hand-written
                 models/tpcds kernels) by construction.

Run as a module (``python -m spark_rapids_tpu.distributed.runner``)
by scripts/dist_launch.py; the per-query entry points are also
importable for in-process tests against any table transport.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from typing import Dict, Optional

import numpy as np

# default query shapes — the launcher AND the single-process reference
# (smoke gate) import these so the comparison can never drift
Q5_PARAMS = dict(rows=4096, stores=32, days=60,
                 join_capacity=1 << 14)
Q72_PARAMS = dict(cs_rows=4096, inv_rows=64, items=64, max_week=16,
                  days=35, join_capacity=1 << 17, limit=100,
                  week0=11_000 // 7)


class OpIds:
    """Centralized op-id allocation: one id per (query, stage) so
    concurrent exchanges can never cross payloads."""

    Q5_REDUCE_SCATTER = 101
    Q5_ALLGATHER = 102
    Q72_REDUCE_SCATTER = 111
    Q72_ALLGATHER = 112
    EQ5_PARTS = 121       # elastic q5: per-shard partial broadcast
    BARRIER = 900
    ELASTIC_BARRIER = 901


def _die_spec() -> Optional[tuple]:
    """Injected worker death (chaos for the elastic gate):
    ``SPARK_RAPIDS_TPU_DIST_DIE="<where>[:<rc>]"`` with ``where`` in
    {'boot', 'q5:scan', 'q5:partials'} — boot exits immediately at
    worker start (the launcher fast-fail path); q5:scan exits after
    generating the dataset, BEFORE any partials exist (survivors'
    sends fail -> membership barrier -> the inheritor recomputes the
    dead shard); q5:partials exits AFTER computing this rank's
    partials but BEFORE broadcasting them (work genuinely lost)."""
    spec = os.environ.get("SPARK_RAPIDS_TPU_DIST_DIE", "")
    if not spec:
        return None
    parts = spec.split(":")
    if parts[-1].isdigit() and len(parts) > 1:
        return ":".join(parts[:-1]), int(parts[-1])
    return spec, 13


_DIE_POINTS = ("boot", "q5:scan", "q5:partials")


def _maybe_die(where: str) -> None:
    spec = _die_spec()
    if spec is not None and spec[0] == where:
        sys.stderr.write(f"injected death at {where} "
                         f"(rc={spec[1]})\n")
        sys.stderr.flush()
        os._exit(spec[1])


# ------------------------------------------------------------- helpers


def _int64_table(arrays):
    """Build an all-INT64 kudo-shuffleable Table from numpy vectors."""
    import jax.numpy as jnp

    from spark_rapids_tpu.columns import dtypes
    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.columns.table import Table
    cols = [Column(dtypes.INT64, len(a),
                   data=jnp.asarray(np.asarray(a, dtype=np.int64)))
            for a in arrays]
    return Table(cols)


def _pad_to(vec: np.ndarray, n: int) -> np.ndarray:
    if len(vec) == n:
        return vec
    out = np.zeros(n, dtype=vec.dtype)
    out[: len(vec)] = vec
    return out


def _reduce_scatter_allgather(transport, op_rs: int, op_ag: int,
                              vecs, overflow: bool):
    """Steps 3+4 for a dense partial group table: slice ``vecs`` (all
    same length) into rank-owned chunks, shuffle chunks to owners,
    sum, allgather the owned sums back, return the global vectors +
    the OR of every rank's overflow flag.  The flag rides as one more
    int64 column so it crosses the same wire as the data."""
    world = transport.world
    n = len(vecs[0])
    chunk = -(-n // world)  # ceil: pad so every rank owns equal rows
    padded = [_pad_to(np.asarray(v, dtype=np.int64), chunk * world)
              for v in vecs]
    ofv = np.full(chunk, int(bool(overflow)), dtype=np.int64)
    parts = []
    for d in range(world):
        sl = slice(d * chunk, (d + 1) * chunk)
        parts.append(_int64_table([v[sl] for v in padded] + [ofv]))
    merged = transport.exchange(op_rs, parts)
    # merged rows = world * chunk, source-rank order: sum per owner
    stacked = [c.to_numpy().reshape(world, chunk)
               for c in merged.columns]
    owned = [s.sum(axis=0, dtype=np.int64) for s in stacked[:-1]]
    of_owned = int(stacked[-1].max(initial=0) > 0)
    gathered = transport.allgather(
        op_ag, _int64_table(
            owned + [np.full(chunk, of_owned, dtype=np.int64)]))
    full = [c.to_numpy() for c in gathered.columns]
    out = [v[:n] for v in full[:-1]]
    return out, bool(full[-1].max(initial=0) > 0)


def _shard(a, rank: int, world: int):
    n = (len(a) // world) * world
    per = n // world
    return a[rank * per: (rank + 1) * per]


@contextlib.contextmanager
def _profiled(op: str, rank: int, world: int):
    """Per-rank query-profile session (ISSUE 13): when
    SPARK_RAPIDS_TPU_PROFILE is on, each rank assembles its own
    EXPLAIN ANALYZE artifact — this process's registry scopes the
    shuffle-link byte deltas, so a rank's profile carries exactly its
    own per-peer traffic.  ``merge_profiles`` stitches the rank
    artifacts into ONE fleet profile via the launcher-seeded trace
    context.  One attribute read when profiling is off."""
    from spark_rapids_tpu import observability as _obs

    sess = _obs.PROFILER.begin(f"{op}-rank{rank}", query=f"dist_{op}",
                               rank=rank, world=world)
    try:
        yield sess
    finally:
        _obs.PROFILER.end(sess)


# ------------------------------------------------------------------ q5


def run_dist_q5(params: Optional[dict] = None, *, transport=None
                ) -> Dict[str, np.ndarray]:
    """Distributed q5 on this rank's shard.  Returns the FULL query
    result (every rank converges to the same bytes) as numpy arrays:
    key / sales / rets / profit / overflow."""
    from spark_rapids_tpu import observability as _obs
    from spark_rapids_tpu.models import tpcds as T
    from spark_rapids_tpu.parallel import exchange as X
    from spark_rapids_tpu.plan import catalog as C

    p = dict(Q5_PARAMS, **(params or {}))
    if transport is None:
        transport = X.table_transport()
    rank, world = transport.rank, transport.world
    with _obs.TRACER.span("dist_q5", kind="query",
                          attrs={"rank": rank, "world": world}), \
            _profiled("q5", rank, world):
        rows = max(int(p["rows"]) // (8 * world), 1) * 8 * world
        d = T.gen_q5(rows=rows, stores=p["stores"], days=p["days"])
        shard_args = tuple(
            _shard(a, rank, world)
            for a in (d.s_date, d.s_store, d.s_price, d.s_profit,
                      d.r_date, d.r_store, d.r_amt, d.r_loss)
        ) + (d.d_date,)

        outs, _cap = C.run_q5_partials(
            shard_args, p["stores"], p["join_capacity"])
        sales, rets, profit, seen, of = outs
        (sales, rets, profit, seen), of_any = \
            _reduce_scatter_allgather(
                transport, OpIds.Q5_REDUCE_SCATTER,
                OpIds.Q5_ALLGATHER,
                [np.asarray(sales), np.asarray(rets),
                 np.asarray(profit), np.asarray(seen)],
                bool(np.asarray(of)))
        key_s, sales_s, ret_s, profit_s, _of = C.run_q5_finish(
            np.asarray(sales), np.asarray(rets),
            np.asarray(profit), np.asarray(seen), of_any,
            np.asarray(d.st_id), p["stores"])
        return {"key": np.asarray(key_s), "sales": np.asarray(sales_s),
                "rets": np.asarray(ret_s),
                "profit": np.asarray(profit_s),
                "overflow": np.asarray(of_any)}


def single_q5(params: Optional[dict] = None) -> Dict[str, np.ndarray]:
    """The single-process reference with the SAME shapes the
    distributed run uses (row count rounded identically)."""
    from spark_rapids_tpu.models import tpcds as T

    p = dict(Q5_PARAMS, **(params or {}))
    world = int(p.get("world", 1))
    rows = max(int(p["rows"]) // (8 * world), 1) * 8 * world
    d = T.gen_q5(rows=rows, stores=p["stores"], days=p["days"])
    run = T.make_q5(p["stores"], p["join_capacity"])
    key_s, sales_s, ret_s, profit_s, of = run(d)
    return {"key": np.asarray(key_s), "sales": np.asarray(sales_s),
            "rets": np.asarray(ret_s), "profit": np.asarray(profit_s),
            "overflow": np.asarray(bool(np.asarray(of)))}


# ---------------------------------------------------------- elastic q5


def run_elastic_q5(params: Optional[dict] = None, *, transport=None
                   ) -> Dict[str, np.ndarray]:
    """q5 on the ELASTIC fleet protocol (ISSUE 15): every shard's
    partial group table is a logical PARTITION broadcast to all live
    ranks; the global sums are local (exact int64, shard order).  A
    dead rank's shards are recomputed by the fleet-assigned inheritor
    (inputs are seeded-deterministic); a straggler's shard is
    speculatively re-executed by the least-loaded survivor with the
    first verified copy winning the (op, shard) dedup; a respawned
    worker recomputes its own shards and catches up on the rest by
    CRC'd replay — every rank, however it got here, converges to
    bytes identical to ``single_q5``."""
    from spark_rapids_tpu import observability as _obs
    from spark_rapids_tpu.models import tpcds as T
    from spark_rapids_tpu.parallel import exchange as X
    from spark_rapids_tpu.plan import catalog as C
    from spark_rapids_tpu.shuffle import kudo as _kudo
    from spark_rapids_tpu.shuffle.schema import schema_of_table

    p = dict(Q5_PARAMS, **(params or {}))
    if transport is None:
        transport = X.table_transport()
    if getattr(transport, "fleet", None) is None:
        # degenerate path: no elastic fabric installed — the classic
        # reduce-scatter runner computes the same bytes
        return run_dist_q5(params, transport=transport)
    fleet = transport.fleet
    rank, world0 = transport.rank, fleet.world0
    with _obs.TRACER.span("elastic_q5", kind="query",
                          attrs={"rank": rank, "world": world0}), \
            _profiled("q5", rank, world0):
        rows = max(int(p["rows"]) // (8 * world0), 1) * 8 * world0
        d = T.gen_q5(rows=rows, stores=p["stores"], days=p["days"])
        _maybe_die("q5:scan")

        def compute_part(shard: int, ctx=None):
            """Deterministic per-shard partials -> one int64 kudo
            table.  Runs for our own shards, for INHERITED shards
            after a rebalance, and (cancel-aware via ``ctx``) as a
            speculative re-execution of a straggler's shard."""
            t0 = time.monotonic_ns()
            args = tuple(
                _shard(a, shard, world0)
                for a in (d.s_date, d.s_store, d.s_price, d.s_profit,
                          d.r_date, d.r_store, d.r_amt, d.r_loss)
            ) + (d.d_date,)
            outs, _cap = C.run_q5_partials(
                args, p["stores"], p["join_capacity"], ctx=ctx)
            sales, rets, profit, seen, of = (np.asarray(o)
                                             for o in outs)
            n = len(sales)
            fleet.note_stage_wall("q5.partials",
                                  time.monotonic_ns() - t0)
            return _int64_table([
                sales, rets, profit, seen,
                np.full(n, int(bool(of)), dtype=np.int64)])

        view = fleet.view()
        for shard in view.shards_of(rank):
            t = compute_part(shard)
            _maybe_die("q5:partials")
            transport.broadcast_part(OpIds.EQ5_PARTS, shard, t)
        got = transport.gather_parts(
            OpIds.EQ5_PARTS, range(world0), compute=compute_part,
            deadline_s=transport.recv_timeout_s)
        fields = schema_of_table(_int64_table([[0]] * 5))
        vecs = None
        of_any = False
        for shard in range(world0):
            merged = _kudo.merge_to_table(got[shard], fields)
            cols = [c.to_numpy().astype(np.int64)
                    for c in merged.columns]
            of_any = of_any or bool(cols[-1].max(initial=0) > 0)
            if vecs is None:
                vecs = cols[:-1]
            else:
                vecs = [a + b for a, b in zip(vecs, cols[:-1])]
        sales, rets, profit, seen = vecs
        key_s, sales_s, ret_s, profit_s, _of = C.run_q5_finish(
            sales, rets, profit, seen, of_any,
            np.asarray(d.st_id), p["stores"])
        return {"key": np.asarray(key_s), "sales": np.asarray(sales_s),
                "rets": np.asarray(ret_s),
                "profit": np.asarray(profit_s),
                "overflow": np.asarray(of_any)}


# ----------------------------------------------------------------- q72


def run_dist_q72(params: Optional[dict] = None, *, transport=None
                 ) -> Dict[str, np.ndarray]:
    """Distributed q72: catalog_sales sharded row-parallel, inventory
    + item dim replicated (the same plan as the mesh variant), counts
    reduce-scattered/allgathered over the kudo shuffle."""
    from spark_rapids_tpu import observability as _obs
    from spark_rapids_tpu.models import tpcds as T
    from spark_rapids_tpu.parallel import exchange as X
    from spark_rapids_tpu.plan import catalog as C

    p = dict(Q72_PARAMS, **(params or {}))
    if transport is None:
        transport = X.table_transport()
    rank, world = transport.rank, transport.world
    with _obs.TRACER.span("dist_q72", kind="query",
                          attrs={"rank": rank, "world": world}), \
            _profiled("q72", rank, world):
        cs_rows = max(int(p["cs_rows"]) // world, 1) * world
        d = T.gen_q72(cs_rows=cs_rows, inv_rows=p["inv_rows"],
                      items=p["items"], days=p["days"])
        shard_args = (
            _shard(d.cs_item, rank, world),
            _shard(d.cs_date, rank, world),
            _shard(d.cs_qty, rank, world),
            d.inv_item, d.inv_date, d.inv_qty, d.item_id)

        outs, _cap = C.run_q72_partials(
            shard_args, p["items"], p["max_week"],
            p["join_capacity"], p["week0"])
        counts, of = outs
        (counts,), of_any = _reduce_scatter_allgather(
            transport, OpIds.Q72_REDUCE_SCATTER,
            OpIds.Q72_ALLGATHER, [np.asarray(counts)],
            bool(np.asarray(of)))
        item, week, cnt, _of = C.run_q72_finish(
            np.asarray(counts), of_any, p["items"],
            p["max_week"], p["limit"], p["week0"])
        return {"item": np.asarray(item), "week": np.asarray(week),
                "cnt": np.asarray(cnt),
                "overflow": np.asarray(of_any)}


def single_q72(params: Optional[dict] = None) -> Dict[str, np.ndarray]:
    from spark_rapids_tpu.models import tpcds as T

    p = dict(Q72_PARAMS, **(params or {}))
    world = int(p.get("world", 1))
    cs_rows = max(int(p["cs_rows"]) // world, 1) * world
    d = T.gen_q72(cs_rows=cs_rows, inv_rows=p["inv_rows"],
                  items=p["items"], days=p["days"])
    run = T.make_q72(p["items"], p["max_week"], p["join_capacity"],
                     limit=p["limit"], week0=p["week0"])
    item, week, cnt, of = run(d)
    return {"item": np.asarray(item), "week": np.asarray(week),
            "cnt": np.asarray(cnt),
            "overflow": np.asarray(bool(np.asarray(of)))}


DIST_QUERIES = {"q5": run_dist_q5, "q72": run_dist_q72}
ELASTIC_QUERIES = {"q5": run_elastic_q5, "q72": run_dist_q72}
SINGLE_QUERIES = {"q5": single_q5, "q72": single_q72}


# ---------------------------------------------------------- worker main


def _parse_trace_ctx():
    from spark_rapids_tpu.observability import SpanContext
    spec = os.environ.get("SPARK_RAPIDS_TPU_DIST_TRACE_CTX", "")
    if ":" not in spec:
        return None
    try:
        tid, sid = spec.split(":")
        return SpanContext(int(tid, 16), int(sid, 16))
    except ValueError:
        return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="spark_rapids_tpu distributed shuffle worker")
    ap.add_argument("--rank", type=int, required=True)
    ap.add_argument("--world", type=int, required=True)
    ap.add_argument("--addresses", required=True,
                    help="comma-separated per-rank listen addresses "
                         "(unix:/path or host:port)")
    ap.add_argument("--ops", default="q5,q72")
    ap.add_argument("--outdir", required=True)
    ap.add_argument("--coordinator", default=None,
                    help="jax.distributed coordinator (mesh attempt)")
    ap.add_argument("--params", default="{}",
                    help="JSON dict of per-query param overrides "
                         "keyed by op name")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic fleet protocol: membership epoch, "
                         "rebalance on peer death, speculation, "
                         "skew re-split")
    args = ap.parse_args(argv)
    _maybe_die("boot")

    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass

    from spark_rapids_tpu import observability as obs
    from spark_rapids_tpu.distributed.mesh import try_form_mesh
    from spark_rapids_tpu.distributed.service import ShuffleService
    from spark_rapids_tpu.observability.dumpio import dump_via
    from spark_rapids_tpu.shuffle import kudo

    rank, world = args.rank, args.world
    outdir = args.outdir
    os.makedirs(outdir, exist_ok=True)
    overrides = json.loads(args.params)

    kudo.set_crc_enabled(True)
    obs.enable()
    obs.enable_tracing()

    mesh_info = try_form_mesh(rank, world,
                              coordinator=args.coordinator)
    service = ShuffleService(
        rank, world, args.addresses.split(","),
        elastic=args.elastic).start().install()
    respawned = os.environ.get(
        "SPARK_RAPIDS_TPU_DIST_RESPAWN", "") == "1"
    parent = _parse_trace_ctx()
    root = obs.TRACER.start_span(
        "dist_worker", kind="process", parent=parent,
        attrs={"rank": rank, "world": world,
               "mesh": mesh_info["mode"],
               "respawned": respawned})
    from spark_rapids_tpu.observability import SpanContext
    # control/replay daemon threads parent under this worker's
    # process span so the fleet trace stays ONE connected tree
    service.trace_ctx = SpanContext(root.trace_id, root.span_id)
    if args.elastic and respawned:
        # a respawned incarnation: announce ourselves so survivors
        # waiting at the elastic barrier learn we are back, and learn
        # their epoch/departed view before sending fenceable frames
        # (after the root span, so the join sends stitch into the
        # fleet trace instead of rooting orphans)
        service.join_fleet()
    queries = ELASTIC_QUERIES if args.elastic else DIST_QUERIES
    ops = [o for o in args.ops.split(",") if o]
    rc = 0
    try:
        for op in ops:
            result = queries[op](overrides.get(op),
                                 transport=service)
            np.savez(os.path.join(
                outdir, f"result_{op}_rank{rank}.npz"), **result)
            if obs.PROFILER.enabled:
                prof = obs.PROFILER.last()
                if prof is not None:
                    dump_via(
                        os.path.join(
                            outdir,
                            f"profile_{op}_rank{rank}.json"),
                        lambda f, p=prof: f.write(
                            json.dumps(p, sort_keys=True,
                                       default=str)))
                    # same-moment registry snapshot: the profile's
                    # link-byte deltas reconcile exactly against
                    # THIS dump (the final metrics_rank dump also
                    # counts post-query barrier traffic)
                    dump_via(
                        os.path.join(
                            outdir,
                            f"metrics_{op}_rank{rank}.json"),
                        lambda f: f.write(
                            obs.METRICS.snapshot_json()))
        if obs.TIMESERIES.enabled:
            # close the final window NOW and dump the same-moment pair
            # (ring + registry): the ring's summed counter deltas equal
            # the registry's cumulative values at this instant exactly,
            # which is the fleet-reconciliation gate's oracle (the
            # post-barrier metrics_rank dump also counts barrier
            # traffic, so it cannot be the comparison point)
            obs.TIMESERIES.tick()
            ts_snap = obs.timeseries_snapshot(
                rank=rank, epoch=(service.fleet.epoch
                                  if service.fleet is not None else 0))
            dump_via(os.path.join(outdir,
                                  f"timeseries_rank{rank}.json"),
                     lambda f: f.write(json.dumps(ts_snap,
                                                  sort_keys=True)))
            dump_via(os.path.join(outdir,
                                  f"metrics_ts_rank{rank}.json"),
                     lambda f: f.write(obs.METRICS.snapshot_json()))
            # publish to rank 0 while the links are still up: the send
            # blocks for the ACK, so after the barrier below rank 0
            # holds every rank's windows
            service.publish_timeseries(ts_snap)
        if args.elastic:
            # membership-tolerant: survives peers leaving AND waits
            # for a respawned peer when the launcher may send one
            service.elastic_barrier(OpIds.ELASTIC_BARRIER)
            # graceful leave: peers still gathering (a respawned
            # straggler) drop us from their barrier wants instead of
            # waiting out a death detection on our closed listener
            service.leave_fleet()
        else:
            service.barrier(OpIds.BARRIER)
    except Exception as e:  # noqa: BLE001 — report, then nonzero exit
        rc = 1
        with open(os.path.join(outdir, f"error_rank{rank}.txt"),
                  "w") as f:
            f.write(f"{type(e).__name__}: {e}\n")
        raise
    finally:
        root.end()
        obs.TRACER.dump_jsonl(
            os.path.join(outdir, f"spans_rank{rank}.jsonl"))
        dump_via(os.path.join(outdir, f"metrics_rank{rank}.json"),
                 lambda f: f.write(obs.METRICS.snapshot_json()))
        # the journal carries the fleet evidence spine
        # (fleet_membership / fleet_speculation / fleet_inherit /
        # shuffle_dup_dropped) the elastic gate and srt-doctor read
        obs.dump_journal_jsonl(
            os.path.join(outdir, f"journal_rank{rank}.jsonl"))
        if rank == 0 and obs.TIMESERIES.enabled:
            # rank 0's merged fleet timeseries (self + every publish
            # folded pre-barrier) — the srt-top file tier and the
            # reconciliation gate read this
            dump_via(os.path.join(outdir, "fleet_timeseries.json"),
                     lambda f: f.write(json.dumps(
                         service.fleet_timeseries.merged(),
                         sort_keys=True)))
        summary = {
            "rank": rank, "world": world, "ops": ops,
            "mesh": mesh_info, "elastic": bool(args.elastic),
            "respawned": respawned,
            "epoch": (service.fleet.epoch
                      if service.fleet is not None else 0),
            "trace_id": (f"{root.trace_id:016x}"
                         if root.trace_id else None),
            "rc": rc,
        }
        dump_via(os.path.join(outdir, f"summary_rank{rank}.json"),
                 lambda f: f.write(json.dumps(summary, indent=1)))
        service.uninstall()
        service.stop()
    return rc


if __name__ == "__main__":
    sys.exit(main())
