"""Whole-stage fusion compiler (ISSUE 11 tentpole).

Takes a :class:`~spark_rapids_tpu.plan.ir.StagePlan` and runs it as
ONE XLA executable: every node between two shuffle boundaries traces
into a single program, AOT-lowered through the process compile cache
(perf/jit_cache) under ``(stage-plan digest, schema-layout digest,
power-of-two row bucket)`` — so a TPC-DS stage pays one dispatch and
zero HBM round-trips between its ops, and the second same-bucket
query compiles NOTHING.

What runs a plan:

  * :meth:`CompiledStage.run` — single process, one AOT executable;
    the only way a stage executes (no switch selects another);
  * :func:`fused_pipeline_fn` — the WHOLE pipeline (boundaries elided,
    ``Reduce`` -> ``lax.psum``, ``Exchange`` -> all-to-all) as one
    function for ``shard_map``: a mesh rank runs one program end to
    end; :class:`MeshPipeline` runs it over sharded tables through the
    compile cache;
  * stage-by-stage through the distributed runner, with the kudo
    socket shuffle carrying each boundary (distributed/runner.py).

:meth:`CompiledStage.run_unfused` walks the same nodes eagerly, one
dispatch each.  It is the REFERENCE the tests compare the executable
with, byte for byte; ``run`` never calls it.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu.plan import ir


def _canon_dtype(a) -> str:
    """The dtype string the traced program will actually see, without
    materializing a device copy (numpy/jnp arrays AND python scalars
    must digest identically to their jnp.asarray form)."""
    import numpy as np

    from jax.dtypes import canonicalize_dtype
    dt = getattr(a, "dtype", None)
    if dt is None:
        dt = np.asarray(a).dtype
    return str(canonicalize_dtype(dt))


class Padded(tuple):
    """A bucketed input's columns already padded to their power-of-two
    row bucket (with each ColSpec's pad value), and the true row count:
    a table held on the device binds as it is, with no copy a call
    (models/resident.py).  A table sharded over a mesh
    (:class:`MeshPipeline`) holds each device's shard padded to one
    bucket, and ``shard_rows`` the true rows of each shard in device
    order (``rows`` is their sum).

    ``window``, where it is set, says the true rows of each shard were
    put in the order of the first column at load (the pad rows stay at
    the tail), and is the most rows of a shard that any window of the
    load's length over that column's values holds: the capacity of an
    ``ir.WindowSlice`` over the table.  A table without it is read
    whole."""

    def __new__(cls, columns, rows: int, shard_rows=None,
                window: Optional[int] = None):
        self = super().__new__(cls, columns)
        self.rows = int(rows)
        self.shard_rows = (None if shard_rows is None
                           else tuple(int(r) for r in shard_rows))
        self.window = None if window is None else int(window)
        return self


def _input_rows(arrs) -> int:
    """True rows of a bound input: a Padded input's count, else the
    first column's length."""
    if isinstance(arrs, Padded):
        return arrs.rows
    shape = jnp.shape(arrs[0])
    return int(shape[0]) if shape else 0


def _bind_rows(env, inp: ir.ScanBind, n_valid=None) -> None:
    """Bind what an input's rows are: ``Mask(input)`` (the first
    ``n_valid`` rows, or every row where it is None) and the true-row
    count a ``WindowSlice`` over the input searches within."""
    first = env[inp.columns[0].name]
    rows = first.shape[0] if getattr(first, "ndim", 0) else 0
    if n_valid is None:
        env[f"__mask__{inp.name}"] = jnp.ones(rows, jnp.bool_)
        env[f"__rows__{inp.name}"] = rows
    else:
        env[f"__mask__{inp.name}"] = (
            jnp.arange(rows, dtype=jnp.int32) < n_valid)
        env[f"__rows__{inp.name}"] = n_valid


# -------------------------------------------------------------- evaluation

_BIN = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "mul": lambda a, b: a * b,
    "div": lambda a, b: a / b,
    "floordiv": lambda a, b: a // b,
    "mod": lambda a, b: a % b,
    "and": lambda a, b: a & b,
    "or": lambda a, b: a | b,
    "eq": lambda a, b: a == b,
    "ne": lambda a, b: a != b,
    "lt": lambda a, b: a < b,
    "le": lambda a, b: a <= b,
    "gt": lambda a, b: a > b,
    "ge": lambda a, b: a >= b,
    "max": jnp.maximum,
    "min": jnp.minimum,
}

# env key under which a trace leaves each node's engine: a SegmentSum's
# "dense" | "scatter", an Idx-bearing Project's "dense" | "gather"
_ENGINES = "__engines__"
# env keys of the stage's lookups: index expression key -> {Idx key:
# Idx}, and Idx key -> (value, engine) of those evaluated so far
_IDX_PEERS = "__idx_peers__"
_LOOKED_UP = "__looked_up__"
# env key under which a mesh trace leaves each Exchange's send counts
# (n_parts,) by node prefix
_SENT = "__sent__"

_CAST = {"i32": jnp.int32, "i64": jnp.int64, "f64": jnp.float64,
         "b": jnp.bool_}


def _eval(e, env):
    if isinstance(e, ir.Col):
        return env[e.name]
    if isinstance(e, ir.Lit):
        if e.dtype is None:
            return e.value          # weak python scalar, like a literal
        return jnp.asarray(e.value, dtype=e.dtype)
    if isinstance(e, ir.Bin):
        return _BIN[e.op](_eval(e.a, env), _eval(e.b, env))
    if isinstance(e, ir.Un):
        a = _eval(e.a, env)
        if e.op == "neg":
            return -a
        if e.op == "not":
            return ~a
        if e.op == "sum":
            return jnp.sum(a)
        return a.astype(_CAST[e.op])
    if isinstance(e, ir.Where):
        return jnp.where(_eval(e.cond, env), _eval(e.a, env),
                         _eval(e.b, env))
    if isinstance(e, ir.Idx):
        return _lookup(e, env)
    if isinstance(e, ir.IsIn):
        a, keys = _eval(e.a, env), _eval(e.keys, env)
        # one elementwise compare a key: the fact stays one flat loop
        hit = a == keys[0]
        for k in range(1, keys.shape[0]):
            hit = hit | (a == keys[k])
        return hit
    if isinstance(e, ir.Mask):
        return env[f"__mask__{e.input}"]
    if isinstance(e, ir.Arange):
        return jnp.arange(e.n, dtype=e.dtype)
    if isinstance(e, ir.Sl):
        return _eval(e.a, env)[e.start:e.stop]
    if isinstance(e, ir.Stack):
        return jnp.stack([_eval(p, env) for p in e.parts])
    raise TypeError(f"unknown expr {type(e).__name__}")


def _children(x):
    """The expressions directly under a node or an expression."""
    for f in dataclasses.fields(x):
        v = getattr(x, f.name)
        for part in v if isinstance(v, tuple) else (v,):
            if isinstance(part, ir.Expr):
                yield part


def _idx_exprs(x) -> list:
    """Every ``Idx`` under a node or an expression, inner ones first."""
    found = [i for c in _children(x) for i in _idx_exprs(c)]
    return found + [x] if isinstance(x, ir.Idx) else found


def _bound(e, env) -> bool:
    """Whether every column ``e`` reads is in ``env`` already."""
    if isinstance(e, ir.Col):
        return e.name in env
    if isinstance(e, ir.Mask):
        return f"__mask__{e.input}" in env
    return all(_bound(c, env) for c in _children(e))


def _lookup(e: ir.Idx, env):
    """``src[idx]`` through ``ops.dense_lookup``.  The stage's other
    lookups on the same index expression whose tables are bound are
    evaluated with it, those of one table length in one call, so that
    they share the one-hots of the index as the hand kernel's do."""
    from spark_rapids_tpu.ops import dense_lookup as _dl
    done = env.setdefault(_LOOKED_UP, {})
    if e.key() not in done:
        peers = [p for p in env.get(_IDX_PEERS, {}).get(
                     e.idx.key(), {}).values()
                 if p.key() not in done and _bound(p.src, env)]
        if e not in peers:
            peers.append(e)
        idx = _eval(e.idx, env)
        by_len: Dict[tuple, list] = {}
        for p in peers:
            table = jnp.asarray(_eval(p.src, env))
            by_len.setdefault(table.shape[:1], []).append((p, table))
        for group in by_len.values():
            tables = [table for _p, table in group]
            which = _dl.engines(tables, jnp.asarray(idx))
            for (p, _t), value, w in zip(group, _dl.lookup(tables, idx),
                                         which):
                done[p.key()] = (value, w)
    return done[e.key()][0]


def _expr_is_bool(e, bool_names=frozenset()) -> bool:
    """Statically decide whether an expression evaluates to a boolean
    (a predicate/mask) from the IR alone — the tap planner must pick
    its nodes BEFORE tracing, and the choice must be a pure function
    of the plan so tapped executables key on digest alone.
    ``bool_names`` carries the names already known boolean upstream
    (JoinProbe ``.valid`` outputs, earlier predicate Projects), so a
    conjunction like ``j.valid AND qty < limit`` still taps."""
    if isinstance(e, ir.Bin):
        if e.op in ("and", "or"):
            return (_expr_is_bool(e.a, bool_names)
                    and _expr_is_bool(e.b, bool_names))
        return e.op in ("eq", "ne", "lt", "le", "gt", "ge")
    if isinstance(e, ir.Un):
        if e.op == "not":
            return _expr_is_bool(e.a, bool_names)
        return e.op == "b"
    if isinstance(e, ir.Where):
        return (_expr_is_bool(e.a, bool_names)
                and _expr_is_bool(e.b, bool_names))
    if isinstance(e, (ir.Mask, ir.IsIn)):
        return True
    if isinstance(e, ir.Idx):
        return _expr_is_bool(e.src, bool_names)
    if isinstance(e, ir.Sl):
        return _expr_is_bool(e.a, bool_names)
    if isinstance(e, ir.Lit):
        return isinstance(e.value, bool)
    if isinstance(e, ir.Col):
        return e.name in bool_names
    return False


def _tap_spec(plan: ir.StagePlan) -> list:
    """The per-node row-count taps this plan admits, in node order:
    ``(node_id, kind, env_key)`` triples.  Only DATA-DEPENDENT
    cardinalities are tapped — JoinProbe match totals (already
    computed by the probe) and boolean Project predicates (one
    popcount each); every other node's output size is statically
    known from its inputs, so observing it would buy nothing."""
    taps = []
    bool_names = set()
    for node in plan.nodes:
        if isinstance(node, ir.JoinProbe):
            bool_names.add(f"{node.prefix}.valid")
            taps.append((node.prefix, "JoinProbe",
                         f"{node.prefix}.total"))
        elif isinstance(node, ir.Project) and \
                _expr_is_bool(node.expr, bool_names):
            bool_names.add(node.out)
            taps.append((node.out, "Project", node.out))
    return taps


def _tap_counts(plan: ir.StagePlan, env) -> list:
    """Scalar int32 observed-row counts for every tap, evaluated from
    the node outputs ALREADY in ``env`` (shared by the fused trace and
    the eager walk — same expressions, so the two engines observe
    identical counts).  A predicate popcount is one reduction over a
    value the program computed anyway; traced, it fuses into the same
    executable."""
    vals = []
    for _nid, kind, key in _tap_spec(plan):
        v = jnp.asarray(env[key])
        if kind == "JoinProbe":
            vals.append(v.astype(jnp.int32))
        else:
            vals.append(jnp.sum(v.astype(jnp.int32))
                        .astype(jnp.int32))
    return vals


def first_rows(order, n_valid, values, past):
    """For each of ``values``, the first of the first ``n_valid`` rows
    of ``order`` (ascending there) whose value is above it where
    ``past``, at least it otherwise; ``n_valid`` where there is none.
    A binary search of one gather a step for all of them, not a pass
    over the rows."""
    rows = order.shape[0]
    hi = jnp.full(values.shape, n_valid, jnp.int32)
    # under shard_map the bounds vary over the mesh axes the column and
    # the values vary over, as the loop's outputs do
    vma = (jax.typeof(order).vma | jax.typeof(values).vma
           | jax.typeof(hi).vma)
    lo, hi = (lax.pcast(b, tuple(vma - jax.typeof(b).vma), to="varying")
              for b in (jnp.zeros_like(hi), hi))

    def step(_i, bounds):
        lo, hi = bounds
        mid = (lo + hi) // 2
        at = order[jnp.minimum(mid, rows - 1)]
        right = (lo < hi) & jnp.where(past, at <= values, at < values)
        return jnp.where(right, mid + 1, lo), jnp.where(right, hi, mid)
    # a loop, not unrolled: the stage traces and lowers one step
    return lax.fori_loop(0, max(rows, 1).bit_length(), step, (lo, hi))[0]


def _eval_nodes(plan: ir.StagePlan, env,
                reduce_axis: Optional[str]) -> None:
    """Evaluate the stage's nodes in order into ``env``, which holds
    the inputs; leaves each SegmentSum's and each Idx-bearing
    Project's engine under ``_ENGINES``."""
    lookups = [(node, _idx_exprs(node)) for node in plan.nodes]
    peers = env[_IDX_PEERS] = {}
    for _node, found in lookups:
        for i in found:
            peers.setdefault(i.idx.key(), {}).setdefault(i.key(), i)
    done = env[_LOOKED_UP] = {}
    for node in plan.nodes:
        _eval_node(node, env, reduce_axis, plan.name)
    for node, found in lookups:
        if found and isinstance(node, ir.Project):
            env.setdefault(_ENGINES, {})[node.out] = "+".join(
                sorted({done[i.key()][1] for i in found}))


def _eval_node(node, env, reduce_axis: Optional[str],
               stage: str) -> None:
    """Evaluate one node into ``env`` (shared by the fused trace and
    the reference walk — one evaluator, so the two cannot drift),
    under the device-side name ``srt/<stage>/<node>``: the
    node's first output column, which survives a rewrite of the op
    underneath (a trace names a fusion by its HLO text otherwise)."""
    with jax.named_scope(f"srt/{stage}/{node.outs()[0]}"):
        _eval_kind(node, env, reduce_axis)


def _eval_kind(node, env, reduce_axis: Optional[str]) -> None:
    if isinstance(node, ir.Project):
        env[node.out] = _eval(node.expr, env)
    elif isinstance(node, ir.JoinProbe):
        from spark_rapids_tpu.ops.device_join import inner_join_device
        lv = (None if node.left_valid is None
              else _eval(node.left_valid, env))
        rv = (None if node.right_valid is None
              else _eval(node.right_valid, env))
        pairs = inner_join_device(_eval(node.left, env),
                                  _eval(node.right, env),
                                  node.capacity,
                                  left_valid=lv, right_valid=rv)
        p = node.prefix
        env[f"{p}.li"] = pairs.left_indices
        env[f"{p}.ri"] = pairs.right_indices
        env[f"{p}.valid"] = pairs.valid
        env[f"{p}.total"] = pairs.total
    elif isinstance(node, ir.Exchange):
        p, valid = node.prefix, _eval(node.valid, env)
        cols = [env[c] for c in node.columns]
        if reduce_axis is not None:
            # Spark's hash exchange over the mesh axis: every device
            # ends with the rows whose partition is its index
            from spark_rapids_tpu.parallel import exchange as _ex
            n = lax.axis_size(reduce_axis)
            part = _ex.hash_partitions(
                [_eval(k, env) for k in node.keys], valid, n)
            cols, valid, _total, sent = _ex.exchange(
                cols, part, reduce_axis, n, node.capacity)
            env.setdefault(_SENT, {})[p] = sent
        for c, a in zip(node.columns, cols):
            env[f"{p}.{c}"] = a
        env[f"{p}.valid"] = valid
    elif isinstance(node, ir.WindowSlice):
        order = _eval(node.order, env)
        rows = order.shape[0]
        n_valid = env[f"__rows__{node.input}"]
        cap = min(node.capacity, rows)
        # the true rows whose order lies in [lo, hi] are [start, end)
        start, end = first_rows(
            order, n_valid, jnp.stack([_eval(node.lo, env),
                                       _eval(node.hi, env)]),
            jnp.array([False, True]))
        # dynamic_slice's own clamp, made explicit for the validity
        at = jnp.minimum(start, rows - cap)
        for c in node.columns:
            env[f"{node.prefix}.{c}"] = lax.dynamic_slice_in_dim(
                env[c], at, cap)
        env[f"{node.prefix}.valid"] = (
            at + lax.iota(jnp.int32, cap) < n_valid)
        env[f"{node.prefix}.over"] = end - start > cap
    elif isinstance(node, ir.SegmentSum):
        from spark_rapids_tpu.ops import segment_sum as _ss
        value = node.value
        if isinstance(value, ir.Un) and value.op == "i64":
            # a widened predicate is a count: the helper counts
            # booleans in one limb where an int64 takes eight
            v = _eval(value.a, env)
            if v.dtype != jnp.bool_:
                v = v.astype(jnp.int64)
        else:
            v = _eval(value, env)
        env.setdefault(_ENGINES, {})[node.out] = _ss.engine(
            v.dtype, node.num_segments)
        env[node.out] = _ss.segment_sum(
            v, _eval(node.ids, env), node.num_segments)
    elif isinstance(node, ir.Sort):
        res = lax.sort(tuple(_eval(o, env) for o in node.operands),
                       num_keys=node.num_keys)
        for name, arr in zip(node.names, res):
            env[name] = arr
    elif isinstance(node, ir.Reduce):
        v = _eval(node.value, env)
        if reduce_axis is None:
            env[node.out] = v
        elif node.kind == "any":
            env[node.out] = lax.psum(v.astype(jnp.int32),
                                     reduce_axis) > 0
        else:
            env[node.out] = lax.psum(v, reduce_axis)
    elif isinstance(node, ir.WindowSum):
        part = _eval(node.part, env)
        sums = jax.ops.segment_sum(
            _eval(node.value, env), part,
            num_segments=node.num_partitions)
        env[node.out] = sums[part]
    elif isinstance(node, ir.WindowRank):
        part = _eval(node.part, env).astype(jnp.int64)
        okey = _eval(node.order, env).astype(jnp.int64)
        n = part.shape[0]
        iota = jnp.arange(n, dtype=jnp.int64)
        p_s, _o, row_s = lax.sort((part, okey, iota), num_keys=3)
        # rank within partition = sorted position minus the running
        # partition start (one cummax, no data-dependent loops)
        first = jnp.concatenate(
            [jnp.ones(1, jnp.bool_), p_s[1:] != p_s[:-1]])
        start = lax.cummax(jnp.where(first, iota, 0))
        env[node.out] = jnp.zeros(n, jnp.int64).at[row_s].set(
            iota - start)
    elif isinstance(node, ir.Rollup):
        n1, n2 = node.cards
        m = _eval(node.mask, env)
        k1 = jnp.where(m, _eval(node.keys[0], env), 0)
        k2 = jnp.where(m, _eval(node.keys[1], env), 0)
        w = jnp.where(m, _eval(node.value, env), 0)
        c = m.astype(jnp.int64)
        gid = k1.astype(jnp.int64) * n2 + k2
        sum0 = jax.ops.segment_sum(w, gid, num_segments=n1 * n2)
        cnt0 = jax.ops.segment_sum(c, gid, num_segments=n1 * n2)
        p = node.prefix
        env[f"{p}.sum0"], env[f"{p}.cnt0"] = sum0, cnt0
        # coarser grouping sets fold from the finest level's exact int
        # sums — byte-stable in any fold order
        env[f"{p}.sum1"] = sum0.reshape(n1, n2).sum(axis=1)
        env[f"{p}.cnt1"] = cnt0.reshape(n1, n2).sum(axis=1)
        env[f"{p}.sumt"] = jnp.sum(sum0)
        env[f"{p}.cntt"] = jnp.sum(cnt0)
        if node.mode == "cube":
            env[f"{p}.sum2"] = sum0.reshape(n1, n2).sum(axis=0)
            env[f"{p}.cnt2"] = cnt0.reshape(n1, n2).sum(axis=0)
    else:
        raise TypeError(f"unknown node {type(node).__name__}")


# --------------------------------------------------------- compiled stage


class CompiledStage:
    """One stage: the fused AOT executable (``run``) and the
    ``shard_map`` body (``fused_fn``), on one evaluator; the eager
    walk (``run_unfused``) is the tests' reference."""

    def __init__(self, plan: ir.StagePlan):
        self.plan = plan.validate()
        # (digest, bucket) -> jitted fn when the process jit cache is
        # disabled: jit's own trace cache then carries same-shape
        # reuse instead of retracing per call (bounded by the distinct
        # shape classes this stage object sees)
        self._nocache: Dict[tuple, object] = {}
        # SegmentSum node -> "dense" | "scatter", Idx-bearing Project
        # -> "dense" | "gather" ("dense+gather" where its lookups
        # differ), left by the newest trace (static per executable;
        # the profile record reads it)
        self._engines: Dict[str, str] = {}

    # number of op dispatches the reference walk pays (the fused
    # program pays exactly 1)
    @property
    def dispatch_count(self) -> int:
        return len(self.plan.nodes)

    # ------------------------------------------------------------ binding

    def _shape_parts(self, inputs: Mapping[str, Sequence]):
        """Digest ingredients for operands_digest — every input's
        canonical dtypes plus its row bucket (exact shape for
        unbucketed inputs) — WITHOUT materializing any padded copy.
        Returns (parts, max_bucket)."""
        import numpy as np

        from spark_rapids_tpu.perf.jit_cache import bucket_rows
        parts, max_bucket = [], 0
        for inp in self.plan.inputs:
            arrs = list(inputs[inp.name])
            if len(arrs) != len(inp.columns):
                raise ValueError(
                    f"input {inp.name!r} expects {len(inp.columns)} "
                    f"columns, got {len(arrs)}")
            if inp.bucket:
                b = bucket_rows(_input_rows(inputs[inp.name]))
                max_bucket = max(max_bucket, b)
                parts.append((",".join(_canon_dtype(a)
                                       for a in arrs), b))
            else:
                parts.append((",".join(
                    f"{_canon_dtype(a)}{tuple(np.shape(a))}"
                    for a in arrs), 0))
        return parts, max_bucket

    def _bind_args(self, inputs: Mapping[str, Sequence]):
        """Pad bucketed inputs to their power-of-two row bucket and
        flatten to the fused arg list: [*columns..., *n_valids...].
        Returns (args, shape_parts, max_bucket)."""
        from spark_rapids_tpu.perf.jit_cache import bucket_rows
        parts, max_bucket = self._shape_parts(inputs)
        cols, nvalids = [], []
        for inp in self.plan.inputs:
            arrs = [jnp.asarray(a) for a in inputs[inp.name]]
            if inp.bucket and isinstance(inputs[inp.name], Padded):
                rows = inputs[inp.name].rows
                b = bucket_rows(rows)
                if any(a.shape[0] != b for a in arrs):
                    raise ValueError(
                        f"input {inp.name!r}: padded to "
                        f"{[a.shape[0] for a in arrs]} rows, bucket {b}")
                cols.extend(arrs)
                nvalids.append(jnp.int32(rows))
            elif inp.bucket:
                rows = int(arrs[0].shape[0])
                b = bucket_rows(rows)
                for spec, a in zip(inp.columns, arrs):
                    if a.shape[0] != rows:
                        raise ValueError(
                            f"ragged input {inp.name!r}")
                    if a.shape[0] != b:
                        widths = ([(0, b - rows)]
                                  + [(0, 0)] * (a.ndim - 1))
                        a = jnp.pad(a, widths,
                                    constant_values=spec.pad)
                    cols.append(a)
                nvalids.append(jnp.int32(rows))
            else:
                cols.extend(arrs)
        return cols + nvalids, parts, max_bucket

    def _fused_callable(self, taps: bool = False):
        """The generic evaluator as a pure fn(*args) for jit: binds
        the flat arg list back to named columns + row masks, then
        walks the nodes — XLA sees ONE program.  With ``taps`` the
        program additionally returns one stacked int32 vector of
        per-node observed row counts (ISSUE 20): the values already
        exist inside the trace (JoinProbe totals, predicate masks),
        so the same single executable carries them out — zero extra
        dispatches."""
        plan = self.plan

        def fn(*args):
            env: Dict[str, object] = {}
            pos = 0
            bucketed = []
            for inp in plan.inputs:
                for spec in inp.columns:
                    env[spec.name] = args[pos]
                    pos += 1
                if inp.bucket:
                    bucketed.append(inp)
            for i, inp in enumerate(bucketed):
                _bind_rows(env, inp, args[pos + i])
            for inp in plan.inputs:
                if not inp.bucket:
                    _bind_rows(env, inp)
            _eval_nodes(plan, env, None)
            self._engines.update(env.get(_ENGINES, {}))
            outs = tuple(env[o] for o in plan.outputs)
            if taps:
                vals = _tap_counts(plan, env)
                counts = (jnp.stack(vals) if vals
                          else jnp.zeros(0, jnp.int32))
                return outs + (counts,)
            return outs

        return fn

    def fused_fn(self, reduce_axis: Optional[str] = None):
        """Unpadded evaluator for shard_map bodies: args are the raw
        input columns (flattened in input order, no n_valid scalars),
        ``Reduce`` nodes psum over ``reduce_axis``."""
        plan = self.plan

        def fn(*args):
            env: Dict[str, object] = {}
            pos = 0
            for inp in plan.inputs:
                for spec in inp.columns:
                    env[spec.name] = args[pos]
                    pos += 1
                _bind_rows(env, inp)
            _eval_nodes(plan, env, reduce_axis)
            return tuple(env[o] for o in plan.outputs)

        return fn

    # ------------------------------------------------------------ engines

    def _run_digest(self, parts) -> str:
        """The full run key: stage-plan digest | all-operand schema
        digest — the jit-cache key AND the stage_fusion journal
        digest (one derivation, no drift)."""
        from spark_rapids_tpu.perf.calibrate import operands_digest
        return f"{self.plan.digest}|{operands_digest(parts)}"

    def _run_fused(self, inputs, taps: bool = False) -> tuple:
        """ONE AOT executable through the process compile cache,
        keyed by (stage-plan digest, all-operand schema digest, row
        bucket).  Returns (outputs, compile_ns, run_digest, counts) —
        ``compile_ns`` is the lower+compile wall when THIS call built
        the executable, 0 on a cache hit (truthiness keeps the old
        compiled-now contract; the attribution ledger carves the
        nanoseconds out of the stage's compute).  ``counts`` is the
        tapped per-node row-count vector (None without ``taps``); a
        tapped program is a DIFFERENT executable, so the compile-cache
        key gets a ``|taps`` suffix while the reported run digest
        stays the base one — journal and profile rows fold together
        whichever way the stats switch points."""
        from spark_rapids_tpu import observability as _obs
        from spark_rapids_tpu.perf import jit_cache as _jc

        with _obs.TRACER.span("stage_bind", kind="phase") as span:
            args, parts, bucket = self._bind_args(inputs)
            span.set_attr("bucket", bucket)
        digest = self._run_digest(parts)
        key_digest = f"{digest}|taps" if taps else digest
        fn = self._fused_callable(taps=taps)
        compiled_now = []

        def build():
            t0 = time.monotonic_ns()
            ex = jax.jit(fn).lower(*args).compile()
            compiled_now.append(time.monotonic_ns() - t0)
            return ex

        if _jc.CACHE.enabled():
            ex = _jc.CACHE.get_or_build(
                f"stage.{self.plan.name}", key_digest, bucket, build,
                cost_bytes=_jc._tree_nbytes(args))
        else:
            # cache disabled: keep ONE jit wrapper per shape class so
            # jit's trace cache still reuses the traced program — a
            # fresh wrapper per call would retrace+recompile every
            # query (the exchange._step_for discipline)
            ex = self._nocache.get((digest, bucket, taps))
            if ex is None:
                ex = self._nocache.setdefault(
                    (digest, bucket, taps), jax.jit(fn))
        with _obs.TRACER.span("dispatch", kind="phase"):
            out = ex(*args)
        counts = None
        if taps:
            counts, out = out[-1], out[:-1]
        return out, (compiled_now[0] if compiled_now else 0), \
            digest, counts

    def _walk_env(self, inputs) -> Dict[str, object]:
        """The reference walk's full environment (every node output by
        name): ``run_unfused`` projects the plan outputs out of it, and
        the stats tests read from it the same count expressions the
        fused program stacks."""
        env: Dict[str, object] = {}
        for inp in self.plan.inputs:
            arrs = [jnp.asarray(a) for a in inputs[inp.name]]
            for spec, a in zip(inp.columns, arrs):
                env[spec.name] = a
            _bind_rows(env, inp)
        _eval_nodes(self.plan, env, None)
        self._engines.update(env.get(_ENGINES, {}))
        return env

    def run_unfused(self, inputs) -> tuple:
        """The REFERENCE: an op-by-op eager walk on unpadded inputs,
        every node paying its own dispatch + HBM round trip.  Same
        evaluator and exact int aggregates as the fused program, so
        the tests compare the two byte for byte.  ``run`` never calls
        it and nothing selects it."""
        env = self._walk_env(inputs)
        return tuple(env[o] for o in self.plan.outputs)

    # -------------------------------------------------------------- entry

    def run(self, inputs: Mapping[str, Sequence]) -> tuple:
        """Execute the stage as its one fused executable, recording
        ``srt_stage_fusion_total{stage,outcome}`` + a ``stage_fusion``
        journal event.  The wall is measured past
        ``block_until_ready`` (an async backend's dispatch-only time
        would lie).  The whole execution is the timeline's
        ``stage_run:<plan>`` span, with ``stage_bind``, ``dispatch``
        and ``device_wait`` under it, and a build's ``compile`` spans
        (JAX's compile events, ``observability``)."""
        from spark_rapids_tpu import observability as _obs

        with _obs.TRACER.span(f"stage_run:{self.plan.name}",
                              kind="phase") as span:
            return self._run(inputs, span)

    def _run(self, inputs: Mapping[str, Sequence], span) -> tuple:
        from spark_rapids_tpu import observability as _obs

        # data-statistics tap (ISSUE 20): ONE attribute read when the
        # stats plane is off — no observation dict, no extra outputs,
        # the exact executable PR 11 shipped
        taps = _obs.STATS.enabled
        t0 = time.monotonic_ns()
        # the event digest is the full RUN key (plan | operand
        # shapes): the stages table must not average walls across row
        # buckets
        out, compile_ns, digest, counts = self._run_fused(
            inputs, taps=taps)
        compiled = bool(compile_ns)
        with _obs.TRACER.span("device_wait", kind="phase",
                              attrs={"stage": self.plan.name}):
            jax.block_until_ready(out)
        wall_ns = time.monotonic_ns() - t0
        if span is not _obs.NOOP_SPAN:
            from spark_rapids_tpu.perf.jit_cache import bucket_rows
            rows = max((_input_rows(inputs[i.name])
                        for i in self.plan.inputs if i.bucket),
                       default=0)
            bucket = bucket_rows(rows) if rows else 0
            for k, v in (("digest", digest), ("engine", "fused"),
                         ("rows", rows), ("bucket", bucket),
                         ("pad_rows", bucket - rows)):
                span.set_attr(k, v)
        _obs.record_stage_fusion(
            self.plan.name, "fused", digest=digest,
            wall_ns=wall_ns, nodes=self.dispatch_count,
            compiled=compiled)
        stats = (self._note_stats(inputs, digest, counts)
                 if taps else None)
        # query-profile feed (ISSUE 13): one structured record per
        # stage execution while the calling thread profiles a query.
        # active() is one attribute read when profiling is off — the
        # record dict (node descriptors, pad-waste) is never built
        if _obs.PROFILER.active():
            _obs.PROFILER.note_stage(self._profile_record(
                inputs, digest=digest,
                wall_ns=wall_ns, compiled=compiled,
                compile_ns=compile_ns, stats=stats))
        return out

    def _note_stats(self, inputs, digest: str, counts) -> Optional[dict]:
        """Fold one execution's observation into the stats plane and
        return the profile's per-stage ``stats`` section.  Input row
        counts are host-known (the n_valid scalars the binder already
        computed); tapped counts arrive as the executable's int32
        vector — np.asarray is the only device sync and it reads
        values the program computed anyway."""
        import numpy as np

        from spark_rapids_tpu import observability as _obs
        spec = _tap_spec(self.plan)
        vals = []
        if counts is not None:
            vals = [int(x) for x in
                    np.asarray(counts).reshape(-1)[:len(spec)]]
        nodes = [{"node": nid, "kind": kind, "rows": v}
                 for (nid, kind, _key), v in zip(spec, vals)]
        ins, cols = [], {}
        for inp in self.plan.inputs:
            arrs = inputs.get(inp.name)
            if not arrs:
                continue
            ins.append({"name": inp.name, "rows": _input_rows(arrs)})
            cols[inp.name] = arrs[0]
        return _obs.STATS.note_stage(
            {"stage": self.plan.name,
             "plan_digest": self.plan.digest,
             "run_digest": digest, "inputs": ins, "nodes": nodes},
            columns=cols)

    def run_spilled(self, partitions: Sequence[Mapping[str, object]]
                    ) -> list:
        """ISSUE 18 seam: a ShuffleBoundary is also a SPILL boundary.
        Run this stage once per hash partition of spilled inputs —
        WITHOUT unfusing: every partition goes through the ordinary
        :meth:`run` entry, so same-bucket partitions share ONE fused
        executable (the second partition is a jit-cache hit, asserted
        by tests/test_spill.py and scripts/spill_smoke.py).

        Each element of ``partitions`` maps input name -> either a
        plain column sequence or a memory/spill.SpillHandle, whose
        batch is streamed back (recording ``srt_spill_restores_total``
        and ``spill_wait``) just-in-time for its partition, PINNED
        (victim-ineligible) while the partition runs, and stays
        registered — spillable again — afterwards; the CALLER owns
        handle close().  Returns the per-partition output tuples in
        partition order (correctness requires hash-partitioned,
        per-partition-complete inputs — the ops/out_of_core
        contract)."""
        import contextlib

        from spark_rapids_tpu.columns.column import Column
        from spark_rapids_tpu.memory.spill import SpillHandle
        outs = []
        for part in partitions:
            with contextlib.ExitStack() as pins:
                stage_inputs = {}
                for name, v in part.items():
                    cols = (pins.enter_context(v.pin())
                            if isinstance(v, SpillHandle) else v)
                    # the store serializes Column batches; stages
                    # consume raw arrays — unwrap through the
                    # logical-dtype host view (the from_numpy inverse)
                    stage_inputs[name] = tuple(
                        c.to_numpy() if isinstance(c, Column) else c
                        for c in cols)
                outs.append(self.run(stage_inputs))
        return outs

    def _profile_record(self, inputs, *, digest: str,
                        wall_ns, compiled: bool,
                        compile_ns: int = 0,
                        stats: Optional[dict] = None) -> dict:
        """The typed per-stage profile row: plan structure (node
        kinds + outputs), per-input rows/bucket/pad-waste, engine,
        wall, compile-vs-cache-hit (plus the build's own wall, for
        the attribution ledger's compile bucket), dispatch count, and
        the monotonic dispatch window the critical path orders by."""
        import numpy as np

        from spark_rapids_tpu.perf.jit_cache import bucket_rows
        ins = []
        for inp in self.plan.inputs:
            arrs = inputs.get(inp.name)
            if not arrs:
                continue
            rows = _input_rows(arrs)
            bucket = bucket_rows(rows) if inp.bucket else rows
            ins.append({"name": inp.name, "rows": rows,
                        "bucket": bucket,
                        "pad_rows": max(bucket - rows, 0)})
        t_end_ns = time.monotonic_ns()
        rec = {
            "stage": self.plan.name,
            "digest": digest,
            "engine": "fused",
            "compiled": bool(compiled),
            "compile_ns": int(compile_ns),
            "wall_ns": int(wall_ns or 0),
            "t_start_ns": t_end_ns - int(wall_ns or 0),
            "t_end_ns": t_end_ns,
            "dispatches": 1,
            "nodes_total": self.dispatch_count,
            "nodes": [{"kind": type(n).__name__,
                       "outs": list(n.outs()),
                       **({"engine": self._engines[n.out]}
                          if isinstance(n, (ir.SegmentSum, ir.Project))
                          and n.out in self._engines else {})}
                      for n in self.plan.nodes],
            "inputs": ins,
        }
        if stats is not None:
            rec["stats"] = stats
        return rec


# plan-verify gate (ISSUE 12): every distinct plan digest is verified
# ONCE before anything lowers — a malformed plan fails as a typed
# PlanVerifyError naming the offending node instead of an XLA trace
# error three layers down.  Memoized by digest so the hot path pays a
# dict hit; SPARK_RAPIDS_TPU_PLAN_VERIFY=0 is the escape hatch.
_VERIFIED: Dict[str, bool] = {}
_VERIFIED_CAP = 4096


def _verify_once(plan_or_pipeline) -> None:
    if os.environ.get("SPARK_RAPIDS_TPU_PLAN_VERIFY", "") == "0":
        return
    digest = plan_or_pipeline.digest
    if digest in _VERIFIED:
        return
    from spark_rapids_tpu.analysis import plan_verify
    if isinstance(plan_or_pipeline, ir.Pipeline):
        plan_verify.verify_pipeline(plan_or_pipeline)
    else:
        plan_verify.verify_stage(plan_or_pipeline)
    if len(_VERIFIED) >= _VERIFIED_CAP:
        for k in list(_VERIFIED)[:_VERIFIED_CAP // 2]:
            del _VERIFIED[k]
    _VERIFIED[digest] = True


# one CompiledStage per plan digest, process-wide: catalog entry
# points build plans per call, and per-instance state (the
# jit-cache-disabled _nocache memo) must survive across calls or the
# "no retrace per query" contract only holds for callers that keep
# the object themselves.  Bounded: oldest half dropped past the cap
# (plan digests are few — catalog shapes x capacity steps).
_STAGE_MEMO: "Dict[str, CompiledStage]" = {}
_STAGE_MEMO_CAP = 128


def compile_stage(plan: ir.StagePlan) -> CompiledStage:
    cs = _STAGE_MEMO.get(plan.digest)
    if cs is None:
        _verify_once(plan)
        cs = CompiledStage(plan)
        if len(_STAGE_MEMO) >= _STAGE_MEMO_CAP:
            for k in list(_STAGE_MEMO)[:_STAGE_MEMO_CAP // 2]:
                del _STAGE_MEMO[k]
        _STAGE_MEMO[plan.digest] = cs
    return cs


# ------------------------------------------------------------- pipelines


class CompiledPipeline:
    """Stages executed in order; columns carried across each boundary
    feed the next stage's matching ScanBind by NAME (single-process:
    direct handoff — the distributed runner replaces this handoff with
    the kudo socket shuffle)."""

    def __init__(self, pipeline: ir.Pipeline):
        _verify_once(pipeline)      # seam checks on top of per-stage
        self.pipeline = pipeline
        self.stages = [compile_stage(s) for s in pipeline.stages]

    def run(self, inputs: Mapping[str, Sequence]) -> tuple:
        # semantic stage cache (ISSUE 19): with the result cache
        # armed, each stage consults a content-keyed entry (plan
        # digest + input bytes) before executing — an unchanged
        # upstream stage short-circuits and only the delta recomputes
        cache = None
        from spark_rapids_tpu.perf import result_cache as _rc
        if _rc.cache_enabled():
            cache = _rc.CACHE
        feed: Dict[str, object] = {}
        out: Tuple = ()
        for cs in self.stages:
            stage_inputs = {}
            for inp in cs.plan.inputs:
                if feed and all(c.name in feed for c in inp.columns):
                    stage_inputs[inp.name] = tuple(
                        feed[c.name] for c in inp.columns)
                else:
                    stage_inputs[inp.name] = inputs[inp.name]
            if cache is not None:
                out = cache.stage_run(cs, stage_inputs)
            else:
                out = cs.run(stage_inputs)
            feed.update(zip(cs.plan.outputs, out))
        return out


def compile_pipeline(pipeline: ir.Pipeline) -> CompiledPipeline:
    return CompiledPipeline(pipeline)


def _external_inputs(pipeline: ir.Pipeline) -> list:
    """The ScanBinds a pipeline binds from its caller, in declaration
    order; a boundary-fed ScanBind (every column defined upstream) is
    not one."""
    defined = set()
    external = []
    for stage in pipeline.stages:
        for inp in stage.inputs:
            if not all(c.name in defined for c in inp.columns):
                external.append(inp)
                defined.update(c.name for c in inp.columns)
        for node in stage.nodes:
            defined.update(node.outs())
    return external


def _exchange_prefixes(pipeline: ir.Pipeline) -> list:
    return [n.prefix for s in pipeline.stages for n in s.nodes
            if isinstance(n, ir.Exchange)]


def fused_pipeline_fn(pipeline: ir.Pipeline,
                      reduce_axis: Optional[str] = None,
                      row_counts: bool = False):
    """The WHOLE pipeline as one function (boundaries elided, Reduce
    -> psum and Exchange -> all-to-all over ``reduce_axis``) for
    shard_map: a mesh rank runs ONE XLA program.  Args are the
    external inputs' columns flattened in declaration order;
    boundary-fed ScanBinds consume no args.  Every ``Mask`` is all
    true, unless ``row_counts``: then one int32 vector of true rows a
    device follows the columns for each bucketed external input, and
    its mask holds the first ``counts[axis_index]`` rows of the shard.
    The function returns the last stage's outputs, then each Exchange
    node's send counts (n_parts,), in node order.  Returns (fn,
    n_args)."""
    _verify_once(pipeline)
    external = _external_inputs(pipeline)
    counted = [i for i in external if row_counts and i.bucket]
    n_args = sum(len(i.columns) for i in external) + len(counted)
    last = pipeline.stages[-1]
    exchanges = _exchange_prefixes(pipeline)

    def fn(*args):
        env: Dict[str, object] = {}
        pos = 0
        for inp in external:
            for spec in inp.columns:
                env[spec.name] = args[pos]
                pos += 1
        for inp in counted:
            _bind_rows(env, inp, args[pos][lax.axis_index(reduce_axis)])
            pos += 1
        for stage in pipeline.stages:
            for inp in stage.inputs:
                if f"__mask__{inp.name}" not in env:
                    _bind_rows(env, inp)
            _eval_nodes(stage, env, reduce_axis)
        return (tuple(env[o] for o in last.outputs)
                + tuple(env[_SENT][p] for p in exchanges))

    return fn, n_args


class MeshPipeline:
    """A pipeline as ONE executable over the first axis of ``mesh``
    (``fused_pipeline_fn`` under ``shard_map``): each device binds its
    own shard of every bucketed input (a :class:`Padded` with
    ``shard_rows``), whose ``Mask`` reads that shard's true rows; the
    other inputs are replicated; ``Reduce`` is a psum and ``Exchange``
    an all-to-all over the axis.  The executable lives in the process
    compile cache under (pipeline digest, operand shapes, devices,
    bucket), and a run records what :meth:`CompiledStage.run` records:
    spans ``stage_run:<pipeline>``, ``stage_bind``, ``compile`` (when
    it builds), ``dispatch`` and ``device_wait`` (attribute ``stage``),
    and ``srt_stage_fusion_total``."""

    def __init__(self, pipeline: ir.Pipeline, mesh):
        self.pipeline, self.mesh = pipeline, mesh
        self.name = pipeline.name
        self.axis = mesh.axis_names[0]
        self.fn, _n_args = fused_pipeline_fn(pipeline, self.axis,
                                             row_counts=True)
        self.external = _external_inputs(pipeline)
        self.exchanges = _exchange_prefixes(pipeline)
        self.nodes = sum(len(s.nodes) for s in pipeline.stages)

    def _bind(self, inputs):
        """(args, in_specs, shape parts, largest shard bucket)."""
        import numpy as np
        from jax.sharding import PartitionSpec as P
        cols, counts, specs, parts, bucket = [], [], [], [], 0
        for inp in self.external:
            arrs = inputs[inp.name]
            if len(arrs) != len(inp.columns):
                raise ValueError(
                    f"input {inp.name!r} expects {len(inp.columns)} "
                    f"columns, got {len(arrs)}")
            if inp.bucket:
                if getattr(arrs, "shard_rows", None) is None:
                    raise ValueError(f"input {inp.name!r} is not sharded "
                                     f"over the mesh (Padded.shard_rows)")
                shard = arrs[0].shape[0] // len(arrs.shard_rows)
                bucket = max(bucket, shard)
                cols.extend(arrs)
                specs += [P(self.axis)] * len(arrs)
                counts.append(np.asarray(arrs.shard_rows, np.int32))
                parts.append((",".join(_canon_dtype(a) for a in arrs),
                              shard))
            else:
                cols.extend(arrs)
                specs += [P()] * len(arrs)
                parts.append((",".join(
                    f"{_canon_dtype(a)}{tuple(np.shape(a))}"
                    for a in arrs), 0))
        specs += [P()] * len(counts)
        return cols + counts, tuple(specs), parts, bucket

    def run(self, inputs: Mapping[str, Sequence]):
        """The last stage's outputs, and each Exchange's send counts
        by node prefix as a (devices, n_parts) numpy array."""
        import numpy as np

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from spark_rapids_tpu import observability as _obs
        from spark_rapids_tpu.perf import jit_cache as _jc
        from spark_rapids_tpu.perf.calibrate import operands_digest

        n_out = len(self.pipeline.stages[-1].outputs)
        t0 = time.monotonic_ns()
        with _obs.TRACER.span(f"stage_run:{self.name}", kind="phase"):
            with _obs.TRACER.span("stage_bind", kind="phase") as span:
                args, specs, parts, bucket = self._bind(inputs)
                span.set_attr("bucket", bucket)
            devices = ",".join(str(d.id) for d in self.mesh.devices.flat)
            digest = (f"{self.pipeline.digest}|{operands_digest(parts)}"
                      f"|{self.axis}:{devices}")
            compiled_now = []

            def build():
                t_build = time.monotonic_ns()
                out_specs = ((P(),) * n_out
                             + (P(self.axis),) * len(self.exchanges))
                ex = jax.jit(shard_map(
                    self.fn, mesh=self.mesh, in_specs=specs,
                    out_specs=out_specs)).lower(*args).compile()
                compiled_now.append(time.monotonic_ns() - t_build)
                return ex

            ex = _jc.CACHE.get_or_build(f"pipeline.{self.name}", digest,
                                        bucket, build)
            with _obs.TRACER.span("dispatch", kind="phase"):
                out = ex(*args)
            with _obs.TRACER.span("device_wait", kind="phase",
                                  attrs={"stage": self.name}):
                jax.block_until_ready(out)
        _obs.record_stage_fusion(
            self.name, "fused", digest=digest,
            wall_ns=time.monotonic_ns() - t0, nodes=self.nodes,
            compiled=bool(compiled_now))
        n_dev = self.mesh.devices.size
        sent = {p: np.asarray(s).reshape(n_dev, -1)
                for p, s in zip(self.exchanges, out[n_out:])}
        return out[:n_out], sent
