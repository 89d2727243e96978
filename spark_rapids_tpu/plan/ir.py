"""Stage IR: the typed plan nodes whole-stage fusion compiles
(ISSUE 11 tentpole).

A *stage* is everything a query does between two shuffle boundaries.
The hand-fused TPC-DS pipelines in models/tpcds.py prove the shape —
scan, join probe, filter, segment aggregate, sort — composes into ONE
XLA program; this module makes that composition a data structure
instead of a hand-written kernel, so the compiler (plan/compiler.py)
can fuse ANY stage the same way, key the executable in the PR-4
jit_cache, and new operators (window functions, rollup/cube) become
IR nodes instead of new hand kernels.

Design rules:

  * nodes are frozen dataclasses with a canonical ``key()`` string;
    the stage digest is a sha1 over every node's key, so two builds of
    the same logical stage — in different processes, sessions, or
    plan-object identities — hit the same compiled executable;
  * expressions (`Col`/`Lit`/`Bin`/`Un`/`Where`/`Idx`/...) are scalarish
    columnar algebra: they evaluate to jnp arrays with EXACTLY the
    dtype-promotion behavior the hand kernels had (python literals
    stay weak-typed; `Lit(v, dtype)` pins a dtype like ``jnp.int64(v)``
    did), which is what makes fused results byte-identical to the
    hand-fused oracles;
  * static shapes only: joins are the fixed-capacity device probe
    (`ops/device_join.inner_join_device`), filters are masks, group
    tables are sized by the query's domain — the same TPU-first
    decisions the hand pipelines made;
  * `Reduce` marks the cross-shard reduction point: identity on a
    single chip, `lax.psum` under shard_map, and *replaced by the kudo
    exchange* in the multi-process runner — one plan, three execution
    modes that cannot drift;
  * `ShuffleBoundary` is the typed seam between stages of a
    `Pipeline`: the compiler fuses everything between boundaries into
    one executable, and the distributed runner ships the boundary's
    columns over the socket shuffle.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ------------------------------------------------------------- expressions


class Expr:
    """Base class for stage expressions (columnar algebra)."""

    def key(self) -> str:  # pragma: no cover - abstract
        raise NotImplementedError


def _k(e) -> str:
    """Canonical key of an Expr operand (plain ints/strings allowed as
    static parameters)."""
    return e.key() if isinstance(e, Expr) else repr(e)


@dataclass(frozen=True)
class Col(Expr):
    """Reference to a bound column (a scan-bind column, a node output,
    or a join-probe output like ``j.li``)."""
    name: str

    def key(self):
        return f"c({self.name})"


@dataclass(frozen=True)
class Lit(Expr):
    """Literal. ``dtype=None`` stays a weak python scalar (promotes
    exactly like a literal in the hand kernels); a dtype string
    ('int32', 'int64', 'float64', ...) pins it like ``jnp.int64(v)``."""
    value: object
    dtype: Optional[str] = None

    def key(self):
        return f"l({self.value!r}:{self.dtype})"


@dataclass(frozen=True)
class Bin(Expr):
    """Binary op: add sub mul floordiv mod and or eq ne lt le gt ge
    max min."""
    op: str
    a: Expr
    b: Expr

    def key(self):
        return f"b({self.op},{_k(self.a)},{_k(self.b)})"


@dataclass(frozen=True)
class Un(Expr):
    """Unary op: neg, not, i32/i64/f64/b (casts), sum (full reduction
    to a scalar)."""
    op: str
    a: Expr

    def key(self):
        return f"u({self.op},{_k(self.a)})"


@dataclass(frozen=True)
class Where(Expr):
    cond: Expr
    a: Expr
    b: Expr

    def key(self):
        return f"w({_k(self.cond)},{_k(self.a)},{_k(self.b)})"


@dataclass(frozen=True)
class Idx(Expr):
    """Lookup: ``src[idx]`` — dense-dimension lookups and join-pair
    gathers, through ``ops.dense_lookup.lookup``: an exact one-hot
    product on the matrix unit for integer and boolean 1-D tables
    under a 1-D integer index while ``rows x 8-bit limbs`` of the
    tables that share the index is within ``DENSE_MAX_TABLE_LIMBS``,
    ``src[idx]`` (a gather) for floats, longer tables, tables that
    are not 1-D and a scalar index.  The ``Idx`` of a stage that share
    an index expression and a table length are evaluated together, so
    they share the index's one-hots."""
    src: Expr
    idx: Expr

    def key(self):
        return f"i({_k(self.src)},{_k(self.idx)})"


@dataclass(frozen=True)
class IsIn(Expr):
    """``a IN (keys)``: True where ``a`` equals one of the entries of
    the short 1-D ``keys`` (a static length, one compare each) — the
    fact side of a dynamically pruned join, whose dimension was
    filtered first and handed over its surviving keys."""
    a: Expr
    keys: Expr

    def key(self):
        return f"in({_k(self.a)},{_k(self.keys)})"


@dataclass(frozen=True)
class Mask(Expr):
    """Row-validity of a bucketed input: True for real rows, False for
    the pad tail (``arange(bucket) < n_valid``).  All-true for
    unbucketed inputs.  Plans AND this into their keep conditions so
    pad rows can never reach an aggregate."""
    input: str

    def key(self):
        return f"m({self.input})"


@dataclass(frozen=True)
class Arange(Expr):
    n: int
    dtype: str = "int64"

    def key(self):
        return f"a({self.n}:{self.dtype})"


@dataclass(frozen=True)
class Sl(Expr):
    """Static slice ``x[start:stop]`` (ORDER BY ... LIMIT)."""
    a: Expr
    start: int
    stop: int

    def key(self):
        return f"s({_k(self.a)},{self.start},{self.stop})"


@dataclass(frozen=True)
class Stack(Expr):
    """``jnp.stack`` of scalar expressions (q9's bucket vectors)."""
    parts: Tuple[Expr, ...]

    def key(self):
        return "k(" + ",".join(_k(p) for p in self.parts) + ")"


# ------------------------------------------------------------------- nodes


class Node:
    """Base class for stage nodes.  ``outs()`` names every column the
    node defines; ``key()`` is the canonical digest contribution."""

    def outs(self) -> Tuple[str, ...]:  # pragma: no cover - abstract
        raise NotImplementedError

    def key(self) -> str:  # pragma: no cover - abstract
        raise NotImplementedError


@dataclass(frozen=True)
class Project(Node):
    """Bind ``out`` to an expression (projections AND filter masks —
    a filter in this static-shape world is a boolean column)."""
    out: str
    expr: Expr

    def outs(self):
        return (self.out,)

    def key(self):
        return f"P({self.out}={_k(self.expr)})"


@dataclass(frozen=True)
class JoinProbe(Node):
    """Fixed-capacity device inner-join probe
    (ops/device_join.inner_join_device, inlined instead of
    round-tripped: two sorts of both sides' keys merge each left row
    with its run of right rows, scans give the bounds).  Defines
    ``<p>.li`` ``<p>.ri`` (int32 pair indices), ``<p>.valid`` (bool per
    slot) and ``<p>.total`` (int64 TRUE pair count; ``total >
    capacity`` is the overflow signal the capacity-retry driver doubles
    on)."""
    prefix: str
    left: Expr
    right: Expr
    capacity: int
    left_valid: Optional[Expr] = None
    right_valid: Optional[Expr] = None

    def outs(self):
        p = self.prefix
        return (f"{p}.li", f"{p}.ri", f"{p}.valid", f"{p}.total")

    def key(self):
        return (f"J({self.prefix},{_k(self.left)},{_k(self.right)},"
                f"{self.capacity},{_k(self.left_valid)},"
                f"{_k(self.right_valid)})")


@dataclass(frozen=True)
class SegmentSum(Node):
    """Hash-aggregate workhorse: ``ops.segment_sum.segment_sum(value,
    ids, num_segments)`` over dictionary-encoded group ids: an exact
    one-hot product on the matrix unit for integer and boolean values
    into up to ``DENSE_MAX_SEGMENTS`` groups, ``jax.ops.segment_sum``
    (a scatter-add) for floats and larger group tables.  A value
    ``Un("i64", <predicate>)`` is counted from the booleans."""
    out: str
    value: Expr
    ids: Expr
    num_segments: int

    def outs(self):
        return (self.out,)

    def key(self):
        return (f"G({self.out}={_k(self.value)}@{_k(self.ids)}"
                f"/{self.num_segments})")


@dataclass(frozen=True)
class Sort(Node):
    """``lax.sort`` over equal-length 1-D operands; the first
    ``num_keys`` operands are the lexicographic sort keys (ORDER BY)."""
    names: Tuple[str, ...]
    operands: Tuple[Expr, ...]
    num_keys: int

    def outs(self):
        return self.names

    def key(self):
        return ("S(" + ",".join(self.names) + "="
                + ",".join(_k(o) for o in self.operands)
                + f"/{self.num_keys})")


@dataclass(frozen=True)
class Reduce(Node):
    """Cross-shard reduction point: identity single-chip, psum under
    shard_map, REPLACED by the kudo exchange in the distributed
    runner.  kind 'sum' (exact int64 partials — any reduction order is
    byte-identical) or 'any' (overflow flags)."""
    out: str
    value: Expr
    kind: str = "sum"

    def outs(self):
        return (self.out,)

    def key(self):
        return f"R({self.out}={_k(self.value)}:{self.kind})"


@dataclass(frozen=True)
class Exchange(Node):
    """Spark's ``Exchange hashpartitioning(keys)``: each row that
    ``valid`` holds goes to the partition Spark's murmur3 of ``keys``
    (seed 42, chained over the keys) pmod the number of partitions,
    with the named ``columns``.  Defines ``<p>.<column>`` for each of
    them, the rows this partition received, and ``<p>.valid``.  One
    chip is one partition: the node lowers to nothing there (the
    outputs are the inputs and ``valid``).  Under ``shard_map`` it is
    ``parallel.exchange.exchange`` over the mesh axis, ``capacity``
    rows a (source, destination) slot; rows past it are dropped and
    show in the send counts the mesh run returns."""
    prefix: str
    keys: Tuple[Expr, ...]
    columns: Tuple[str, ...]
    valid: Expr
    capacity: int

    def outs(self):
        return tuple(f"{self.prefix}.{c}" for c in self.columns) + (
            f"{self.prefix}.valid",)

    def key(self):
        return (f"X({self.prefix},{','.join(_k(k) for k in self.keys)}"
                f";{','.join(self.columns)},{_k(self.valid)},"
                f"{self.capacity})")


@dataclass(frozen=True)
class WindowSlice(Node):
    """A zone-map scan of the bucketed input ``input``, whose true rows
    are in ascending order of its column ``order`` (a resident fact
    ordered at load: ``plan.compiler.Padded.window``): the
    ``capacity`` rows from the first true row whose ``order`` is at
    least ``lo``, found by a binary search over the true rows.
    Defines ``<prefix>.<column>`` for each of ``columns``,
    ``<prefix>.valid`` (the slice's true rows) and ``<prefix>.over``:
    True where the true rows whose ``order`` lies in [``lo``, ``hi``]
    are more than ``capacity``, so that the slice misses some.  A
    slice that would run past the bucket starts earlier, and rows
    outside [``lo``, ``hi``] are in it: the caller's own filter drops
    them, as it would over the whole input.  On a mesh each device
    searches and slices its own shard."""
    prefix: str
    input: str
    order: Expr
    lo: Expr
    hi: Expr
    columns: Tuple[str, ...]
    capacity: int

    def outs(self):
        return tuple(f"{self.prefix}.{c}" for c in self.columns) + (
            f"{self.prefix}.valid", f"{self.prefix}.over")

    def key(self):
        return (f"Z({self.prefix},{self.input},{_k(self.order)},"
                f"{_k(self.lo)},{_k(self.hi)};{','.join(self.columns)},"
                f"{self.capacity})")


@dataclass(frozen=True)
class WindowSum(Node):
    """Window aggregate ``sum(value) OVER (PARTITION BY part)``
    broadcast back to every row: segment-sum + gather."""
    out: str
    part: Expr
    value: Expr
    num_partitions: int

    def outs(self):
        return (self.out,)

    def key(self):
        return (f"WS({self.out}={_k(self.value)}@{_k(self.part)}"
                f"/{self.num_partitions})")


@dataclass(frozen=True)
class WindowRank(Node):
    """``rank() OVER (PARTITION BY part ORDER BY order ASC)`` (callers
    negate for DESC), 0-based, ties broken by row index — one
    lax.sort + cummax, no data-dependent loops."""
    out: str
    part: Expr
    order: Expr

    def outs(self):
        return (self.out,)

    def key(self):
        return f"WR({self.out}={_k(self.order)}@{_k(self.part)})"


@dataclass(frozen=True)
class Rollup(Node):
    """GROUP BY ROLLUP/CUBE over two key columns with cardinalities
    ``cards`` — the grouping-sets aggregate as one node.  Defines
    ``<p>.sum0``/``<p>.cnt0`` (k1 x k2 finest level), ``<p>.sum1``/
    ``<p>.cnt1`` (per-k1, k2 rolled up), ``<p>.sumt``/``<p>.cntt``
    (grand total), and for mode='cube' additionally ``<p>.sum2``/
    ``<p>.cnt2`` (per-k2).  Coarser levels fold from the finest level's
    exact int sums, so every level is byte-stable in any order."""
    prefix: str
    keys: Tuple[Expr, Expr]
    cards: Tuple[int, int]
    value: Expr
    mask: Expr
    mode: str = "rollup"

    def outs(self):
        p = self.prefix
        base = (f"{p}.sum0", f"{p}.cnt0", f"{p}.sum1", f"{p}.cnt1",
                f"{p}.sumt", f"{p}.cntt")
        if self.mode == "cube":
            base = base + (f"{p}.sum2", f"{p}.cnt2")
        return base

    def key(self):
        return (f"U({self.prefix},{_k(self.keys[0])},{_k(self.keys[1])}"
                f",{self.cards},{_k(self.value)},{_k(self.mask)},"
                f"{self.mode})")


# ------------------------------------------------------------------ inputs


@dataclass(frozen=True)
class ColSpec:
    """One bound input column.  ``pad`` is the value the compiler pads
    the bucket tail with — join-key columns use side-specific
    sentinels (-1 vs -2) so pad rows can never match each other, and
    dense-lookup indices pad with an in-range value while ``Mask``
    kills their contribution."""
    name: str
    pad: int = 0


@dataclass(frozen=True)
class ScanBind(Node):
    """Stage input: binds caller arrays to named columns.  Bucketed
    inputs (facts) are padded to the next power-of-two row bucket and
    carry a traced ``n_valid`` scalar (so nearby batch sizes share one
    executable — the PR-4 contract); unbucketed inputs (group tables,
    dims, scalars) keep exact shapes, folded into the digest."""
    name: str
    columns: Tuple[ColSpec, ...]
    bucket: bool = True

    def outs(self):
        return tuple(c.name for c in self.columns)

    def key(self):
        cols = ",".join(f"{c.name}:{c.pad}" for c in self.columns)
        return f"I({self.name},[{cols}],{int(self.bucket)})"


@dataclass(frozen=True)
class ShuffleBoundary:
    """Typed seam between two stages of a Pipeline: ``carry`` names the
    columns that cross (single-chip: direct handoff; distributed: kudo
    tables over the socket shuffle).  Everything on either side fuses
    into its own single executable."""
    carry: Tuple[str, ...]

    def key(self):
        return "B(" + ",".join(self.carry) + ")"


# ------------------------------------------------------------------- plans


@dataclass(frozen=True)
class StagePlan:
    """One fusable stage: inputs, an SSA-ordered node list (each node
    may only reference columns defined above it), and named outputs."""
    name: str
    inputs: Tuple[ScanBind, ...]
    nodes: Tuple[Node, ...]
    outputs: Tuple[str, ...]

    @property
    def digest(self) -> str:
        s = ";".join([self.name]
                     + [i.key() for i in self.inputs]
                     + [n.key() for n in self.nodes]
                     + list(self.outputs))
        return hashlib.sha1(s.encode()).hexdigest()[:16]

    def validate(self) -> "StagePlan":
        defined = set()
        for i in self.inputs:
            defined.update(i.outs())
        for n in self.nodes:
            for out in n.outs():
                if out in defined:
                    raise ValueError(f"duplicate column {out!r} in "
                                     f"stage {self.name!r}")
                defined.add(out)
        missing = [o for o in self.outputs if o not in defined]
        if missing:
            raise ValueError(f"stage {self.name!r} outputs undefined "
                             f"columns {missing}")
        return self


@dataclass(frozen=True)
class Pipeline:
    """Stages joined by typed shuffle boundaries:
    ``stages[i] -> boundaries[i] -> stages[i+1]``.  A stage after a
    boundary binds the carried columns through a ScanBind whose column
    names EQUAL the carry names (the compiler feeds them by name)."""
    name: str
    stages: Tuple[StagePlan, ...]
    boundaries: Tuple[ShuffleBoundary, ...] = field(default=())

    @property
    def digest(self) -> str:
        s = ";".join([self.name] + [st.digest for st in self.stages]
                     + [b.key() for b in self.boundaries])
        return hashlib.sha1(s.encode()).hexdigest()[:16]
