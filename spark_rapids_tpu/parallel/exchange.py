"""ICI fast-path shuffle: hash-partition exchange over a jax mesh axis.

The reference's data-parallel story delegates cross-node movement to
Spark's byte-blob shuffle (SURVEY.md §2.2 checklist).  On TPU, chips in a
slice are directly connected (ICI), so the idiomatic exchange is NOT bytes
through the host: columns stay arrays and move with jax.lax.all_to_all
inside shard_map, with XLA scheduling the collective.

Because XLA collectives need static shapes, partitions are exchanged in
fixed-capacity slots: each device sends an (n_parts, capacity, ...) padded
block per column plus true counts; receivers get (n_parts*capacity, ...)
padded rows and a validity mask.  Capacity is the caller's budget — the
same memory-budgeted-chunking philosophy as the reference's
get_json_object batching (SURVEY.md §3.4).  Rows beyond capacity are
dropped from the padded slots, but true per-destination sizes travel
alongside the data, so overflow is detectable, never silent.

Overflow handling is CENTRALIZED in `with_capacity_retry` below: wrap a
capacity-parameterized program factory and the driver re-runs with a
doubled budget whenever the program reports overflow — the same
retry-with-larger-budget loop the reference's OOM machinery enforces on
the JVM side (SparkResourceAdaptor split-and-retry).  Callers no longer
hand-roll the check.
"""

from __future__ import annotations

import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from spark_rapids_tpu import observability as _obs

_I32 = jnp.int32


def build_padded_sends(arrays: Sequence[jnp.ndarray], part: jnp.ndarray,
                       n_parts: int, capacity: int):
    """Pack rows into per-destination padded slots.

    arrays: per-column row-major arrays (rows, ...) sharing axis 0.
    part:   (rows,) int32 destination partition per row, in
            ``[0, n_parts]``: ``n_parts`` sends the row nowhere (a pad
            row of a bucketed shard).
    Returns (sends, counts): sends[i] has shape (n_parts, capacity, ...);
    counts is (n_parts,) true row counts (may exceed capacity — caller
    checks).

    Slot (p, r) holds the r-th row, in row order, of those bound for
    p, for r under ``min(counts[p], capacity)``, and zeros past it.
    The layout is ONE stable ``lax.sort`` on the destination with the
    columns as payloads, then ``n_parts`` slices of ``capacity`` rows
    at each destination's start: a (partition, rank) slot is the
    row's stable sorted position.  No scatter: on a TPU a scatter
    costs some 113 ns an element (PERF.md, PR 28), a sort with its
    payloads some 7.5 (PR 39)."""
    pi = part.astype(_I32)
    counts = jnp.sum(pi[:, None] == jnp.arange(n_parts, dtype=_I32),
                     axis=0, dtype=_I32)
    starts = jnp.cumsum(counts) - counts
    flat = [a.reshape(a.shape[0], -1) for a in arrays]
    widths = [f.shape[1] for f in flat]
    cols = [f[:, j] for f in flat for j in range(f.shape[1])]
    cols = lax.sort((pi,) + tuple(cols), num_keys=1, is_stable=True)[1:]
    live = jnp.arange(capacity, dtype=_I32)[None, :] < jnp.minimum(
        counts, capacity)[:, None]
    sends, at = [], 0
    for a, w in zip(arrays, widths):
        packed = []
        for c in cols[at:at + w]:
            c = jnp.concatenate([c, jnp.zeros(capacity, c.dtype)])
            block = jnp.stack([lax.dynamic_slice(c, (starts[p],),
                                                 (capacity,))
                               for p in range(n_parts)])
            packed.append(jnp.where(live, block, jnp.zeros((), c.dtype)))
        at += w
        sends.append(jnp.stack(packed, axis=-1).reshape(
            (n_parts, capacity) + a.shape[1:]))
    return sends, counts


def hash_partitions(keys: Sequence[jnp.ndarray], valid: jnp.ndarray,
                    n_parts: int) -> jnp.ndarray:
    """Spark's ``HashPartitioning(keys, n_parts)``: ``pmod(murmur3(keys,
    seed 42), n_parts)`` per row (``ops/hash.py``'s Spark-exact kernel,
    each key hashed as its own type, the seed chained across them), and
    ``n_parts`` (sent nowhere) where ``valid`` is False."""
    from spark_rapids_tpu.columns import dtypes
    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.ops.hash import murmur3_32
    rows = int(keys[0].shape[0])
    h = murmur3_32([Column(dtypes.from_numpy(k.dtype), rows, data=k)
                    for k in keys]).data
    return jnp.where(valid, h % n_parts, n_parts).astype(_I32)


def exchange(arrays: Sequence[jnp.ndarray], part: jnp.ndarray,
             axis_name: str, n_parts: int, capacity: int):
    """All-to-all hash exchange inside shard_map.

    Each device keeps rows with part == its own index after the exchange.
    Returns (received arrays each (n_parts*capacity, ...), valid mask
    (n_parts*capacity,), total_received (int32 scalar), send_counts
    (n_parts,) int32 — the TRUE outbound sizes; any entry > capacity means
    rows were dropped and the caller must retry with a larger budget)."""
    sends, send_counts = build_padded_sends(arrays, part, n_parts, capacity)
    recv_counts = jax.lax.all_to_all(
        send_counts.reshape(n_parts, 1), axis_name, split_axis=0,
        concat_axis=0).reshape(n_parts)
    recv_counts = jnp.minimum(recv_counts, capacity)
    received = []
    for s in sends:
        r = jax.lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0)
        received.append(r.reshape((n_parts * capacity,) + s.shape[2:]))
    slot_idx = jnp.arange(n_parts * capacity, dtype=_I32) % capacity
    src_idx = jnp.arange(n_parts * capacity, dtype=_I32) // capacity
    valid = slot_idx < recv_counts[src_idx]
    return received, valid, jnp.sum(recv_counts).astype(_I32), send_counts


class CapacityExceeded(RuntimeError):
    """Raised when a budgeted SPMD program still overflows at the retry
    ceiling (the analog of GpuSplitAndRetryOOM escaping the retries).

    ``send_counts`` carries the observed overflow indicator from the
    last attempt — for the raw exchange that is the TRUE per-destination
    row counts, so the caller (and the journal) can see HOW FAR over
    budget the exchange was, not just that it overflowed."""

    def __init__(self, capacity: int, doublings: int,
                 send_counts=None, reason: str = "overflowed"):
        super().__init__(
            f"exchange capacity {capacity} still {reason} after "
            f"{doublings} doublings"
            + (f" (observed counts {send_counts})"
               if send_counts is not None else ""))
        self.capacity = capacity
        self.doublings = doublings
        self.send_counts = send_counts


def _observed_counts(indicator: np.ndarray):
    """The true per-destination sizes carried into CapacityExceeded
    when the caller opted into a counts indicator (bounded: the first
    64 entries)."""
    if indicator.size:
        return [int(x) for x in indicator.reshape(-1)[:64]]
    return None


# ------------------------------------------------- pluggable transport
# The SPMD exchange above moves arrays over ICI inside one process.
# Table-granularity exchanges (the kudo shuffle) go through a pluggable
# TRANSPORT instead: by default an in-process loopback that still
# round-trips the real wire bytes (partition -> kudo write -> kudo
# read/merge), and — when the distributed runtime installs its
# ShuffleService (spark_rapids_tpu/distributed/) — TCP/unix-socket
# links between worker processes.  Callers write against
# ``exchange_tables`` and never know which side of a process boundary
# their peers live on.


class InProcessKudoTransport:
    """Single-process loopback transport: every destination is this
    process.  Partitions still serialize through the kudo wire format
    and merge back through ``read_tables``/``merge_to_table``, so the
    byte path (KTRX trace context, KCRC trailers included) is
    identical to the socket transport's — only the socket is elided."""

    rank = 0
    world = 1

    def exchange(self, op_id: int, tables_by_dest, fields=None):
        import io

        from spark_rapids_tpu.shuffle import kudo as _kudo
        from spark_rapids_tpu.shuffle.schema import schema_of_table
        if len(tables_by_dest) != 1:
            raise ValueError(
                "in-process transport has world=1; got "
                f"{len(tables_by_dest)} destinations (install a "
                "distributed transport via set_table_transport)")
        table = tables_by_dest[0]
        if fields is None:
            fields = schema_of_table(table)
        buf = io.BytesIO()
        _kudo.write_to_stream_with_metrics(
            table.columns, buf, 0, table.num_rows)
        buf.seek(0)
        return _kudo.merge_to_table(_kudo.read_tables(buf), fields)

    def allgather(self, op_id: int, table, fields=None):
        return self.exchange(op_id, [table], fields)


_TABLE_TRANSPORT = [None]


def set_table_transport(transport) -> object:
    """Install the process's table transport (the distributed runtime
    registers its ShuffleService here; ``None`` restores the
    in-process loopback).  Returns the prior transport."""
    prior = _TABLE_TRANSPORT[0]
    _TABLE_TRANSPORT[0] = transport
    return prior


def table_transport():
    """The installed transport, or the in-process loopback default."""
    t = _TABLE_TRANSPORT[0]
    if t is None:
        t = _TABLE_TRANSPORT[0] = InProcessKudoTransport()
    return t


def exchange_tables(op_id: int, tables_by_dest, fields=None):
    """All-to-all at table granularity over the installed transport:
    ``tables_by_dest[d]`` goes to rank ``d``; returns the merged Table
    of everything addressed to THIS rank, partitions concatenated in
    source-rank order (deterministic merge — the property the
    byte-identity gates assert)."""
    return table_transport().exchange(op_id, tables_by_dest, fields)


def allgather_table(op_id: int, table, fields=None):
    """Every rank contributes ``table``; every rank receives the
    rank-ordered concatenation of all contributions."""
    return table_transport().allgather(op_id, table, fields)


def with_capacity_retry(make_step: Callable[[int], Callable],
                        initial_capacity: int, *,
                        max_doublings: int = 6,
                        overflow_index: int = -1,
                        policy=None,
                        counts_indicator: bool = False,
                        check: Optional[Callable[[], None]] = None):
    """Centralized overflow retry for fixed-capacity SPMD programs.

    make_step(capacity) must return a callable whose output tuple
    carries an overflow indicator at `overflow_index`.  By default it
    is a truthiness flag (any shape; any true/non-zero element means
    rows were dropped).  With ``counts_indicator=True`` the indicator
    is instead the RAW send_counts array: the driver compares it
    against the current capacity itself, and a terminal
    CapacityExceeded reports the true per-destination sizes.  (The
    interpretation is an explicit opt-in — an integer 0/1 flag under
    the default stays a flag.)  The wrapper runs the program, checks
    the indicator on the host, and re-builds at double the capacity
    until clean — compilation per capacity is cached by jit, so
    steady-state workloads pay the retry only while the budget is
    learning.

    The attempt loop rides the SAME RetryPolicy the task-level retry
    drivers use (robustness/retry.py): `policy` bounds attempts
    (default ``max_doublings + 1``), applies its backoff between
    rebuilds, and its wall-clock deadline — a deadline hit raises
    CapacityExceeded early instead of compiling ever-larger programs.

    ``check`` (optional) runs at the top of EVERY capacity attempt —
    the elastic fleet passes ``QueryContext.check_cancel`` here so a
    speculative re-execution whose original arrived mid-retry unwinds
    through the cooperative cancel machinery instead of compiling the
    next doubling for a result nobody wants.

    Returns run(*args) -> (outputs, capacity_used)."""
    from spark_rapids_tpu.perf import jit_cache as _jc
    from spark_rapids_tpu.robustness.retry import RetryPolicy
    steps = {}
    pol = policy or RetryPolicy(max_attempts=max_doublings + 1,
                                base_backoff_s=0.0)

    def _step_for(cap: int):
        """Capacity-parameterized programs live in the process compile
        cache (perf/jit_cache.py): one entry per (factory, capacity),
        so steady-state budgets survive across driver instances, show
        up in srt_jit_cache_* stats, and participate in LRU eviction.
        The factory object itself is the entry owner — identity-checked
        on hits, so a recycled id() can never resurrect a stale step."""
        if not _jc.CACHE.enabled():
            if cap not in steps:
                steps[cap] = make_step(cap)
            return steps[cap]
        return _jc.CACHE.get_or_build(
            "exchange.step", f"factory@{id(make_step)}", cap,
            lambda: make_step(cap), owner=make_step,
            counts_compile=False)

    def run(*args):
        # stage-level span: one per driver invocation, covering every
        # capacity attempt (per-attempt sub-spans would double-count
        # the final successful run's time)
        with _obs.TRACER.span("exchange_capacity_retry",
                              kind="stage") as sp:
            cap = int(initial_capacity)
            t0 = pol.clock()
            attempt = 0
            lost_ns = 0
            prev_backoff = 0.0
            while True:
                if check is not None:
                    check()
                attempt_t0 = time.monotonic_ns()
                out = _step_for(cap)(*args)
                indicator = np.asarray(out[overflow_index])
                if counts_indicator:
                    overflowed = bool(np.any(indicator > cap))
                else:
                    overflowed = bool(np.any(indicator))
                if not overflowed:
                    sp.set_attr("capacity", cap)
                    sp.set_attr("attempts", attempt + 1)
                    if attempt:
                        _obs.record_retry_episode(
                            "exchange_capacity", attempts=attempt + 1,
                            retries=attempt, splits=0,
                            max_split_depth=0, lost_ns=lost_ns,
                            outcome="success",
                            errors=["CapacityOverflow"] * attempt)
                    return out, cap
                attempt += 1
                lost_ns += time.monotonic_ns() - attempt_t0
                deadline_hit = (pol.deadline_s is not None
                                and pol.clock() - t0 >= pol.deadline_s)
                if attempt >= pol.max_attempts or deadline_hit:
                    counts = (_observed_counts(indicator)
                              if counts_indicator else None)
                    sp.set_attr("capacity", cap)
                    sp.set_attr("overflowed", True)
                    _obs.JOURNAL.emit("exchange_capacity_exceeded",
                                      capacity=cap,
                                      doublings=attempt - 1,
                                      send_counts=counts)
                    _obs.record_retry_episode(
                        "exchange_capacity", attempts=attempt,
                        retries=attempt, splits=0, max_split_depth=0,
                        lost_ns=lost_ns, outcome="exhausted:deadline"
                        if deadline_hit else "exhausted:attempts",
                        errors=["CapacityOverflow"] * attempt)
                    raise CapacityExceeded(
                        cap, attempt - 1, send_counts=counts,
                        reason="over deadline" if deadline_hit
                        else "overflowed")
                _obs.record_exchange_doubling(cap, cap * 2, attempt - 1)
                # thread the previous pause through so jittered
                # policies get true decorrelated backoff (retry.py)
                backoff = pol.backoff_for(attempt, prev_backoff)
                prev_backoff = backoff
                if backoff > 0:
                    pol.sleep(backoff)
                cap *= 2

    return run
