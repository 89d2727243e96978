"""Process-wide kernel compile cache with shape bucketing.

The cache answers one question for the hot paths: "I need THIS kernel
for THIS schema layout at THIS row count — give me an executable
without recompiling".  Three mechanisms make that cheap:

  * **Row bucketing** — row counts are rounded up to the next power of
    two before keying, and operands are zero-padded to the bucket, so
    repeated batches of nearby sizes share one compiled executable.
    Padded output rows are sliced off by the caller.
  * **AOT compilation** — a miss runs ``jax.jit(fn).lower(*args)
    .compile()`` once and stores the resulting executable; a hit calls
    it directly, so a hit can never trigger XLA compilation (the
    recompile-count tests and ``make perf-smoke`` assert on exactly
    this property via :meth:`JitCache.stats`).
  * **Buffer donation** — the padded operands are throwaway copies, so
    on backends that honor donation (TPU) they are donated to the
    executable and the pad cost is not also an HBM residency cost.

Eviction is LRU under two budgets: an entry count and an estimated
byte footprint (the sum of operand bytes per entry — a proxy for
executable + workspace size; XLA does not expose the true number
portably).  Every hit/miss/eviction also lands in the observability
registry (``srt_jit_cache_*``) when metrics are enabled.
"""

from __future__ import annotations

import hashlib
import os
import time
from collections import OrderedDict
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.analysis.lockdep import make_rlock

_MIN_BUCKET = 8


def cache_enabled() -> bool:
    """Dynamic env check so operators can flip the cache off per run
    (``SPARK_RAPIDS_TPU_JIT_CACHE=0``) without code changes."""
    return os.environ.get("SPARK_RAPIDS_TPU_JIT_CACHE", "1") != "0"


def enable_persistent_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.
    Entry points that run on the chip (chip_smoke.py, bench.py,
    bench_all.py) call this before their first compile; importing the
    package never does.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX
    reads it itself and nothing here sets another directory; otherwise
    the cache lives at ``<checkout>/.jax_cache`` — a fixed path, because
    the path is part of the cache key."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def bucket_rows(n: int, min_bucket: int = _MIN_BUCKET) -> int:
    """Power-of-two row bucket: smallest 2^k >= n (floor min_bucket)."""
    if n <= min_bucket:
        return min_bucket
    return 1 << (int(n) - 1).bit_length()


def pad_axis0(arr: jnp.ndarray, bucket: int) -> jnp.ndarray:
    """Zero-pad the leading (rows) axis up to ``bucket``.  The copy is
    intentional: the padded array is a throwaway the compiled kernel
    may take by donation.  When the row count already equals the
    bucket, donation-active backends (TPU — the same condition
    cached_call uses) still get a copy: an executable compiled with
    donation donates whatever buffer it is handed, and handing it the
    CALLER'S live column buffer would invalidate the caller's data.
    Backends that ignore donation (CPU) keep the zero-copy fast path."""
    n = int(arr.shape[0])
    if n == bucket:
        if jax.default_backend() == "tpu":
            return jnp.array(arr, copy=True)
        return arr
    widths = [(0, bucket - n)] + [(0, 0)] * (arr.ndim - 1)
    return jnp.pad(arr, widths)


def schema_digest(schema: Sequence, nullable: Sequence[bool] = (),
                  extra: str = "") -> str:
    """Stable digest of a schema layout: one (kind, scale) pair per
    column plus the nullability pattern (validity presence changes the
    kernel's pytree signature) plus a free-form discriminator."""
    parts = ";".join(f"{dt.kind}:{dt.scale}" for dt in schema)
    nulls = "".join("1" if b else "0" for b in nullable)
    s = f"{parts}|{nulls}|{extra}"
    return hashlib.sha1(s.encode()).hexdigest()[:16]


def _tree_nbytes(tree) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nb = getattr(leaf, "nbytes", None)
        if nb is not None:
            total += int(nb)
    return total


class _Entry:
    __slots__ = ("fn", "cost_bytes", "owner", "compile_ns")

    def __init__(self, fn, cost_bytes, owner, compile_ns):
        self.fn = fn
        self.cost_bytes = int(cost_bytes)
        self.owner = owner
        self.compile_ns = int(compile_ns)


class JitCache:
    """LRU registry of compiled kernels keyed by
    (kernel name, digest, row bucket)."""

    def __init__(self, max_entries: Optional[int] = None,
                 max_bytes: Optional[int] = None):
        self._lock = make_rlock("perf.jit_cache")
        self._entries: "OrderedDict[Tuple[str, str, int], _Entry]" = \
            OrderedDict()
        self._max_entries = max_entries
        self._max_bytes = max_bytes
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.compiles = 0
        self.compile_ns_total = 0
        self._by_kernel: Dict[str, Dict[str, int]] = {}

    # ------------------------------------------------------------ budgets

    def max_entries(self) -> int:
        if self._max_entries is not None:
            return self._max_entries
        try:
            return int(os.environ.get(
                "SPARK_RAPIDS_TPU_JIT_CACHE_ENTRIES", "256"))
        except ValueError:
            return 256

    def max_bytes(self) -> int:
        if self._max_bytes is not None:
            return self._max_bytes
        try:
            return int(os.environ.get(
                "SPARK_RAPIDS_TPU_JIT_CACHE_BYTES", str(8 << 30)))
        except ValueError:
            return 8 << 30

    def enabled(self) -> bool:
        return cache_enabled()

    # ------------------------------------------------------------- stats

    def _kernel_stat(self, name: str) -> Dict[str, int]:
        return self._by_kernel.setdefault(
            name, {"hits": 0, "misses": 0, "evictions": 0})

    def stats(self) -> dict:
        with self._lock:
            return {
                "enabled": self.enabled(),
                "entries": len(self._entries),
                "bytes": self._bytes,
                "max_entries": self.max_entries(),
                "max_bytes": self.max_bytes(),
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "compiles": self.compiles,
                "compile_ns_total": self.compile_ns_total,
                "kernels": {k: dict(v)
                            for k, v in sorted(self._by_kernel.items())},
            }

    def clear(self, reset_stats: bool = False) -> int:
        """Drop every entry (compiled executables are released);
        returns the number dropped.  Cumulative stats survive unless
        ``reset_stats``."""
        with self._lock:
            n = len(self._entries)
            self._entries.clear()
            self._bytes = 0
            if reset_stats:
                self.hits = self.misses = self.evictions = 0
                self.compiles = self.compile_ns_total = 0
                self._by_kernel.clear()
            return n

    # ------------------------------------------------------------ lookup

    def get_or_build(self, name: str, digest: str, bucket: int,
                     build: Callable[[], Callable], *,
                     cost_bytes: int = 0, owner=None,
                     counts_compile: bool = True) -> Callable:
        """Return the cached callable for (name, digest, bucket),
        invoking ``build()`` on a miss.  ``owner`` (optional) is held
        strongly in the entry and identity-checked on hits — callers
        keyed by object identity (exchange step factories) use it to
        make id-reuse collisions impossible."""
        from spark_rapids_tpu import observability as _obs

        key = (name, digest, int(bucket))
        with self._lock:
            e = self._entries.get(key)
            if e is not None and (owner is None or e.owner is owner):
                self._entries.move_to_end(key)
                self.hits += 1
                self._kernel_stat(name)["hits"] += 1
                _obs.record_jit_cache("hit", name)
                return e.fn

        # build outside the lock: compiles can take seconds and must
        # not serialize unrelated kernels.  A racing thread may build
        # the same entry twice; last insert wins (both are correct).
        # compile_begin marks the START too: a multi-second
        # lower+compile is the classic slow-but-alive window, and the
        # lifeguard's heartbeat hook must see a sign of life on BOTH
        # edges or a first-touch compile longer than the hang
        # threshold reads as a hung worker
        _obs.record_jit_cache("compile_begin", name)
        t0 = time.monotonic_ns()
        fn = build()
        dt = time.monotonic_ns() - t0

        with self._lock:
            self.misses += 1
            ks = self._kernel_stat(name)
            ks["misses"] += 1
            if counts_compile:
                self.compiles += 1
                self.compile_ns_total += dt
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old.cost_bytes
            self._entries[key] = _Entry(fn, cost_bytes, owner, dt)
            self._bytes += int(cost_bytes)
            evicted = self._evict_over_budget()
        _obs.record_jit_cache("miss", name, compile_ns=dt)
        for ev_name in evicted:
            _obs.record_jit_cache("eviction", ev_name)
        return fn

    def _evict_over_budget(self):
        """Caller holds the lock.  Returns kernel names evicted."""
        evicted = []
        max_e, max_b = self.max_entries(), self.max_bytes()
        while len(self._entries) > max(1, max_e) or \
                (self._bytes > max_b and len(self._entries) > 1):
            key, e = self._entries.popitem(last=False)
            self._bytes -= e.cost_bytes
            self.evictions += 1
            self._kernel_stat(key[0])["evictions"] += 1
            evicted.append(key[0])
        return evicted

    # ------------------------------------------------------- cached call

    def cached_call(self, name: str, digest: str, fn: Callable,
                    args: tuple, *, bucket: int,
                    donate_argnums: Tuple[int, ...] = ()):
        """Run ``fn(*args)`` through an AOT-compiled executable cached
        under (name, digest, bucket).  ``args`` must already be padded
        to the bucket; every later call with the same key must pass the
        same pytree structure / shapes / dtypes (bucketing guarantees
        this for row-shaped operands).  Donation is applied only on
        backends that honor it (TPU) to avoid per-compile warnings."""
        donate = donate_argnums if jax.default_backend() == "tpu" else ()
        cost = _tree_nbytes(args)

        def build():
            return jax.jit(fn, donate_argnums=donate).lower(*args).compile()

        compiled = self.get_or_build(name, digest, bucket, build,
                                     cost_bytes=cost)
        return compiled(*args)


CACHE = JitCache()
