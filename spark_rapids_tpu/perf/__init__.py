"""Performance layer: process-wide kernel compile cache + shape
bucketing (ISSUE 4 tentpole).

The row-conversion / hash / exchange hot paths build one XLA program
per (kernel, schema layout, row count) they see.  Row counts vary batch
to batch, so without bucketing every batch recompiles; and an eager
212-column conversion dispatches thousands of tiny ops.  This package
centralizes the fix:

  * :mod:`spark_rapids_tpu.perf.jit_cache` — a registry of
    AOT-compiled kernels keyed by (kernel name, schema-layout digest,
    row bucket), with power-of-two row bucketing + pad/slice wrappers,
    buffer donation on the padded operands (TPU), and LRU eviction
    under a byte/entry budget.

Consumers: ops/row_conversion.py (to-rows / from-rows), ops/hash.py
(row hashes), parallel/exchange.py (capacity-retry step builders).  Stats surface
through srt_jit_cache_* metrics (observability), the shim
(jit_cache_stats / jit_cache_clear), and tools/metrics_report.py.

Env knobs (read dynamically; docs/performance.md):
  SPARK_RAPIDS_TPU_JIT_CACHE=0          disable (eager fallback paths)
  SPARK_RAPIDS_TPU_JIT_CACHE_ENTRIES=N  LRU entry budget (default 256)
  SPARK_RAPIDS_TPU_JIT_CACHE_BYTES=N    LRU byte budget (default 8 GiB
                                        of estimated operand footprint)
"""

from spark_rapids_tpu.perf.jit_cache import (  # noqa: F401
    CACHE, JitCache, bucket_rows, pad_axis0, schema_digest)
