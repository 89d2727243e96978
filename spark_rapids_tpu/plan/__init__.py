"""Whole-stage fusion (ISSUE 11): a small stage IR + compiler that
fuses everything a query does between shuffle boundaries into ONE XLA
executable, AOT-keyed in the perf/jit_cache.

  ir.py        typed plan nodes (scan-bind, project/filter exprs,
               hash-join probe, segment/window/rollup aggregates,
               sort, cross-shard reduce, shuffle boundary)
  compiler.py  one evaluator: the fused AOT executable and the
               shard_map pipeline body (the op-by-op walk is the
               tests' reference)
  catalog.py   TPC-DS stages (q3/q5/q9/q72 re-expressed — the hand
               kernels in models/tpcds stay as byte-identity oracles —
               plus the new q67 rollup+rank and q89 window shapes)
"""

from spark_rapids_tpu.plan import catalog, compiler, ir  # noqa: F401
from spark_rapids_tpu.plan.compiler import (  # noqa: F401
    CompiledStage, compile_pipeline, compile_stage, fused_pipeline_fn)
