"""Multi-process mesh formation with graceful degradation (ISSUE 10).

The ideal scale-out promotes the virtual single-process mesh to a
genuine multi-process ``jax.distributed`` mesh (SNIPPETS.md [1][2] —
pjit across TPU-pod processes with a call-site mesh).  On the CPU
backend cross-process collectives are not reliably available, so mesh formation is an ATTEMPT with a bounded
timeout, and the distributed runner degrades to the process-per-shard
harness: every rank computes its shard with plain local jit, and ALL
cross-rank movement rides the kudo shuffle service — which is the
contract under test anyway (shuffle bytes must cross the process
boundary regardless of how the local step was compiled).

``SPARK_RAPIDS_TPU_DIST_MESH``:
  * ``0`` (default) — don't attempt; harness mode.
  * ``auto``/``1``  — try ``jax.distributed.initialize`` against the
    coordinator; any failure (timeout, unsupported backend, version)
    falls back to harness mode and says so in the worker summary.
"""

from __future__ import annotations

import os
from typing import Optional


def mesh_mode() -> str:
    v = os.environ.get("SPARK_RAPIDS_TPU_DIST_MESH", "0").lower()
    return "attempt" if v in ("1", "auto", "true") else "harness"


def try_form_mesh(rank: int, world: int,
                  coordinator: Optional[str] = None,
                  timeout_s: float = 10.0) -> dict:
    """Attempt the jax.distributed mesh; never raises.  Returns
    ``{"mode": "mesh"|"harness", "detail": str, "local_devices": n}``.
    In harness mode callers must shard/reduce through the shuffle
    service; in mesh mode a caller MAY shard_map over
    ``jax.devices()`` — the shuffle service still carries the
    table-granularity exchanges either way."""
    import jax

    if mesh_mode() != "attempt":
        return {"mode": "harness",
                "detail": "mesh attempt disabled "
                          "(SPARK_RAPIDS_TPU_DIST_MESH=0)",
                "local_devices": jax.local_device_count()}
    if coordinator is None:
        return {"mode": "harness", "detail": "no coordinator address",
                "local_devices": jax.local_device_count()}
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator, num_processes=world,
            process_id=rank,
            initialization_timeout=int(max(1, timeout_s)))
        ndev = jax.device_count()
        if ndev < world:
            return {"mode": "harness",
                    "detail": f"mesh formed but only {ndev} global "
                              f"devices for {world} ranks",
                    "local_devices": jax.local_device_count()}
        return {"mode": "mesh",
                "detail": f"{ndev} global devices across {world} "
                          f"processes",
                "local_devices": jax.local_device_count()}
    except Exception as e:  # noqa: BLE001 — degradation is the contract
        return {"mode": "harness",
                "detail": f"mesh init failed: "
                          f"{type(e).__name__}: {e}",
                "local_devices": jax.local_device_count()}
