"""Benchmark entry point. Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "platform": ..., "device_kind": ..., "device_count": N}

Headline metric (BASELINE.json): row<->columnar conversion GB/s.
vs_baseline is the ratio against a single-thread numpy host conversion of the
same table (the CPU reference the Spark plugin would otherwise use), since the
reference publishes no GPU numbers (BASELINE.md).

One process, on ``jax.devices()[0]``.  Without a TPU it exits non-zero:
a CPU timing is not this metric.  A caller who wants the CPU run for
its own sake says so with ``JAX_PLATFORMS=cpu``, and the line then names
the platform it ran on.

Env knobs:
  BENCH_METRICS_SIDECAR  path: run with the observability spine enabled
                       and write its JSON snapshot (registry + per-task
                       rollup + journal stats) there, next to the
                       BENCH_*.json the driver captures from stdout
"""

import json
import os
import sys


def require_device():
    """``jax.devices()[0]``, or exit: a measurement path that finds no
    chip fails unless the caller itself pinned the CPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit(f"bench: no TPU (platform {dev.platform!r}); set "
                 "JAX_PLATFORMS=cpu to time the CPU on purpose")
    return dev


def device_fields(dev) -> dict:
    import jax
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": jax.device_count()}


def main():
    import jax
    jax.config.update("jax_enable_x64", True)

    from spark_rapids_tpu.perf.jit_cache import enable_persistent_cache
    enable_persistent_cache()
    dev = require_device()

    sidecar = os.environ.get("BENCH_METRICS_SIDECAR", "")
    if sidecar:
        from spark_rapids_tpu import observability as obs
        obs.enable()
        obs.reset()

    from bench_impl import run
    result = run()
    result.update(device_fields(dev))
    if sidecar:
        with open(sidecar, "w") as f:
            json.dump(obs.snapshot(), f, sort_keys=True, indent=2)
        result["metrics_sidecar"] = sidecar
    print(json.dumps(result))


if __name__ == "__main__":
    main()
