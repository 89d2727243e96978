"""What the benchmark reads from the program's own counters: compiles
inside a window, peak device bytes, the stage verdicts that ran.
(Copies of chip_smoke.py's helpers; the yardstick keeps its own.)"""


class Compiles:
    """New executables built in this process: the stage compiler's own
    counter (perf/jit_cache) and every XLA backend compile JAX reports
    (plain ``jax.jit`` pipelines such as q9 never touch the former)."""

    def __init__(self):
        import jax.monitoring
        self.backend = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend += 1

    def snap(self):
        from spark_rapids_tpu.perf.jit_cache import CACHE
        return CACHE.stats()["compiles"], self.backend

    def since(self, snap):
        now = self.snap()
        return {"jit_cache": now[0] - snap[0], "backend": now[1] - snap[1]}


def device_bytes():
    """Peak bytes on the fullest device (0 where the backend does not
    report it, as the CPU's does not)."""
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def stage_outcomes():
    from spark_rapids_tpu import observability as obs
    fam = obs.METRICS.snapshot().get("srt_stage_fusion_total", {})
    return {":".join(s["labels"]): s["value"]
            for s in fam.get("series", [])}
