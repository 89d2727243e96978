#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell (an entry of ``workloads`` in ``BENCHMARK.json``).
Set-up (imports, native libraries, server start, data from ``--seed``,
every shape of the cell warmed) is timed as ``setup_s``; then the window
runs for ``--seconds`` and until the operation in flight has ended; then
the answers are compared with the plain reference.  The last line of
standard output is the result; everything else is on earlier lines.

Nothing here lists cells, configurations, drivers, references or
metrics: each is a file found by the name ``BENCHMARK.json`` gives it
(see README.md).  Without a TPU the command exits non-zero, unless the
caller itself set ``JAX_PLATFORMS=cpu`` and passed ``--size toy``: a
rehearsal, which says so and names the platform it ran on.
"""

import time

T_START = time.perf_counter()

import argparse            # noqa: E402
import contextlib          # noqa: E402
import importlib.util      # noqa: E402
import json                # noqa: E402
import os                  # noqa: E402
import shutil              # noqa: E402
import sys                 # noqa: E402
import types               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def say(**kv):
    """One earlier line of standard output; ``t`` is seconds since the
    process started."""
    kv["t"] = round(time.perf_counter() - T_START, 3)
    print(json.dumps(kv, sort_keys=True, default=str), flush=True)


def load(kind, name):
    """The module ``benchmark/<kind>/<name>.py``, by name.  A metric
    split by what its cells report (``rows_per_s.hostgen``) is read by
    the file of the quantity (``rows_per_s.py``) unless it has one of
    its own."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(HERE, kind, name.rsplit(".", 1)[0] + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError("no %s named %r (%s)" % (kind, name, path))
    spec = importlib.util.spec_from_file_location(
        "benchmark_%s_%s" % (kind, name.replace(".", "_").replace("-", "_")),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def with_pending(manifest, name):
    """``manifest``, or where it holds no workload ``name`` and
    ``pending/<name>.json`` does, a copy with that file's entries
    copied in as a later PR would: an entry whose name a group already
    has adds its ``workloads`` to that entry's list, any other is
    appended.  So a cell that is built and not in ``BENCHMARK.json``
    can still be rehearsed and tested; the driver's check never comes
    this way."""
    path = os.path.join(HERE, "pending", name + ".json")
    if (name in [w["name"] for w in manifest["workloads"]]
            or not os.path.exists(path)):
        return manifest
    merged = dict(manifest)
    for group, entries in load_json(path).items():
        if not isinstance(entries, list):
            continue
        have = {e["name"]: dict(e) for e in manifest.get(group, [])}
        for e in entries:
            if e["name"] in have and "workloads" in have[e["name"]]:
                have[e["name"]]["workloads"] = (
                    have[e["name"]]["workloads"] + e.get("workloads", []))
            else:
                have.setdefault(e["name"], e)
        merged[group] = list(have.values())
    return merged


class Cell:
    """One workload of BENCHMARK.json with its files."""

    def __init__(self, manifest, name, seed, size):
        found = [w for w in manifest["workloads"] if w["name"] == name]
        if not found:
            raise SystemExit("run.py: no workload %r in BENCHMARK.json"
                             % name)
        self.entry = found[0]
        self.name, self.seed, self.size = name, int(seed), size
        self.chips = int(self.entry["chips"])
        cfg = [c for c in manifest["configs"]
               if c["name"] == self.entry["config"]][0]
        self.config = load_json(ROOT, cfg["file"])
        self.traffic = load_json(HERE, "traffic",
                                 self.entry["traffic"] + ".json")
        self.sizes = self.config["sizes"][size]
        self.reference = load("reference", self.traffic["reference"])
        self.load = load

    def metrics(self, manifest, group):
        """The metrics of ``group`` this cell reports."""
        return [m for m in manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]


def find_device(chips, size):
    """The devices the run uses, or exit: a measurement path that finds
    no chip fails."""
    import jax

    dev = jax.devices()[0]
    rehearsal = (size == "toy" and dev.platform == "cpu"
                 and os.environ.get("JAX_PLATFORMS", "") == "cpu")
    if dev.platform != "tpu" and not rehearsal:
        print("run.py: no TPU (platform %r); the CPU is accepted only as "
              "`JAX_PLATFORMS=cpu ... --size toy`" % dev.platform,
              file=sys.stderr)
        raise SystemExit(2)
    if jax.device_count() < chips:
        print("run.py: the cell needs %d chips, JAX reports %d"
              % (chips, jax.device_count()), file=sys.stderr)
        raise SystemExit(2)
    if rehearsal:
        say(note="REHEARSAL on the CPU at toy size - not a chip run, no "
                 "number below is a device number")
    return dev, rehearsal


def traced(driver, cell):
    """Whole operations under the profiler: the reduced trace and the
    records of the operations it covers."""
    import jax

    from lib import trace as T

    out = os.path.join(TRACE_DIR, cell.name)
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    names = set()

    @contextlib.contextmanager
    def annotate(name):
        names.add(name)
        with jax.profiler.TraceAnnotation(name):
            yield

    k = int(cell.traffic.get("trace_ops_per_stream", 1))
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        records = driver.window(0.0, annotate=annotate, min_ops=k,
                                max_ops=k)
    finally:
        jax.profiler.stop_trace()
    path = T.newest_xplane(out)
    lines = []
    device_events, spans = T.read_xplane(path, names.__contains__, lines)
    reduced = T.reduce_trace(device_events, spans)
    say(trace=path, bytes=os.path.getsize(path), lines=lines[:40],
        spans=len(spans))
    shutil.rmtree(out, ignore_errors=True)
    return reduced, records


def run_cell(args, manifest=None):
    """One run; returns (exit code, result or None)."""
    manifest = with_pending(
        manifest or load_json(ROOT, "BENCHMARK.json"), args.workload)
    cell = Cell(manifest, args.workload, args.seed, args.size)
    for key in cell.config.get("must_be_unset", []):
        if os.environ.get(key):
            print("run.py: %s is set; this configuration runs without it"
                  % key, file=sys.stderr)
            return 2, None
    os.environ.update(cell.config.get("environment", {}))

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    import jax

    import spark_rapids_tpu  # noqa: F401  (turns x64 on)
    from spark_rapids_tpu import observability as obs
    from spark_rapids_tpu.perf.jit_cache import enable_persistent_cache

    from lib import counters

    cache_dir = enable_persistent_cache()
    # every program of the cell goes to the cache, however quick its
    # compile, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    dev, rehearsal = find_device(cell.chips, args.size)
    obs.enable()     # the counters only: the stage verdicts come from them
    compiles = counters.Compiles()
    say(phase="start", workload=cell.name, seed=cell.seed, size=args.size,
        platform=dev.platform, kind=dev.device_kind,
        devices=jax.device_count(), compile_cache=cache_dir,
        seconds=args.seconds, trace=args.trace)

    driver = load("drivers", cell.traffic["driver"]).Driver(cell)
    info = driver.setup()
    setup_s = time.perf_counter() - T_START
    say(phase="setup", setup_s=setup_s, compiles=compiles.since((0, 0)),
        **info)

    snap = compiles.snap()
    t0 = time.perf_counter()
    records = driver.window(float(args.seconds))
    elapsed = time.perf_counter() - t0
    in_window = compiles.since(snap)
    say(phase="window", elapsed_s=elapsed, operations=len(records),
        failed=sum(1 for r in records if not r["ok"]),
        compiles=in_window, stages=counters.stage_outcomes(),
        op_ms=[round((r["t_end"] - r["t_start"]) * 1e3, 1)
               for r in records[:200]])

    trace, traced_records = None, []
    probes = {}
    if args.trace:
        trace, traced_records = traced(driver, cell)
        probes = driver.probes()
        say(phase="trace", reduced=trace, probes=probes)
    peak = counters.device_bytes()
    say(phase="memory", memory_peak_bytes=peak)

    # the comparison: after the window, the peak read, the state freed
    produced = driver.produced()
    driver.release()
    everything = records + traced_records
    try:
        numbers = driver.check(everything, produced)
    except Exception as e:      # an answer the comparison cannot read
        say(phase="check", error=repr(e))
        numbers = {"answers_unreadable": 1}
    limits = dict(cell.reference.LIMITS, answers_missing=0,
                  answers_unreadable=0)
    compared = {k: {"value": v, "limit": limits[k]}
                for k, v in numbers.items() if k in limits}
    correct = bool(compared) and all(
        c["value"] <= c["limit"] for c in compared.values())

    # what a metric reader may read
    run = types.SimpleNamespace(cell=cell, manifest=manifest, setup_s=setup_s, records=records,
              elapsed_s=elapsed, window_compiles=in_window, trace=trace,
              traced_records=traced_records, probes=probes,
              device_kind=dev.device_kind, rehearsal=rehearsal)
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(manifest, group):
        value = load("layer_metrics" if args.trace else "end_to_end",
                     m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(everything),
              "failed": sum(1 for r in everything if not r["ok"]),
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["compared"] = dict(
        compared, answers_compared=numbers.get("answers_compared"))
    for name, c in compared.items():
        print("compared %s = %r (limit %r)" % (name, c["value"],
                                               c["limit"]),
              file=sys.stderr)
    print("correct = %s" % correct, file=sys.stderr, flush=True)
    return 0, result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--size", choices=("full", "toy"), default="full",
                    help="toy = CPU rehearsal of the same code path")
    args = ap.parse_args(argv)
    code, result = run_cell(args)
    if result is not None:
        sys.stdout.flush()
        print(json.dumps(result))
    return code


if __name__ == "__main__":
    sys.exit(main())
