"""Device time inside named scopes of the program's executables, from
a profile of the reader's own.

The stage compiler evaluates each node of a plan under the scope
``srt/<stage>/<node's first output>`` (``plan/compiler.py``
``_eval_node``), which XLA keeps as each operation's ``op_name``.  A
TPU trace names a device operation by its HLO instruction alone; the
trace's metadata plane holds the programs, and xprof's ``op_profile``
(the converter installed with JAX's profiler plugin) gives each HLO
operation of each program its ``tf_op`` name (``provenance``).
``run.py`` deletes its own profile before a reader runs, so, as
``lib/direction.py`` does, ``profile_queries`` takes one: after the
window, the check and the server's stop, it runs the cell's catalog
query ``ROUNDS`` times from the catalog (the resident database is
still held), each under an annotation.  ``scope_seconds`` sums, per
annotated query and device, the operations of the "XLA Ops" line whose
``tf_op`` holds one of the given prefixes (a ``tf_op`` is the whole
path, ``jit(...)/.../srt/<stage>/...``); ``scope_ms`` averages over the
devices and takes the median over the queries."""

import json
import os
import shutil

from lib import trace
from lib.stats import percentile

ROUNDS = 3
BRACKET = "scopes:query"
MODULES_LINE = "XLA Modules"
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".bench_trace")


def tf_ops(path):
    """{(program id, HLO operation): tf_op} of a trace, from xprof's
    ``op_profile``; ``{}`` where the converter is missing or fails."""
    try:
        from xprof.convert import raw_to_tool_data
        data, _kind = raw_to_tool_data.xspace_to_tool_data(
            [path], "op_profile", {})
        tree = json.loads(data)["byProgram"]
    except Exception:           # no converter, or a trace it cannot read
        return {}
    names = {}

    def walk(node):
        xla = node.get("xla")
        if xla and xla.get("provenance"):
            names[(str(xla.get("programId")), node["name"])] = (
                xla["provenance"])
        for child in node.get("children", ()):
            walk(child)
    walk(tree)
    return names


def _program(module_name):
    """The program id of an "XLA Modules" event: ``jit_fn(<id>)``."""
    return module_name.rsplit("(", 1)[-1].rstrip(")")


def scope_seconds(path, prefixes, bracket=BRACKET):
    """[per annotated ``bracket``, in order: [per device: seconds of its
    "XLA Ops" inside the bracket whose tf_op holds one of
    ``prefixes``]], and whether any operation did; ``None`` where the
    trace has no device plane or no bracket."""
    from jax.profiler import ProfileData

    names = tf_ops(path)
    devices, brackets = [], []
    for plane in ProfileData.from_file(path).planes:
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        if not plane.name.startswith(trace.DEVICE_PLANE):
            brackets += [(int(e.start_ns), int(e.duration_ns))
                         for events in lines.values() for e in events
                         if e.name == bracket]
            continue
        modules = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                          _program(e.name))
                         for e in lines.get(MODULES_LINE, ()))
        ops = []
        for e in lines.get(trace.OPS_LINE, ()):
            start = int(e.start_ns)
            program = next((p for lo, hi, p in modules if lo <= start < hi),
                           "")
            op = e.name.split(" = ", 1)[0].lstrip("%")
            ops.append((names.get((program, op), ""), start,
                        int(e.duration_ns)))
        if ops:
            devices.append(ops)
    if not devices or not brackets:
        return None
    found, per_bracket = False, []
    for start, dur in sorted(brackets):
        row = []
        for ops in devices:
            inside = [(s, d) for tf_op, s, d in ops
                      if start <= s < start + dur
                      and any(p in tf_op for p in prefixes)]
            found = found or bool(inside)
            row.append(trace.busy_seconds(trace.union(inside)))
        per_bracket.append(row)
    return per_bracket, found


def profile_queries(cell, rounds=ROUNDS):
    """The path of a profile of ``rounds`` of the cell's query, each
    under the annotation ``BRACKET``, or ``None``."""
    import jax

    from spark_rapids_tpu.models import run_catalog_query

    op, pool = cell.traffic["op"], cell.traffic.get("seed_pool", 1)
    queries = [cell.reference.query_params(
        cell.sizes, cell.traffic["params"],
        (cell.seed * 1_000_003 + i) % (2 ** 31 - 1))
        for i in range(min(rounds, int(pool)))]
    run_catalog_query(op, queries[0])       # the executables are warm
    out = os.path.join(TRACE_DIR, cell.name + ".scopes")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        for q in queries:
            with jax.profiler.TraceAnnotation(BRACKET):
                run_catalog_query(op, q)
        # one more, outside any bracket: a TPU trace stopped right after
        # a query can lack that query's device operations
        run_catalog_query(op, queries[0])
    finally:
        jax.profiler.stop_trace()
    return trace.newest_xplane(out)


def scope_ms(run, prefixes):
    """Median over the profiled queries of the device milliseconds
    inside the scopes that hold one of ``prefixes``, averaged over the
    devices; ``None`` without ``--trace 1``, on a rehearsal, and where
    no operation of the profile lies in such a scope (a program without
    them).  The profile is taken and read once a run, for every reader
    of the same prefixes."""
    if run.rehearsal or not run.trace or not prefixes:
        return None
    key = tuple(prefixes)
    if getattr(run, "scopes", (None,))[0] != key:
        try:
            path = profile_queries(run.cell)
            got = path and scope_seconds(path, prefixes)
        except Exception as e:   # a profile that cannot be taken or read
            print(json.dumps({"phase": "scope_profile",
                              "error": repr(e)}), flush=True)
            path, got = None, None
        run.scopes = (key, got)
        if path:
            shutil.rmtree(os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.dirname(path)))), ignore_errors=True)
    got = run.scopes[1]
    if not got or not got[1]:
        return None
    ms = [1e3 * sum(row) / len(row) for row in got[0]]
    print(json.dumps({"phase": "scope_profile", "prefixes": prefixes,
                      "ms": ms}, sort_keys=True), flush=True)
    return percentile(ms, 50)
