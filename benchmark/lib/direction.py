"""One direction of the row-conversion round trip against the HBM
roofline, from the device trace, and the program's own spans of a
round trip.

A direction reads the columnar table and writes the rows, or the
reverse: half of the reference's ``min_bytes`` (never from the
implementation).  Its time is the device-busy time inside the
direction's own bracket of a profile.  ``run.trace`` holds the traced
operations reduced to one busy time, and the profile itself is gone
when a reader is called (``run.py:traced``), so ``profile_directions``
takes one of its own: it builds the cell's table once more (the driver
has let go of its own), warms one round trip and profiles ``ROUNDS``
more, each direction under an annotation of its name, and sums the
device's operations inside each bracket as ``lib/trace.reduce_trace``
does for the whole window.  The host's dispatch and its handling of
the results are outside the sum: a kernel that halves its device time
doubles its share, whatever the host does."""

import contextlib
import json
import os
import shutil

from lib import spans, trace
from lib.peaks import peak
from lib.stats import percentile

ROUNDS = 3
SPANS = ("to_rows", "from_rows")
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".bench_trace")


def profile_directions(cell, rounds=ROUNDS):
    """{span: [device-busy seconds inside each of its ``rounds``
    brackets]} of the cell's operation; ``None`` where the cell's
    traffic binds no operation or the profile holds no device events."""
    import jax

    if "op_binding" not in cell.traffic:
        return None
    op = cell.load("ops", cell.traffic["op_binding"])
    state = op.build(cell.reference.make_inputs(
        cell.sizes, cell.traffic["params"], cell.seed))
    op.run(state, lambda _name: contextlib.nullcontext())
    out = os.path.join(TRACE_DIR, cell.name + ".directions")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        for _ in range(rounds):
            op.run(state, jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    del state
    path = trace.newest_xplane(out)
    device_events, brackets = (trace.read_xplane(path, SPANS.__contains__)
                               if path else ([], []))
    shutil.rmtree(out, ignore_errors=True)
    if not device_events:
        return None
    merged = [trace.union((s, d) for _n, s, d in dev)
              for dev in device_events]
    busy = {}
    for name, start, dur in brackets:
        busy.setdefault(name, []).append(sum(
            trace.busy_seconds(trace.clip(m, start, start + dur))
            for m in merged) / len(merged))
    print(json.dumps({"phase": "direction_profile", "busy_s": busy},
                     sort_keys=True), flush=True)
    return busy


def roofline_share(run, span):
    """Percent of the peak bandwidth that the direction ``span``
    reaches over its median device-busy time; ``None`` without
    ``--trace 1``, on a rehearsal (no device number from the CPU) and
    where the profile has no bracket of that name.  The profile is
    taken once a run, for both directions."""
    if run.rehearsal or not run.trace:
        return None
    if not hasattr(run, "direction_busy"):
        run.direction_busy = profile_directions(run.cell)
    seconds = percentile((run.direction_busy or {}).get(span, []), 50)
    if not seconds:
        return None
    cell = run.cell
    least = cell.reference.min_bytes(cell.sizes, cell.traffic["params"]) / 2
    return 100.0 * least / peak(run.device_kind, "hbm_bytes_per_s") / seconds


def program_span_ms(run, names):
    """Median over the window's operations of the time the program's
    own spans ``names`` took inside each (every operation has to hold
    one of each, started inside it); ``None`` where the clocks differ,
    spans dropped, or the program records no such span."""
    records = [r for r in run.records if r["ok"]]
    if not records or not spans.same_clock():
        return None
    got = spans.program_records()
    if got is None or got[2]:
        return None
    mine = sorted((s for s in got[0] if s["name"] in names),
                  key=lambda s: s["t_ns"])
    want = sorted(names)
    per_op, at = [], 0
    for r in sorted(records, key=lambda r: r["t_start"]):
        lo, hi = r["t_start"] * 1e9, r["t_end"] * 1e9
        while at < len(mine) and mine[at]["t_ns"] < lo:
            at += 1
        inside = []
        while at < len(mine) and mine[at]["t_ns"] <= hi:
            inside.append(mine[at])
            at += 1
        if sorted(s["name"] for s in inside) != want:
            return None
        per_op.append(sum(s["dur_ns"] for s in inside))
    return percentile(per_op, 50) / 1e6
