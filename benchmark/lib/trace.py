"""Reduction of a profiler trace to device busy time, idle gaps and top
operations.  Pure functions over (start, duration) intervals; the only
JAX here is ``read_xplane``, which turns an ``.xplane.pb`` into them."""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"
BETWEEN = "between-operations"
NAME_CHARS = 160      # a TPU trace names an op by its whole HLO line
# the CPU backend has no device plane: a rehearsal reads XLA's own
# worker threads instead, so the same code path runs end to end there
CPU_LINE = "tf_XLA"
CPU_NOISE = ("ThreadpoolListener", "SlinkyThreadPool")


def union(intervals):
    """Merged, sorted [start, end) list of (start, duration) pairs."""
    out = []
    for s, e in sorted((s, s + d) for s, d in intervals if d > 0):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def busy_seconds(merged):
    return sum(e - s for s, e in merged) / 1e9


def gaps(merged, lo, hi):
    """The idle [start, end) stretches of [lo, hi) not covered."""
    out, at = [], lo
    for s, e in merged:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def label_gap(gap, spans):
    """The benchmark's own annotation that covers most of the gap;
    ``spans`` is a list of (name, start, duration)."""
    cover = {}
    for name, s, d in spans:
        c = min(gap[1], s + d) - max(gap[0], s)
        if c > 0:
            cover[name] = cover.get(name, 0) + c
    # most cover wins; a tie goes to the name that sorts first
    return min(cover, key=lambda n: (-cover[n], n)) if cover else BETWEEN


def top_ops(events, n=10):
    """[[name, seconds], ...] of the n names with most summed time;
    ``events`` is a list of (name, start, duration)."""
    total = {}
    for name, _s, d in events:
        name = name[:NAME_CHARS]
        total[name] = total.get(name, 0) + d
    ranked = sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))
    return [[name, d / 1e9] for name, d in ranked[:n]]


def reduce_trace(device_events, spans, n=10):
    """``device_events``: one list of (name, start_ns, duration_ns) per
    device; ``spans``: the benchmark's annotations (name, start, dur).
    The window is the extent of the spans (whole operations), or of
    the device events where no span was found.  Busy time is the
    union per device, averaged over the devices."""
    flat = [e for dev in device_events for e in dev]
    if not flat:
        return None
    if spans:
        lo = min(s for _n, s, _d in spans)
        hi = max(s + d for _n, s, d in spans)
    else:
        lo = min(s for _n, s, _d in flat)
        hi = max(s + d for _n, s, d in flat)
    busy, idle = [], []
    for dev in device_events:
        merged = clip(union((s, d) for _n, s, d in dev), lo, hi)
        busy.append(busy_seconds(merged))
        idle += [(e - s, label_gap((s, e), spans))
                 for s, e in gaps(merged, lo, hi)]
    idle.sort(key=lambda g: -g[0])
    inside = [(nm, s, d) for nm, s, d in flat if s + d > lo and s < hi]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (hi - lo) / 1e9,
        "device_ops": top_ops(inside, n),
        "idle_gaps": [[name, d / 1e9] for d, name in idle[:n]],
        "devices": len(device_events),
    }


def newest_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return found[-1] if found else None


def read_xplane(path, span_names, describe=None):
    """(device_events, spans) from an .xplane.pb.  ``span_names`` is a
    predicate on a host event's name that picks the benchmark's own
    annotations.  ``describe``, if given, is a list that receives one
    line per plane/line (what the trace holds, for the log)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_events, spans, cpu_events = [], [], []
    for plane in data.planes:
        is_dev = plane.name.startswith(DEVICE_PLANE)
        lines = list(plane.lines)
        has_ops = any(ln.name == OPS_LINE for ln in lines)
        dev = []
        for line in lines:
            events = [(e.name, int(e.start_ns), int(e.duration_ns))
                      for e in line.events]
            if describe is not None:
                describe.append("%s | %s | %d events" % (
                    plane.name, line.name, len(events)))
            if is_dev:
                if line.name == OPS_LINE or not has_ops:
                    dev += events
            else:
                spans += [e for e in events if span_names(e[0])]
                if line.name.startswith(CPU_LINE):
                    cpu_events += [
                        e for e in events
                        if e[2] > 0 and not e[0].startswith(CPU_NOISE)]
        if is_dev and dev:
            device_events.append(dev)
    if not device_events and cpu_events:
        device_events = [cpu_events]
    return device_events, spans
