"""The query timeline inside the served path (ISSUE 27): the spans a
served query records under the metrics switch alone, their mirror in
the profiler's trace (``srt:<name>``), the device-side ``srt/...``
scope names, and the benchmark's seven readers of the timeline.

Every profiler session of the test suite lives in this file."""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from spark_rapids_tpu import observability as obs
from spark_rapids_tpu.observability.tracing import (
    ANNOTATION_PREFIX, NOOP_SPAN, TIMELINE_KINDS)
from spark_rapids_tpu.server import QueryServer, ServerConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run as harness  # noqa: E402  (benchmark/run.py: loads by name)
from lib import spans as libspans  # noqa: E402
from lib import trace as libtrace  # noqa: E402

QUERIES = ("tpcds_q3_fused", "tpcds_q3")
PARAMS = {"rows": 4096, "items": 128, "brands": 16, "manufact": 3}
STAGE_ONLY = ("stage_run:q3", "stage_bind")
BOTH_PATHS = ("ingest", "execute", "dispatch", "device_wait", "rows")
CELLS = ("sf10-q3-streams2", "sf10-q3-streams4",
         "sf10-q3-handfused-streams4")
NEW_METRICS = ("queue_wait_ms", "front_door_overhead_ms",
               "ingest_span_ms", "rows_ms", "dispatch_host_ms",
               "device_wait_ms", "device_unfed_pct")


@pytest.fixture
def switches():
    """Both switches off and every ring empty, before and after."""
    prior_m, prior_t = obs.is_enabled(), obs.is_tracing_enabled()
    obs.disable()
    obs.disable_tracing()
    obs.reset()
    yield
    obs.reset()
    (obs.enable if prior_m else obs.disable)()
    (obs.enable_tracing if prior_t else obs.disable_tracing)()


def serve(query, n=1, seed=11):
    """``n`` queries through an in-process server; the last one's id."""
    srv = QueryServer(ServerConfig(max_concurrency=2))
    srv.start()
    try:
        for i in range(n):
            qid = srv.submit("tenant_a", query,
                             dict(PARAMS, seed=seed + i))
            st = srv.poll(qid, timeout_s=300)
            assert st["state"] == "done", st
    finally:
        srv.stop()
    return qid


def end(span):
    return span["t_ns"] + span["dur_ns"]


# ------------------------------------------------- (a) the spans served


@pytest.mark.parametrize("query", QUERIES)
def test_served_query_records_its_timeline_under_metrics_alone(
        switches, query):
    obs.enable()                       # the metrics switch only
    qid = serve(query)
    recs = obs.TRACER.records()
    roots = [r for r in recs if r["name"] == "server_query:" + query]
    assert len(roots) == 1
    root = roots[0]
    assert root["span_kind"] == "query" and root["parent_id"] is None
    assert root["attrs"]["query_id"] == qid
    assert root["attrs"]["wait_ns"] >= 0
    assert all(r["trace_id"] == root["trace_id"] for r in recs)
    assert all(r["attrs"]["query_id"] == qid for r in recs)
    assert all(r["span_kind"] in TIMELINE_KINDS for r in recs)
    names = [r["name"] for r in recs]
    want = BOTH_PATHS + (STAGE_ONLY if query.endswith("_fused") else ())
    for name in want:
        assert names.count(name) == 1, (name, names)
    by_name = {r["name"]: r for r in recs}
    assert by_name["execute"]["attrs"]["path"] == (
        "stage" if query.endswith("_fused") else "handfused")
    assert by_name["ingest"]["attrs"]["rows"] == PARAMS["rows"]
    assert by_name["ingest"]["attrs"]["bytes"] > 16 * PARAMS["rows"] - 1
    assert by_name["rows"]["attrs"]["rows_out"] > 0
    if query.endswith("_fused"):
        st = by_name["stage_run:q3"]["attrs"]
        assert st["engine"] == "fused" and st["rows"] == PARAMS["rows"]
        assert st["pad_rows"] == st["bucket"] - st["rows"] >= 0
        assert by_name["stage_run:q3"]["parent_id"] == \
            by_name["execute"]["span_id"]
    # every child lies inside its parent, siblings do not overlap
    by_id = {r["span_id"]: r for r in recs}
    kids = {}
    for r in recs:
        if r["parent_id"] is not None:
            parent = by_id[r["parent_id"]]
            assert parent["t_ns"] <= r["t_ns"]
            assert end(r) <= end(parent)
            kids.setdefault(r["parent_id"], []).append(r)
    for group in kids.values():
        group.sort(key=lambda r: r["t_ns"])
        for a, b in zip(group, group[1:]):
            assert end(a) <= b["t_ns"], (a["name"], b["name"])
    assert {r["name"] for r in kids[root["span_id"]]} == {
        "ingest", "execute", "rows"}


# --------------------------------------------------- (b) the switches


@pytest.mark.parametrize("query", QUERIES)
def test_both_switches_off_record_nothing(switches, query):
    assert obs.TRACER.start_span("x", kind="phase") is NOOP_SPAN
    assert obs.TRACER.start_span("x", kind="query") is NOOP_SPAN
    serve(query)
    assert len(obs.TRACER) == 0 and obs.TRACER.depth() == 0
    assert obs.JOURNAL.records("span") == []


@pytest.mark.parametrize("kind,recorded", [
    ("query", True), ("phase", True), ("compile", True),
    ("op", False), ("stage", False), ("io", False), ("oom", False),
    ("shuffle_write", False), ("shuffle_merge", False)])
def test_metrics_switch_alone_records_the_timeline_kinds_only(
        switches, kind, recorded):
    obs.enable()
    span = obs.TRACER.start_span("probe", kind=kind)
    assert (span is not NOOP_SPAN) == recorded
    span.end()
    assert len(obs.TRACER) == int(recorded)
    # the tracing switch records every kind, as before
    obs.disable()
    obs.enable_tracing()
    obs.TRACER.start_span("probe", kind=kind).end()
    assert len(obs.TRACER) == int(recorded) + 1


def test_op_range_keeps_one_annotation_of_its_own(switches):
    """``op_range`` annotates the profiler's trace itself; its ``op``
    span is no timeline kind, so the tracer writes no second one."""
    from spark_rapids_tpu.utils.profiler import op_range
    seen = []
    real = obs.TRACER.annotate
    obs.TRACER.annotate = lambda name: seen.append(name) or real(name)
    try:
        obs.enable()
        obs.enable_tracing()
        with obs.TRACER.span("outer", kind="phase"):
            with op_range("some_op"):
                pass
    finally:
        obs.TRACER.annotate = real
    assert seen == [ANNOTATION_PREFIX + "outer"]
    assert [r["name"] for r in obs.TRACER.records()] == [
        "some_op", "outer"]


# ----------------------------------- (c) the profiler's trace holds them


@pytest.mark.parametrize("query", QUERIES)
def test_profiler_trace_holds_one_srt_event_per_timeline_span(
        switches, query, tmp_path):
    obs.enable()
    serve(query)                       # compile outside the session
    obs.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        serve(query, n=2, seed=21)
    finally:
        jax.profiler.stop_trace()
    recs = obs.TRACER.records()
    assert len(recs) >= 2 * len(BOTH_PATHS)
    _dev, events = libtrace.read_xplane(
        libtrace.newest_xplane(str(tmp_path)),
        lambda name: name.startswith(ANNOTATION_PREFIX))
    assert len(events) == len(recs)
    assert sorted(n for n, _s, _d in events) == sorted(
        ANNOTATION_PREFIX + r["name"] for r in recs)
    # same order on both clocks, durations equal within a millisecond
    events.sort(key=lambda e: e[1])
    recs.sort(key=lambda r: r["t_ns"])
    for (name, _start, dur), rec in zip(events, recs):
        assert name == ANNOTATION_PREFIX + rec["name"]
        assert abs(dur - rec["dur_ns"]) < 1_000_000, (name, dur, rec)


# ------------------------------------------------ device-side scope names


def scope_names(compiled_text):
    return {part for line in compiled_text.splitlines()
            if 'op_name="' in line
            for part in [line.split('op_name="', 1)[1].split('"')[0]]}


@pytest.mark.parametrize("path", ["stage", "handfused"])
def test_stage_nodes_and_q3_kernels_carry_srt_scope_names(path):
    from spark_rapids_tpu.models import tpcds
    from spark_rapids_tpu.plan import catalog as plan_catalog
    from spark_rapids_tpu.plan.compiler import compile_stage
    d = tpcds.gen_q3(rows=2048, items=128, days=730, brands=16, seed=3)
    if path == "stage":
        st = compile_stage(plan_catalog.q3_plan(10_957, 2, 16, 3))
        args, _parts, _bucket = st._bind_args({
            "s": (d.s_date, d.s_item, d.s_price),
            "dims": (d.d_moy, d.d_year, d.i_brand, d.i_manufact)})
        fn = st._fused_callable()
        # `brand` has no operation of its own since PR 33: its lookup
        # shares `keep`'s one-hots and is built under that scope
        want = ("srt/q3/sums0", "srt/q3/cnts0", "srt/q3/keep",
                "srt/q3/year_idx", "srt/q3/_a")
    else:
        kernel = tpcds._q3_kernel(10_957, 2, 16, 3, 11, 100,
                                  lambda x: x)
        fn, args = kernel, tuple(d)
        want = ("srt/q3/segment_sum", "srt/q3/dim_gather",
                "srt/q3/sort_limit")
    names = scope_names(jax.jit(fn).lower(*args).compile().as_text())
    for scope in want:
        assert any(scope + "/" in n or n.endswith(scope) for n in names), \
            (scope, sorted(names)[:20])


# --------------------------------- (d) the readers on hand-built records


MS = 1_000_000


def span(name, t_ms, dur_ms, trace, parent="p", **attrs):
    rec = {"kind": "span", "name": name, "span_kind": "phase",
           "trace_id": trace, "span_id": name + trace,
           "parent_id": parent, "t_ns": int(t_ms * MS),
           "dur_ns": int(dur_ms * MS), "thread": 1}
    if attrs:
        rec["attrs"] = attrs
    return rec


def query_spans(trace, t0, wait, ingest, host, wait_dev, rows):
    """One query's spans from its dequeue at ``t0`` ms: ingest, then
    execute = host (dispatch) + device wait, then rows; 1 ms of the
    runner's own time before each phase."""
    t = t0 + 1
    out = [span("ingest", t, ingest, trace)]
    t += ingest + 1
    out += [span("execute", t, host + wait_dev, trace),
            span("dispatch", t, host, trace),
            span("device_wait", t + host, wait_dev, trace)]
    t += host + wait_dev + 1
    out.append(span("rows", t, rows, trace))
    out.append(span("server_query:q", t0, t + rows + 1 - t0, trace,
                    parent=None, query_id=trace, wait_ns=wait * MS))
    return out


def hand_built():
    """Two streams, three queries in a window that starts at 1000 s:
    A [0, 100] and B [10, 130] overlap on the device, C [135, 200]
    follows a gap.  Walls are 2 ms longer than wait + runner."""
    base = 1_000_000.0     # ms on the shared clock
    spec = [("a", 0, 2, 10, 4, 80, 1), ("b", 10, 4, 20, 4, 90, 2),
            ("c", 135, 6, 30, 5, 20, 3)]
    spans, events, records = [], [], []
    for trace, t0, wait, ingest, host, wait_dev, rows in spec:
        got = query_spans(trace, base + t0, wait, ingest, host,
                          wait_dev, rows)
        root = got[-1]
        spans += got
        events.append({"kind": "server_dequeue", "t_ns": root["t_ns"],
                       "wait_ns": wait * MS})
        records.append({
            "ok": True,
            "t_start": (root["t_ns"] - wait * MS - MS) / 1e9,
            "t_end": (root["t_ns"] + root["dur_ns"] + MS) / 1e9})
    # before and after the window: the warm-up and the traced pair
    spans += query_spans("warm", base - 500, 1, 5, 5, 5, 5)
    spans += query_spans("traced", base + 900, 1, 5, 5, 5, 5)
    elapsed = max(r["t_end"] for r in records) - min(
        r["t_start"] for r in records)
    run = types.SimpleNamespace(records=records, elapsed_s=elapsed)
    return run, spans, events


# per query: ingest 10/20/30, rows 1/2/3, device_wait 80/90/20,
# execute->device_wait 4/4/5, queue wait 2/4/6, front door 2 each.
# fed: A [12,96] + B [32,126] = [12,126] -> 114, C [167,192] -> 25;
# the window is [-3, 198]: 201 ms.
EXPECTED = {"queue_wait_ms": 4.0, "front_door_overhead_ms": 2.0,
            "ingest_span_ms": 20.0, "rows_ms": 2.0,
            "dispatch_host_ms": 4.0, "device_wait_ms": 80.0,
            "device_unfed_pct": 100.0 * (1 - (114 + 25) / 201)}


@pytest.fixture
def program(monkeypatch):
    """The readers' source, hand-built: (run, set) where ``set`` swaps
    the program's records."""
    run, spans, events = hand_built()
    state = {"spans": spans, "events": events, "dropped": 0}
    monkeypatch.setattr(
        libspans, "program_records",
        lambda: (state["spans"], state["events"], state["dropped"]))
    return run, state


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_reader_on_hand_built_records(program, metric):
    run, _state = program
    value = harness.load("layer_metrics", metric).read(run)
    assert value == pytest.approx(EXPECTED[metric], rel=1e-6)


def test_timeline_keeps_the_window_and_unions_overlapping_streams(
        program):
    run, _state = program
    t = libspans.timeline(run)
    assert [q["root"]["trace_id"] for q in t["queries"]] == [
        "a", "b", "c"]
    assert libspans.fed_seconds(t) == pytest.approx(0.139)


@pytest.mark.parametrize("fault", ["dropped_span", "missing_root",
                                   "foreign_clock", "failed_query",
                                   "no_recorder"])
def test_readers_return_none_where_the_timeline_is_not_whole(
        program, monkeypatch, fault):
    run, state = program
    if fault == "dropped_span":
        state["dropped"] = 1
    elif fault == "missing_root":
        state["spans"] = [s for s in state["spans"]
                          if s["span_id"] != "server_query:qb"]
    elif fault == "foreign_clock":
        monkeypatch.setattr(libspans, "same_clock", lambda: False)
    elif fault == "failed_query":
        run.records[1]["ok"] = False
    else:
        monkeypatch.setattr(libspans, "program_records", lambda: None)
    assert libspans.timeline(run) is None
    for metric in NEW_METRICS:
        assert harness.load("layer_metrics", metric).read(run) is None


def test_queue_wait_alone_is_left_out_where_the_journal_wrapped(program):
    run, state = program
    state["events"] = state["events"][1:]
    got = {m: harness.load("layer_metrics", m).read(run)
           for m in NEW_METRICS}
    assert got.pop("queue_wait_ms") is None
    assert all(v is not None for v in got.values())


def test_perf_counter_and_monotonic_are_one_clock_here():
    assert libspans.same_clock()


# --------------------------------------------- (e) the whole rehearsal


def _metric(metrics, name):
    """The one entry of a result's ``metrics`` for the quantity
    ``name``: a cell reports it under the plain name or with one
    ``.<variant>`` suffix, as ``run.py``'s ``load`` reads both with the
    quantity's file (``to_rows_ms.hostpaced`` -> ``to_rows_ms.py``)."""
    found = [k for k in metrics
             if k == name or k.rsplit(".", 1)[0] == name]
    assert len(found) == 1, (name, sorted(metrics))
    return metrics[found[0]]


@pytest.mark.parametrize("metrics,name,want", [
    ({"to_rows_ms.hostpaced": 1, "from_rows_ms.hostpaced": 2},
     "to_rows_ms", 1),
    ({"to_rows_ms": 1, "from_rows_ms": 2}, "to_rows_ms", 1),
    ({"query_ms.p50": 3, "query_ms.p99": 4, "rows_ms": 5}, "rows_ms", 5),
])
def test_metric_lookup_ignores_one_variant_suffix(metrics, name, want):
    assert _metric(metrics, name) == want


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_line_holds_the_seven_new_metrics(cell):
    """``--seconds 1``: at toy size the server answers some three
    hundred queries a second and the journal's 8192-event ring holds
    about seven hundred of them, so a longer window would lose
    ``queue_wait_ms`` (which is left out, not wrong)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         cell, "--size", "toy", "--seconds", "1", "--trace", "1",
         "--seed", "2147483659"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    metrics = result["metrics"]
    for name in NEW_METRICS + ("query_tail_ms", "host_ingest_ms",
                               "window_compiles"):
        _metric(metrics, name)
    # off the chip the two device-trace metrics are left out
    assert not any(k.split(".")[0] in ("hbm_roofline",
                                       "device_idle_pct")
                   for k in metrics)
    assert _metric(metrics, "window_compiles")["value"] == 0
    assert 0.0 <= _metric(metrics, "device_unfed_pct")["value"] <= 100.0
    assert _metric(metrics, "dispatch_host_ms")["value"] > 0


# ------------------------------- (f) the row-conversion cell's readers

ROWCONV_CELL = "rowconv-fixed-212x1m-roundtrip"


def test_rehearsal_line_holds_the_row_conversion_metrics():
    """The cell's line on a rehearsal: the program's own ``to_rows`` /
    ``from_rows`` spans are read (``rowconv_dispatch_ms``) beside the
    benchmark's blocking spans; the two roofline shares are device
    numbers and are left out off the chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         ROWCONV_CELL, "--size", "toy", "--seconds", "1", "--trace", "1",
         "--seed", "2147483659"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["row_bytes_differing"]["value"] == 0
    assert result["compared"]["column_bytes_differing"]["value"] == 0
    metrics = result["metrics"]
    for name in ("to_rows_ms", "from_rows_ms",
                 "rowconv_dispatch_ms", "window_compiles"):
        _metric(metrics, name)
    assert not any(k.split(".")[0] in (
        "to_rows_roofline", "from_rows_roofline", "hbm_roofline",
        "device_idle_pct") for k in metrics)
    assert _metric(metrics, "window_compiles")["value"] == 0
    # the program's spans lie inside the benchmark's blocking ones
    assert 0 < _metric(metrics, "rowconv_dispatch_ms")["value"] <= (
        _metric(metrics, "to_rows_ms")["value"]
        + _metric(metrics, "from_rows_ms")["value"])


def test_from_rows_span_counts_its_results_and_the_counter_the_columns(
        switches):
    """212 columns, one of them with a null: the span ``from_rows``
    says how many arrays the extract executable handed over (values,
    the validity words, the all-valid word), and
    ``srt_from_rows_validity_total`` how each column's validity
    resolved when it was first read (ISSUE 37)."""
    import numpy as np

    from spark_rapids_tpu.columns import dtypes
    from spark_rapids_tpu.columns.column import Column
    from spark_rapids_tpu.columns.table import Table
    from spark_rapids_tpu.ops import row_conversion as RC

    obs.enable()
    rows = 256
    valid = np.ones(rows, np.uint8)
    valid[5] = 0
    table = Table([Column.from_numpy(
        np.arange(rows, dtype=np.int32) + i,
        validity=valid if i == 100 else None, dtype=dtypes.INT32)
        for i in range(212)])
    back = RC.convert_from_rows(RC.convert_to_rows(table),
                                [dtypes.INT32] * 212)
    (span,) = [s for s in obs.TRACER.records() if s["name"] == "from_rows"]
    assert span["span_kind"] == "phase"
    assert span["attrs"]["results"] == 214
    assert span["attrs"]["engine"] == "words"
    family = obs.METRICS.family_snapshot("srt_from_rows_validity_total")
    assert [s for s in family["series"] if s["value"]] == []
    assert [c.has_validity for c in back.columns] == [
        i == 100 for i in range(212)]
    assert np.array_equal(np.asarray(back.columns[100].validity), valid)
    family = obs.METRICS.family_snapshot("srt_from_rows_validity_total")
    assert family["labels"] == ["outcome"]
    assert {s["labels"][0]: s["value"] for s in family["series"]} == {
        "absent": 211, "materialized": 1}


@pytest.mark.parametrize("metric,span", [("to_rows_roofline", "to_rows"),
                                         ("from_rows_roofline",
                                          "from_rows")])
def test_direction_roofline_on_hand_built_busy_times(metric, span):
    """A direction moves the 2^20 x 212 table once each way:
    2,155,872,256 B, 2.632 ms at 819 GB/s; a median device-busy time of
    12 ms inside its brackets is 21.94 % of the roofline.  Untraced
    runs, rehearsals and a profile without the bracket read nothing."""
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = harness.Cell(manifest, ROWCONV_CELL, 1, "full")
    assert cell.reference.min_bytes(
        cell.sizes, cell.traffic["params"]) == 4_311_744_512
    run = types.SimpleNamespace(
        cell=cell, trace={"busy_s": 0.025}, device_kind="TPU v5 lite",
        rehearsal=False, direction_busy={span: [0.011, 0.012, 0.3]})
    read = harness.load("layer_metrics", metric).read
    assert read(run) == pytest.approx(100 * 2_155_872_256 / 819e9 / 0.012)
    assert 21.9 < read(run) < 22.0
    run.rehearsal = True
    assert read(run) is None
    run.rehearsal, run.trace = False, None
    assert read(run) is None
    run.trace, run.direction_busy = {"busy_s": 0.025}, {}
    assert read(run) is None
    run.direction_busy = None       # a profile with no device events
    assert read(run) is None


def test_direction_profile_brackets_both_directions(switches):
    """The readers' own profile at toy size (the CPU's XLA threads stand
    in for the device plane, as in ``lib/trace.read_xplane``): one busy
    time per round and direction, each above 0 and inside the round.
    The counters are on, as ``run.py`` has them: the cell's binding
    reads which engine served its warm round trip."""
    from lib import direction

    obs.enable()
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = harness.Cell(manifest, ROWCONV_CELL, 2147483659, "toy")
    busy = direction.profile_directions(cell, rounds=2)
    assert sorted(busy) == ["from_rows", "to_rows"]
    for seconds in busy.values():
        assert len(seconds) == 2 and all(0 < s < 5 for s in seconds)
    cell.traffic = {k: v for k, v in cell.traffic.items()
                    if k != "op_binding"}
    assert direction.profile_directions(cell) is None


@pytest.mark.parametrize("metric", ["to_rows_roofline",
                                    "from_rows_roofline"])
def test_direction_rooflines_are_read_from_the_device_trace(metric):
    """A share of a roofline is a device number; its
    manifest entry says where it comes from."""
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry = [m for m in manifest["per_layer"] if m["name"] == metric][0]
    assert entry["source"] == "device_trace"
    assert entry["workloads"] == [ROWCONV_CELL]


def test_rowconv_dispatch_reads_one_span_of_each_name_per_operation(
        switches):
    """Hand-built operations around real spans; an operation that lacks
    one of the two spans leaves the metric out."""
    import time

    obs.enable()
    records = []
    for k in range(3):
        t0 = time.perf_counter()
        for name in ("to_rows", "from_rows"):
            if k == 2 and name == "from_rows":
                continue
            with obs.TRACER.start_span(name, kind="phase"):
                time.sleep(0.002)
        records.append({"ok": True, "t_start": t0,
                        "t_end": time.perf_counter()})
    read = harness.load("layer_metrics", "rowconv_dispatch_ms").read
    whole = types.SimpleNamespace(records=records[:2])
    assert 4.0 <= read(whole) < 40.0
    assert read(types.SimpleNamespace(records=records)) is None
