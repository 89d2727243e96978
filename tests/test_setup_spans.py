"""Set-up with names: every JAX compile step of the process as a
``compile`` span under the span that caused it (JAX's compile events,
``observability/__init__.py``), the q5 load's ``table_gen`` /
``table_upload`` / ``table_order`` under ``table_load``, and the
benchmark's five readers of set-up (``benchmark/lib/setup_spans.py``)."""

import os
import sys
import threading
import types

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from spark_rapids_tpu import observability as obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run as harness  # noqa: E402  (benchmark/run.py: loads by name)
from lib import setup_spans  # noqa: E402
from lib import spans as libspans  # noqa: E402

STEP_EVENTS = {
    "trace": "/jax/core/compile/jaxpr_trace_duration",
    "lower": "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "backend": "/jax/core/compile/backend_compile_duration",
}
USE_CACHE = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
READERS = ("setup_compile_s", "setup_compile_misses", "setup_table_gen_s",
           "setup_table_upload_s", "setup_table_order_s")


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


def compile_counts():
    fam = obs.METRICS.snapshot().get("srt_compile_total", {})
    return {tuple(s["labels"]): s["value"] for s in fam.get("series", ())}


def synthetic_step(step, fun="f", cache=()):
    """One compile step as JAX reports it: the start scalar, the cache
    events inside it, the time span at its end."""
    event = STEP_EVENTS[step]
    t0 = 1.0e9
    jax.monitoring.record_scalar(event, t0, fun_name=fun)
    for e in cache:
        jax.monitoring.record_event(e)
    jax.monitoring.record_event_time_span(event, t0, t0 + 0.5,
                                          fun_name=fun)


def end(r):
    return r["t_ns"] + r["dur_ns"]


# ------------------------------------------------- (a) compile spans


def test_compile_spans_one_per_step_under_the_open_span(switches):
    obs.enable()
    with obs.TRACER.span("table_load", kind="phase") as outer:
        jax.jit(lambda x: jnp.where(x > 2, x * 3 + 1, 0))(
            jnp.arange(16)).block_until_ready()
    recs = obs.TRACER.records()
    by_id = {r["span_id"]: r for r in recs}
    comp = [r for r in recs if r["name"] == "compile"]
    assert comp and all(r["span_kind"] == "compile" for r in comp)
    steps = [r["attrs"]["step"] for r in comp
             if "<lambda>" in r["attrs"]["fun"]
             and r["parent_id"] == f"{outer.span_id:016x}"]
    assert sorted(steps) == ["backend", "lower", "trace"]
    for r in comp:
        a = r["attrs"]
        assert a["step"] in STEP_EVENTS
        assert ("persistent" in a) == (a["step"] == "backend")
        # a nested trace goes under the trace that caused it; every
        # step lies inside its parent, on the tracer's clock
        parent = by_id[r["parent_id"]]
        assert parent["name"] in ("compile", "table_load")
        if parent["name"] == "compile":
            assert parent["attrs"]["step"] == "trace"
        assert parent["t_ns"] <= r["t_ns"] and end(r) <= end(parent)
    # the spans go through the same finish path: ring, journal,
    # histogram, and one counter increment a step
    journal = [e for e in obs.JOURNAL.records("span")
               if e["name"] == "compile"]
    assert len(journal) == len(comp)
    kinds = {}
    for r in comp:
        key = (r["attrs"]["step"], r["attrs"].get("persistent", "-"))
        kinds[key] = kinds.get(key, 0) + 1
    assert compile_counts() == kinds


@pytest.mark.parametrize("cache,verdict", [
    ((), "off"), ((USE_CACHE,), "miss"), ((USE_CACHE, CACHE_HIT), "hit")])
def test_backend_step_reads_the_persistent_cache(switches, cache,
                                                 verdict):
    obs.enable()
    synthetic_step("backend", cache=cache)
    (rec,) = obs.TRACER.records()
    assert rec["attrs"] == {"step": "backend", "fun": "f",
                            "persistent": verdict}
    assert compile_counts() == {("backend", verdict): 1}
    # the verdict belongs to that step alone
    synthetic_step("backend")
    assert obs.TRACER.records()[-1]["attrs"]["persistent"] == "off"


def test_persistent_cache_hit_miss_off_from_real_compiles(
        switches, tmp_path):
    cfg = jax.config
    prior = (cfg.jax_compilation_cache_dir,
             cfg.jax_persistent_cache_min_compile_time_secs,
             cfg.jax_persistent_cache_min_entry_size_bytes,
             cfg.jax_enable_compilation_cache)
    obs.enable()

    def f(x):
        return x * 5 - 11

    def verdict():
        jax.clear_caches()
        jax.jit(f)(jnp.arange(8.0)).block_until_ready()
        return [r["attrs"]["persistent"] for r in obs.TRACER.records()
                if r["attrs"]["step"] == "backend"
                and r["attrs"]["fun"] == "jit(f)"][-1]
    try:
        cfg.update("jax_compilation_cache_dir", str(tmp_path))
        cfg.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        cfg.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cfg.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
        assert verdict() == "miss"
        assert verdict() == "hit"
        cfg.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        assert verdict() == "off"
    finally:
        cfg.update("jax_compilation_cache_dir", prior[0])
        cfg.update("jax_persistent_cache_min_compile_time_secs", prior[1])
        cfg.update("jax_persistent_cache_min_entry_size_bytes", prior[2])
        cfg.update("jax_enable_compilation_cache", prior[3])
        compilation_cache.reset_cache()


def test_metrics_switch_off_records_and_counts_nothing(switches):
    jax.jit(lambda x: x - 9)(jnp.arange(4)).block_until_ready()
    for step in STEP_EVENTS:
        synthetic_step(step, cache=(USE_CACHE,))
    assert len(obs.TRACER) == 0 and obs.TRACER.depth() == 0
    assert compile_counts() == {}
    # and a step that started with the switch off stays unrecorded
    event = STEP_EVENTS["lower"]
    jax.monitoring.record_scalar(event, 1.0, fun_name="g")
    obs.enable()
    jax.monitoring.record_event_time_span(event, 1.0, 2.0, fun_name="g")
    assert len(obs.TRACER) == 0 and obs.TRACER.depth() == 0


def test_pool_thread_compiles_under_the_span_that_handed_them(switches):
    """``activate`` holds under the metrics switch too: a pool's
    compile goes under the span of the thread that gave it work."""
    obs.enable()
    lowered = jax.jit(lambda x: x * 7).lower(jnp.arange(32))
    with obs.TRACER.span("table_order", kind="phase") as span:
        here = obs.TRACER.current_context()

        def work():
            with obs.TRACER.activate(here):
                lowered.compile()
        th = threading.Thread(target=work)
        th.start()
        th.join()
    recs = obs.TRACER.records()
    (backend,) = [r for r in recs if r["name"] == "compile"
                  and r["attrs"]["step"] == "backend"
                  and "<lambda>" in r["attrs"]["fun"]]
    assert backend["parent_id"] == f"{span.span_id:016x}"
    assert backend["thread"] != threading.get_ident()
    assert [r["name"] for r in recs if r["name"] != "compile"] == [
        "table_order"]
    # with both switches off the placeholder is the no-op one
    obs.disable()
    assert obs.TRACER.activate(here) is obs.NOOP_SPAN


# ---------------------------------------------- (b) the q5 load's spans


def test_q5_load_has_gen_upload_order_under_table_load(switches):
    from spark_rapids_tpu.models import resident, tpcds
    from spark_rapids_tpu.server import QueryServer, ServerConfig
    obs.enable()
    resident.REGISTRY.clear()
    params = {"sales_date": "2000-08-23", "db_seed": 7, "sizes": SIZES,
              "limit": 100}
    srv = QueryServer(ServerConfig(max_concurrency=1)).start()
    try:
        qid = srv.submit("t", "tpcds_q5_channels", params)
        assert srv.poll(qid, timeout_s=600.0)["state"] == "done"
    finally:
        srv.stop()
        resident.REGISTRY.clear()
    recs = obs.TRACER.records()
    (load,) = [r for r in recs if r["name"] == "table_load"]
    kids = sorted((r for r in recs if r["parent_id"] == load["span_id"]
                   and r["name"] != "compile"), key=lambda r: r["t_ns"])
    assert [r["name"] for r in kids] == [
        "table_gen", "table_upload", "table_order"]
    for a, b in zip(kids, kids[1:]):
        assert end(a) <= b["t_ns"]
    assert sum(r["dur_ns"] for r in kids) <= load["dur_ns"]
    gen, upload, order = kids
    assert gen["attrs"]["rows"] == sum(SIZES[t] for t in tpcds.Q5_FACTS)
    assert upload["attrs"]["bytes"] >= load["attrs"]["bytes"] > 0
    # the sorts' backend steps ran on a pool's threads, under the order
    backends = [r for r in recs if r["name"] == "compile"
                and r["parent_id"] == order["span_id"]
                and r["attrs"]["step"] == "backend"]
    assert len(backends) == len(tpcds.Q5_FACTS)
    assert {r["thread"] for r in backends} != {order["thread"]}


def test_q5_load_holds_no_drawn_fact_past_its_sort(monkeypatch):
    """The waits keep no reference: while the facts are put in order
    the device holds the database and one fact's drawn columns, never
    every fact twice."""
    from spark_rapids_tpu.models import tpcds
    from spark_rapids_tpu.plan import catalog as C

    def live():
        return sum({a.unsafe_buffer_pointer(): a.nbytes
                    for a in jax.live_arrays()}.values())
    peak, real = [0], C._window_capacity

    def spy(*a):
        peak[0] = max(peak[0], live())
        return real(*a)
    monkeypatch.setattr(C, "_window_capacity", spy)
    before = live()
    tables = C.q5_channels_tables(
        tpcds.gen_q5_db(tpcds.q5_sizes(SIZES), 7), None,
        tpcds.Q5_WINDOW_DAYS)
    held = live() - before
    widest = max(sum(a.nbytes for a in tables[name])
                 for name in C.q5_windows(tables))
    assert held > 0 and peak[0] - before <= held + widest + 1024


SIZES = {"store_sales": 12000, "store_returns": 1200,
         "catalog_sales": 6000, "catalog_returns": 600, "web_sales": 3000,
         "web_returns": 300, "date_dim": 73049, "store": 12,
         "catalog_page": 60, "web_site": 6, "item": 500}


# ---------------------------------------------- (c) the five readers


def span(name, sid, parent, t0, dur, **attrs):
    return {"kind": "span", "name": name, "span_kind": "phase",
            "span_id": sid, "parent_id": parent, "trace_id": "t",
            "t_ns": t0, "dur_ns": dur, "thread": 1, "attrs": attrs}


def compile_span(sid, parent, t0, dur, step="trace", **attrs):
    s = span("compile", sid, parent, t0, dur, step=step, fun="f", **attrs)
    s["span_kind"] = "compile"
    return s


S = 10 ** 9
HAND_MADE = [
    span("server_query:q", "r", None, 1 * S, 20 * S),
    span("table_load", "l", "r", 2 * S, 9 * S),
    span("table_gen", "g", "l", 2 * S, 3 * S),
    span("table_upload", "u", "l", 5 * S, 2 * S),
    span("table_order", "o", "l", 7 * S, 4 * S),
    # the order's lowering, and two backend steps side by side on a
    # pool's threads (a cache hit and a miss), 1.5 s of wall
    compile_span("c1", "o", 7 * S, S // 2, step="lower"),
    compile_span("c2", "o", 8 * S, S, step="backend", persistent="hit"),
    compile_span("c3", "o", 8 * S + S // 2, S, step="backend",
                 persistent="miss"),
    span("execute", "e", "r", 12 * S, 9 * S),
    # the map stage's trace with a trace nested in it: 3 s once
    compile_span("c4", "e", 12 * S, 3 * S),
    compile_span("c5", "c4", 13 * S, S),
    # the window's: not set-up
    span("server_query:q", "w", None, 30 * S, 2 * S),
    compile_span("c6", "w", 30 * S, S, step="backend", persistent="miss"),
]


def hand_made_run(monkeypatch, spans_, dropped=0, clock=True):
    monkeypatch.setattr(libspans, "program_records",
                        lambda: (spans_, [], dropped))
    monkeypatch.setattr(libspans, "same_clock", lambda: clock)
    return types.SimpleNamespace(records=[
        {"t_start": 30.0, "t_end": 32.0, "ok": True}])


def read_all(run):
    return {name: harness.load("layer_metrics", name).read(run)
            for name in READERS}


def test_readers_take_the_spans_before_the_window(monkeypatch):
    got = read_all(hand_made_run(monkeypatch, HAND_MADE))
    assert got == pytest.approx({
        "setup_compile_s": 0.5 + 1.5 + 3.0,
        "setup_compile_misses": 1,
        "setup_table_gen_s": 3.0, "setup_table_upload_s": 2.0,
        "setup_table_order_s": 4.0 - 2.0})
    # gen + upload + order + the compiles inside the load fill the load
    assert (got["setup_table_gen_s"] + got["setup_table_upload_s"]
            + got["setup_table_order_s"] + 2.0) <= 9.0


@pytest.mark.parametrize("how", ["dropped", "other_clock", "no_window",
                                 "no_recorder"])
def test_readers_give_none_where_set_up_cannot_be_read(monkeypatch, how):
    run = hand_made_run(monkeypatch, HAND_MADE,
                        dropped=int(how == "dropped"),
                        clock=how != "other_clock")
    if how == "no_window":
        run.records = []
    if how == "no_recorder":
        monkeypatch.setattr(libspans, "program_records", lambda: None)
    assert read_all(run) == {name: None for name in READERS}


def test_readers_give_none_on_a_program_without_the_spans(monkeypatch):
    """The parent's set-up: a ``stage_compile`` span, no ``compile``
    span and no ``table_*`` children."""
    old = [span("server_query:q", "r", None, S, 5 * S),
           span("table_load", "l", "r", S, 2 * S),
           dict(span("stage_compile", "s", "r", 3 * S, S),
                span_kind="compile")]
    assert read_all(hand_made_run(monkeypatch, old)) == {
        name: None for name in READERS}
    assert setup_spans.setup(hand_made_run(monkeypatch, old)) == old
