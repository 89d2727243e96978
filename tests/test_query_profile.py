"""Query profiles (ISSUE 13): EXPLAIN ANALYZE for every query —
session lifecycle + noop discipline, attribution correctness vs
hand-computed deltas, stage-IR tree records from the compiler, golden
tree render, fleet merge + skew table, profile-diff thresholds,
server last-K retention/eviction, shim + socket doors, and the
flight-recorder/doctor/report-tool satellites."""

import copy
import json
import os
import socket
import threading
import time

import pytest

from spark_rapids_tpu import observability as obs
from spark_rapids_tpu.observability.journal import EventJournal
from spark_rapids_tpu.observability.profile import (QueryProfiler,
                                                    diff_profiles,
                                                    merge_profiles)
from spark_rapids_tpu.observability.registry import MetricsRegistry
from spark_rapids_tpu.observability.task_metrics import \
    TaskMetricsTable


# --------------------------------------------------------------- helpers


def isolated_profiler():
    """A fully injected profiler over fresh rings (the unit-test
    twin of the observability wiring)."""
    journal = EventJournal(capacity=512)        # enabled_ref None: on
    tasks = TaskMetricsTable()
    registry = MetricsRegistry(enabled=True)
    prof = QueryProfiler(journal=journal, tasks=tasks,
                         registry=registry)
    prof.enabled = True
    return prof, journal, tasks, registry


@pytest.fixture
def profiling():
    """Arm the real observability profiler (and metrics) around a
    test, restoring the prior switches after."""
    prior_m = obs.is_enabled()
    prior_p = obs.is_profiling_enabled()
    obs.enable()
    obs.enable_profiling()
    obs.reset()
    yield
    obs.reset()
    if not prior_p:
        obs.disable_profiling()
    if not prior_m:
        obs.disable()


# ------------------------------------------------------ session lifecycle


class TestSessionLifecycle:

    def test_begin_disabled_returns_none(self):
        prof, *_ = isolated_profiler()
        prof.enabled = False
        assert prof.begin("q") is None
        assert prof.end(None) is None
        assert not prof.active()
        assert prof.stats()["assembled"] == 0

    def test_note_stage_without_session_counts_dropped(self):
        prof, *_ = isolated_profiler()
        prof.note_stage({"stage": "x"})
        assert prof.stats()["dropped"] == {"no_session": 1}

    def test_end_assembles_and_retains(self):
        prof, *_ = isolated_profiler()
        sess = prof.begin("q-1", tenant="a", query="tpcds_q3")
        assert prof.active()
        p = prof.end(sess)
        assert p is not None and p["query_id"] == "q-1"
        assert p["tenant"] == "a" and p["wall_ns"] >= 0
        assert prof.last() is p
        assert not prof.active()

    def test_nested_begin_dropped_outer_wins(self):
        prof, *_ = isolated_profiler()
        outer = prof.begin("outer")
        assert prof.begin("inner") is None
        assert prof.stats()["dropped"] == {"nested": 1}
        prof.note_stage({"stage": "s", "digest": "d",
                         "engine": "fused", "wall_ns": 5})
        p = prof.end(outer)
        assert p["query_id"] == "outer"
        assert len(p["stages"]) == 1

    def test_thread_keyed_sessions_independent(self):
        prof, *_ = isolated_profiler()
        results = {}

        def work(name):
            sess = prof.begin(name)
            prof.note_stage({"stage": name, "digest": "d",
                            "engine": "fused", "wall_ns": 1})
            results[name] = prof.end(sess)

        ts = [threading.Thread(target=work, args=(f"q{i}",))
              for i in range(2)]
        [t.start() for t in ts]
        [t.join(10) for t in ts]
        for name in ("q0", "q1"):
            assert results[name]["query_id"] == name
            assert [s["stage"] for s in results[name]["stages"]] \
                == [name]

    def test_retention_ring_bounded(self):
        prof = QueryProfiler(keep=2)
        prof.enabled = True
        for i in range(4):
            prof.end(prof.begin(f"q{i}"))
        kept = [p["query_id"] for p in prof.retained()]
        assert kept == ["q2", "q3"]

    def test_keep_zero_disables_retention(self):
        prof = QueryProfiler(keep=0)
        prof.enabled = True
        p = prof.end(prof.begin("q"))
        assert p is not None                  # still assembled...
        assert prof.last() is None            # ...never retained
        assert prof.retained() == []
        assert prof.stats()["assembled"] == 1

    def test_begin_snapshot_failure_releases_reservation(self):
        """A snapshot failure in begin() must neither fail the query
        nor leave the thread's reservation behind (which would read
        as 'nested' forever and kill profiling on that thread)."""

        class BoomTracer:
            def current_context(self):
                raise RuntimeError("boom")

        prof = QueryProfiler(tracer=BoomTracer())
        prof.enabled = True
        assert prof.begin("q") is None
        assert prof.stats()["dropped"] == {"begin_error": 1}
        # the thread is NOT poisoned: a clean begin works
        prof.tracer = None
        sess = prof.begin("q2")
        assert sess is not None
        assert prof.end(sess)["query_id"] == "q2"


# ----------------------------------------------------------- attribution


class TestAttribution:

    def test_op_deltas_hand_computed(self):
        prof, _j, tasks, _r = isolated_profiler()
        tid = threading.get_ident()
        tasks.bind_thread(tid, [7])
        tasks.note_op("kudo_write", 1000)      # pre-session baseline
        sess = prof.begin("q")
        tasks.note_op("kudo_write", 200)
        tasks.note_op("kudo_write", 300)
        tasks.note_op("join", 50)
        p = prof.end(sess)
        assert p["ops"] == {"kudo_write": {"calls": 2,
                                           "time_ns": 500},
                            "join": {"calls": 1, "time_ns": 50}}

    def test_shared_unattributed_row_not_claimed_when_overlapping(
            self):
        """Two overlapping sessions with NO task binding (an
        adaptorless server pool): neither may claim the shared
        UNATTRIBUTED rollup row, or tenant B's ops would land in
        tenant A's profile."""
        prof, _j, tasks, _r = isolated_profiler()
        release = threading.Event()
        started = threading.Event()
        out = {}

        def overlapping():
            sess = prof.begin("B")
            tasks.note_op("b_op", 500)
            started.set()
            release.wait(10)
            out["B"] = prof.end(sess)

        sess_a = prof.begin("A")
        t = threading.Thread(target=overlapping)
        t.start()
        assert started.wait(10)
        tasks.note_op("a_op", 100)
        p_a = prof.end(sess_a)
        release.set()
        t.join(10)
        assert p_a["ops"] == {}            # shared row dropped
        assert out["B"]["ops"] == {}
        # a REAL task binding still attributes under overlap
        tasks.bind_thread(threading.get_ident(), [7])
        sess = prof.begin("C")
        sess.shared = True
        tasks.note_op("c_op", 9)
        p_c = prof.end(sess)
        assert p_c["ops"] == {"c_op": {"calls": 1, "time_ns": 9}}

    def test_lone_session_keeps_unattributed_ops(self):
        prof, _j, tasks, _r = isolated_profiler()
        sess = prof.begin("solo")
        tasks.note_op("solo_op", 42)
        p = prof.end(sess)
        assert p["ops"] == {"solo_op": {"calls": 1, "time_ns": 42}}

    def test_other_threads_ops_excluded(self):
        prof, _j, tasks, _r = isolated_profiler()
        tasks.bind_thread(threading.get_ident(), [7])
        sess = prof.begin("q")
        # a neighbor task on another thread works during the window
        t = threading.Thread(
            target=lambda: (tasks.bind_thread(threading.get_ident(),
                                              [8]),
                            tasks.note_op("neighbor", 9999)))
        t.start()
        t.join(10)
        p = prof.end(sess)
        assert "neighbor" not in p["ops"]

    def test_task_counter_deltas(self):
        prof, _j, tasks, _r = isolated_profiler()
        tid = threading.get_ident()
        tasks.bind_thread(tid, [7])
        tasks.fold_rmm_task(7, retry_oom=2, blocked_time_ns=100)
        sess = prof.begin("q")
        tasks.fold_rmm_task(7, retry_oom=1, blocked_time_ns=40)
        p = prof.end(sess)
        assert p["tasks"]["7"] == {"retry_oom": 1,
                                   "blocked_time_ns": 40}

    def test_journal_window_and_thread_scoping(self):
        prof, journal, *_ = isolated_profiler()
        me = threading.get_ident()
        journal.emit("retry_episode", name="before", attempts=9,
                     retries=9, splits=0, lost_ns=9, outcome="x",
                     thread=me)
        sess = prof.begin("q")
        journal.emit("retry_episode", name="mine", attempts=2,
                     retries=1, splits=1, lost_ns=100,
                     outcome="recovered", thread=me)
        journal.emit("retry_episode", name="theirs", attempts=5,
                     retries=5, splits=0, lost_ns=999, outcome="x",
                     thread=me + 1)
        journal.emit("oom_retry", thread=me, task=-1)
        journal.emit("thread_unblocked", thread=me, task=-1,
                     blocked_ns=77)
        journal.emit("kernel_path", op="join_inner",
                     path="device_hash", rows=10, thread=me)
        p = prof.end(sess)
        assert p["retries"] == {"episodes": 1, "attempts": 2,
                                "splits": 1, "lost_ns": 100,
                                "outcomes": {"recovered": 1}}
        assert p["oom"] == {"retry": 1, "split_retry": 0,
                            "blocked_ns": 77}
        assert p["kernel_paths"] == {"join_inner:device_hash": 1}
        # kind counts honor the same attribution filter: the foreign
        # thread's episode is not this query's story
        assert p["events"]["retry_episode"] == 1
        assert p["events"]["oom_retry"] == 1

    def test_shuffle_link_registry_delta(self):
        prof, _j, _t, registry = isolated_profiler()
        fam = registry.counter("srt_shuffle_link_bytes_total",
                               labels=("direction", "peer"))
        fam.inc(100, labels=("send", "1"))      # pre-session traffic
        sess = prof.begin("q")
        fam.inc(50, labels=("send", "1"))
        fam.inc(30, labels=("recv", "1"))
        p = prof.end(sess)
        assert p["shuffle_links"]["bytes"] == {"send": {"1": 50},
                                               "recv": {"1": 30}}

    def test_jit_cache_delta(self):
        prof, _j, _t, registry = isolated_profiler()
        hits = registry.counter("srt_jit_cache_hits_total",
                                labels=("kernel",))
        misses = registry.counter("srt_jit_cache_misses_total",
                                  labels=("kernel",))
        hits.inc(5, labels=("stage.q3",))
        sess = prof.begin("q")
        hits.inc(2, labels=("stage.q3",))
        misses.inc(1, labels=("stage.q5",))
        p = prof.end(sess)
        assert p["jit"] == {"stage.q3": {"hits": 2},
                            "stage.q5": {"misses": 1}}


# --------------------------------------------------------- stage records


class TestStageRecords:

    def test_fused_q3_stage_record(self, profiling):
        from spark_rapids_tpu.models import tpcds
        from spark_rapids_tpu.plan import catalog as C
        d = tpcds.gen_q3(rows=1500, items=64, days=730, brands=8)
        sess = obs.PROFILER.begin("q", query="q3")
        C.run_q3(d, 10_957, years=3, brands=8, manufact=2)
        p = obs.PROFILER.end(sess)
        (s,) = p["stages"]
        plan = C.q3_plan(10_957, 3, 8, 2)
        assert s["stage"] == "q3" and s["engine"] == "fused"
        assert s["dispatches"] == 1
        assert s["nodes_total"] == len(plan.nodes)
        assert len(s["nodes"]) == len(plan.nodes)
        facts = [i for i in s["inputs"] if i["name"] == "s"]
        assert facts and facts[0]["rows"] == 1500
        assert facts[0]["bucket"] == 2048
        assert facts[0]["pad_rows"] == 548   # bucket - rows
        assert p["hot_stage"] == "q3"

    def test_repeat_calls_aggregate(self, profiling):
        from spark_rapids_tpu.models import tpcds
        from spark_rapids_tpu.plan import catalog as C
        d = tpcds.gen_q3(rows=1100, items=64, days=730, brands=8)
        sess = obs.PROFILER.begin("q")
        C.run_q3(d, 10_957, years=3, brands=8, manufact=2)
        C.run_q3(d, 10_957, years=3, brands=8, manufact=2)
        p = obs.PROFILER.end(sess)
        (s,) = p["stages"]
        assert s["calls"] == 2
        assert s["wall_ns"] > 0

    def test_noop_when_disabled(self):
        prior = obs.is_profiling_enabled()
        obs.disable_profiling()
        try:
            from spark_rapids_tpu.models import tpcds
            from spark_rapids_tpu.plan import catalog as C
            before = obs.PROFILER.stats()["assembled"]
            d = tpcds.gen_q3(rows=800, items=64, days=730, brands=8)
            assert obs.PROFILER.begin("q") is None
            C.run_q3(d, 10_957, years=3, brands=8, manufact=2)
            assert obs.PROFILER.end(None) is None
            assert obs.PROFILER.stats()["assembled"] == before
        finally:
            if prior:
                obs.enable_profiling()


# -------------------------------------------------------- golden render


GOLDEN_PROFILE = {
    "profile_version": 1, "query_id": "q-000042", "tenant": "acme",
    "query": "tpcds_q5_fused", "rank": 0, "world": 1,
    "trace_id": "00000000deadbeef", "t_unix_ms": 0,
    "wall_ns": 10_000_000,
    "stages": [
        {"stage": "q5_partials", "digest": "abc", "engine": "fused",
         "compiled": True, "wall_ns": 8_000_000, "dispatches": 1,
         "nodes_total": 3, "calls": 1,
         "nodes": [{"kind": "JoinProbe", "outs": ["j.li"]},
                   {"kind": "Project", "outs": ["x"]},
                   {"kind": "SegmentSum", "outs": ["s"]}],
         "inputs": [{"name": "s", "rows": 6000, "bucket": 8192,
                     "pad_rows": 2192}]},
        {"stage": "q5_finish", "digest": "def", "engine": "fused",
         "compiled": False, "wall_ns": 1_000_000, "dispatches": 1,
         "nodes_total": 2, "calls": 1, "nodes": [], "inputs": []},
    ],
    "hot_stage": "q5_partials",
    "ops": {"kudo_write": {"calls": 2, "time_ns": 500_000}},
    "retries": {"episodes": 1, "attempts": 2, "splits": 0,
                "lost_ns": 250_000, "outcomes": {"recovered": 1}},
    "oom": {"retry": 1, "split_retry": 0, "blocked_ns": 100_000},
    "kernel_paths": {"join_inner:device_hash": 1},
    "jit": {"stage.q5_partials": {"hits": 0, "misses": 1}},
    "shuffle_links": {"bytes": {"send": {"1": 2048},
                                "recv": {"1": 1024}}},
    "spans": {"count": 3, "by_kind": {"query": 1, "stage": 2}},
}

GOLDEN_RENDER = [
    "srt-explain: tpcds_q5_fused  (query_id q-000042, tenant acme, "
    "trace 00000000deadbeef)",
    "wall 10.000 ms   stages 2   hot q5_partials",
    "plan tree (stage-IR attribution):",
    "  q5_partials      [fused, compiled, 1 dispatch / 3 nodes]  "
    "    8.000 ms  (80%)  <-- HOT",
    "      inputs: s rows=6000/8192 pad=2192",
    "      nodes: JoinProbe, Project, SegmentSum",
    "  q5_finish        [fused, cache-hit, 1 dispatch / 2 nodes]  "
    "    1.000 ms  (10%)",
    "shuffle links: send[1]=2.0KiB  recv[1]=1.0KiB",
    "task-scoped ops: kudo_write=0.500ms/2",
    "retries: 1 episodes (2 attempts, 0 splits, 0.250 ms lost)   "
    "oom: 1 retry / 0 split, blocked 0.100 ms",
    "kernel paths: join_inner:device_hash=1",
    "jit cache: stage.q5_partials(hits=0,misses=1)",
    "trace-scoped spans: 3 (query=1 stage=2)",
]


class TestGoldenRender:

    def test_golden_tree_render(self):
        from spark_rapids_tpu.tools.srt_explain import render_profile
        assert render_profile(GOLDEN_PROFILE) == GOLDEN_RENDER

    def test_nodes_flag_lists_every_node(self):
        from spark_rapids_tpu.tools.srt_explain import render_profile
        lines = render_profile(GOLDEN_PROFILE, nodes=True)
        assert any("JoinProbe" in ln and "j.li" in ln
                   for ln in lines)

    def test_render_diff_golden(self):
        from spark_rapids_tpu.tools.srt_explain import render_diff
        assert render_diff([], 1.5) == \
            ["diff: no per-stage regression beyond x1.5"]
        lines = render_diff([{"stage": "q5_partials", "ratio": 4.0,
                              "base_mean_ms": 1.0,
                              "cur_mean_ms": 4.0}], 1.5)
        assert lines[0].startswith("diff: 1 stage(s) regressed")
        assert "q5_partials" in lines[1] and "x4.00" in lines[1]


# ------------------------------------------------------- merge and skew


def _rank_profile(rank, walls, trace="t0", links=None):
    return {
        "profile_version": 1, "query_id": f"q5-rank{rank}",
        "query": "dist_q5", "tenant": "", "rank": rank, "world": 2,
        "trace_id": trace, "t_unix_ms": 1000 + rank,
        "wall_ns": sum(walls.values()),
        "stages": [{"stage": s, "digest": "d", "engine": "fused",
                    "compiled": rank == 0, "wall_ns": w, "calls": 1,
                    "dispatches": 1, "nodes_total": 3, "nodes": [],
                    "inputs": []}
                   for s, w in walls.items()],
        "hot_stage": max(walls, key=walls.get),
        "ops": {}, "tasks": {}, "events": {},
        "retries": {"episodes": rank, "attempts": rank},
        "oom": {"retry": 0, "split_retry": 0, "blocked_ns": 0},
        "kernel_paths": {},
        "jit": {},
        "shuffle_links": links or {"bytes": {}},
        "spans": {},
    }


class TestMergeAndSkew:

    def test_max_over_ranks_and_skew_table(self):
        p0 = _rank_profile(0, {"q5_partials": 100, "q5_finish": 10})
        p1 = _rank_profile(1, {"q5_partials": 400, "q5_finish": 10})
        m = merge_profiles([p0, p1])
        assert m["fleet"] and m["world"] == 2
        assert m["ranks"] == [0, 1]
        assert m["trace_consistent"] and m["trace_id"] == "t0"
        parts = next(s for s in m["stages"]
                     if s["stage"] == "q5_partials")
        assert parts["wall_ns"] == 400
        assert parts["per_rank_wall_ns"] == {"0": 100, "1": 400}
        assert parts["compiled"] is True
        row = next(r for r in m["skew"]
                   if r["stage"] == "q5_partials")
        assert row["skew_ratio"] == 4.0
        assert m["wall_ns"] == max(p0["wall_ns"], p1["wall_ns"])
        assert m["retries"]["episodes"] == 1   # summed over ranks

    def test_trace_mismatch_flagged(self):
        p0 = _rank_profile(0, {"s": 1}, trace="aaa")
        p1 = _rank_profile(1, {"s": 2}, trace="bbb")
        m = merge_profiles([p0, p1])
        assert m["trace_consistent"] is False
        assert m["trace_id"] is None

    def test_missing_trace_ids_not_blessed_as_consistent(self):
        """Two tracing-off profiles cannot PROVE they belong to one
        fleet — the merge must flag, not silently bless them."""
        from spark_rapids_tpu.tools.srt_explain import render_profile
        p0 = _rank_profile(0, {"s": 1}, trace=None)
        p1 = _rank_profile(1, {"s": 2}, trace=None)
        m = merge_profiles([p0, p1])
        assert m["trace_consistent"] is False
        assert any("UNVERIFIED" in ln for ln in render_profile(m))
        # one rank missing its id is equally unproven
        m2 = merge_profiles([
            _rank_profile(0, {"s": 1}, trace="t0"),
            _rank_profile(1, {"s": 2}, trace=None)])
        assert m2["trace_consistent"] is False

    def test_links_keep_per_rank_resolution(self):
        p0 = _rank_profile(0, {"s": 1}, links={
            "bytes": {"send": {"1": 700}, "recv": {"1": 700}}})
        p1 = _rank_profile(1, {"s": 1}, links={
            "bytes": {"send": {"0": 700}, "recv": {"0": 700}}})
        m = merge_profiles([p0, p1])
        per_rank = m["shuffle_links"]["per_rank"]
        assert per_rank["0"]["bytes"]["send"] == {"1": 700}
        assert per_rank["1"]["bytes"]["recv"] == {"0": 700}

    def test_single_profile_passthrough(self):
        p0 = _rank_profile(0, {"s": 5})
        m = merge_profiles([p0])
        assert m == p0 and m is not p0
        with pytest.raises(ValueError):
            merge_profiles([])


# ------------------------------------------------------------------ diff


class TestDiff:

    def test_equal_profiles_no_regression(self):
        p = _rank_profile(0, {"s": 10_000_000})
        assert diff_profiles(p, copy.deepcopy(p)) == []

    def test_flags_ratio_above_threshold(self):
        base = _rank_profile(0, {"a": 10_000_000, "b": 10_000_000})
        cur = _rank_profile(0, {"a": 40_000_000, "b": 11_000_000})
        out = diff_profiles(base, cur, threshold=1.5)
        assert [f["stage"] for f in out] == ["a"]
        assert out[0]["ratio"] == 4.0

    def test_min_delta_floor_suppresses_micro_stages(self):
        base = _rank_profile(0, {"tiny": 1_000})       # 1 us
        cur = _rank_profile(0, {"tiny": 100_000})      # 100 us, x100
        assert diff_profiles(base, cur, threshold=1.5,
                             min_delta_ns=1_000_000) == []
        assert diff_profiles(base, cur, threshold=1.5,
                             min_delta_ns=0) != []

    def test_new_stage_is_not_a_regression(self):
        base = _rank_profile(0, {"a": 10_000_000})
        cur = _rank_profile(0, {"a": 10_000_000,
                                "brand_new": 99_000_000})
        assert diff_profiles(base, cur) == []


# ---------------------------------------------------------------- server


def _stub_runner(query, params, ctx):
    time.sleep(0.002)
    return {"ok": query}


class TestServerRetention:

    def _server(self, keep=2):
        from spark_rapids_tpu.server import QueryServer, ServerConfig
        cfg = ServerConfig(max_concurrency=1, profile_keep=keep)
        return QueryServer(cfg, runner=_stub_runner).start()

    def test_last_k_retention_and_eviction(self, profiling):
        srv = self._server(keep=2)
        try:
            qids = [srv.submit("acme", f"q{i}") for i in range(3)]
            for q in qids:
                assert srv.poll(q, timeout_s=30)["state"] == "done"
            assert srv.profile(qids[0]) is None     # evicted
            for q in qids[1:]:
                p = srv.profile(q)
                assert p is not None and p["query_id"] == q
            assert srv.profile_ids("acme") == qids[1:]
            assert srv.profile("nope") is None
        finally:
            srv.stop()

    def test_profiles_scoped_per_tenant(self, profiling):
        srv = self._server(keep=1)
        try:
            qa = srv.submit("a", "qx")
            qb = srv.submit("b", "qy")
            for q in (qa, qb):
                assert srv.poll(q, timeout_s=30)["state"] == "done"
            # one retained per tenant — neither evicts the other
            assert srv.profile(qa) is not None
            assert srv.profile(qb) is not None
        finally:
            srv.stop()

    def test_failed_query_still_profiled(self, profiling):
        def boom(query, params, ctx):
            raise RuntimeError("kaput")

        from spark_rapids_tpu.server import QueryServer, ServerConfig
        srv = QueryServer(ServerConfig(max_concurrency=1,
                                       profile_keep=2),
                          runner=boom).start()
        try:
            q = srv.submit("a", "qx")
            st = srv.poll(q, timeout_s=30)
            assert st["state"] == "failed"
            assert srv.profile(q) is not None
        finally:
            srv.stop()

    def test_disabled_profiling_retains_nothing(self):
        prior = obs.is_profiling_enabled()
        obs.disable_profiling()
        try:
            srv = self._server()
            try:
                q = srv.submit("a", "qx")
                assert srv.poll(q, timeout_s=30)["state"] == "done"
                assert srv.profile(q) is None
            finally:
                srv.stop()
        finally:
            if prior:
                obs.enable_profiling()

    def test_tenant_count_bounded(self, profiling):
        """A client looping fresh tenant strings must recycle whole
        tenant profile windows (LRU), not grow resident state."""
        srv = self._server(keep=1)
        cap = srv._MAX_TENANT_ROWS
        try:
            first = srv.submit("tenant-first", "q")
            assert srv.poll(first, timeout_s=30)["state"] == "done"
            for i in range(cap):
                q = srv.submit(f"tenant-{i}", "q")
                assert srv.poll(q, timeout_s=30)["state"] == "done"
            assert len(srv._profile_order) <= cap
            assert srv.profile(first) is None   # oldest tenant gone
        finally:
            srv.stop()

    def test_profile_keep_zero_disables_retention(self, profiling):
        srv = self._server(keep=0)
        try:
            q = srv.submit("a", "qx")
            assert srv.poll(q, timeout_s=30)["state"] == "done"
            assert srv.profile(q) is None
        finally:
            srv.stop()


class TestDoors:

    def test_socket_profile_op(self, profiling, tmp_path):
        from spark_rapids_tpu.server import SocketFrontDoor
        srv = TestServerRetention()._server(keep=4)
        path = str(tmp_path / "door.sock")
        door = SocketFrontDoor(srv, path).start()
        try:
            with socket.socket(socket.AF_UNIX,
                               socket.SOCK_STREAM) as c:
                c.connect(path)
                f = c.makefile("rwb")

                def ask(req):
                    f.write(json.dumps(req).encode() + b"\n")
                    f.flush()
                    return json.loads(f.readline())

                qid = ask({"op": "submit", "tenant": "a",
                           "query": "qx"})["query_id"]
                ask({"op": "poll", "query_id": qid,
                     "timeout_s": 30})
                got = ask({"op": "profile", "query_id": qid})
                assert got["ok"] and \
                    got["profile"]["query_id"] == qid
                miss = ask({"op": "profile", "query_id": "nope"})
                assert not miss["ok"]
                assert miss["error"]["type"] == "UnknownProfile"
        finally:
            door.stop()
            srv.stop()

    def test_shim_profile_switch_and_last(self, profiling):
        from spark_rapids_tpu.shim import jni_entry
        assert jni_entry.profile_enabled() is True
        prior = jni_entry.profile_set_enabled(True)
        assert prior is True
        prof, *_ = (obs.PROFILER,)
        sess = obs.PROFILER.begin("shim-q", tenant="t")
        obs.PROFILER.end(sess)
        blob = jni_entry.profile_last_json()
        assert json.loads(blob)["query_id"] == "shim-q"

    def test_shim_server_profile_json(self, profiling, monkeypatch):
        import spark_rapids_tpu.server as srv_pkg
        from spark_rapids_tpu.shim import jni_entry
        srv = TestServerRetention()._server(keep=4)
        monkeypatch.setattr(srv_pkg, "_SERVER", srv)
        try:
            qid = srv.submit("a", "qx")
            assert srv.poll(qid, timeout_s=30)["state"] == "done"
            got = json.loads(jni_entry.server_profile_json(qid))
            assert got["ok"] and got["profile"]["query_id"] == qid
            miss = json.loads(jni_entry.server_profile_json("no"))
            assert not miss["ok"]
            assert miss["error"]["type"] == "UnknownProfile"
        finally:
            monkeypatch.setattr(srv_pkg, "_SERVER", None)
            srv.stop()


# ------------------------------------------------- bundle/doctor/tools


class TestBundleAndDoctor:

    def test_bundle_carries_profile_and_tools_read_it(
            self, profiling, tmp_path):
        from spark_rapids_tpu.models import tpcds
        from spark_rapids_tpu.plan import catalog as C
        from spark_rapids_tpu.tools import expand_bundle_input
        from spark_rapids_tpu.tools import srt_explain as E
        from spark_rapids_tpu.tools.doctor import Bundle, analyze
        d = tpcds.gen_q3(rows=1200, items=64, days=730, brands=8)
        sess = obs.PROFILER.begin("q-slow", tenant="a",
                                  query="tpcds_q3_fused")
        C.run_q3(d, 10_957, years=3, brands=8, manufact=2)
        assert obs.PROFILER.end(sess) is not None
        obs.enable_flight_recorder(out_dir=str(tmp_path),
                                   max_bytes=1 << 22)
        try:
            path = obs.FLIGHT.trigger("manual", force=True,
                                      severity="info")
        finally:
            obs.disable_flight_recorder()
        assert path is not None
        assert os.path.isfile(os.path.join(path, "profile.json"))
        # expand_bundle_input resolves the bundle dir for srt-explain
        assert expand_bundle_input(path, "profile") == \
            [os.path.join(path, "profile.json")]
        (prof,) = E.load_profiles([path])
        assert prof["query_id"] == "q-slow"
        # doctor names the slowest plan node
        findings = analyze(Bundle(path))
        slow = [f for f in findings if f["kind"] == "slow_plan_node"]
        assert slow and "q3" in slow[0]["message"] \
            and "q-slow" in slow[0]["message"]

    def test_bundle_without_profile_fails_loudly(self, tmp_path):
        from spark_rapids_tpu.tools import expand_bundle_input
        d = tmp_path / "not_a_bundle"
        d.mkdir()
        with pytest.raises(FileNotFoundError):
            expand_bundle_input(str(d), "profile")


class TestReportSatellites:

    def test_histogram_table_renders_dash_rows(self):
        from spark_rapids_tpu.tools.metrics_report import \
            render_histogram_table
        registry = MetricsRegistry(enabled=True)
        fired = registry.histogram("srt_live_ns")
        registry.histogram("srt_idle_ns")       # exists, never fired
        fired.observe(5000)
        lines = render_histogram_table(registry.snapshot())
        live = [ln for ln in lines if ln.startswith("srt_live_ns")]
        idle = [ln for ln in lines if ln.startswith("srt_idle_ns")]
        assert live and "-" not in live[0]
        assert idle and idle[0].split()[1:] == ["-"] * 5
        # dash rows sort after live rows
        assert lines.index(live[0]) < lines.index(idle[0])

    def test_trace_export_stats_reports_fusion_counts(
            self, tmp_path):
        from spark_rapids_tpu.tools.trace_export import (
            fusion_counts, load_files)
        snap = {"srt_stage_fusion_total": {
            "kind": "counter", "labels": ["stage", "outcome"],
            "series": [
                {"labels": ["q5_partials", "fused"], "value": 3},
                {"labels": ["q5_partials", "compile"], "value": 1},
                {"labels": ["q3", "unfused"], "value": 2}]}}
        p = tmp_path / "journal.jsonl"
        with open(p, "w") as f:
            f.write(json.dumps({"kind": "registry_snapshot",
                                "registry": snap}) + "\n")
        fc = fusion_counts(load_files([str(p)]))
        assert fc == {"q5_partials": {"fused": 3, "compile": 1},
                      "q3": {"unfused": 2}}

    def test_trace_export_stats_sums_across_files(self, tmp_path):
        from spark_rapids_tpu.tools.trace_export import (
            fusion_counts, load_files)
        snap = {"srt_stage_fusion_total": {
            "series": [{"labels": ["q5_partials", "fused"],
                        "value": 2}]}}
        paths = []
        for r in range(2):
            p = tmp_path / f"journal_rank{r}.jsonl"
            with open(p, "w") as f:
                f.write(json.dumps({"kind": "registry_snapshot",
                                    "registry": snap}) + "\n")
            paths.append(str(p))
        fc = fusion_counts(load_files(paths))
        assert fc == {"q5_partials": {"fused": 4}}


class TestExplainCLI:

    def test_cli_renders_and_diffs(self, tmp_path, capsys):
        from spark_rapids_tpu.tools.srt_explain import main
        p1 = tmp_path / "base.json"
        with open(p1, "w") as f:
            json.dump(GOLDEN_PROFILE, f)
        assert main([str(p1)]) == 0
        out = capsys.readouterr().out
        assert "<-- HOT" in out and "q5_partials" in out
        assert main([str(p1), "--diff", str(p1)]) == 0
        slowed = copy.deepcopy(GOLDEN_PROFILE)
        for s in slowed["stages"]:
            s["wall_ns"] = s["wall_ns"] * 4 + 50_000_000
        p2 = tmp_path / "slow.json"
        with open(p2, "w") as f:
            json.dump(slowed, f)
        assert main([str(p2), "--diff", str(p1)]) == 1

    def test_cli_merges_rank_inputs(self, tmp_path, capsys):
        from spark_rapids_tpu.tools.srt_explain import main
        paths = []
        for r in range(2):
            p = tmp_path / f"rank{r}.json"
            with open(p, "w") as f:
                json.dump(_rank_profile(
                    r, {"q5_partials": (r + 1) * 1_000_000}), f)
            paths.append(str(p))
        assert main(paths + ["--json"]) == 0
        merged = json.loads(capsys.readouterr().out)
        assert merged["fleet"] and merged["ranks"] == [0, 1]

    def test_cli_rejects_non_profile(self, tmp_path):
        from spark_rapids_tpu.tools.srt_explain import main
        p = tmp_path / "junk.json"
        with open(p, "w") as f:
            json.dump({"nope": 1}, f)
        assert main([str(p)]) == 2
