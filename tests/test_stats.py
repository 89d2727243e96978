"""Data-statistics plane (observability/stats.py, ISSUE 20): sketch
accuracy differential vs exact numpy, the persistent StatsStore's
key/TTL discipline, collector est-vs-actual join + misestimate
sentinel, the disabled-path cost budget, and fused-vs-unfused tap
count reconciliation through plan/compiler."""

import json
import os
import time

import numpy as np
import pytest

from spark_rapids_tpu import observability as obs
from spark_rapids_tpu.models import tpcds
from spark_rapids_tpu.observability import stats as S
from spark_rapids_tpu.plan import catalog as C


@pytest.fixture
def isolated_store(monkeypatch, tmp_path):
    """Point the file layer at a throwaway path and reset the process
    side so tests never cross-talk through /tmp."""
    monkeypatch.setenv("SPARK_RAPIDS_TPU_STATS_STORE",
                       str(tmp_path / "stats.json"))
    obs.STATS.reset()
    yield
    obs.STATS.reset()


@pytest.fixture
def stats_on(isolated_store):
    prior = obs.is_stats_enabled()
    obs.enable_stats()
    yield
    if not prior:
        obs.disable_stats()


# ----------------------------------------------------------- sketches


class TestSketchAccuracy:

    def test_kmv_ndv_within_5pct_at_1e6_rows(self):
        rng = np.random.default_rng(7)
        vals = rng.integers(0, 200_000, 1_000_000, dtype=np.int64)
        true = len(np.unique(vals))
        sk = S.kmv_sketch(vals)
        assert not sk["exact"]
        assert abs(sk["ndv"] - true) / true < 0.05

    def test_kmv_exact_below_k(self):
        vals = np.arange(1000, dtype=np.int64) % 300
        sk = S.kmv_sketch(vals)
        assert sk["exact"] and sk["ndv"] == 300

    def test_kmv_strings_and_floats(self):
        strs = np.array(["a", "b", "a", "c", "b", "a"])
        assert S.kmv_sketch(strs)["ndv"] == 3
        # every NaN bit pattern is ONE distinct value
        f = np.array([1.0, np.nan, float.fromhex("0x1.8p+0"),
                      np.float64("nan"), 1.0])
        assert S.kmv_sketch(f)["ndv"] == 3

    def test_heavy_hitter_topk_exact_recall_on_zipf(self):
        rng = np.random.default_rng(11)
        vals = rng.zipf(1.5, 200_000)
        vals = vals[vals < 10_000]
        u, c = np.unique(vals, return_counts=True)
        true_top8 = set(u[np.argsort(-c)[:8]].tolist())
        sk = S.heavy_hitter_sketch(vals)
        assert set(S.heavy_hitter_topk(sk, 8)) == true_top8

    def test_heavy_hitter_overestimate_bound(self):
        """Space-saving guarantee: reported count overestimates the
        true one by at most the recorded err."""
        rng = np.random.default_rng(3)
        vals = rng.zipf(1.3, 100_000)
        vals = vals[vals < 50_000]
        u, c = np.unique(vals, return_counts=True)
        true = dict(zip(u.tolist(), c.tolist()))
        sk = S.heavy_hitter_sketch(vals)
        assert len(sk["items"]) <= sk["capacity"]
        for v, count, err in sk["items"]:
            t = true.get(v, 0)
            assert t <= count <= t + err

    def test_histogram_exact_on_uniform(self):
        vals = np.repeat(np.arange(160, dtype=np.int64), 25)
        h = S.histogram_sketch(vals, bins=16)
        assert h["counts"] == [250] * 16
        assert (h["lo"], h["hi"]) == (0.0, 159.0)

    def test_histogram_edge_cases(self):
        assert S.histogram_sketch(np.array(["x", "y"])) is None
        assert S.histogram_sketch(np.array([], dtype=np.int64)) is None
        assert S.histogram_sketch(
            np.array([np.nan, np.nan])) is None
        const = S.histogram_sketch(np.full(10, 7.0))
        assert const == {"bins": 1, "lo": 7.0, "hi": 7.0,
                         "counts": [10]}

    def test_column_stats_null_frac_minmax(self):
        vals = np.array([1.0, np.nan, 3.0, np.nan, 2.0, np.nan])
        cs = S.column_stats(vals)
        assert cs["rows"] == 6
        assert cs["null_frac"] == 0.5
        assert (cs["min"], cs["max"]) == (1.0, 3.0)
        assert cs["ndv"] == 4   # 3 finite + the canonical NaN

    def test_column_stats_row_cap(self):
        vals = np.arange(10_000, dtype=np.int64)
        cs = S.column_stats(vals, max_rows=1000)
        assert cs["rows"] == 1000 and cs["ndv"] == 1000


# -------------------------------------------------------------- store


class TestStatsStore:

    def test_record_lookup_roundtrip(self, isolated_store):
        st = S.StatsStore()
        st.record("dig", "j1", {"s": 0}, 1389)
        rec = st.lookup("dig", "j1", {"s": 0})
        assert rec["rows"] == 1389 and rec["calls"] == 1
        st.record("dig", "j1", {"s": 0}, 1400)
        assert st.lookup("dig", "j1", {"s": 0})["calls"] == 2
        assert st.lookup("dig", "j1", {"s": 0})["rows"] == 1400

    def test_epoch_bump_starts_fresh_key(self, isolated_store):
        st = S.StatsStore()
        st.record("dig", "j1", {"s": 0}, 100)
        assert st.lookup("dig", "j1", {"s": 1}) is None

    def test_survives_process_reset_via_file(self, isolated_store):
        S.StatsStore().record("dig", "of", {"s": 0, "r": 2}, 7)
        fresh = S.StatsStore()   # new process-side cache, same file
        assert fresh.lookup("dig", "of", {"s": 0, "r": 2})["rows"] == 7

    def test_ttl_expires_stale_entries(self, isolated_store):
        st = S.StatsStore()
        st.record("dig", "j1", {}, 5)
        path = S.store_path()
        with open(path) as f:
            d = json.load(f)
        for rec in d.values():
            rec["t"] = time.time() - S._ttl() - 60  # srt-lint: disable=SRT005 test backdates the TTL stamp
        with open(path, "w") as f:
            json.dump(d, f)
        assert S.StatsStore().lookup("dig", "j1", {}) is None

    def test_torn_file_reads_as_empty(self, isolated_store):
        with open(S.store_path(), "w") as f:
            f.write('{"torn":')
        assert S.StatsStore().lookup("dig", "n", {}) is None

    def test_clear_drops_file_and_process(self, isolated_store):
        st = S.StatsStore()
        st.record("dig", "j1", {}, 5)
        assert st.clear() == 1
        assert st.lookup("dig", "j1", {}) is None
        assert S._load(S.store_path()) == {}


# ---------------------------------------------------------- collector


def _mk_collector(events):
    return S.StatsCollector(
        store=S.StatsStore(),
        on_observation=lambda stage, nodes, mis: events.append(
            ("obs", stage, len(nodes), len(mis))),
        on_misestimate=lambda **kw: events.append(("mis", kw)),
        on_sketch=lambda ns: events.append(("sketch", ns)))


class TestCollector:

    def test_disabled_returns_none(self, isolated_store):
        c = _mk_collector([])
        assert c.note_stage({"stage": "q", "inputs": [],
                             "nodes": []}) is None

    def test_estimates_and_source_fallback(self, isolated_store):
        c = _mk_collector([])
        c.register_input_estimates("q5", {"s": 6000}, origin="catalog")
        c.note_source_rows("r", 750)
        assert c.estimate_for("q5", "input:s")["rows"] == 6000
        assert c.estimate_for("q5", "input:r")["origin"] == \
            "parquet_footer"
        assert c.estimate_for("q5", "input:zzz") is None
        assert c.estimate_for("q5", "j1") is None

    def test_note_stage_section_and_selectivity(self, isolated_store):
        events = []
        c = _mk_collector(events)
        c.enabled = True
        section = c.note_stage(
            {"stage": "q5", "plan_digest": "dig",
             "inputs": [{"name": "s", "rows": 1000}],
             "nodes": [{"node": "f", "kind": "Project", "rows": 250},
                       {"node": "j", "kind": "JoinProbe",
                        "rows": 40}]},
            columns={"s": np.arange(1000, dtype=np.int64)})
        by = {n["node"]: n for n in section["nodes"]}
        assert section["rows_in"] == 1000
        assert section["rows_out"] == 40
        assert by["input:s"]["ndv"] == 1000
        assert by["f"]["selectivity"] == 0.25
        assert "selectivity" not in by["j"]     # joins can expand
        assert ("obs", "q5", 3, 0) in events
        assert c.last("q5")["rows_in"] == 1000

    def test_misestimate_sentinel_first_flag(self, isolated_store,
                                             monkeypatch):
        monkeypatch.setenv("SPARK_RAPIDS_TPU_STATS_MISEST_RATIO", "8")
        events = []
        c = _mk_collector(events)
        c.enabled = True
        c.register_estimate("q5", "j", 100_000, origin="manual")
        ob = {"stage": "q5", "plan_digest": "dig", "inputs": [],
              "nodes": [{"node": "j", "kind": "JoinProbe",
                         "rows": 40}]}
        c.note_stage(ob)
        c.note_stage(ob)
        mis = [e[1] for e in events if e[0] == "mis"]
        assert len(mis) == 2
        assert mis[0]["first"] is True and mis[1]["first"] is False
        assert mis[0]["est"] == 100_000 and mis[0]["actual"] == 40
        assert mis[0]["ratio"] > 8
        sec = c.last("q5")
        assert sec["nodes"][0]["misestimate"] is True

    def test_within_threshold_is_silent(self, isolated_store,
                                        monkeypatch):
        monkeypatch.setenv("SPARK_RAPIDS_TPU_STATS_MISEST_RATIO", "8")
        events = []
        c = _mk_collector(events)
        c.enabled = True
        c.register_estimate("q5", "j", 100, origin="manual")
        c.note_stage({"stage": "q5", "plan_digest": "dig",
                      "inputs": [],
                      "nodes": [{"node": "j", "kind": "JoinProbe",
                                 "rows": 350}]})
        assert not [e for e in events if e[0] == "mis"]
        node = c.last("q5")["nodes"][0]
        assert node["est"] == 100 and "misestimate" not in node

    def test_sketch_memoized_per_epoch(self, isolated_store):
        events = []
        c = _mk_collector(events)
        c.enabled = True
        ob = {"stage": "q5", "plan_digest": "dig",
              "inputs": [{"name": "s", "rows": 100}], "nodes": []}
        col = {"s": np.arange(100, dtype=np.int64)}
        c.note_stage(ob, columns=col)
        c.note_stage(ob, columns=col)
        assert len([e for e in events if e[0] == "sketch"]) == 1

    def test_note_stage_never_raises(self, isolated_store):
        c = _mk_collector([])
        c.enabled = True
        assert c.note_stage({"stage": "q", "inputs": [
            {"bogus": "shape"}], "nodes": []}) is None


# -------------------------------------------------- disabled-path cost


class TestDisabledOverhead:

    def test_disabled_note_stage_under_budget(self, isolated_store):
        """The noop contract: with stats off the whole hook is one
        attribute read — budget < 1µs per call with slack for CI."""
        assert not obs.is_stats_enabled()
        ob = {"stage": "q5", "inputs": [], "nodes": []}
        n = 200_000
        t0 = time.monotonic_ns()
        for _ in range(n):
            obs.STATS.note_stage(ob)
        per_call = (time.monotonic_ns() - t0) / n
        assert per_call < 1000, f"{per_call:.0f}ns per disabled call"


# ------------------------------------------- compiler tap reconcile


class TestCompilerTaps:

    def _run_q5(self):
        d = tpcds.gen_q5(rows=2000, stores=16, days=60)
        return d, C.run_q5(d, 16, 1 << 11)

    def test_fused_unfused_taps_agree_and_bytes_identical(
            self, stats_on):
        """The fused executable's tapped counts against the same count
        expressions read on the host off the reference walk."""
        from spark_rapids_tpu.plan import compiler as PC
        d = tpcds.gen_q5(rows=2000, stores=16, days=60)
        plan = C.q5_partials_plan(16, 1 << 13)
        st = PC.compile_stage(plan)
        inputs = {"s": (d.s_date, d.s_store, d.s_price, d.s_profit),
                  "r": (d.r_date, d.r_store, d.r_amt, d.r_loss),
                  "d": (d.d_date,)}
        fused = st.run(inputs)
        fsec = obs.STATS.last("q5_partials")
        assert fsec is not None and fsec["nodes"]
        frows = {n["node"]: n["rows"] for n in fsec["nodes"]
                 if n["kind"] != "input"}
        env = st._walk_env(inputs)
        spec = PC._tap_spec(plan)
        urows = {nid: int(v) for (nid, _kind, _key), v in
                 zip(spec, PC._tap_counts(plan, env))}
        assert frows and frows == urows
        for g, o in zip(fused, plan.outputs):
            assert np.asarray(g).tobytes() == \
                np.asarray(env[o]).tobytes()

    def test_stats_do_not_change_results(self, isolated_store):
        prior = obs.is_stats_enabled()
        obs.disable_stats()
        try:
            _, base = self._run_q5()
            obs.enable_stats()
            _, tapped = self._run_q5()
        finally:
            obs.enable_stats() if prior else obs.disable_stats()
        for g, w in zip(tapped, base):
            assert np.asarray(g).tobytes() == np.asarray(w).tobytes()

    def test_catalog_estimates_registered(self, stats_on):
        self._run_q5()
        est = obs.STATS.estimate_for("q5_partials", "input:s")
        assert est is not None and est["rows"] == 2000
        assert est["origin"] == "catalog"
        sec = obs.STATS.last("q5_partials")
        ins = {n["node"]: n for n in sec["nodes"]
               if n["kind"] == "input"}
        assert ins["input:s"]["est"] == ins["input:s"]["rows"] == 2000
