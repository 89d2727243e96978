"""Tiered spill store + out-of-core execution (ISSUE 18): victim
ordering against the memory ledger, host->disk demotion under tier
budgets, byte-identical out-of-core join/agg at 4x-over-budget build
sides, spill rescue under injected OOM (chaos fault rules),
corrupt-spill-file recompute with file-path evidence, fused
stage-per-partition with zero recompiles on the second partition, and
the restore-under-concurrent-free race."""

import os
import threading
import time

import numpy as np
import pytest

from spark_rapids_tpu.columns import dtypes
from spark_rapids_tpu.columns.column import Column
from spark_rapids_tpu.columns.table import Table
from spark_rapids_tpu.memory import spill as spill_mod
from spark_rapids_tpu.memory.spill import (SpillStore, TIER_DEVICE,
                                           TIER_DISK, TIER_FREED,
                                           TIER_HOST)
from spark_rapids_tpu.ops import joins
from spark_rapids_tpu.ops import groupby
from spark_rapids_tpu.ops.out_of_core import (out_of_core_groupby,
                                              out_of_core_hash_join)


def _col_bytes(c):
    parts = []
    for buf in (c.data, c.validity, c.offsets):
        parts.append(b"" if buf is None else np.asarray(buf).tobytes())
    return tuple(parts)


def _assert_cols_identical(got, want):
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        assert _col_bytes(g) == _col_bytes(w), f"column {i}"


# --------------------------------------------- victim ordering (ledger)


class _StubAdaptor:
    """memory_ledger + spill-range surface the store touches."""

    def __init__(self, resident):
        self.resident = dict(resident)     # task_id -> active_bytes
        self.freed = 0

    def memory_ledger(self, timeline=0):
        return {
            "allocated_bytes": sum(self.resident.values()),
            "tasks": {str(t): {"active_bytes": b}
                      for t, b in self.resident.items()},
        }

    def spill_range_start(self):
        pass

    def spill_range_done(self):
        pass

    def deallocate(self, n):
        self.freed += n

    def allocate(self, n):
        pass


def _small_cols(v=1):
    return [Column.from_pylist([v, v + 1, None], dtypes.INT64)]


class TestVictimOrdering:

    def _store(self, tmp_path):
        store = SpillStore(spill_dir=str(tmp_path))
        stub = _StubAdaptor({1: 100, 2: 500})
        store._adaptor = lambda: stub          # instance-attr shadow
        return store, stub

    def test_victims_follow_priority_then_ledger(self, tmp_path):
        store, _ = self._store(tmp_path)
        h_pool = store.register(_small_cols(), device_bytes=64,
                                name="pool", task_id=None)
        h_t1 = store.register(_small_cols(), device_bytes=100,
                              name="t1", task_id=1)
        h_t2a = store.register(_small_cols(), device_bytes=50,
                               name="t2a", task_id=2)
        h_t2b = store.register(_small_cols(), device_bytes=200,
                               name="t2b", task_id=2)
        # task 2 is the newest task -> lowest priority, spilled first;
        # within it the larger handle goes first; pool data (no task,
        # max priority) is last in line
        assert [h.name for h in store._victims()] == \
            ["t2b", "t2a", "t1", "pool"]
        assert store.spillable_bytes() == 64 + 100 + 50 + 200
        # an explicit per-handle priority overrides the task formula
        h_t1._priority = -1
        assert store._victims()[0] is h_t1
        h_t1._priority = None
        for h in (h_pool, h_t1, h_t2a, h_t2b):
            h.close()

    def test_ensure_headroom_spills_only_enough(self, tmp_path):
        store, stub = self._store(tmp_path)
        store.register(_small_cols(), device_bytes=100, name="t1",
                       task_id=1)
        h_t2b = store.register(_small_cols(), device_bytes=200,
                               name="t2b", task_id=2)
        freed = store.ensure_headroom(1)
        assert freed == 200                   # one victim was enough
        assert h_t2b.tier == TIER_HOST
        assert stub.freed == 200
        assert store.spillable_bytes() == 100
        # a demand larger than everything drains the device tier
        assert store.ensure_headroom(1 << 40) == 100
        assert store.spillable_bytes() == 0
        assert store.stats()["spills_host"] == 2
        store.close()


# ------------------------------------------------------------- pinning


class TestPinning:

    def test_pinned_handles_are_not_victims(self, tmp_path):
        store = SpillStore(spill_dir=str(tmp_path))
        stub = _StubAdaptor({2: 500})
        store._adaptor = lambda: stub
        h1 = store.register(_small_cols(), device_bytes=100, name="a",
                            task_id=2)
        h2 = store.register(_small_cols(), device_bytes=50, name="b",
                            task_id=2)
        with h1.pin() as cols:
            assert cols is h1.columns
            # while an operator computes on h1, headroom passes must
            # not release its reservation out from under it
            assert [h.name for h in store._victims()] == ["b"]
            assert store.spillable_bytes() == 50
            assert store.stats()["spillable_bytes"] == 50
            assert store.ensure_headroom(1 << 40) == 50
            assert h1.tier == TIER_DEVICE and h2.tier == TIER_HOST
            assert h1.spill() == 0            # direct spill refused too
        # pin released -> victim-eligible again
        assert h1.pins == 0
        assert store.ensure_headroom(1 << 40) == 100
        assert h1.tier == TIER_HOST
        store.close()

    def test_pin_restores_spilled_batch(self, tmp_path):
        store = SpillStore(spill_dir=str(tmp_path))
        h = store.register(_small_cols(4), name="p")
        h.spill()
        assert h.tier == TIER_HOST
        with h.pin() as cols:
            _assert_cols_identical(cols, _small_cols(4))
            assert h.tier == TIER_DEVICE and h.pins == 1
            assert store.spillable_bytes() == 0
        assert h.pins == 0 and store.spillable_bytes() > 0
        h.close()
        store.close()


# ------------------------------------------------- host->disk demotion


class TestTierDemotion:

    def test_oldest_host_payload_demotes_first(self, tmp_path):
        store = SpillStore(spill_dir=str(tmp_path))
        h1 = store.register(_small_cols(1), name="first")
        h2 = store.register(_small_cols(9), name="second")
        h1.spill()
        payload_len = store._host_bytes
        assert payload_len > 0 and h1.tier == TIER_HOST
        # room for exactly one payload: spilling the second pushes the
        # OLDEST spill (h1) down to disk, the fresh one stays hosted
        store._host_limit = payload_len
        h2.spill()
        assert h1.tier == TIER_DISK and h2.tier == TIER_HOST
        assert h1.path and os.path.exists(h1.path)
        assert h1.path.endswith(".g1.kudo")
        st = store.stats()
        assert st["spills_host"] == 2 and st["spills_disk"] == 1
        assert st["tiers"][TIER_HOST]["bytes"] == payload_len
        # disk restore round-trips byte-identical and re-promotes
        got = h1.get()
        _assert_cols_identical(got, _small_cols(1))
        assert h1.tier == TIER_DEVICE and h1.path is None
        assert store.stats()["restores"] == 1
        store.close()
        assert not os.path.exists(str(h2.path or ""))


# ------------------------------------- out-of-core join/agg byte-identity


class TestOutOfCore:

    def _join_tables(self, nl=4000, nr=2000, nkeys=600):
        rng = np.random.default_rng(7)
        lk = rng.integers(0, nkeys, nl).astype(np.int64)
        rk = rng.integers(0, nkeys, nr).astype(np.int64)
        lv = rng.random(nl) < 0.05            # some nulls on each side
        rv = rng.random(nr) < 0.05
        left = Table([Column.from_numpy(lk, validity=~lv)], ["k"])
        right = Table([Column.from_numpy(rk, validity=~rv)], ["k"])
        return left, right

    def test_join_byte_identical_at_4x_over_budget(self, tmp_path):
        left, right = self._join_tables()
        want_l, want_r = joins.hash_inner_join(left, right,
                                               joins.NULL_EQUAL)
        budget = spill_mod.columns_nbytes(right.columns) // 4
        store = SpillStore(spill_dir=str(tmp_path))
        got_l, got_r = out_of_core_hash_join(
            left, right, joins.NULL_EQUAL, budget=budget, store=store)
        assert np.asarray(got_l).tobytes() == \
            np.asarray(want_l).tobytes()
        assert np.asarray(got_r).tobytes() == \
            np.asarray(want_r).tobytes()
        st = store.stats()
        assert st["spills_host"] >= 4        # every partition spilled
        assert st["restores"] >= 4           # ...and streamed back
        assert st["handles"] == 0            # all closed after the run
        store.close()

    def test_join_disabled_path_is_direct(self):
        left, right = self._join_tables(nl=64, nr=32, nkeys=8)
        want_l, want_r = joins.hash_inner_join(left, right,
                                               joins.NULL_EQUAL)
        got_l, got_r = out_of_core_hash_join(left, right,
                                             joins.NULL_EQUAL,
                                             budget=None)
        assert np.asarray(got_l).tobytes() == \
            np.asarray(want_l).tobytes()
        assert np.asarray(got_r).tobytes() == \
            np.asarray(want_r).tobytes()

    def test_groupby_byte_identical_at_4x_over_budget(self, tmp_path):
        rng = np.random.default_rng(11)
        n, ngroups = 6000, 500
        k = rng.integers(0, ngroups, n).astype(np.int64)
        v = rng.standard_normal(n)
        nulls = rng.random(n) < 0.07
        keys = Table([Column.from_numpy(k)], ["k"])
        val = Column.from_numpy(v, validity=~nulls)
        vals = [val] * 5
        aggs = ["sum", "count", "min", "max", "mean"]
        want = groupby.groupby_aggregate(keys, vals, aggs)
        budget = spill_mod.columns_nbytes(
            list(keys.columns) + vals) // 4
        store = SpillStore(spill_dir=str(tmp_path))
        got = out_of_core_groupby(keys, vals, aggs, budget=budget,
                                  store=store)
        _assert_cols_identical(list(got.columns), list(want.columns))
        st = store.stats()
        assert st["spills_host"] >= 4 and st["restores"] >= 4
        assert st["handles"] == 0
        store.close()


# ------------------------------------------ spill rescue under real OOM


class TestSpillUnderOOM:

    @pytest.fixture
    def runtime(self, tmp_path):
        from spark_rapids_tpu.memory import rmm_spark
        ad = rmm_spark.set_event_handler(1000)
        store = spill_mod.install(
            SpillStore(spill_dir=str(tmp_path)))
        try:
            yield ad, store
        finally:
            spill_mod.uninstall()
            rmm_spark.clear_event_handler()

    def test_alloc_failure_spills_before_bufn(self, runtime):
        """A dedicated task thread holds 800/1000 bytes through a
        registered spillable batch; a chaos-injected GpuRetryOOM plus
        a real over-limit allocation both resolve through the retry
        loop WITHOUT shedding: the adaptor's alloc-failure path calls
        ensure_headroom, the store spills the batch, and the retried
        allocation lands."""
        from spark_rapids_tpu.memory import rmm_spark
        from spark_rapids_tpu.robustness import retry
        ad, store = runtime
        out = {}

        def worker():
            try:
                tid = rmm_spark.current_thread_id()
                rmm_spark.start_dedicated_task_thread(tid, 7)
                ad.allocate(800)
                h = store.register(_small_cols(), device_bytes=800,
                                   name="big", task_id=7,
                                   stage="oom-test")
                rmm_spark.force_retry_oom(tid, 1)  # chaos fault rule

                def attempt():
                    retry.check_injected_oom("spill-oom")
                    ad.allocate(600)
                    return "ok"

                out["result"] = retry.with_retry(attempt,
                                                 name="spill-oom")
                out["state"] = ad.get_state_of(tid)
                out["tier"] = h.tier
                ad.deallocate(600)
                h.close()
                rmm_spark.task_done(7)
            except BaseException as e:     # pragma: no cover
                out["error"] = e

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive(), "spill rescue deadlocked"
        assert "error" not in out, out.get("error")
        assert out["result"] == "ok"
        assert out["tier"] in (TIER_HOST, TIER_DISK)
        st = store.stats()
        assert st["spills_host"] == 1       # the rescue, nothing else
        assert "RUNNING" in out["state"]


# ------------------------------------------- corrupt spill file handling


def _to_disk(tmp_path, cols, recompute=None):
    store = SpillStore(spill_dir=str(tmp_path), host_limit_bytes=0)
    h = store.register(list(cols), name="t", recompute=recompute)
    h.spill()
    assert h.tier == TIER_DISK and os.path.exists(h.path)
    return store, h


class TestCorruptSpill:

    def test_corrupt_file_recomputes_from_source(self, tmp_path):
        cols = _small_cols(5)
        store, h = _to_disk(tmp_path, cols,
                            recompute=lambda: list(cols))
        with open(h.path, "r+b") as f:       # flip payload bytes
            f.seek(40)
            raw = f.read(4)
            f.seek(40)
            f.write(bytes(b ^ 0xFF for b in raw))
        got = h.get()
        _assert_cols_identical(got, cols)
        st = store.stats()
        assert st["corrupt"] == 1 and st["recomputes"] == 1
        store.close()

    def test_corrupt_file_without_recompute_names_file(self, tmp_path):
        from spark_rapids_tpu.shuffle import kudo
        store, h = _to_disk(tmp_path, _small_cols(5))
        path = h.path
        with open(path, "r+b") as f:
            f.seek(40)
            raw = f.read(4)
            f.seek(40)
            f.write(bytes(b ^ 0xFF for b in raw))
        with pytest.raises(kudo.KudoCorruptException) as ei:
            h.get()
        assert ei.value.path == path
        assert ei.value.generation == 1
        assert path in str(ei.value) and "generation 1" in str(ei.value)
        assert store.stats()["corrupt"] == 1
        store.close()


# ---------------------------------- fused stage over spilled partitions


class TestFusedStageSpilled:

    def _plan(self):
        from spark_rapids_tpu.plan import ir
        return ir.StagePlan(
            name="t_spill_seg",
            inputs=(ir.ScanBind("f", (ir.ColSpec("k"),
                                      ir.ColSpec("v"))),),
            nodes=(
                ir.Project("keep", ir.Bin(
                    "and", ir.Mask("f"),
                    ir.Bin("gt", ir.Col("v"), ir.Lit(0)))),
                ir.Project("w", ir.Where(ir.Col("keep"), ir.Col("v"),
                                         ir.Lit(0, "int64"))),
                ir.SegmentSum("sums", ir.Col("w"), ir.Col("k"), 16),
            ),
            outputs=("sums",)).validate()

    def test_second_partition_is_a_cache_hit(self, tmp_path):
        from spark_rapids_tpu.perf.jit_cache import CACHE
        from spark_rapids_tpu.plan import compiler as PC
        rng = np.random.default_rng(3)
        n = 256                              # same rows -> same bucket
        k0 = rng.integers(0, 16, n).astype(np.int64)
        v0 = rng.integers(-5, 50, n).astype(np.int64)
        k1 = rng.integers(0, 16, n).astype(np.int64)
        v1 = rng.integers(-5, 50, n).astype(np.int64)
        cs = PC.compile_stage(self._plan())
        store = SpillStore(spill_dir=str(tmp_path))
        h = store.register(
            [Column.from_numpy(k0), Column.from_numpy(v0)], name="p0")
        h.spill()
        CACHE.clear(reset_stats=True)
        (out0,) = cs.run_spilled([{"f": h}])
        stats = CACHE.stats()
        assert stats["kernels"]["stage.t_spill_seg"]["misses"] == 1
        compiles = stats["compiles"]
        # second (same-bucket) partition: the fused executable is
        # REUSED — per-partition execution does not unfuse and does
        # not recompile
        (out1,) = cs.run_spilled([{"f": (k1, v1)}])
        stats = CACHE.stats()
        assert stats["compiles"] == compiles
        assert stats["kernels"]["stage.t_spill_seg"]["hits"] >= 1
        assert store.stats()["restores"] == 1
        # the spilled partition's fused result matches the plain run
        want0 = cs.run({"f": (k0, v0)})
        assert np.asarray(out0[0]).tobytes() == \
            np.asarray(want0[0]).tobytes()
        h.close()
        store.close()


# ------------------------------------- restore vs concurrent close race


class TestRestoreCloseRace:

    def test_reader_wins_and_nothing_leaks(self, tmp_path):
        cols = _small_cols(3)
        store = SpillStore(spill_dir=str(tmp_path),
                           host_limit_bytes=0)
        h = store.register(list(cols), name="raced")
        h.spill()
        path = h.path
        assert path and os.path.exists(path)

        in_restore = threading.Event()
        orig = store._deserialize

        def slow_deserialize(*a, **kw):
            in_restore.set()
            time.sleep(0.05)                 # hold the busy window
            return orig(*a, **kw)

        store._deserialize = slow_deserialize
        out = {}

        def reader():
            try:
                out["cols"] = h.get()
            except BaseException as e:       # pragma: no cover
                out["error"] = e

        t = threading.Thread(target=reader, daemon=True)
        t.start()
        assert in_restore.wait(timeout=10)
        h.close()                            # free while restoring
        t.join(timeout=10)
        assert not t.is_alive()
        assert "error" not in out, out.get("error")
        # the racing reader still got valid data...
        _assert_cols_identical(out["cols"], cols)
        # ...and the store leaked nothing: no handle, no host bytes,
        # no spill file, closed tier
        assert h.closed and h.tier == TIER_FREED
        assert store._handles == {}
        assert store._host_bytes == 0 and store._disk_bytes == 0
        assert not os.path.exists(path)
        store.close()

    def test_deferred_release_runs_outside_store_lock(self, tmp_path):
        """Regression (REVIEW 18): the closed-during-restore device
        release must run AFTER the store lock is dropped.  deallocate
        takes the adaptor lock, and an adaptor-lock holder (the BUFN
        deadlock probe) concurrently takes the store lock via
        spillable_bytes() — releasing under the store lock is an ABBA
        deadlock.  The stub adaptor proves the store lock is free from
        ANOTHER thread (the RLock would lie for our own) on every
        deallocate."""
        store = SpillStore(spill_dir=str(tmp_path),
                           host_limit_bytes=0)
        lock_free = []

        class _Ad:
            def spill_range_start(self):
                pass

            def spill_range_done(self):
                pass

            def allocate(self, n):
                pass

            def deallocate(self, n):
                got = {}

                def probe():
                    got["ok"] = store._lock.acquire(timeout=5)
                    if got["ok"]:
                        store._lock.release()

                t = threading.Thread(target=probe)
                t.start()
                t.join()
                lock_free.append(bool(got.get("ok")))

        stub = _Ad()
        store._adaptor = lambda: stub
        h = store.register(_small_cols(3), name="raced2")
        h.spill()

        in_restore = threading.Event()
        orig = store._deserialize

        def slow_deserialize(*a, **kw):
            in_restore.set()
            time.sleep(0.05)
            return orig(*a, **kw)

        store._deserialize = slow_deserialize
        out = {}

        def reader():
            try:
                out["cols"] = h.get()
            except BaseException as e:       # pragma: no cover
                out["error"] = e

        t = threading.Thread(target=reader, daemon=True)
        t.start()
        assert in_restore.wait(timeout=10)
        h.close()                            # free while restoring
        t.join(timeout=10)
        assert not t.is_alive()
        assert "error" not in out, out.get("error")
        # the spill's release + the deferred closed-during-restore
        # release both observed a free store lock
        assert len(lock_free) == 2 and all(lock_free)
        assert h.tier == TIER_FREED
        store.close()


# ------------------------------------------------ split floor (retry)


class TestSplitFloor:

    def test_floor_raises_typed_error_with_evidence(self):
        from spark_rapids_tpu.memory import exceptions as mem_exc
        from spark_rapids_tpu.robustness import retry

        def boom(part):
            raise mem_exc.GpuSplitAndRetryOOM("will not fit")

        policy = retry.RetryPolicy(base_backoff_s=0, jitter=False)
        with pytest.raises(retry.SplitFloorReached) as ei:
            retry.split_and_retry(boom, [1, 2], name="floor",
                                  policy=policy)
        err = ei.value
        assert isinstance(err, retry.RetryExhausted)
        assert err.reason == "split_floor"
        assert isinstance(err.resident_bytes, dict)
        assert "split_floor" in str(err)
