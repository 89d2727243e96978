"""ISSUE 12: srt-check — srt-lint rules on embedded snippets, lockdep
cycle/blocking synthetics, plan-verify accept/reject, compiler gate,
CLI JSON golden, doctor lockdep triage."""

import json
import os
import threading

import pytest

from spark_rapids_tpu.analysis import catalog, lint, lockdep

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(findings):
    return [f.rule for f in findings]


def lint_src(src, relpath="spark_rapids_tpu/somefile.py"):
    found, suppressed = lint.lint_source(src, relpath)
    return found, suppressed


# ------------------------------------------------------------ lint rules


class TestLintRules:
    def test_metric_prefix_violation(self):
        found, _ = lint_src(
            'M.counter("srtx_bad_name", "help")\n')
        assert rules_of(found) == ["SRT001"]

    def test_metric_not_in_catalog(self):
        found, _ = lint_src(
            'M.gauge("srt_not_a_real_family", "help")\n')
        assert rules_of(found) == ["SRT002"]

    def test_metric_kind_mismatch(self):
        # srt_op_latency_ns is catalogued as a histogram
        found, _ = lint_src(
            'M.counter("srt_op_latency_ns", "help")\n')
        assert rules_of(found) == ["SRT002"]
        assert "histogram" in found[0].message

    def test_metric_good(self):
        found, _ = lint_src(
            'M.histogram("srt_op_latency_ns", "help")\n')
        assert found == []

    def test_knob_uncatalogued(self):
        found, _ = lint_src(
            'import os\n'
            'v = os.environ.get("SPARK_RAPIDS_TPU_NO_SUCH_KNOB")\n')
        assert rules_of(found) == ["SRT003"]

    def test_knob_good_and_subscript(self):
        found, _ = lint_src(
            'import os\n'
            'a = os.environ.get("SPARK_RAPIDS_TPU_METRICS")\n'
            'b = os.environ["SPARK_RAPIDS_TPU_TRACE"]\n'
            'c = os.getenv("SPARK_RAPIDS_TPU_JIT_CACHE")\n')
        assert found == []

    def test_knob_prefix_concat_resolves_wildcard(self):
        # the calibrate pinned_path pattern: prefix + dynamic suffix
        found, _ = lint_src(
            'import os, re\n'
            'def pin(op):\n'
            '    env = "SPARK_RAPIDS_TPU_PATH_" + op.upper()\n'
            '    return os.environ.get(env)\n')
        assert found == []

    def test_knob_unknown_prefix_flagged(self):
        found, _ = lint_src(
            'import os\n'
            'def pin(op):\n'
            '    env = "SPARK_RAPIDS_TPU_BOGUS_" + op\n'
            '    return os.environ.get(env)\n')
        assert rules_of(found) == ["SRT003"]

    def test_shim_typed_raise(self):
        src = 'def f():\n    raise ValueError("nope")\n'
        found, _ = lint_src(src, "spark_rapids_tpu/shim/jni_entry.py")
        assert rules_of(found) == ["SRT004"]
        # same source outside the shim entry is not in scope
        found, _ = lint_src(src, "spark_rapids_tpu/ops/thing.py")
        assert found == []

    def test_digest_purity(self):
        src = ('import time, random, os\n'
               'a = time.time()\n'
               'b = random.random()\n'
               'c = os.urandom(8)\n'
               'd = time.monotonic_ns()\n')   # monotonic is fine
        found, _ = lint_src(src, "spark_rapids_tpu/plan/ir.py")
        assert rules_of(found) == ["SRT005"] * 3
        found, _ = lint_src(src, "spark_rapids_tpu/ops/thing.py")
        assert found == []

    def test_lock_blocking(self):
        src = ('import time, threading\n'
               'lock = threading.Lock()\n'
               'def f(sock):\n'
               '    with lock:\n'
               '        time.sleep(1)\n'
               '        sock.sendall(b"x")\n'
               '    time.sleep(2)\n')          # outside: fine
        found, _ = lint_src(
            src, "spark_rapids_tpu/server/thing.py")
        assert sorted(rules_of(found)) == ["SRT006", "SRT006"]
        # out-of-scope directory: not flagged
        found, _ = lint_src(src, "spark_rapids_tpu/ops/thing.py")
        assert found == []

    def test_lock_blocking_nested_def_excluded(self):
        src = ('def f(lock):\n'
               '    with lock:\n'
               '        def worker():\n'
               '            import time\n'
               '            time.sleep(1)\n'
               '        return worker\n')
        found, _ = lint_src(
            src, "spark_rapids_tpu/observability/thing.py")
        assert found == []

    def test_bare_except_and_swallowed_base(self):
        src = ('try:\n    pass\nexcept:\n    pass\n'
               'try:\n    pass\nexcept BaseException:\n    x = 1\n'
               'try:\n    pass\nexcept BaseException:\n    raise\n')
        found, _ = lint_src(src)
        assert rules_of(found) == ["SRT007", "SRT007"]  # re-raise ok

    def test_lockdep_adoption(self):
        src = ('import threading\n'
               'L = threading.Lock()\n'
               'R = threading.RLock()\n')
        found, _ = lint_src(src, "spark_rapids_tpu/server/server.py")
        assert rules_of(found) == ["SRT009", "SRT009"]
        found, _ = lint_src(src, "spark_rapids_tpu/ops/thing.py")
        assert found == []

    def test_suppression_with_reason(self):
        src = ('import time\n'
               '# srt-lint: disable=SRT005 test fixture reason\n'
               'a = time.time()\n')
        found, suppressed = lint_src(
            src, "spark_rapids_tpu/plan/ir.py")
        assert found == [] and suppressed == 1

    def test_suppression_without_reason_is_srt000(self):
        src = ('import time\n'
               '# srt-lint: disable=SRT005\n'
               'a = time.time()\n')
        found, _ = lint_src(src, "spark_rapids_tpu/plan/ir.py")
        assert sorted(rules_of(found)) == ["SRT000", "SRT005"]

    def test_file_wide_suppression(self):
        src = ('# srt-lint: disable-file=SRT005 golden fixture\n'
               'import time\n'
               'a = time.time()\n'
               'b = time.time()\n')
        found, suppressed = lint_src(
            src, "spark_rapids_tpu/plan/ir.py")
        assert found == [] and suppressed == 2

    def test_syntax_error_is_a_finding_not_a_crash(self):
        found, _ = lint_src("def broken(:\n")
        assert rules_of(found) == ["SRT-SYNTAX"]

    def test_tree_is_clean_and_docs_cross_check(self):
        res = lint.lint_paths(REPO_ROOT)
        assert res.findings == [], res.render_text()
        assert res.suppressed >= 5
        assert catalog.check_docs(REPO_ROOT) == []

    def test_json_output_golden_stable(self):
        src = ('import os\n'
               'v = os.environ.get("SPARK_RAPIDS_TPU_NOPE_A")\n'
               'w = os.environ.get("SPARK_RAPIDS_TPU_NOPE_B")\n')
        found, _ = lint_src(src, "spark_rapids_tpu/x.py")
        res = lint.LintResult(findings=sorted(
            found, key=lambda f: (f.path, f.line, f.rule, f.message)))
        got = json.loads(res.to_json())
        assert got == {
            "version": 1, "files": 0, "suppressed": 0,
            "findings": [
                {"path": "spark_rapids_tpu/x.py", "line": 2,
                 "rule": "SRT003",
                 "message": "env knob 'SPARK_RAPIDS_TPU_NOPE_A' is "
                            "not in analysis/catalog.py"},
                {"path": "spark_rapids_tpu/x.py", "line": 3,
                 "rule": "SRT003",
                 "message": "env knob 'SPARK_RAPIDS_TPU_NOPE_B' is "
                            "not in analysis/catalog.py"},
            ]}
        # byte-stable across repeated renders
        assert res.to_json() == res.to_json()


# --------------------------------------------------------------- lockdep


@pytest.fixture
def fresh_lockdep():
    lockdep.reset()
    yield
    lockdep.reset()


class TestLockdep:
    def test_off_by_default_returns_plain_lock(self, monkeypatch):
        monkeypatch.delenv("SPARK_RAPIDS_TPU_LOCKDEP", raising=False)
        lk = lockdep.make_lock("test.plain")
        assert type(lk) is type(threading.Lock())

    def test_abba_cycle_detected(self, monkeypatch, fresh_lockdep):
        monkeypatch.setenv("SPARK_RAPIDS_TPU_LOCKDEP", "1")
        a = lockdep.make_lock("t.A")
        b = lockdep.make_lock("t.B")
        e1, e2 = threading.Event(), threading.Event()

        def t1():
            with a:
                e1.set()
                e2.wait(2)
                if b.acquire(timeout=0.2):
                    b.release()

        def t2():
            e1.wait(2)
            with b:
                e2.set()
                if a.acquire(timeout=0.2):
                    a.release()

        th1, th2 = (threading.Thread(target=t1),
                    threading.Thread(target=t2))
        th1.start(); th2.start(); th1.join(5); th2.join(5)
        rep = lockdep.report()
        cycles = [c["cycle"] for c in rep["cycles"]]
        assert any("t.A" in c and "t.B" in c for c in cycles)
        # evidence carries stacks for both directions
        cyc = rep["cycles"][0]
        assert cyc["forward"]["stack"]
        assert {"t.A", "t.B"} <= set(rep["classes"])

    def test_consistent_order_no_cycle(self, monkeypatch,
                                       fresh_lockdep):
        monkeypatch.setenv("SPARK_RAPIDS_TPU_LOCKDEP", "1")
        a = lockdep.make_lock("o.A")
        b = lockdep.make_lock("o.B")
        for _ in range(3):
            with a:
                with b:
                    pass
        rep = lockdep.report()
        assert rep["cycles"] == []
        assert {"from": "o.A", "to": "o.B", "count": 3} in rep["edges"]

    def test_rlock_reentrant_no_self_edge(self, monkeypatch,
                                          fresh_lockdep):
        monkeypatch.setenv("SPARK_RAPIDS_TPU_LOCKDEP", "1")
        r = lockdep.make_rlock("t.R")
        with r:
            with r:       # reentrant: no self-edge, no cycle
                pass
        rep = lockdep.report()
        assert rep["cycles"] == []
        assert all(e["from"] != "t.R" or e["to"] != "t.R"
                   for e in rep["edges"])

    def test_held_across_blocking(self, monkeypatch, fresh_lockdep):
        monkeypatch.setenv("SPARK_RAPIDS_TPU_LOCKDEP", "1")
        lk = lockdep.make_lock("t.IO")
        lockdep.note_blocking("unit.noheld")   # nothing held: no event
        with lk:
            lockdep.note_blocking("unit.op")
        rep = lockdep.report()
        assert rep["blocking_total"] == 1
        ev = rep["blocking"][0]
        assert ev["op"] == "unit.op" and ev["held"] == ["t.IO"]
        assert ev["stack"]

    def test_condition_over_instrumented_lock(self, monkeypatch,
                                              fresh_lockdep):
        # the server wraps its instrumented lock in a Condition; wait/
        # notify must keep the held-stack balanced
        monkeypatch.setenv("SPARK_RAPIDS_TPU_LOCKDEP", "1")
        lk = lockdep.make_lock("t.CV")
        cv = threading.Condition(lk)
        hits = []

        def waiter():
            with cv:
                cv.wait(timeout=2)
                hits.append(lockdep.held_classes())

        th = threading.Thread(target=waiter)
        th.start()
        import time
        time.sleep(0.05)
        with cv:
            cv.notify()
        th.join(5)
        assert hits and hits[0] == ["t.CV"]
        assert lockdep.held_classes() == []

    def test_cycle_evidence_reaches_metrics_and_journal(
            self, monkeypatch, fresh_lockdep):
        from spark_rapids_tpu import observability as obs
        monkeypatch.setenv("SPARK_RAPIDS_TPU_LOCKDEP", "1")
        obs.reset()
        obs.enable()
        try:
            a = lockdep.make_lock("ev.A")
            b = lockdep.make_lock("ev.B")
            e1, e2 = threading.Event(), threading.Event()

            def t1():
                with a:
                    e1.set(); e2.wait(2)
                    if b.acquire(timeout=0.2):
                        b.release()

            def t2():
                e1.wait(2)
                with b:
                    e2.set()
                    if a.acquire(timeout=0.2):
                        a.release()

            th1, th2 = (threading.Thread(target=t1),
                        threading.Thread(target=t2))
            th1.start(); th2.start(); th1.join(5); th2.join(5)
            snap = obs.METRICS.snapshot()
            series = snap["srt_lockdep_cycles_total"]["series"]
            assert series and series[0]["value"] >= 1
            recs = [r for r in obs.JOURNAL.records()
                    if r.get("kind") == "lockdep"]
            assert recs and recs[0]["event"] == "cycle"
            assert "ev.A" in recs[0]["cycle"]
        finally:
            obs.disable()
            obs.reset()


# ----------------------------------------------------------- plan-verify


class TestPlanVerify:
    @pytest.fixture(autouse=True)
    def _imports(self):
        from spark_rapids_tpu.analysis import plan_verify
        from spark_rapids_tpu.plan import ir
        self.pv = plan_verify
        self.ir = ir

    def good_plan(self):
        ir = self.ir
        return ir.StagePlan(
            name="t_good",
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
            outputs=("sums",))

    def test_accepts_good_plan(self):
        assert self.pv.verify_stage(self.good_plan()) is not None

    def test_accepts_every_catalog_plan(self):
        from spark_rapids_tpu.tools.srt_check import _catalog_plans
        for name, build in _catalog_plans():
            plan = build()
            if isinstance(plan, self.ir.Pipeline):
                self.pv.verify_pipeline(plan)
            else:
                self.pv.verify_stage(plan)

    def test_dtypes_flow_through_the_q5_slices(self):
        from spark_rapids_tpu.plan import catalog as pc
        slots = (("web_returns", 1024), ("web_sales", 16384))
        windows = tuple((s, 1024) for s in ("ss", "sr", "cs", "cr", "ws",
                                            "wr"))
        plan = pc.q5_channels_map_plan((150, 12, 6), (150, 6, 3), 10,
                                       1024, exchange_slots=slots,
                                       windows=windows)
        assert sum(isinstance(n, self.ir.WindowSlice)
                   for n in plan.nodes) == 6
        dtypes = {i.name: tuple(
            "int64" if c.name.split("_", 1)[1] in (
                "price", "profit", "amt", "loss") else "int32"
            for c in i.columns) for i in plan.inputs}
        self.pv.verify_stage(plan, dtypes)

    def slice_plan(self, input_name="f", capacity=8):
        ir = self.ir
        return ir.StagePlan(
            "t_slice",
            inputs=(ir.ScanBind("f", (ir.ColSpec("d"), ir.ColSpec("v"))),),
            nodes=(ir.WindowSlice("z", input_name, ir.Col("d"),
                                  ir.Lit(3), ir.Lit(5), ("d", "v"),
                                  capacity),),
            outputs=("z.v", "z.valid", "z.over"))

    def test_a_slice_binds_its_input_and_a_capacity(self):
        self.pv.verify_stage(self.slice_plan(), {"f": ("int32", "int64")})
        self._expect_reject(self.slice_plan(input_name="g"),
                            "slice input 'g'")
        self._expect_reject(self.slice_plan(capacity=0),
                            "non-positive slice capacity 0")

    def _expect_reject(self, plan_or_pipe, *needles):
        with pytest.raises(self.pv.PlanVerifyError) as ei:
            if isinstance(plan_or_pipe, self.ir.Pipeline):
                self.pv.verify_pipeline(plan_or_pipe)
            else:
                self.pv.verify_stage(plan_or_pipe)
        msg = str(ei.value)
        for n in needles:
            assert n in msg, (n, msg)
        assert ei.value.node     # names the offender
        return ei.value

    def test_reject_unbound_column(self):
        ir = self.ir
        p = ir.StagePlan(
            "t_unbound",
            inputs=(ir.ScanBind("f", (ir.ColSpec("x"),)),),
            nodes=(ir.Project("y", ir.Col("ghost")),),
            outputs=("y",))
        e = self._expect_reject(p, "ghost")
        assert "Project" in e.node

    def test_reject_duplicate_definition(self):
        ir = self.ir
        p = ir.StagePlan(
            "t_dup",
            inputs=(ir.ScanBind("f", (ir.ColSpec("x"),)),),
            nodes=(ir.Project("x", ir.Col("x")),),
            outputs=("x",))
        self._expect_reject(p, "duplicate column 'x'")

    def test_reject_unknown_bin_op(self):
        ir = self.ir
        p = ir.StagePlan(
            "t_op",
            inputs=(ir.ScanBind("f", (ir.ColSpec("x"),)),),
            nodes=(ir.Project("y", ir.Bin("xor", ir.Col("x"),
                                          ir.Lit(1))),),
            outputs=("y",))
        self._expect_reject(p, "unknown binary op 'xor'")

    def test_reject_sort_num_keys(self):
        ir = self.ir
        p = ir.StagePlan(
            "t_sort",
            inputs=(ir.ScanBind("f", (ir.ColSpec("x"),)),),
            nodes=(ir.Sort(("sx",), (ir.Col("x"),), num_keys=2),),
            outputs=("sx",))
        self._expect_reject(p, "num_keys 2 outside")

    def test_reject_bad_reduce_kind(self):
        ir = self.ir
        p = ir.StagePlan(
            "t_red",
            inputs=(ir.ScanBind("f", (ir.ColSpec("x"),)),),
            nodes=(ir.Reduce("r", ir.Col("x"), kind="mean"),),
            outputs=("r",))
        self._expect_reject(p, "unknown Reduce kind 'mean'")

    def test_reject_nonpositive_capacity(self):
        ir = self.ir
        p = ir.StagePlan(
            "t_cap",
            inputs=(ir.ScanBind("f", (ir.ColSpec("x"),)),),
            nodes=(ir.JoinProbe("j", ir.Col("x"), ir.Col("x"), 0),),
            outputs=("j.total",))
        self._expect_reject(p, "non-positive join capacity")

    def test_reject_unhashable_node_field(self):
        ir = self.ir
        p = ir.StagePlan(
            "t_hash",
            inputs=(ir.ScanBind("f", (ir.ColSpec("x"),)),),
            nodes=(ir.Project("y", ir.Lit([1, 2, 3])),),
            outputs=("y",))
        self._expect_reject(p, "list")

    def test_reject_mask_over_non_input(self):
        ir = self.ir
        p = ir.StagePlan(
            "t_mask",
            inputs=(ir.ScanBind("f", (ir.ColSpec("x"),)),),
            nodes=(ir.Project("y", ir.Mask("ghost")),),
            outputs=("y",))
        self._expect_reject(p, "does not name a stage input")

    def test_reject_undefined_output(self):
        ir = self.ir
        p = ir.StagePlan(
            "t_out",
            inputs=(ir.ScanBind("f", (ir.ColSpec("x"),)),),
            nodes=(),
            outputs=("ghost",))
        self._expect_reject(p, "ghost")

    def test_dtype_flow_where_needs_bool(self):
        ir = self.ir
        p = ir.StagePlan(
            "t_dtype",
            inputs=(ir.ScanBind("f", (ir.ColSpec("x"),)),),
            nodes=(ir.Project("y", ir.Where(ir.Col("x"), ir.Col("x"),
                                            ir.Lit(0))),),
            outputs=("y",))
        # no dtypes supplied: structurally fine
        self.pv.verify_stage(p)
        with pytest.raises(self.pv.PlanVerifyError) as ei:
            self.pv.verify_stage(p, input_dtypes={"f": ("int64",)})
        assert "expected bool" in str(ei.value)

    def test_dtype_flow_segment_ids_must_be_int(self):
        ir = self.ir
        p = ir.StagePlan(
            "t_ids",
            inputs=(ir.ScanBind("f", (ir.ColSpec("v"),
                                      ir.ColSpec("ids"))),),
            nodes=(ir.SegmentSum("s", ir.Col("v"), ir.Col("ids"),
                                 8),),
            outputs=("s",))
        self.pv.verify_stage(
            p, input_dtypes={"f": ("int64", "int32")})
        with pytest.raises(self.pv.PlanVerifyError):
            self.pv.verify_stage(
                p, input_dtypes={"f": ("int64", "float64")})

    def test_pipeline_boundary_must_carry_consumed_columns(self):
        ir = self.ir
        s1 = ir.StagePlan(
            "t_s1",
            inputs=(ir.ScanBind("f", (ir.ColSpec("x"),)),),
            nodes=(ir.Project("a", ir.Col("x")),
                   ir.Project("b", ir.Col("x"))),
            outputs=("a", "b"))
        s2 = ir.StagePlan(
            "t_s2",
            inputs=(ir.ScanBind("carry", (ir.ColSpec("a"),
                                          ir.ColSpec("b")),
                                bucket=False),),
            nodes=(ir.Project("out", ir.Bin("add", ir.Col("a"),
                                            ir.Col("b"))),),
            outputs=("out",))
        good = ir.Pipeline("t_pipe", (s1, s2),
                           (ir.ShuffleBoundary(("a", "b")),))
        self.pv.verify_pipeline(good)
        # carrying only 'a' while stage 2 consumes 'b' upstream:
        # works single-process, breaks distributed -> rejected
        bad = ir.Pipeline("t_pipe_bad", (s1, s2),
                          (ir.ShuffleBoundary(("a",)),))
        with pytest.raises(self.pv.PlanVerifyError) as ei:
            self.pv.verify_pipeline(bad)
        assert "uncarried" in str(ei.value)

    def test_pipeline_boundary_carries_unknown_column(self):
        ir = self.ir
        s1 = ir.StagePlan(
            "t_b1",
            inputs=(ir.ScanBind("f", (ir.ColSpec("x"),)),),
            nodes=(ir.Project("a", ir.Col("x")),),
            outputs=("a",))
        s2 = ir.StagePlan(
            "t_b2",
            inputs=(ir.ScanBind("carry", (ir.ColSpec("a"),),
                                bucket=False),),
            nodes=(),
            outputs=("a",))
        bad = ir.Pipeline("t_carry_ghost", (s1, s2),
                          (ir.ShuffleBoundary(("a", "ghost")),))
        with pytest.raises(self.pv.PlanVerifyError) as ei:
            self.pv.verify_pipeline(bad)
        assert "ghost" in str(ei.value)


# --------------------------------------------------------- compiler gate


class TestCompilerGate:
    def test_compile_stage_verifies_broken_plan(self, monkeypatch):
        from spark_rapids_tpu.analysis import plan_verify
        from spark_rapids_tpu.plan import compiler, ir
        monkeypatch.delenv("SPARK_RAPIDS_TPU_PLAN_VERIFY",
                           raising=False)
        broken = ir.StagePlan(
            "t_gate",
            inputs=(ir.ScanBind("f", (ir.ColSpec("x"),)),),
            nodes=(ir.Project("y", ir.Col("ghost")),),
            outputs=("y",))
        compiler._STAGE_MEMO.pop(broken.digest, None)
        compiler._VERIFIED.pop(broken.digest, None)
        with pytest.raises(plan_verify.PlanVerifyError):
            compiler.compile_stage(broken)

    def test_escape_hatch_skips_verification(self, monkeypatch):
        from spark_rapids_tpu.plan import compiler, ir
        monkeypatch.setenv("SPARK_RAPIDS_TPU_PLAN_VERIFY", "0")
        broken = ir.StagePlan(
            "t_hatch",
            inputs=(ir.ScanBind("f", (ir.ColSpec("x"),)),),
            nodes=(ir.Project("y", ir.Col("ghost")),),
            outputs=("y",))
        compiler._STAGE_MEMO.pop(broken.digest, None)
        compiler._VERIFIED.pop(broken.digest, None)
        cs = compiler.compile_stage(broken)   # no verify -> no raise
        assert cs is not None
        compiler._STAGE_MEMO.pop(broken.digest, None)

    def test_verification_memoized_per_digest(self, monkeypatch):
        from spark_rapids_tpu.analysis import plan_verify
        from spark_rapids_tpu.plan import compiler, ir
        monkeypatch.delenv("SPARK_RAPIDS_TPU_PLAN_VERIFY",
                           raising=False)
        plan = ir.StagePlan(
            "t_memo",
            inputs=(ir.ScanBind("f", (ir.ColSpec("x"),)),),
            nodes=(ir.Project("y", ir.Col("x")),),
            outputs=("y",))
        compiler._STAGE_MEMO.pop(plan.digest, None)
        compiler._VERIFIED.pop(plan.digest, None)
        calls = []
        real = plan_verify.verify_stage
        monkeypatch.setattr(plan_verify, "verify_stage",
                            lambda p, **kw: (calls.append(1),
                                             real(p, **kw))[1])
        compiler.compile_stage(plan)
        compiler._STAGE_MEMO.pop(plan.digest, None)   # force re-entry
        compiler.compile_stage(plan)
        assert calls == [1]           # second compile = dict hit
        compiler._STAGE_MEMO.pop(plan.digest, None)

    def test_fused_q3_still_runs_through_gate(self):
        # end-to-end: a real catalog stage lowers and executes with
        # the verifier in the path
        import numpy as np
        from spark_rapids_tpu.plan import catalog as pc
        from spark_rapids_tpu.plan import compiler
        base, years, brands = 1990, 2, 4
        plan = pc.q3_plan(base=base, years=years, brands=brands,
                          manufact=4)
        cs = compiler.compile_stage(plan)
        assert compiler._VERIFIED.get(plan.digest) is True
        n, days = 64, years * 365
        rng = np.random.default_rng(0)
        inputs = {
            "s": (base + rng.integers(0, days, n),
                  rng.integers(0, 8, n),
                  rng.integers(1, 100, n).astype(np.int64)),
            "dims": (1 + (rng.integers(0, days, days) % 12),
                     base + np.arange(days) // 365,
                     rng.integers(0, brands, 8),
                     rng.integers(0, 8, 8)),
        }
        out = cs.run_unfused(inputs)
        assert len(out) == len(plan.outputs)


# -------------------------------------------------------------- CLI


class TestCli:
    def test_list_rules(self, capsys):
        from spark_rapids_tpu.tools import srt_check
        assert srt_check.main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("SRT000", "SRT003", "SRT005", "SRT006", "SRT008",
                    "SRT009"):
            assert rid in out

    def test_lint_single_file_json_golden(self, tmp_path, capsys):
        from spark_rapids_tpu.tools import srt_check
        bad = tmp_path / "spark_rapids_tpu" / "plan"
        bad.mkdir(parents=True)
        (bad / "ir.py").write_text("import time\nt = time.time()\n")
        rc = srt_check.main(
            ["--root", str(tmp_path), "--no-docs-check",
             "--json", "spark_rapids_tpu/plan/ir.py"])
        assert rc == 1
        got = json.loads(capsys.readouterr().out)
        assert got["version"] == 1 and got["files"] == 1
        assert [f["rule"] for f in got["findings"]] == ["SRT005"]
        assert got["findings"][0]["path"] == \
            "spark_rapids_tpu/plan/ir.py"
        assert got["findings"][0]["line"] == 2

    def test_plan_mode_json(self, capsys):
        from spark_rapids_tpu.tools import srt_check
        assert srt_check.main(["--plan", "--json"]) == 0
        got = json.loads(capsys.readouterr().out)
        assert len(got["plans"]) == 7
        assert all(p["ok"] for p in got["plans"])
        names = {p["plan"] for p in got["plans"]}
        assert {"q3", "q9", "q67", "cube", "q89", "q5_pipeline",
                "q72_pipeline"} == names

    def test_repo_tree_clean_via_cli(self, capsys):
        from spark_rapids_tpu.tools import srt_check
        assert srt_check.main([]) == 0
        assert "0 finding(s)" in capsys.readouterr().out


# ------------------------------------------------------ doctor triage


class TestDoctorLockdep:
    def _bundle(self, tmp_path, trigger, journal_records):
        b = tmp_path / "incident-1-lockdep_cycle-1"
        b.mkdir()
        (b / "trigger.json").write_text(json.dumps(trigger))
        (b / "journal.jsonl").write_text(
            "\n".join(json.dumps(r) for r in journal_records))
        (b / "MANIFEST.json").write_text(json.dumps({"version": 1}))
        return str(b)

    def test_doctor_ranks_lockdep_cycle_trigger(self, tmp_path):
        from spark_rapids_tpu.tools import doctor
        path = self._bundle(
            tmp_path,
            {"kind": "lockdep_cycle", "severity": "warn",
             "detail": {
                 "cycle": ["server.query_server", "shim.handles",
                           "server.query_server"],
                 "evidence": {"forward": {
                     "edge": ["shim.handles", "server.query_server"],
                     "stack": ["  File x.py, line 3, in f"]}}}},
            [{"kind": "lockdep", "event": "cycle", "t_ns": 1,
              "cycle": ["server.query_server", "shim.handles",
                        "server.query_server"]}])
        findings = doctor.analyze(doctor.Bundle(path))
        top = findings[0]
        assert top["kind"] == "lockdep_cycle"
        assert "server.query_server -> shim.handles" in top["message"]
        assert "ABBA" in top["message"]

    def test_doctor_surfaces_journal_lockdep_history(self, tmp_path):
        from spark_rapids_tpu.tools import doctor
        path = self._bundle(
            tmp_path,
            {"kind": "retry_exhausted", "severity": "error",
             "detail": {"name": "s", "errors": []}},
            [{"kind": "lockdep", "event": "blocking", "t_ns": 1,
              "op": "fileio.read_range", "held": ["perf.jit_cache"]},
             {"kind": "lockdep", "event": "cycle", "t_ns": 2,
              "cycle": ["a", "b", "a"]}])
        findings = doctor.analyze(doctor.Bundle(path))
        kinds = [f["kind"] for f in findings]
        assert "lockdep_cycle" in kinds
        assert "lockdep_blocking" in kinds
        blocking = next(f for f in findings
                        if f["kind"] == "lockdep_blocking")
        assert "fileio.read_range" in blocking["message"]
        assert "perf.jit_cache" in blocking["message"]
