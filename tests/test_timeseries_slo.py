"""ISSUE 16 suite: windowed time-series ring (delta math, conservation,
windowed percentiles), per-tenant SLO burn-rate monitoring (fire /
non-fire / cooldown with synthetic clocks), fleet snapshot merging with
stale-epoch fencing, the srt-top --once --json frame, the slo_burn
bundle -> srt-doctor chain, and the Monitor-liveness gauge."""

import contextlib
import io
import json
import os

import pytest

from spark_rapids_tpu import observability as obs
from spark_rapids_tpu.observability import slo as slo_mod
from spark_rapids_tpu.observability import timeseries as ts_mod
from spark_rapids_tpu.tools import doctor
from spark_rapids_tpu.tools import srt_top


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


class FakeRegistry:
    """A registry stand-in whose snapshot the test scripts by hand."""

    def __init__(self):
        self.snap = {}

    def snapshot(self):
        return json.loads(json.dumps(self.snap))  # deep copy


def counter(value, labels=()):
    return {"kind": "counter", "help": "", "labels": [],
            "series": [{"labels": list(labels), "value": value}]}


def gauge(value):
    return {"kind": "gauge", "help": "", "labels": [],
            "series": [{"labels": [], "value": value}]}


def histogram(bucket_counts, total, count, buckets=(1e3, 1e6, 1e9)):
    return {"kind": "histogram", "help": "", "labels": [],
            "buckets": list(buckets),
            "series": [{"labels": [], "sum": total, "count": count,
                        "bucket_counts": list(bucket_counts)}]}


# --------------------------------------------------------- ring deltas


def test_window_counter_deltas_hand_computed():
    reg = FakeRegistry()
    clock = FakeClock()
    s = ts_mod.TimeseriesSampler(reg, window_s=5.0, capacity=8,
                                 clock=clock, wall_clock=clock)
    s.enabled = True
    reg.snap = {"srt_x_total": counter(100)}
    s.tick()
    reg.snap = {"srt_x_total": counter(130)}
    clock.advance(5.0)
    s.tick()
    w = s.windows()
    # first window carries the since-boot total, second the delta
    assert w[0]["counters"]["srt_x_total"] == {"": 100.0}
    assert w[1]["counters"]["srt_x_total"] == {"": 30.0}
    assert w[1]["dur_s"] == pytest.approx(5.0)
    # conservation: ring total == cumulative registry value
    assert ts_mod.sum_counter_windows(w, "srt_x_total") == {"": 130.0}


def test_window_gauge_last_value_and_quiet_family_skipped():
    reg = FakeRegistry()
    s = ts_mod.TimeseriesSampler(reg, window_s=1.0,
                                 clock=FakeClock(),
                                 wall_clock=FakeClock())
    s.enabled = True
    reg.snap = {"srt_g": gauge(7.0), "srt_x_total": counter(5)}
    s.tick()
    reg.snap = {"srt_g": gauge(3.0), "srt_x_total": counter(5)}
    s.tick()
    w = s.windows()
    assert w[1]["gauges"]["srt_g"] == {"": 3.0}
    # the unchanged counter must not appear in the second window
    assert "srt_x_total" not in w[1]["counters"]


def test_window_histogram_deltas_and_recent_percentile():
    reg = FakeRegistry()
    s = ts_mod.TimeseriesSampler(reg, window_s=1.0,
                                 clock=FakeClock(),
                                 wall_clock=FakeClock())
    s.enabled = True
    # era 1: 90 fast observations in the lowest bucket
    reg.snap = {"srt_h_ns": histogram([90, 0, 0, 0], 90e2, 90)}
    s.tick()
    # era 2: 10 slow observations land in the 3rd bucket
    reg.snap = {"srt_h_ns": histogram([90, 0, 10, 0], 90e2 + 10e8, 100)}
    s.tick()
    got = s.recent_histogram("srt_h_ns", n=1)
    assert got is not None
    buckets, counts, _sum, count = got
    assert counts == [0, 0, 10, 0] and count == 10
    # windowed p50 sits in the slow decade; since-boot p50 in the fast
    p50_recent = ts_mod.histogram_quantile(buckets, counts, 0.5)
    p50_boot = ts_mod.histogram_quantile(buckets, [90, 0, 10, 0], 0.5)
    assert p50_recent > 1e6
    assert p50_boot <= 1e3


def test_ring_capacity_bounded():
    reg = FakeRegistry()
    s = ts_mod.TimeseriesSampler(reg, window_s=1.0, capacity=4,
                                 clock=FakeClock(),
                                 wall_clock=FakeClock())
    s.enabled = True
    for i in range(10):
        reg.snap = {"srt_x_total": counter(i)}
        s.tick()
    assert len(s.windows()) == 4


def test_maybe_tick_respects_window_and_disabled():
    reg = FakeRegistry()
    clock = FakeClock()
    s = ts_mod.TimeseriesSampler(reg, window_s=5.0, clock=clock,
                                 wall_clock=clock)
    reg.snap = {"srt_x_total": counter(1)}
    assert s.maybe_tick() is None          # disabled: pure noop
    s.enabled = True
    s.tick()
    clock.advance(1.0)
    assert s.maybe_tick() is None          # window not yet elapsed
    clock.advance(4.5)
    assert s.maybe_tick() is not None


# ------------------------------------------------------------ SLO burn


def _monitor(clock, **kw):
    kw.setdefault("fast_s", 60.0)
    kw.setdefault("slow_s", 600.0)
    kw.setdefault("threshold", 4.0)
    m = slo_mod.SloMonitor(clock=clock, **kw)
    m.enabled = True
    return m


def test_burn_fires_only_when_both_windows_exceed():
    clock = FakeClock()
    # objective 0.9: a 10% error budget keeps the slow window diluted
    # by the healthy history while the fast window saturates
    m = _monitor(clock, configs={"*": slo_mod.SloConfig(objective=0.9)})
    # long healthy history fills the slow window
    for _ in range(400):
        m.observe("t", "success", 1_000_000)
        clock.advance(1.0)
    # then a fast-window spike of pure badness: fast burn explodes but
    # the slow window is still diluted by the healthy history
    for _ in range(30):
        m.observe("t", "failed", 1_000_000)
        clock.advance(1.0)
    fired = m.evaluate()
    st = m.status()["t"]
    assert st["burn_fast"] >= 4.0
    assert st["burn_slow"] < 4.0
    assert fired == []                      # one window alone: no alert
    # keep burning until the slow window crosses too
    for _ in range(300):
        m.observe("t", "failed", 1_000_000)
        clock.advance(1.0)
    fired = m.evaluate()
    assert len(fired) == 1 and fired[0]["tenant"] == "t"


def test_burn_cooldown_and_breach_counter():
    clock = FakeClock()
    burns = []
    m = _monitor(clock, cooldown_s=100.0,
                 on_burn=lambda t, a: burns.append(t))
    for _ in range(20):
        m.observe("t", "failed", 1_000_000)
    assert len(m.evaluate()) == 1
    clock.advance(10.0)
    assert m.evaluate() == []               # inside the cooldown
    clock.advance(200.0)
    for _ in range(20):
        m.observe("t", "failed", 1_000_000)
    assert len(m.evaluate()) == 1           # cooldown elapsed: refires
    assert burns == ["t", "t"]
    assert m.status()["t"]["breaches"] == 2


def test_neutral_outcomes_spend_no_budget():
    clock = FakeClock()
    m = _monitor(clock)
    for out in ("cancelled", "rejected", "shed", "requeued"):
        m.observe("t", out, 10**12)
    assert "t" not in m.status()            # no SLI events recorded
    m.observe("t", "success", 1_000)
    assert m.status()["t"]["events"] == 1


def test_latency_over_target_is_bad_even_on_success():
    clock = FakeClock()
    m = _monitor(clock, configs={
        "*": slo_mod.SloConfig(latency_target_ns=int(250e6),
                               objective=0.9)})
    m.observe("t", "success", int(400e6))   # success but too slow
    m.observe("t", "success", int(10e6))
    assert m.attainment("t") == pytest.approx(0.5)


def test_slo_config_parse_inline_and_errors(tmp_path):
    cfgs = slo_mod.parse_slo_config(
        '{"*": {"latency_ms": 100, "objective": 0.95}}')
    assert cfgs["*"].latency_target_ns == int(100e6)
    p = tmp_path / "slo.json"
    p.write_text('{"acme": {"latency_ms": 50, "objective": 0.5}}')
    cfgs = slo_mod.parse_slo_config("@" + str(p))
    assert cfgs["acme"].objective == 0.5
    with pytest.raises(ValueError):
        slo_mod.parse_slo_config("{not json")
    with pytest.raises(ValueError):
        slo_mod.SloConfig(objective=1.5)


# ---------------------------------------------------------- fleet merge


def snap_for(rank, epoch, seqs, value=10):
    return {"rank": rank, "epoch": epoch,
            "windows": [{"window": q, "t_unix_ms": 0, "dur_s": 1.0,
                         "counters": {"srt_x_total": {"": value}},
                         "gauges": {}, "histograms": {}}
                        for q in seqs]}


def test_fleet_merge_dedup_and_stale_epoch_fencing():
    fleet = ts_mod.FleetTimeseries()
    assert fleet.offer(snap_for(0, 3, [1, 2])) == "merged"
    assert fleet.offer(snap_for(1, 3, [1])) == "merged"
    # replay of already-merged windows: dup, nothing double-counted
    assert fleet.offer(snap_for(0, 3, [1, 2])) == "dup"
    # a pre-reconfiguration straggler is fenced
    assert fleet.offer(snap_for(1, 2, [5, 6])) == "stale_epoch"
    # newer epoch advances the fence
    assert fleet.offer(snap_for(1, 4, [2])) == "merged"
    assert fleet.epoch == 4
    totals = fleet.totals("srt_x_total")
    assert totals["0"] == {"": 20.0} and totals["1"] == {"": 20.0}
    merged = fleet.merged()
    assert sorted(merged["ranks"]) == ["0", "1"]
    assert merged["ranks"]["0"]["last_window"] == 2


def test_fleet_merge_partial_overlap_takes_new_windows_only():
    fleet = ts_mod.FleetTimeseries()
    fleet.offer(snap_for(0, 1, [1, 2]))
    # overlapping republish [2, 3]: only window 3 is new
    assert fleet.offer(snap_for(0, 1, [2, 3])) == "merged"
    assert [w["window"] for w in fleet.rank_windows(0)] == [1, 2, 3]


# ------------------------------------------------------------- srt-top


def test_srt_top_once_json_golden(tmp_path):
    snap = snap_for(0, 1, [1, 2, 3])
    snap["windows"][-1]["counters"]["srt_server_completed_total"] = \
        {"acme|success": 4}
    snap["windows"][-1]["gauges"]["srt_server_running"] = {"acme": 2.0}
    snap["slo"] = {"acme": {"latency_target_ms": 250.0,
                            "objective": 0.99, "events": 4,
                            "attainment": 1.0, "burn_fast": 0.0,
                            "burn_slow": 0.0, "breaches": 0}}
    path = tmp_path / "timeseries_rank0.json"
    path.write_text(json.dumps(snap, sort_keys=True))

    outs = []
    for _ in range(2):
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = srt_top.main([str(path), "--once", "--json"])
        assert rc == 0
        outs.append(buf.getvalue())
    assert outs[0] == outs[1]               # frame is input-pure
    frame = json.loads(outs[0])
    assert frame["ranks"]["0"]["last_window"] == 3
    assert frame["tenants"]["acme"]["running"] == 2.0
    assert frame["tenants"]["acme"]["completed_s"] > 0
    assert frame["tenants"]["acme"]["slo"]["attainment"] == 1.0


def test_srt_top_text_render_smoke(tmp_path):
    path = tmp_path / "timeseries_rank0.json"
    path.write_text(json.dumps(snap_for(0, 1, [1])))
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert srt_top.main([str(path), "--once"]) == 0
    assert "rank" in buf.getvalue()


def test_srt_top_no_inputs_errors():
    buf = io.StringIO()
    with contextlib.redirect_stderr(buf), pytest.raises(SystemExit):
        srt_top.main(["--once"])
    assert "dump-dir" in buf.getvalue()


# -------------------------------------------- slo_burn bundle -> doctor


def test_slo_burn_bundle_doctor_chain(tmp_path):
    obs.enable()
    obs.reset()
    # the process-wide recorder limits bundles to one per 30 s: a
    # bundle frozen by another test file on this worker just before
    # must not suppress this one
    prior_iv = obs.FLIGHT.min_interval_s
    obs.enable_flight_recorder(out_dir=str(tmp_path / "inc"),
                               min_interval_s=0.0)
    obs.enable_slo()
    obs.SLO.reset()
    try:
        for i in range(25):
            obs.record_server_complete("acme", "q5", f"s{i}",
                                       "success", 900_000_000,
                                       100_000_000)
        fired = obs.evaluate_slo()
        assert len(fired) == 1 and fired[0]["tenant"] == "acme"
        assert obs.evaluate_slo() == []     # cooldown: one bundle only
        bundles = doctor.find_bundles(str(tmp_path / "inc"))
        assert len(bundles) == 1
        b = doctor.Bundle(bundles[0])
        assert b.trigger["kind"] == "slo_burn"
        findings = doctor.analyze(b)
        burn = [f for f in findings if f["kind"] == "slo_burn"]
        assert burn and "acme" in burn[0]["message"]
        assert burn[0]["severity"] == 87
        # breach counter + burn gauges landed in the registry
        snap = obs.METRICS.snapshot()
        fam = snap["srt_slo_breaches_total"]
        assert [s for s in fam["series"]
                if s["labels"] == ["acme"] and s["value"] == 1]
    finally:
        obs.disable_slo()
        obs.FLIGHT.configure(min_interval_s=prior_iv)
        obs.disable_flight_recorder()
        obs.disable()


# ----------------------------------------------------- monitor liveness


def test_monitor_liveness_gauge_and_health():
    obs.enable()
    obs.reset()
    try:
        obs.record_monitor_sample(now=100.0)
        obs._refresh_liveness(now=107.5)
        snap = obs.METRICS.snapshot()
        fam = snap["srt_monitor_last_sample_age_s"]
        assert fam["series"][0]["value"] == pytest.approx(7.5)
        h = obs.health()
        assert "monitor" in h
        assert h["monitor"]["last_sample_age_s"] is not None
    finally:
        obs.disable()


def test_doctor_flags_stalled_sampler(tmp_path):
    bdir = tmp_path / "incident-1-manual-001"
    os.makedirs(bdir)
    (bdir / "MANIFEST.json").write_text("{}")
    (bdir / "trigger.json").write_text(json.dumps(
        {"kind": "manual", "detail": {"reason": "test"}}))
    (bdir / "metrics.json").write_text(json.dumps({"registry": {
        "srt_monitor_last_sample_age_s": {
            "kind": "gauge", "series": [{"labels": [],
                                         "value": 42.0}]}}}))
    findings = doctor.analyze(doctor.Bundle(str(bdir)))
    stalled = [f for f in findings if f["kind"] == "stalled_sampler"]
    assert stalled and "42.0s" in stalled[0]["message"]


def test_doctor_quiet_on_fresh_sampler(tmp_path):
    bdir = tmp_path / "incident-2-manual-001"
    os.makedirs(bdir)
    (bdir / "MANIFEST.json").write_text("{}")
    (bdir / "trigger.json").write_text(json.dumps(
        {"kind": "manual", "detail": {}}))
    (bdir / "metrics.json").write_text(json.dumps({"registry": {
        "srt_monitor_last_sample_age_s": {
            "kind": "gauge", "series": [{"labels": [],
                                         "value": 1.0}]}}}))
    findings = doctor.analyze(doctor.Bundle(str(bdir)))
    assert not [f for f in findings if f["kind"] == "stalled_sampler"]
