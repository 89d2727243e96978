"""Query-lifeguard suite (ISSUE 7): per-query deadlines are covered in
test_query_server.py; here — heartbeats, the hung-worker watchdog
(orphan + replace + force-release + query_hang bundle), the
poison-query quarantine breaker (open / half-open probe / close), the
socket idle timeout, and graceful drain/restart."""

import json
import os
import socket
import threading
import time

import pytest

from spark_rapids_tpu import observability as obs
from spark_rapids_tpu.memory import exceptions as mem_exc
from spark_rapids_tpu.robustness import lifeguard
from spark_rapids_tpu.server import (QueryServer, ServerConfig,
                                     ServerOverloaded, SocketFrontDoor)


def wait_for(predicate, timeout_s=10.0, interval=0.01):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


def lifeguard_server(runner, *, concurrency=1, hang_s=0.2,
                     quarantine_failures=0, cooldown_s=0.2,
                     max_requeues=0, drain_deadline_s=10.0):
    cfg = ServerConfig(max_concurrency=concurrency, max_queue=16,
                       stall_ms=0, max_requeues=max_requeues,
                       hang_s=hang_s, watchdog_interval_s=0.02,
                       quarantine_failures=quarantine_failures,
                       quarantine_cooldown_s=cooldown_s,
                       drain_deadline_s=drain_deadline_s)
    return QueryServer(cfg, runner=runner).start()


# ------------------------------------------------------------ heartbeats


def test_beat_and_last_beat_roundtrip():
    ident = threading.get_ident()
    # beats are consumer-gated: with no lifeguard installed the hot
    # seams pay a single global read and record nothing
    while lifeguard._HOOK_INSTALLS > 0:
        lifeguard.release_heartbeat_hook()
    lifeguard.clear_beat(ident)
    lifeguard.beat("ignored")
    assert lifeguard.last_beat(ident) is None
    lifeguard.install_heartbeat_hook()
    try:
        lifeguard.beat("unit")
        b = lifeguard.last_beat(ident)
        assert b is not None
        t_ns, label = b
        assert label == "unit"
        assert time.monotonic_ns() - t_ns < 5e9
        lifeguard.clear_beat(ident)
        assert lifeguard.last_beat(ident) is None
    finally:
        lifeguard.release_heartbeat_hook()


def test_retry_attempts_count_as_heartbeats():
    from spark_rapids_tpu.robustness import retry as R
    lifeguard.install_heartbeat_hook()
    try:
        lifeguard.clear_beat(threading.get_ident())
        R.with_retry(lambda: 1, name="lg_beat",
                     policy=R.RetryPolicy(base_backoff_s=0.0))
        b = lifeguard.last_beat(threading.get_ident())
        assert b is not None and b[1] == "retry:lg_beat"
    finally:
        lifeguard.release_heartbeat_hook()


def test_op_close_heartbeats_via_observability_hook():
    lifeguard.install_heartbeat_hook()
    try:
        lifeguard.clear_beat(threading.get_ident())
        obs.record_op("lg_op", 123)  # metrics off: only the hook fires
        b = lifeguard.last_beat(threading.get_ident())
        assert b is not None and b[1] == "op:lg_op"
    finally:
        lifeguard.release_heartbeat_hook()


def test_thread_stack_names_live_frames():
    here = threading.Event()
    done = threading.Event()

    def parked():
        here.set()
        done.wait(10)

    t = threading.Thread(target=parked, daemon=True)
    t.start()
    assert here.wait(5)
    stack = lifeguard.thread_stack(t.ident)
    assert any("parked" in line or "done.wait" in line
               for line in stack)
    done.set()
    t.join(5)
    assert lifeguard.thread_stack(None) == []


# ------------------------------------------------------------- signature


def test_signature_folds_tenant_query_and_params():
    a = lifeguard.signature("t", "q", {"rows": 1024})
    assert a.startswith("t/q@")
    assert a == lifeguard.signature("t", "q", {"rows": 1024})
    assert a != lifeguard.signature("t", "q", {"rows": 2048})
    assert a != lifeguard.signature("u", "q", {"rows": 1024})
    # unserializable params still produce a stable signature
    obj = object()
    assert lifeguard.signature("t", "q", {"x": obj}) \
        == lifeguard.signature("t", "q", {"x": obj})


# ----------------------------------------------------- quarantine breaker


def test_quarantine_breaker_open_probe_close_cycle():
    clock = {"t": 0.0}
    br = lifeguard.QuarantineBreaker(failures=2, cooldown_s=10.0,
                                     clock=lambda: clock["t"])
    sig = "t/q@abc"
    assert br.admit(sig)["verdict"] == "ok"
    assert not br.note_death(sig, "failed")["quarantined"]
    info = br.note_death(sig, "hung")
    assert info["quarantined"] and info["opened"]
    assert info["retry_after_s"] == pytest.approx(10.0)
    # open: refused with the remaining cooldown
    clock["t"] = 4.0
    v = br.admit(sig)
    assert v["verdict"] == "refused"
    assert v["retry_after_s"] == pytest.approx(6.0)
    # cooldown over: exactly ONE half-open probe
    clock["t"] = 10.5
    assert br.admit(sig)["verdict"] == "probe"
    assert br.admit(sig)["verdict"] == "refused"   # probe in flight
    # probe success closes and resets
    br.note_success(sig, probe=True)
    assert br.admit(sig)["verdict"] == "ok"
    assert br.snapshot()["quarantined"] == {}


def test_quarantine_failed_probe_escalates_cooldown():
    clock = {"t": 0.0}
    br = lifeguard.QuarantineBreaker(failures=1, cooldown_s=1.0,
                                     clock=lambda: clock["t"])
    sig = "t/q@bad"
    assert br.note_death(sig, "shed")["opened"]
    clock["t"] = 1.5
    assert br.admit(sig)["verdict"] == "probe"
    info = br.note_death(sig, "shed", probe=True)
    assert info["opened"] and info["quarantined"]
    # second open doubles the cooldown
    assert info["retry_after_s"] == pytest.approx(2.0)
    # a cancelled probe re-arms the door instead of wedging half-open
    clock["t"] = 4.0
    assert br.admit(sig)["verdict"] == "probe"
    br.note_neutral(sig, probe=True)
    assert br.admit(sig)["verdict"] == "probe"


def test_quarantine_entries_bounded():
    br = lifeguard.QuarantineBreaker(failures=1, cooldown_s=1.0)
    for i in range(br.MAX_ENTRIES + 50):
        br.note_death(f"t/q@{i}", "failed")
    assert br.snapshot()["tracked"] <= 2 * br.MAX_ENTRIES


def test_quarantine_open_entry_survives_signature_churn():
    """Signature churn (the exact load the LRU bound exists for) must
    not flush an OPEN quarantine out of the table — that would
    re-admit the poison query with a clean slate."""
    clock = {"t": 0.0}
    br = lifeguard.QuarantineBreaker(failures=2, cooldown_s=100.0,
                                     clock=lambda: clock["t"])
    poison = "t/poison@sig"
    br.note_death(poison, "failed")
    br.note_death(poison, "hung")
    assert br.admit(poison)["verdict"] == "refused"
    # a tenant cycling fresh params: single-strike CLOSED entries
    for i in range(br.MAX_ENTRIES + 100):
        sig = f"t/churn@{i}"
        br.note_death(sig, "failed")
        if i % 7 == 0:
            br.admit(poison)        # poison is actively refused
    v = br.admit(poison)
    assert v["verdict"] == "refused", \
        "open circuit was evicted by closed-entry churn"
    assert v["retry_after_s"] > 0


def test_stale_half_open_probe_self_heals():
    """A probe whose outcome never comes back (server died mid-probe)
    must not quarantine the signature forever: past a generous window
    the door re-arms and grants a new probe."""
    clock = {"t": 0.0}
    br = lifeguard.QuarantineBreaker(failures=1, cooldown_s=1.0,
                                     clock=lambda: clock["t"])
    sig = "t/q@zzz"
    br.note_death(sig, "failed")
    clock["t"] = 1.5
    assert br.admit(sig)["verdict"] == "probe"   # ...never reported
    clock["t"] = 2.0
    assert br.admit(sig)["verdict"] == "refused"
    clock["t"] = 1.5 + 61.0                      # past the stale bar
    assert br.admit(sig)["verdict"] == "probe"


def test_queued_deadline_expiry_is_not_a_quarantine_death():
    """A deadline that expires while the job is still QUEUED is queue
    congestion, not poison: it must not accrue strikes against the
    signature."""
    gate = threading.Event()
    started = []

    def runner(query, params, ctx):
        started.append(query)
        while not gate.wait(0.02):
            ctx.check_cancel()
        return ["ok"]

    s = lifeguard_server(runner, concurrency=1, hang_s=0,
                         quarantine_failures=1, cooldown_s=60.0)
    try:
        s.submit("t", "blocker")
        assert wait_for(lambda: started == ["blocker"])
        doomed = s.submit("t", "congested", {"k": 1},
                          deadline_s=0.05)
        r = s.poll(doomed, timeout_s=20)
        assert r["state"] == "failed"
        assert r["error"]["reason"] == "deadline_expired_queued"
        # threshold is 1: had the expiry counted as a death, this
        # submit would bounce quarantined — it must be admitted
        again = s.submit("t", "congested", {"k": 1})
        gate.set()
        assert s.poll(again, timeout_s=20)["state"] == "done"
        assert s.stats()["lifeguard"]["quarantine"]["quarantined"] \
            == {}
    finally:
        gate.set()
        s.stop()


def test_user_cancel_dominates_lapsed_deadline():
    from spark_rapids_tpu.models import (QueryCancelled, QueryContext,
                                         QueryDeadlineExceeded)
    ev = threading.Event()
    ev.set()
    ctx = QueryContext("q-x", "t", cancel_event=ev,
                       deadline_ns=time.monotonic_ns() - 1)
    # both conditions hold: the explicit cancel wins, so the server
    # reports "cancelled" (keyed off cancel_reason), never a bogus
    # deadline death
    with pytest.raises(QueryCancelled) as ei:
        ctx.check_cancel()
    assert not isinstance(ei.value, QueryDeadlineExceeded)


def test_heartbeat_hook_released_with_last_server():
    from spark_rapids_tpu import observability as _obs
    base = lifeguard._HOOK_INSTALLS
    s1 = lifeguard_server(lambda q, p, c: ["ok"], hang_s=0)
    s2 = lifeguard_server(lambda q, p, c: ["ok"], hang_s=0)
    assert lifeguard._HOOK_INSTALLS == base + 2
    assert _obs._HEARTBEAT_HOOK is not None
    s1.stop()
    # one server still lives: the hook must survive for its watchdog
    assert _obs._HEARTBEAT_HOOK is not None
    s2.stop()
    assert lifeguard._HOOK_INSTALLS == base
    if base == 0:
        assert _obs._HEARTBEAT_HOOK is None


# ------------------------------------------------------ hung-worker story


def test_watchdog_releases_hung_worker_and_pool_recovers(tmp_path):
    """A runner that goes silent (no heartbeat, no cancel polling)
    past hang_s is declared hung: the job fails typed, a query_hang
    bundle freezes the evidence, the pool replaces the orphaned
    worker (capacity survives on a 1-thread pool), and the orphan
    exits instead of serving when it finally wakes."""
    obs.enable()
    obs.reset()
    obs.enable_flight_recorder(out_dir=str(tmp_path / "incidents"),
                               min_interval_s=0.0)
    release = threading.Event()
    hung_entered = threading.Event()

    def runner(query, params, ctx):
        if query == "wedge":
            hung_entered.set()
            release.wait(30)        # silent: never beats, never polls
            return ["late"]
        return ["ok", query]

    s = lifeguard_server(runner, concurrency=1, hang_s=0.15)
    try:
        qid = s.submit("victim_tenant", "wedge", {"rows": 7})
        assert hung_entered.wait(10)
        r = s.poll(qid, timeout_s=20)
        assert r["state"] == "failed", r
        assert r["error"]["type"] == "QueryHung"
        assert r["hung"] is True
        assert s.stats()["tenants"]["victim_tenant"]["hung"] == 1
        # the replacement worker keeps the 1-slot pool serving
        nxt = s.submit("neighbor", "fine")
        assert s.poll(nxt, timeout_s=20)["state"] == "done"
        # watchdog evidence in the journal
        acts = [e for e in obs.JOURNAL.records("server_watchdog")
                if e.get("action") == "hang_release"]
        assert acts and acts[0]["query_id"] == qid
        # the orphan exits on release; its late result is discarded
        release.set()
        assert wait_for(
            lambda: s.stats()["lifeguard"]["orphaned_workers"] == 0)
        assert s.poll(qid)["state"] == "failed"
    finally:
        release.set()
        s.stop()
        obs.disable_flight_recorder()
    from spark_rapids_tpu.tools import doctor
    bundles = doctor.find_bundles(str(tmp_path / "incidents"))
    assert bundles, "hang produced no query_hang bundle"
    b = doctor.Bundle(bundles[-1])
    assert b.trigger["kind"] == "query_hang"
    detail = b.trigger["detail"]
    assert detail["query"] == "wedge"
    assert detail["tenant"] == "victim_tenant"
    assert detail["silent_ms"] >= 100
    findings = doctor.analyze(b)
    hang = [f for f in findings if f["kind"] == "query_hang"]
    assert hang and "'wedge'" in hang[0]["message"]
    # the stack capture names where the worker was stuck
    assert any(f["kind"] == "hung_stack" for f in findings)
    obs.reset()
    obs.disable()


def test_hung_job_task_force_released_unblocks_ledger():
    """A hung job holding device memory: the watchdog's force-release
    unwinds its RmmSpark associations, so the ledger stops
    attributing the bytes and a blocked neighbor can make progress."""
    from spark_rapids_tpu.memory import rmm_spark
    rmm_spark.clear_event_handler()
    rmm_spark.set_event_handler(1 << 20)
    release = threading.Event()
    held = threading.Event()

    def runner(query, params, ctx):
        if query == "hog":
            rmm_spark.get_adaptor().allocate(4096)
            held.set()
            release.wait(30)        # hangs while holding the bytes
            return ["late"]
        return ["ok"]

    s = lifeguard_server(runner, concurrency=1, hang_s=0.15)
    try:
        qid = s.submit("piggy", "hog")
        assert held.wait(10)
        assert s.poll(qid, timeout_s=20)["state"] == "failed"
        # post-release: no live task attribution for the tenant
        assert s.stats()["tenants"]["piggy"]["device_bytes"] == 0
        adaptor = rmm_spark.installed_adaptor()
        states = adaptor.thread_state_dump()
        assert all(not t["pool_tasks"] for t in states)
        # the force-release logged its deliberate eviction
        assert any("FORCE_RELEASE" in row for row in
                   adaptor.get_log())
    finally:
        release.set()
        s.stop()
        rmm_spark.clear_event_handler()


def test_adaptor_force_release_task_direct():
    from spark_rapids_tpu.memory import rmm_spark
    rmm_spark.clear_event_handler()
    rmm_spark.set_event_handler(1 << 20)
    try:
        adaptor = rmm_spark.get_adaptor()
        tid = rmm_spark.current_thread_id()
        rmm_spark.pool_thread_working_on_tasks(False, tid, [777001])
        adaptor.allocate(2048)
        info = adaptor.force_release_task(777001)
        assert info["threads"] == [tid]
        assert info["held_bytes"] == 2048
        # this (running) thread was disassociated, not wedged
        assert adaptor.thread_state_dump() == [] or all(
            777001 not in t["pool_tasks"]
            for t in adaptor.thread_state_dump())
        adaptor.deallocate(2048)
    finally:
        rmm_spark.clear_event_handler()


# ------------------------------------------------- quarantine end-to-end


def test_poison_query_quarantined_then_probe_readmits():
    obs.enable()
    obs.reset()
    healthy = {"on": False}

    def runner(query, params, ctx):
        if query == "poison" and not healthy["on"]:
            raise mem_exc.GpuSplitAndRetryOOM("still too big")
        return ["ok", query]

    s = lifeguard_server(runner, quarantine_failures=2,
                         cooldown_s=0.15, max_requeues=0)
    try:
        # two deaths (OOM-exhausted against quota -> "shed") open it
        for _ in range(2):
            qid = s.submit("acme", "poison", {"rows": 1})
            assert s.poll(qid, timeout_s=20)["state"] == "failed"
        with pytest.raises(ServerOverloaded) as ei:
            s.submit("acme", "poison", {"rows": 1})
        assert ei.value.reason == "quarantined"
        assert ei.value.retry_after_s > 0
        # the same query with DIFFERENT params is a different
        # signature: not quarantined
        other = s.submit("acme", "poison", {"rows": 2})
        s.poll(other, timeout_s=20)
        # neighbors entirely unaffected
        ok = s.submit("bravo", "fine")
        assert s.poll(ok, timeout_s=20)["state"] == "done"
        # journal carries the breaker transitions
        events = {e["event"] for e in
                  obs.JOURNAL.records("server_quarantine")}
        assert "opened" in events and "rejected" in events
        # cooldown passes -> half-open probe; healthy now -> closes
        healthy["on"] = True
        time.sleep(0.2)
        probe = s.submit("acme", "poison", {"rows": 1})
        assert s.poll(probe, timeout_s=20)["state"] == "done"
        events = {e["event"] for e in
                  obs.JOURNAL.records("server_quarantine")}
        assert "probe" in events and "closed" in events
        # fully re-admitted
        again = s.submit("acme", "poison", {"rows": 1})
        assert s.poll(again, timeout_s=20)["state"] == "done"
        assert s.stats()["lifeguard"]["quarantine"]["quarantined"] \
            == {}
    finally:
        s.stop()
        obs.reset()
        obs.disable()


def test_failed_probe_reopens_quarantine():
    def runner(query, params, ctx):
        raise RuntimeError("always broken")

    s = lifeguard_server(runner, quarantine_failures=1,
                         cooldown_s=0.1)
    # the breaker reads a clock the test moves: a sleep past the
    # cooldown raced the wall clock under loaded workers
    now = [1000.0]
    s._quarantine.clock = lambda: now[0]
    try:
        qid = s.submit("t", "bad")
        assert s.poll(qid, timeout_s=20)["state"] == "failed"
        with pytest.raises(ServerOverloaded):
            s.submit("t", "bad")
        now[0] += 0.15
        probe = s.submit("t", "bad")     # half-open probe
        assert s.poll(probe, timeout_s=20)["state"] == "failed"
        # reopened, with escalated cooldown > the original 0.1
        with pytest.raises(ServerOverloaded) as ei:
            s.submit("t", "bad")
        assert ei.value.reason == "quarantined"
        assert ei.value.retry_after_s > 0.1
    finally:
        s.stop()


# ----------------------------------------------------- socket idle timeout


def test_socket_idle_timeout_answers_typed_and_closes(tmp_path):
    s = lifeguard_server(lambda q, p, c: ["ok"], hang_s=0)
    path = str(tmp_path / "lg.sock")
    door = SocketFrontDoor(s, path, idle_s=0.2).start()
    try:
        conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        conn.connect(path)
        f = conn.makefile("rwb")
        # a half-open client: partial line, no newline, then silence
        f.write(b'{"op": "stats"')
        f.flush()
        conn.settimeout(5)
        line = f.readline()
        resp = json.loads(line)
        assert not resp["ok"]
        assert resp["error"]["type"] == "IdleTimeout"
        assert f.readline() == b""      # server closed the stream
        conn.close()
        # a live client on a fresh connection still works
        conn2 = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        conn2.connect(path)
        f2 = conn2.makefile("rwb")
        f2.write(json.dumps({"op": "stats"}).encode() + b"\n")
        f2.flush()
        assert json.loads(f2.readline())["ok"]
        conn2.close()
    finally:
        door.stop()
        s.stop()


# --------------------------------------------------------------- drain


def test_drain_finishes_inflight_refuses_new_and_reports(tmp_path):
    obs.enable()
    obs.reset()
    gate = threading.Event()
    started = []

    def runner(query, params, ctx):
        started.append(query)
        while not gate.wait(0.02):
            ctx.check_cancel()
        return ["done", query]

    s = lifeguard_server(runner, concurrency=2, hang_s=0,
                         drain_deadline_s=10.0)
    report_box = {}
    try:
        a = s.submit("t", "a")
        b = s.submit("t", "b")
        assert wait_for(lambda: len(started) == 2)

        def do_drain():
            report_box["r"] = s.drain(
                flush_dir=str(tmp_path / "drainout"))

        dr = threading.Thread(target=do_drain)
        dr.start()
        assert wait_for(lambda: s._draining)
        # draining: new submits bounce typed
        with pytest.raises(ServerOverloaded) as ei:
            s.submit("t", "late")
        assert ei.value.reason == "draining"
        assert ei.value.retry_after_s > 0
        gate.set()                     # in-flight work finishes
        dr.join(20)
        r = report_box["r"]
        assert r["state"] == "drained"
        assert r["in_flight"] == 2
        assert r["completed"] == 2
        assert r["cancelled"] == 0 and r["abandoned"] == 0
        assert s.poll(a)["state"] == "done"
        assert s.poll(b)["state"] == "done"
        # dumpio flush actually landed
        d = r["flush"]["dir"]
        for name in ("journal.jsonl", "spans.jsonl", "metrics.json"):
            assert os.path.isfile(os.path.join(d, name)), r["flush"]
        drains = obs.JOURNAL.records("server_drain")
        assert {e["phase"] for e in drains} == {"begin", "end"}
    finally:
        gate.set()
        if report_box.get("r") is None:
            s.stop()
        obs.reset()
        obs.disable()
    # the pool is fully stopped; a restart serves again
    assert not s._started
    s.start()
    try:
        qid = s.submit("t", "after")
        assert s.poll(qid, timeout_s=20)["state"] == "done"
    finally:
        s.stop()


def test_drain_deadline_cancels_stragglers():
    stuck = threading.Event()

    def runner(query, params, ctx):
        stuck.set()
        while True:                 # cooperative but never finishes
            ctx.check_cancel()
            time.sleep(0.01)

    s = lifeguard_server(runner, hang_s=0, drain_deadline_s=0.2)
    try:
        qid = s.submit("t", "straggler")
        assert stuck.wait(10)
        r = s.drain()
        assert r["in_flight"] == 1
        assert r["completed"] == 0
        assert r["cancelled"] == 1
        assert r["abandoned"] == 0     # it honored the cancel
        st = s.poll(qid)
        assert st["state"] == "cancelled"
        assert st["cancel_reason"] == "drain"
    finally:
        if s._started:
            s.stop()


def test_module_level_drain_clears_singleton_and_restarts():
    from spark_rapids_tpu import models as m
    from spark_rapids_tpu import server as srv
    m.register_query("lg_echo", lambda params, ctx: params.get("v"))
    try:
        srv.start_server(ServerConfig(max_concurrency=1, max_queue=4,
                                      stall_ms=0))
        report = srv.drain_server(deadline_s=5.0)
        assert report["state"] == "drained"
        assert srv.get_server() is None
        assert srv.drain_server() == {"state": "not_running"}
        # restart serves again (the process caches stay warm)
        s2 = srv.start_server(ServerConfig(max_concurrency=1,
                                           max_queue=4, stall_ms=0))
        qid = s2.submit("t", "lg_echo", {"v": 7})
        assert s2.poll(qid, timeout_s=20)["result"] == 7
    finally:
        srv.stop_server()
        m.unregister_query("lg_echo")


def test_drain_server_leaves_newer_servers_door_alone(tmp_path):
    """A slow drain racing a stop+start must not tear down the FRESH
    server's socket door when it finally finishes."""
    from spark_rapids_tpu import server as srv
    old = QueryServer(ServerConfig(max_concurrency=1, max_queue=4,
                                   stall_ms=0),
                      runner=lambda q, p, c: ["ok"]).start()
    fresh = QueryServer(ServerConfig(max_concurrency=1, max_queue=4,
                                     stall_ms=0),
                        runner=lambda q, p, c: ["ok"]).start()
    door = SocketFrontDoor(fresh, str(tmp_path / "fresh.sock")).start()
    try:
        with srv._LOCK:
            saved_server, saved_door = srv._SERVER, srv._DOOR
            srv._SERVER, srv._DOOR = old, door
        report = srv.drain_server(deadline_s=5.0)
        assert report["state"] == "drained"
        # the door fronts the FRESH server, not the drained one: it
        # must survive and stay registered
        assert srv._DOOR is door
        assert door._sock is not None
    finally:
        with srv._LOCK:
            srv._SERVER, srv._DOOR = saved_server, saved_door
        door.stop()
        fresh.stop()
        if old._started:
            old.stop()


def test_socket_drain_op(tmp_path):
    s = lifeguard_server(lambda q, p, c: ["ok"], hang_s=0)
    path = str(tmp_path / "drain.sock")
    door = SocketFrontDoor(s, path).start()
    try:
        conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        conn.connect(path)
        f = conn.makefile("rwb")
        f.write(json.dumps({"op": "drain",
                            "deadline_s": 5.0}).encode() + b"\n")
        f.flush()
        resp = json.loads(f.readline())
        assert resp["ok"], resp
        assert resp["report"]["state"] == "drained"
        # post-drain submits answer typed (server no longer started)
        f.write(json.dumps({"op": "submit", "tenant": "t",
                            "query": "q"}).encode() + b"\n")
        f.flush()
        resp2 = json.loads(f.readline())
        assert not resp2["ok"]
        assert resp2["error"]["type"] == "ServerOverloaded"
        conn.close()
    finally:
        door.stop()
        if s._started:
            s.stop()
