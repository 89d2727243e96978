"""Time-attribution gate (`make attribution-smoke`, ISSUE 17
acceptance):

  * a CLEAN profiled fused q5 must carry an embedded attribution
    ledger whose buckets sum EXACTLY to the measured wall
    (conservation), with live compute evidence and the
    ``srt_attribution_*`` counters lit;
  * a CHAOS run (an injected retryable failure burning real wall
    inside the session) must STAY conserved and its
    ``dominant_overhead`` must name the injected cause;
  * a REAL 2-process q5 fleet, clean then under a ``slow:0:150``
    link fault, must return byte-identical results; the cross-rank
    critical path over the span dumps must solve with ZERO clamped
    (negative) edges and its exchange-edge leaderboard must name the
    slowed link's destination;
  * ``srt-explain --diff`` of the slowed fleet against the clean one
    must exit NONZERO and attribute the delta to a shuffle bucket;
  * ``--where --json`` and ``--critical-path --json`` must be
    byte-deterministic across invocations (digest-stable);
  * with everything disabled, the record hooks must stay at
    attribute-read cost.

Exits non-zero on the first missing signal."""

import contextlib
import hashlib
import io
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

WORLD = 2
SLOW_MS = 150


def fail(msg: str) -> "NoReturn":  # noqa: F821
    print(f"attribution-smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def say(msg: str) -> None:
    print(f"attribution-smoke: {msg}")


def _capture(fn, *args):
    """(rc, stdout_text) of a CLI main."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = fn(*args)
    return rc, buf.getvalue()


def main() -> int:
    t_start = time.monotonic()
    import numpy as np

    from spark_rapids_tpu import observability as obs
    from spark_rapids_tpu.memory import exceptions as exc
    from spark_rapids_tpu.models import tpcds as T
    from spark_rapids_tpu.observability.attribution import (
        BUCKETS, attribute_many, diff_attribution)
    from spark_rapids_tpu.observability.critical_path import (
        critical_path)
    from spark_rapids_tpu.plan import catalog as C
    from spark_rapids_tpu.robustness import retry as R
    from spark_rapids_tpu.tools import read_jsonl
    from spark_rapids_tpu.tools import srt_explain as E

    obs.enable()
    obs.enable_tracing()
    obs.enable_profiling()
    obs.enable_attribution()
    obs.reset()

    # ---- clean single-process q5: conservation is EXACT -------------
    sess = obs.PROFILER.begin("attr-q5-clean", tenant="smoke",
                              query="q5")
    d5 = T.gen_q5(rows=6000, stores=32, days=60)
    C.run_q5(d5, 32, 1 << 15)
    prof = obs.PROFILER.end(sess)
    if prof is None:
        fail("PROFILER.end assembled no profile")
    led = prof.get("attribution")
    if not led:
        fail("no attribution ledger embedded in the profile with "
             "the switch on")
    if set(led["buckets"]) != set(BUCKETS):
        fail(f"ledger buckets {sorted(led['buckets'])} != the "
             f"exhaustive set")
    total = sum(led["buckets"].values())
    if total != led["wall_ns"]:
        fail(f"buckets sum {total} != wall {led['wall_ns']} "
             f"(conservation must be exact on a clean run)")
    if not led["conserved"]:
        fail(f"clean run not conserved: overcount {led['overcount_ns']}")
    comp = (led["buckets"]["compute_fused"]
            + led["buckets"]["compute_unfused"])
    if comp <= 0:
        fail("no compute nanoseconds attributed on a q5 run")
    last = obs.attribution_last()
    if not last or last.get("query_id") != "attr-q5-clean":
        fail("attribution_last() does not return the clean ledger")
    snap = obs.METRICS.snapshot()
    qfam = snap.get("srt_attribution_queries_total") or {}
    ok_series = {tuple(s["labels"]): s["value"]
                 for s in qfam.get("series", [])}
    if ok_series.get(("true",), 0) < 1:
        fail("srt_attribution_queries_total{conserved=true} not lit")
    tfam = snap.get("srt_attribution_ns_total") or {}
    if not any(s["labels"][0] == "smoke"
               for s in tfam.get("series", [])):
        fail("srt_attribution_ns_total has no tenant=smoke series")
    say(f"clean ledger OK: wall {led['wall_ns'] / 1e6:.1f} ms fully "
        f"attributed, dominant={led['dominant']}, "
        f"compute {comp / 1e6:.1f} ms")

    # ---- chaos: injected retry burn names itself --------------------
    # the burn must stay below the compute it is carved from, and the
    # chaos session runs WARM (compile cache hit), so size it off a
    # warm measurement run rather than the cold one above
    sess = obs.PROFILER.begin("attr-q5-warm", tenant="smoke",
                              query="q5")
    C.run_q5(d5, 32, 1 << 15)
    warm = obs.PROFILER.end(sess)["attribution"]["buckets"]
    warm_comp = warm["compute_fused"] + warm["compute_unfused"]
    burn_s = min(max(warm_comp * 0.3 / 1e9, 0.002), 0.15)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            time.sleep(burn_s)
            raise exc.CudfException("attribution-smoke injected")
        return 42

    sess = obs.PROFILER.begin("attr-q5-chaos", tenant="smoke",
                              query="q5")
    C.run_q5(d5, 32, 1 << 15)
    if R.with_retry(flaky, name="attr_smoke_inject") != 42:
        fail("with_retry did not recover the injected failure")
    prof2 = obs.PROFILER.end(sess)
    led2 = (prof2 or {}).get("attribution")
    if not led2:
        fail("chaos run produced no ledger")
    if not led2["conserved"]:
        fail(f"chaos run broke conservation: overcount "
             f"{led2['overcount_ns']} of wall {led2['wall_ns']}")
    if sum(led2["buckets"].values()) != led2["wall_ns"]:
        fail("chaos buckets do not sum to the wall")
    lost = led2["buckets"]["retry_lost"]
    if lost < burn_s * 1e9 * 0.9:
        fail(f"retry_lost {lost} ns does not cover the injected "
             f"{burn_s * 1e9:.0f} ns burn")
    if led2["dominant_overhead"] != "retry_lost":
        fail(f"dominant_overhead {led2['dominant_overhead']!r} does "
             f"not name the injected cause (want retry_lost)")
    say(f"chaos ledger OK: conserved, retry_lost {lost / 1e6:.1f} ms "
        f"dominates the overhead buckets")

    # ---- disabled-mode overhead gate --------------------------------
    obs.disable_attribution()
    obs.disable_profiling()
    obs.disable()
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        obs.record_shuffle_wire(0, 0)
        obs.record_shuffle_wait(0, 0, 0)
        obs.is_attribution_enabled()
    per_call_us = (time.perf_counter() - t0) / n * 1e6
    if per_call_us > 25.0:
        fail(f"disabled-mode hooks cost {per_call_us:.2f} us per "
             f"wire+wait+enabled loop (budget 25 us)")
    say(f"disabled-mode OK: {per_call_us:.2f} us per "
        f"wire+wait+enabled loop")

    # ---- 2-process fleet: clean vs slow:0 link, bytes identical -----
    from spark_rapids_tpu.distributed import launcher
    env = {"SPARK_RAPIDS_TPU_PROFILE": "1",
           "SPARK_RAPIDS_TPU_ATTRIBUTION": "1"}
    out_clean = tempfile.mkdtemp(prefix="attr_smoke_clean_")
    out_slow = tempfile.mkdtemp(prefix="attr_smoke_slow_")
    say(f"launching {WORLD}-process q5 fleet (clean) -> {out_clean}")
    launcher.launch(WORLD, out_clean, ops=("q5",), worker_env=env,
                    timeout_s=240.0)
    say(f"launching {WORLD}-process q5 fleet (slow:0:{SLOW_MS} on "
        f"rank 1) -> {out_slow}")
    launcher.launch(WORLD, out_slow, ops=("q5",),
                    fault=f"slow:0:{SLOW_MS}", fault_rank=1,
                    worker_env=env, timeout_s=240.0)

    for r in range(WORLD):
        a = np.load(os.path.join(out_clean, f"result_q5_rank{r}.npz"))
        b = np.load(os.path.join(out_slow, f"result_q5_rank{r}.npz"))
        if sorted(a.files) != sorted(b.files):
            fail(f"rank {r} result columns differ across runs")
        for k in a.files:
            if a[k].tobytes() != b[k].tobytes():
                fail(f"rank {r} column {k!r} not byte-identical "
                     f"under the slow link — a fault must never "
                     f"change results")
    say("fleet results byte-identical across clean and slowed runs")

    clean_paths = [os.path.join(out_clean,
                                f"profile_q5_rank{r}.json")
                   for r in range(WORLD)]
    slow_paths = [os.path.join(out_slow, f"profile_q5_rank{r}.json")
                  for r in range(WORLD)]
    clean_profs = [json.load(open(p)) for p in clean_paths]
    slow_profs = [json.load(open(p)) for p in slow_paths]
    for tag, profs in (("clean", clean_profs), ("slow", slow_profs)):
        for p in profs:
            emb = p.get("attribution")
            if not emb:
                fail(f"{tag} rank {p.get('rank')} profile has no "
                     f"embedded ledger (workers ran with "
                     f"SPARK_RAPIDS_TPU_ATTRIBUTION=1)")
            if not emb["conserved"]:
                fail(f"{tag} rank {p.get('rank')} ledger broke "
                     f"conservation: overcount {emb['overcount_ns']}")

    # ---- cross-rank critical path names the slowed link -------------
    def solve(outdir):
        return critical_path({
            r: read_jsonl(os.path.join(outdir,
                                       f"spans_rank{r}.jsonl"))
            for r in range(WORLD)})

    cp_clean, cp_slow = solve(out_clean), solve(out_slow)
    for tag, cp in (("clean", cp_clean), ("slow", cp_slow)):
        if not cp["path"]:
            fail(f"{tag} trace solved to an empty critical path")
        if cp["clamped_edges"] != 0:
            fail(f"{tag} solve clamped {cp['clamped_edges']} "
                 f"negative edges — clock normalization regressed")
        if cp["truncated_ranks"]:
            fail(f"{tag} solve truncated ranks "
                 f"{cp['truncated_ranks']}")

    def worst_into(cp, dst):
        gaps = [e["gap_ns"] for e in cp["exchange_edges"]
                if e["to_rank"] == dst]
        return max(gaps) if gaps else 0

    slow_into0 = worst_into(cp_slow, 0)
    clean_into0 = worst_into(cp_clean, 0)
    if slow_into0 < 40e6:
        fail(f"slowed run's worst exchange gap into rank 0 is "
             f"{slow_into0 / 1e6:.1f} ms — the {SLOW_MS} ms link "
             f"fault left no evidence")
    if slow_into0 <= clean_into0:
        fail(f"slowed gap into rank 0 ({slow_into0 / 1e6:.1f} ms) "
             f"not above the clean run's ({clean_into0 / 1e6:.1f} ms)")
    cross = [e for e in cp_slow["exchange_edges"]
             if e["from_rank"] == 1 and e["to_rank"] == 0]
    if not cross:
        fail("no cross-rank 1->0 exchange edge on the slowed "
             "leaderboard")
    say(f"critical path OK: worst gap into rank 0 "
        f"{slow_into0 / 1e6:.1f} ms slowed vs "
        f"{clean_into0 / 1e6:.1f} ms clean, 0 clamped edges")

    # ---- --diff: nonzero exit, delta attributed to a shuffle bucket -
    rows = diff_attribution(attribute_many(clean_profs),
                            attribute_many(slow_profs),
                            min_delta_ns=20_000_000)
    grew = [r for r in rows if r["delta_ms"] > 0]
    if not grew or grew[0]["bucket"] not in ("shuffle_wire",
                                             "shuffle_wait"):
        fail(f"diff attribution top growth "
             f"{grew[0]['bucket'] if grew else None!r} is not a "
             f"shuffle bucket: {rows}")
    merged_path = os.path.join(out_clean, "fleet.profile.json")
    with open(merged_path, "w") as f:
        json.dump(E.merge_profiles(clean_profs), f, default=str)
    rc, out = _capture(
        E.main, slow_paths + ["--diff", merged_path,
                              "--threshold", "1.02",
                              "--min-delta-ms", "20"])
    rc2, out2 = _capture(E.main, slow_paths + ["--where"])
    if rc == 0:
        fail("srt-explain --diff exited 0 on the slowed fleet")
    if "shuffle" not in out:
        fail(f"--diff output names no shuffle bucket:\n{out}")
    if "dominant" not in out2:
        fail("--where waterfall missing its dominant marker")
    say(f"--diff OK: rc {rc}, top bucket {grew[0]['bucket']} "
        f"(+{grew[0]['delta_ms']} ms)")

    # ---- determinism: --where/--critical-path --json digest-stable --
    digests = []
    for argv in (slow_paths + ["--where", "--json"],
                 [os.path.join(out_slow, f"spans_rank{r}.jsonl")
                  for r in range(WORLD)]
                 + ["--critical-path", "--json"]):
        rc_a, out_a = _capture(E.main, list(argv))
        rc_b, out_b = _capture(E.main, list(argv))
        if rc_a != 0 or rc_b != 0:
            fail(f"{argv[-2]} --json exited {rc_a}/{rc_b}")
        if out_a != out_b:
            fail(f"{argv[-2]} --json not byte-deterministic")
        digests.append(hashlib.sha256(
            out_a.encode()).hexdigest()[:12])
    say(f"determinism OK: --where digest {digests[0]}, "
        f"--critical-path digest {digests[1]}")

    say(f"OK ({time.monotonic() - t_start:.1f}s): conservation "
        f"clean+chaos, fleet bytes identical under slow link, "
        f"critical path names the slowed exchange, --diff gates, "
        f"noop-when-disabled")
    return 0


if __name__ == "__main__":
    sys.exit(main())
