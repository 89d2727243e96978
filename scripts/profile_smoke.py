"""Query-profile gate (`make profile-smoke`, ISSUE 13 acceptance):

  * ONE profiled session running the fused q3/q5/q72 catalog
    pipelines must produce a plan tree matching the 5-executable
    stage count (q3, q5_partials, q5_finish, q72_partials,
    q72_finish), with live pad-waste and compile evidence, and
    per-stage call counts reconciling with
    ``srt_stage_fusion_total`` in the metrics registry;
  * a REAL 2-process q5 fleet launched with
    ``SPARK_RAPIDS_TPU_PROFILE=1`` must dump one profile per rank,
    and ``srt-explain`` must merge them into ONE fleet profile whose
    per-stage walls are the max over ranks and whose per-rank
    shuffle-link bytes reconcile EXACTLY with each rank's own
    metrics dump (``srt_shuffle_link_bytes_total`` series);
  * ``srt-explain --diff`` must exit NONZERO on an injected
    per-stage slowdown and ZERO on a self-diff;
  * with profiling disabled, the hook surface (begin/end/active)
    must stay at attribute-read cost — the noop discipline the
    tracer set.

Exits non-zero on the first missing signal."""

import copy
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


def fail(msg: str) -> "NoReturn":  # noqa: F821
    print(f"profile-smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def say(msg: str) -> None:
    print(f"profile-smoke: {msg}")


def main() -> int:
    t_start = time.monotonic()
    from spark_rapids_tpu import observability as obs
    from spark_rapids_tpu.models import tpcds as T
    from spark_rapids_tpu.plan import catalog as C
    from spark_rapids_tpu.tools import srt_explain as E

    obs.enable()
    obs.enable_tracing()
    obs.enable_profiling()
    obs.reset()

    # ---- one session over q3+q5+q72: tree == 5 stage executables ---
    W0 = 11_000 // 7
    sess = obs.PROFILER.begin("smoke-q3q5q72", tenant="smoke",
                              query="q3+q5+q72")
    if sess is None:
        fail("PROFILER.begin returned None with profiling enabled")
    d5 = T.gen_q5(rows=6000, stores=32, days=60)
    d3 = T.gen_q3(rows=6000, items=64, days=730, brands=8)
    d72 = T.gen_q72(cs_rows=3000, inv_rows=3000, items=64, days=35)
    C.run_q3(d3, 10_957, years=3, brands=8, manufact=2)
    C.run_q5(d5, 32, 1 << 15)
    C.run_q72(d72, 64, 16, 1 << 19, week0=W0)
    prof = obs.PROFILER.end(sess)
    if prof is None:
        fail("PROFILER.end assembled no profile")
    stages = {s["stage"] for s in prof["stages"]}
    want = {"q3", "q5_partials", "q5_finish", "q72_partials",
            "q72_finish"}
    if stages != want:
        fail(f"profile tree stages {sorted(stages)} != the "
             f"5-executable set {sorted(want)}")
    pad = [i for s in prof["stages"] for i in s.get("inputs", ())
           if i.get("pad_rows", 0) > 0]
    if not pad:
        fail("no pad-waste evidence in any stage input (6000 rows "
             "must pad to the 8192 bucket)")
    if not any(s.get("compiled") for s in prof["stages"]):
        fail("no stage reported compile=True on a cold cache")
    # per-stage call counts must reconcile with the registry counter
    snap = obs.METRICS.snapshot()
    fam = snap.get("srt_stage_fusion_total") or {}
    fused_counts = {tuple(s["labels"]): s["value"]
                    for s in fam.get("series", [])}
    for s in prof["stages"]:
        got = fused_counts.get((s["stage"], "fused"), 0)
        if got < s["calls"]:
            fail(f"stage {s['stage']}: profile calls {s['calls']} "
                 f"not covered by srt_stage_fusion_total fused={got}")
    if prof["hot_stage"] not in stages:
        fail(f"hot_stage {prof['hot_stage']!r} not in the tree")
    tree = E.render_profile(prof)
    for line in tree:
        print(f"  {line}")
    if not any("<-- HOT" in line for line in tree):
        fail("rendered tree has no hot-path highlight")
    say(f"single-process tree OK: 5 stages, hot={prof['hot_stage']}, "
        f"pad-waste on {len(pad)} input(s)")

    # ---- world=2 fleet: rank profiles -> ONE merged profile --------
    from spark_rapids_tpu.distributed import launcher
    outdir = tempfile.mkdtemp(prefix="profile_smoke_")
    os.environ["SPARK_RAPIDS_TPU_PROFILE"] = "1"
    try:
        say(f"launching {WORLD}-process q5 fleet with profiling on "
            f"-> {outdir}")
        launcher.launch(WORLD, outdir, ops=("q5",), timeout_s=240.0)
    finally:
        os.environ.pop("SPARK_RAPIDS_TPU_PROFILE", None)
    rank_paths = [os.path.join(outdir, f"profile_q5_rank{r}.json")
                  for r in range(WORLD)]
    for p in rank_paths:
        if not os.path.isfile(p):
            fail(f"missing rank profile {p}")
    rank_profs = [json.load(open(p)) for p in rank_paths]
    fleet = E.merge_profiles(rank_profs)
    if not fleet.get("fleet") or fleet.get("world") != WORLD:
        fail(f"merge did not produce a world={WORLD} fleet profile: "
             f"{ {k: fleet.get(k) for k in ('fleet', 'world')} }")
    if not fleet.get("trace_consistent"):
        fail("rank profiles do not share the launcher-seeded "
             "trace context")
    # per-stage wall = max over ranks (critical path), skew table live
    for s in fleet["stages"]:
        walls = s.get("per_rank_wall_ns") or {}
        if len(walls) != WORLD:
            fail(f"fleet stage {s['stage']} has per-rank walls for "
                 f"{sorted(walls)} (want {WORLD} ranks)")
        if s["wall_ns"] != max(walls.values()):
            fail(f"fleet stage {s['stage']} wall {s['wall_ns']} != "
                 f"max over ranks {max(walls.values())}")
    if len(fleet.get("skew") or ()) != len(fleet["stages"]):
        fail("fleet skew table does not cover every stage")
    # each rank's profile link bytes reconcile EXACTLY with that
    # rank's own metrics dump
    for r in range(WORLD):
        metrics = json.load(open(os.path.join(
            outdir, f"metrics_q5_rank{r}.json")))
        fam = metrics.get("srt_shuffle_link_bytes_total") or {}
        reg = {tuple(s["labels"]): int(s["value"])
               for s in fam.get("series", []) if s.get("value")}
        got = {}
        bytes_ = (rank_profs[r].get("shuffle_links") or {}) \
            .get("bytes") or {}
        for direction, peers in bytes_.items():
            for peer, n in peers.items():
                got[(direction, peer)] = int(n)
        if not got:
            fail(f"rank {r} profile carries no shuffle-link bytes")
        if got != reg:
            fail(f"rank {r} profile link bytes {got} != registry "
                 f"{reg}")
    say(f"fleet merge OK: world={WORLD}, both ranks' link bytes "
        f"reconcile with their registries, "
        f"skew table over {len(fleet['stages'])} stages")
    merged_path = os.path.join(outdir, "fleet.profile.json")
    with open(merged_path, "w") as f:
        json.dump(fleet, f, default=str)
    rc = E.main(rank_paths)
    if rc != 0:
        fail(f"srt-explain over the rank profiles exited {rc}")

    # ---- --diff: self-diff rc 0, injected slowdown rc != 0 ---------
    slowed = copy.deepcopy(fleet)
    for s in slowed["stages"]:
        if s["stage"] == "q5_partials":
            s["wall_ns"] = s["wall_ns"] * 4 + 80_000_000
    slowed_path = os.path.join(outdir, "slowed.profile.json")
    with open(slowed_path, "w") as f:
        json.dump(slowed, f, default=str)
    rc_same = E.main([merged_path, "--diff", merged_path])
    if rc_same != 0:
        fail(f"self-diff exited {rc_same}, want 0")
    rc_reg = E.main([slowed_path, "--diff", merged_path])
    if rc_reg == 0:
        fail("srt-explain --diff exited 0 on an injected 4x "
             "q5_partials slowdown")
    say(f"--diff OK: self-diff rc 0, injected slowdown rc {rc_reg}")

    # ---- disabled-mode overhead gate -------------------------------
    obs.disable_profiling()
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        s = obs.PROFILER.begin("x")
        obs.PROFILER.active()
        obs.PROFILER.end(s)
    per_call_us = (time.perf_counter() - t0) / n * 1e6
    # three disabled hooks per loop; anything near dict/lock work
    # would blow this budget by orders of magnitude
    if per_call_us > 25.0:
        fail(f"disabled-mode hooks cost {per_call_us:.2f} us per "
             f"begin+active+end loop (budget 25 us) — the noop "
             f"fast path regressed")
    before = obs.PROFILER.stats()["assembled"]
    C.run_q3(d3, 10_957, years=3, brands=8, manufact=2)
    if obs.PROFILER.stats()["assembled"] != before:
        fail("a profile was assembled with profiling disabled")
    say(f"disabled-mode OK: {per_call_us:.2f} us per "
        f"begin+active+end loop, no artifacts assembled")

    say(f"OK ({time.monotonic() - t_start:.1f}s): 5-stage tree, "
        f"world={WORLD} fleet merge + registry reconciliation, "
        f"--diff guardrail, noop-when-disabled")
    return 0


if __name__ == "__main__":
    sys.exit(main())
