"""Data-statistics gate (`make stats-smoke`, ISSUE 20 acceptance):

  * fused q5 + q72 runs with the stats plane armed must produce
    per-node observed row counts that reconcile EXACTLY with numpy
    recomputation over the generated data (join-pair totals,
    predicate survivor counts, generator input sizes) while staying
    byte-identical to the stats-off baseline;
  * the est-vs-actual join must be live (catalog generator estimates
    on every scan input) and `srt_stats_observations_total` must
    light up in the registry;
  * a second same-bucket run must compile ZERO new executables
    (taps ride the SAME one-executable-per-stage contract);
  * a seeded 100x misestimate must fire the full sentinel chain —
    `srt_stats_misestimate_total`, a `cardinality_misestimate`
    journal event, exactly ONE flight-recorder bundle even across a
    repeat run (first-detection-per-node discipline, rate limit set
    to zero so dedup is what's tested), and `srt-doctor` on the
    bundle must name the node and ratio;
  * with stats disabled the hook must stay at attribute-read cost.

Exits non-zero on the first missing signal."""

import io
import json
import os
import sys
import tempfile
import time
from contextlib import redirect_stdout

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402

Q5_ROWS, Q5_STORES, Q5_CAP = 6000, 32, 1 << 15
Q72_ROWS, Q72_ITEMS, Q72_MAX_WEEK, Q72_CAP = 3000, 64, 16, 1 << 19
WEEK0 = 11_000 // 7


def fail(msg: str) -> "NoReturn":  # noqa: F821
    print(f"stats-smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def say(msg: str) -> None:
    print(f"stats-smoke: {msg}")


def pair_total(probe, build) -> int:
    """Inner-join pair count the JoinProbe tap must reproduce."""
    u, c = np.unique(np.asarray(build), return_counts=True)
    m = dict(zip(u.tolist(), c.tolist()))
    return int(sum(m.get(int(v), 0) for v in np.asarray(probe)))


def q72_keep_count(d) -> int:
    """Numpy recompute of q72's `keep` predicate survivors over the
    full join pair set."""
    cs_i = np.asarray(d.cs_item)
    inv_i = np.asarray(d.inv_item)
    cs_date, cs_qty = np.asarray(d.cs_date), np.asarray(d.cs_qty)
    inv_date, inv_qty = np.asarray(d.inv_date), np.asarray(d.inv_qty)
    keep = 0
    for item in np.unique(cs_i):
        a = np.where(cs_i == item)[0]
        b = np.where(inv_i == item)[0]
        if not len(a) or not len(b):
            continue
        ow = cs_date[a][:, None] // 7
        iw = inv_date[b][None, :] // 7
        wk = ow - WEEK0
        k = ((iw == ow + 1)
             & (inv_qty[b][None, :] < cs_qty[a][:, None])
             & (wk >= 0) & (wk < Q72_MAX_WEEK))
        keep += int(k.sum())
    return keep


def node_rows(section, node: str) -> int:
    for n in section["nodes"]:
        if n["node"] == node:
            return int(n["rows"])
    fail(f"node {node!r} missing from stats section "
         f"{[n['node'] for n in section['nodes']]}")


def main() -> int:
    t_start = time.monotonic()
    from spark_rapids_tpu import observability as obs
    from spark_rapids_tpu.models import tpcds as T
    from spark_rapids_tpu.perf.jit_cache import CACHE
    from spark_rapids_tpu.plan import catalog as C
    from spark_rapids_tpu.tools import doctor

    tmp = tempfile.mkdtemp(prefix="stats_smoke_")
    os.environ["SPARK_RAPIDS_TPU_STATS_STORE"] = \
        os.path.join(tmp, "stats_store.json")
    os.environ["SPARK_RAPIDS_TPU_STATS_MISEST_RATIO"] = "8"
    obs.enable()
    obs.reset()
    obs.disable_stats()

    d5 = T.gen_q5(rows=Q5_ROWS, stores=Q5_STORES, days=60)
    d72 = T.gen_q72(cs_rows=Q72_ROWS, inv_rows=Q72_ROWS,
                    items=Q72_ITEMS, days=35)

    # ---- stats-off baseline (byte-identity oracle) -----------------
    base5 = C.run_q5(d5, Q5_STORES, Q5_CAP)
    base72 = C.run_q72(d72, Q72_ITEMS, Q72_MAX_WEEK, Q72_CAP,
                       week0=WEEK0)

    # ---- armed run: taps on, same bytes, exact reconciliation ------
    obs.enable_stats()
    compiles_before = CACHE.stats()["compiles"]
    got5 = C.run_q5(d5, Q5_STORES, Q5_CAP)
    got72 = C.run_q72(d72, Q72_ITEMS, Q72_MAX_WEEK, Q72_CAP,
                      week0=WEEK0)
    for name, got, want in (("q5", got5, base5), ("q72", got72,
                                                  base72)):
        for i, (g, w) in enumerate(zip(got, want)):
            if np.asarray(g).tobytes() != np.asarray(w).tobytes():
                fail(f"{name} output {i} not byte-identical with "
                     f"stats armed")

    s5 = obs.STATS.last("q5_partials")
    s72 = obs.STATS.last("q72_partials")
    if s5 is None or s72 is None:
        fail("armed fused runs produced no per-stage stats section")

    j1 = pair_total(d5.s_date, d5.d_date)
    j2 = pair_total(d5.r_date, d5.d_date)
    jq72 = pair_total(d72.cs_item, d72.inv_item)
    keep = q72_keep_count(d72)
    checks = [
        ("q5_partials", s5, "input:s", len(np.asarray(d5.s_date))),
        ("q5_partials", s5, "input:r", len(np.asarray(d5.r_date))),
        ("q5_partials", s5, "input:d", len(np.asarray(d5.d_date))),
        ("q5_partials", s5, "j1", j1),
        ("q5_partials", s5, "j2", j2),
        ("q5_partials", s5, "of", 0),
        ("q72_partials", s72, "j", jq72),
        ("q72_partials", s72, "keep", keep),
        ("q72_partials", s72, "of", 0),
    ]
    for stage, sec, node, want in checks:
        got = node_rows(sec, node)
        if got != want:
            fail(f"{stage} node {node!r}: observed rows {got} != "
                 f"numpy recompute {want}")
    # est side: every scan input carries its catalog estimate
    for sec, inputs in ((s5, ("s", "r", "d")),
                        (s72, ("cs", "inv", "dim"))):
        for name in inputs:
            n = next(x for x in sec["nodes"]
                     if x["node"] == f"input:{name}")
            if n.get("est") != n["rows"] or \
                    n.get("est_origin") != "catalog":
                fail(f"input:{name} est {n.get('est')!r} "
                     f"(origin {n.get('est_origin')!r}) does not "
                     f"match observed {n['rows']}")
    fam = obs.METRICS.snapshot().get(
        "srt_stats_observations_total") or {}
    obs_total = sum(s["value"] for s in fam.get("series", []))
    if obs_total < len(checks):
        fail(f"srt_stats_observations_total {obs_total} < "
             f"{len(checks)} reconciled nodes")
    say(f"reconciliation OK: {len(checks)} per-node actuals exact "
        f"(q5 j1={j1} j2={j2}; q72 pairs={jq72} keep={keep}), "
        f"byte-identical to the stats-off baseline")

    # ---- second same-bucket run: ZERO new executables --------------
    compiles_mid = CACHE.stats()["compiles"]
    C.run_q5(d5, Q5_STORES, Q5_CAP)
    C.run_q72(d72, Q72_ITEMS, Q72_MAX_WEEK, Q72_CAP, week0=WEEK0)
    if CACHE.stats()["compiles"] != compiles_mid:
        fail(f"second same-bucket armed run compiled "
             f"{CACHE.stats()['compiles'] - compiles_mid} new "
             f"executables (want 0)")
    say(f"compile discipline OK: tapped stages cached "
        f"({compiles_mid - compiles_before} tap builds on first "
        f"armed run, 0 on repeat)")

    # ---- seeded 100x misestimate: the full sentinel chain ----------
    bundles = os.path.join(tmp, "incidents")
    # rate limit OFF so the exactly-one assertion tests the sentinel's
    # own first-detection-per-node dedup, not the recorder throttle
    obs.enable_flight_recorder(out_dir=bundles, max_bytes=8 << 20,
                               min_interval_s=0.0)
    obs.STATS.register_estimate("q5_partials", "j1", j1 * 100,
                                origin="seeded")
    C.run_q5(d5, Q5_STORES, Q5_CAP)
    C.run_q5(d5, Q5_STORES, Q5_CAP)   # repeat must NOT add a bundle
    incidents = [i for i in obs.FLIGHT.incident_list()
                 if i["kind"] == "cardinality_misestimate"]
    if len(incidents) != 1:
        fail(f"expected exactly ONE cardinality_misestimate bundle, "
             f"found {len(incidents)}")
    events = [e for e in obs.JOURNAL.records()
              if e.get("kind") == "cardinality_misestimate"]
    if not events or events[-1].get("node") != "j1":
        fail(f"journal carries no cardinality_misestimate event "
             f"naming j1: {events}")
    fam = obs.METRICS.snapshot().get(
        "srt_stats_misestimate_total") or {}
    mseries = {tuple(s["labels"]): s["value"]
               for s in fam.get("series", [])}
    if mseries.get(("q5_partials", "j1"), 0) < 2:
        fail(f"srt_stats_misestimate_total missing the repeat "
             f"detections: {mseries}")
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = doctor.main([incidents[0]["path"]])
    report = buf.getvalue()
    print(report)
    if rc != 0:
        fail(f"srt-doctor exited {rc} on the misestimate bundle")
    for needle, why in (("'j1'", "the misestimated node"),
                        ("q5_partials", "the stage"),
                        ("SPARK_RAPIDS_TPU_STATS_MISEST_RATIO",
                         "the threshold knob")):
        if needle not in report:
            fail(f"doctor diagnosis missing {why} ({needle!r})")
    say("sentinel OK: 1 bundle across 2 detections, journal + "
        "metric recorded, doctor names node j1")

    # ---- disabled-path budget --------------------------------------
    obs.disable_stats()
    ob = {"stage": "q5_partials", "inputs": [], "nodes": []}
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        obs.STATS.note_stage(ob)
    per_call_us = (time.perf_counter() - t0) / n * 1e6
    if per_call_us > 1.0:
        fail(f"disabled note_stage costs {per_call_us:.3f} us per "
             f"call (budget 1 us) — the noop fast path regressed")
    say(f"disabled-mode OK: {per_call_us:.3f} us per call")

    say(f"OK ({time.monotonic() - t_start:.1f}s): exact per-node "
        f"reconciliation, 0 recompiles on repeat, one-bundle "
        f"sentinel chain, noop-when-disabled")
    return 0


if __name__ == "__main__":
    sys.exit(main())
