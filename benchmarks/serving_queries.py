"""Serving benchmark: cold-compile vs warm-cache latency and throughput.

Drives a mixed TPC-H-style query stream through ``QueryService`` and
measures the properties the serving tier exists for:

  1. warm-cache latency ≥ 10× lower than cold-compile latency on the same
     stream (the plan + executable caches amortise parse/GYO/XLA work);
  2. repeated queries after same-bucket data growth trigger ZERO recompiles
     (shape bucketing + freq-masked padding), verified via cache counters;
  3. micro-batched throughput on a skewed request mix (dashboards repeat
     the same handful of fingerprints);
  4. cross-fingerprint fusion: a dashboard of N *distinct* queries whose
     plan DAGs overlap served via one ``submit_many`` must beat serving
     them individually on total XLA compiles AND wall-clock, with
     bitwise-identical answers per query;
  5. partial fusion across join shapes: a workload where every whole plan
     prefix is distinct (so PR 2's equal-prefix rule fuses nothing) must
     still fuse via shared subplans — gated on the ``partial_fusions`` and
     ``subplan_saved`` counters;
  6. cross-CALLER batch formation: N threads each submitting ONE query via
     ``submit_async`` land in one batching window, so the scheduler runs
     fewer fused compiles than there are requests or even distinct
     fingerprints, with answers bitwise-identical to serial ``submit``
     calls — and a malformed query in the window fails only its own
     future while every valid batch-mate is still answered;
  7. RESTART warm start: two successive *processes* share a ``cache_dir``.
     The first (cold) persists every plan, XLA executable, and table
     statistic; the second (warm) must answer the same query mix with
     ZERO plan rebuilds (``plan_builds == 0``, ``persist_hits`` ==
     distinct fingerprints), ZERO statistics recomputes
     (``stat_refreshes == 0``), a gating-decision trace identical to the
     cold process's, bitwise-identical answers, and — in the timed run —
     a lower startup-to-answers wall-clock than the cold process.

    PYTHONPATH=src python benchmarks/serving_queries.py [--tiny] [--smoke]

  8. OBSERVABILITY overhead: the same warm query mix through a traced
     and an untraced (``tracing=False``) service must produce bitwise
     identical answers, and tracing's warm hot-path cost must stay ≤ 3%
     (plus a small absolute floor, so micro-benchmark noise on tiny
     tables cannot flake the gate); the traced service's per-stage
     latency histograms (p50/p95/p99) feed the ``--record`` trajectory.

  9. MESH serving: a database 4× larger than any other scenario here,
     served by ``QueryService(mesh=...)`` on 8 devices (forced host
     devices in a subprocess).  Answers must be bitwise-identical to a
     single-device service padded to the same capacities (the
     ``min_bucket = n_shards × mesh_min_bucket`` identity), individually
     AND fused; within-bucket per-shard growth must cause zero
     recompiles; and a warm restart over the shared ``cache_dir`` must
     re-plan nothing (``plan_builds == 0``) — the same serving
     guarantees, one graph interpreter, beyond one device.

 10. MIS-FUSION gate: a cheap 3-way lookup whose op DAG overlaps two
     expensive 5-way dashboards.  Overlap grouping alone would fuse all
     three, so every lookup pays the dashboards' latency; the default
     cost-calibrated admission must band the lookup out
     (``fusion_cost_rejects``) while still fusing the two dashboards,
     its p95 engine-measured serve time must beat the ungated
     ``fusion_disparity=float("inf")`` baseline, answers must stay
     bitwise-identical, and a forced serve-time regression on the fused
     pair must demote it on the very next batch (``fusion_demotions``).

``--smoke`` runs only the fused-batching + mixed-shape + async +
mis-fusion + restart + observability + mesh scenarios on tiny tables and
asserts cache/fusion/scheduler/persistence/calibration counters and
answer identity (plus the tracing overhead and mis-fusion p95 gates) —
what ``scripts/verify.sh --smoke`` runs so serving regressions fail CI
fast.  ``--record [PATH]`` writes a
schema-versioned ``BENCH_serving.json`` (rows + per-stage histogram
snapshots + counters; validated by ``python -m benchmarks.recorder``).

Every gate here counts, compares answers, or times XLA's CPU backend, so
the script runs on the CPU only: it sets ``jax_platforms="cpu"`` before
any backend starts, and its child processes inherit
``JAX_PLATFORMS=cpu``.  No process it starts ever opens a chip; device
numbers come from ``chip_smoke.py`` and the chip benchmark.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import jax
import numpy as np

# run both as `python benchmarks/serving_queries.py` (script dir on
# sys.path, repo root not) and as `python -m benchmarks.serving_queries`
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.data import make_tpch_db
from repro.service import (QueryService, TenantAdmissionError, TenantPolicy)
from repro.tables.table import Table, bucket_capacity

FIG1 = """
SELECT MIN(s.s_acctbal), MAX(s.s_acctbal)
FROM region r, nation n, supplier s, partsupp ps, part p
WHERE r.r_regionkey = n.n_regionkey AND n.n_nationkey = s.s_nationkey
  AND s.s_suppkey = ps.ps_suppkey AND ps.ps_partkey = p.p_partkey
  AND r.r_name IN (2, 3) AND p.p_price > 1200.0
"""
FIG1_RENAMED = """
SELECT MAX(su.s_acctbal), MIN(su.s_acctbal)
FROM part pa, supplier su, region re, partsupp pp, nation na
WHERE pa.p_price > 1200.0 AND na.n_nationkey = su.s_nationkey
  AND re.r_regionkey = na.n_regionkey AND pp.ps_partkey = pa.p_partkey
  AND su.s_suppkey = pp.ps_suppkey AND re.r_name IN (3, 2)
"""
FIG1_MEDIAN = """
SELECT MEDIAN(s.s_acctbal)
FROM region r, nation n, supplier s, partsupp ps, part p
WHERE r.r_regionkey = n.n_regionkey AND n.n_nationkey = s.s_nationkey
  AND s.s_suppkey = ps.ps_suppkey AND ps.ps_partkey = p.p_partkey
  AND r.r_name IN (0, 1) AND p.p_price > 800.0
"""
SUPP_BY_NATION = """
SELECT COUNT(*) AS suppliers, AVG(s.s_acctbal) AS avg_bal
FROM supplier s, nation n
WHERE s.s_nationkey = n.n_nationkey
GROUP BY s.s_nationkey
"""
# grouping by a nation attribute spreads the output vars over two atoms →
# unguarded → served by the eager fallback (reported separately; its cost
# never amortises, which is the point of the comparison)
SUPP_BY_REGION_EAGER = """
SELECT COUNT(*) AS suppliers, AVG(s.s_acctbal) AS avg_bal
FROM supplier s, nation n
WHERE s.s_nationkey = n.n_nationkey
GROUP BY n.n_regionkey
"""
COSTLY_PARTS = """
SELECT SUM(ps.ps_supplycost), COUNT(*)
FROM partsupp ps, part p
WHERE ps.ps_partkey = p.p_partkey AND p.p_price > 1500.0
"""

# (name, sql) — all jittable; FIG1_RENAMED shares FIG1's fingerprint
DISTINCT_QUERIES = [
    ("fig1-minmax", FIG1),
    ("fig1-median", FIG1_MEDIAN),
    ("supp-by-nation", SUPP_BY_NATION),
    ("costly-parts", COSTLY_PARTS),
]

# ---- mixed dashboard workload (cross-fingerprint fusion) -------------------
# N distinct queries over shared dimension joins.  Family A: four aggregates
# over supplier⋈nation⋈region with identical selections (one shared
# semi-join prefix); family B: two over partsupp⋈part (a second prefix);
# plus the 5-way FIG1, whose join shape matches nobody but whose DAG
# overlaps family A on the filtered region scan + nation/supplier semi-join
# chain.  Subplan-overlap grouping therefore fuses {A ∪ FIG1} and {B}:
# 2 compiles instead of 7, with the A∪FIG1 program counted as a *partial*
# fusion (its members do not share one whole prefix).
_SUPP_DIMS = """FROM supplier s, nation n, region r
WHERE s.s_nationkey = n.n_nationkey AND n.n_regionkey = r.r_regionkey
  AND r.r_name IN (2, 3)"""
_PART_DIMS = """FROM partsupp ps, part p
WHERE ps.ps_partkey = p.p_partkey AND p.p_price > 1500.0"""
DASHBOARD_QUERIES = [
    ("dash-minmax", f"SELECT MIN(s.s_acctbal), MAX(s.s_acctbal) {_SUPP_DIMS}"),
    ("dash-sum", f"SELECT SUM(s.s_acctbal) {_SUPP_DIMS}"),
    ("dash-by-nation", "SELECT COUNT(*) AS suppliers, AVG(s.s_acctbal) AS "
                       f"avg_bal {_SUPP_DIMS} GROUP BY s.s_nationkey"),
    ("dash-median", f"SELECT MEDIAN(s.s_acctbal) {_SUPP_DIMS}"),
    ("dash-supplycost", f"SELECT SUM(ps.ps_supplycost), COUNT(*) {_PART_DIMS}"),
    ("dash-by-supp", "SELECT AVG(ps.ps_supplycost) AS avg_cost "
                     f"{_PART_DIMS} GROUP BY ps.ps_suppkey"),
    ("dash-fig1", FIG1),
]
DASHBOARD_FUSION_SETS = 2     # {A-family ∪ FIG1}, {B-family}
DASHBOARD_FUSED_PROGRAMS = 2  # fusion sets with ≥ 2 members
DASHBOARD_FUSED_QUERIES = 7   # members of the two multi-query programs

# ---- mixed-JOIN-SHAPE dashboard (partial fusion) ---------------------------
# Four queries whose whole plan prefixes are pairwise DISTINCT — under
# PR 2's equal-prefix rule nothing here fuses, ever — but whose op DAGs
# overlap: the 3/4/5-way queries share the filtered region scan and the
# nation/supplier semi-join chain, and the 2-way query shares the filtered
# part scan + partsupp semi-join with the 5-way.  Overlap grouping is
# transitive, so the op-graph executor compiles ALL FOUR into one program.
MIX_3WAY = f"SELECT MIN(s.s_acctbal) {_SUPP_DIMS}"
MIX_4WAY = """SELECT MIN(s.s_acctbal), MAX(s.s_acctbal)
FROM supplier s, nation n, region r, partsupp ps
WHERE s.s_nationkey = n.n_nationkey AND n.n_regionkey = r.r_regionkey
  AND s.s_suppkey = ps.ps_suppkey AND r.r_name IN (2, 3)"""
MIX_2WAY = """SELECT SUM(ps.ps_supplycost) FROM partsupp ps, part p
WHERE ps.ps_partkey = p.p_partkey AND p.p_price > 1200.0"""
MIXED_SHAPE_QUERIES = [
    ("mix-3way", MIX_3WAY),
    ("mix-4way", MIX_4WAY),
    ("mix-5way", FIG1),
    ("mix-2way", MIX_2WAY),
]

# ---- MIS-FUSION workload (cost-calibrated admission + feedback) ------------
# A cheap 3-way lookup whose op DAG overlaps two expensive 5-way dashboards
# (shared filtered-region scan + nation/supplier semi-join chain).  Overlap
# grouping alone would fuse all three into ONE program, so every lookup
# would pay the 5-way program's latency — the mis-fusion the cost gate
# exists to prevent.  The gated (default) service must band the lookup out
# (``fusion_cost_rejects``) while still fusing the two bigs; the ungated
# baseline (``fusion_disparity=float("inf")``) fuses everything.
FIG1_SUM = """
SELECT SUM(s.s_acctbal)
FROM region r, nation n, supplier s, partsupp ps, part p
WHERE r.r_regionkey = n.n_regionkey AND n.n_nationkey = s.s_nationkey
  AND s.s_suppkey = ps.ps_suppkey AND ps.ps_partkey = p.p_partkey
  AND r.r_name IN (2, 3) AND p.p_price > 1200.0
"""
MISFUSION_QUERIES = [
    ("small-lookup", f"SELECT COUNT(*) {_SUPP_DIMS}"),
    ("big-minmax", FIG1),
    ("big-sum", FIG1_SUM),
]


def _values_equal(a: dict, b: dict) -> bool:
    """Bitwise equality of two QueryResult.values dicts."""
    if set(a) != set(b):
        return False
    for k, va in a.items():
        vb = b[k]
        if k == "groups":
            if set(va) != set(vb) or any(
                    not np.array_equal(np.asarray(va[c]), np.asarray(vb[c]))
                    for c in va):
                return False
        elif not np.array_equal(np.asarray(va), np.asarray(vb)):
            return False
    return True


def _grow_within_bucket(db: dict[str, Table], rel: str, seed: int = 0):
    """New-rows copy of `rel` grown to exactly its current shape bucket."""
    tab = db[rel]
    bucket = bucket_capacity(tab.capacity)
    extra = bucket - tab.capacity
    if extra == 0:
        return None, 0
    rng = np.random.default_rng(seed)
    cols = {}
    for name, col in tab.columns.items():
        base = np.asarray(col)
        new = base[rng.integers(0, len(base), extra)]  # resample real rows
        cols[name] = np.concatenate([base, new])
    return Table.from_numpy(cols), extra


def run(scale: int = 1000, warm_iters: int = 25, seed: int = 0):
    db, schema = make_tpch_db(scale=scale, seed=seed)
    svc = QueryService(db, schema)
    report: dict = {"scale": scale}

    # ---- cold pass: first sight of each fingerprint (parse+plan+compile)
    cold = {}
    for name, sql in DISTINCT_QUERIES:
        t0 = time.perf_counter()
        svc.submit(sql)
        cold[name] = time.perf_counter() - t0
    report["cold_s"] = cold

    # ---- warm pass: mixed stream over the same fingerprints -------------
    stream = []
    for i in range(warm_iters):
        stream.append(DISTINCT_QUERIES[i % len(DISTINCT_QUERIES)])
        if i % 3 == 0:
            # alias-renamed → same fingerprint as fig1-minmax
            stream.append(("fig1-minmax", FIG1_RENAMED))
    lat: list[float] = []
    per_query: dict[str, list[float]] = {}
    t_stream = time.perf_counter()
    for name, sql in stream:
        t0 = time.perf_counter()
        svc.submit(sql)
        dt = time.perf_counter() - t0
        lat.append(dt)
        per_query.setdefault(name, []).append(dt)
    stream_s = time.perf_counter() - t_stream
    report["warm_median_s"] = float(np.median(lat))
    report["warm_p99_s"] = float(np.percentile(lat, 99))
    report["throughput_qps"] = len(stream) / stream_s
    # per-fingerprint amortisation: this query's cold (parse+plan+compile+
    # run) over its own warm median (run only)
    report["speedup_per_query"] = {
        name: cold[name] / float(np.median(ts))
        for name, ts in per_query.items()}
    report["speedup"] = min(report["speedup_per_query"].values())

    # ---- micro-batched throughput (skewed mix, one submit_many call) ----
    batch = [FIG1, FIG1_RENAMED] * 8 + [SUPP_BY_NATION] * 4
    t0 = time.perf_counter()
    svc.submit_many(batch)
    report["batched_qps"] = len(batch) / (time.perf_counter() - t0)

    # ---- eager fallback (unguarded plan), for contrast -----------------
    t0 = time.perf_counter()
    r = svc.submit(SUPP_BY_REGION_EAGER)
    report["eager_s"] = time.perf_counter() - t0
    report["eager_mode"] = r.stats.mode

    # ---- growth inside the shape bucket: zero recompiles ----------------
    compiles_before = svc.metrics()["compiles"]
    grown, extra = _grow_within_bucket(db, "partsupp", seed=seed + 1)
    if grown is not None:
        svc.update_table("partsupp", grown)
    for sql in (FIG1, FIG1_MEDIAN, COSTLY_PARTS):
        svc.submit(sql)
    m = svc.metrics()
    report["growth_rows"] = extra
    report["growth_recompiles"] = m["compiles"] - compiles_before
    report["metrics"] = m
    return report


def run_fused(scale: int = 1000, repeats: int = 3, seed: int = 0):
    """Mixed dashboard workload: N distinct prefix-sharing queries, served
    individually vs via fused ``submit_many``.  Returns walls, compile
    counts, per-query identity, and the fused service's metrics."""
    db, schema = make_tpch_db(scale=scale, seed=seed)
    sqls = [sql for _, sql in DASHBOARD_QUERIES]

    svc_solo = QueryService(db, schema)
    t0 = time.perf_counter()
    for _ in range(repeats):
        solo = [svc_solo.submit(sql) for sql in sqls]
    solo_s = time.perf_counter() - t0

    # disparity=inf: this scenario pins the fusion MACHINERY (grouping,
    # partial fusion, subplan dedup, the fused cache) on a deliberately
    # cost-disparate mix; admission POLICY is the mis-fusion scenario's job
    svc_fused = QueryService(db, schema, fusion_disparity=float("inf"))
    t0 = time.perf_counter()
    for _ in range(repeats):
        fused = svc_fused.submit_many(sqls)
    fused_s = time.perf_counter() - t0

    identical = all(_values_equal(a.values, b.values)
                    for a, b in zip(solo, fused))
    return {
        "queries": len(sqls),
        "repeats": repeats,
        "solo_s": solo_s,
        "fused_s": fused_s,
        "solo_compiles": svc_solo.metrics()["compiles"],
        "fused_compiles": svc_fused.metrics()["compiles"],
        "identical": identical,
        "fused_metrics": svc_fused.metrics(),
    }


def check_fused(rf: dict) -> list[str]:
    """Gate the fused scenario's counters + identity; returns failures."""
    fails = []
    m = rf["fused_metrics"]
    if not rf["identical"]:
        fails.append("fused answers differ from individual serving")
    if rf["fused_compiles"] >= rf["solo_compiles"]:
        fails.append(f"fused used {rf['fused_compiles']} compiles, "
                     f"individual used {rf['solo_compiles']}")
    if rf["fused_compiles"] != DASHBOARD_FUSION_SETS:
        fails.append(f"expected {DASHBOARD_FUSION_SETS} fused-path "
                     f"compiles, got {rf['fused_compiles']}")
    if m["fused_queries"] != rf["repeats"] * DASHBOARD_FUSED_QUERIES:
        fails.append(f"fused_queries={m['fused_queries']} != "
                     f"{rf['repeats']} × {DASHBOARD_FUSED_QUERIES}")
    if m["fused_hits"] < (rf["repeats"] - 1) * DASHBOARD_FUSED_PROGRAMS:
        fails.append(f"fused executable cache hits {m['fused_hits']} — "
                     "repeat dashboards are not reusing fused programs")
    if m["partial_fusions"] < rf["repeats"]:
        fails.append(f"partial_fusions={m['partial_fusions']} — FIG1 is "
                     "not being fused into the A-family program")
    if m["subplan_saved"] <= 0:
        fails.append("subplan_saved=0 — the fused trace memo deduped "
                     "nothing")
    return fails


def run_mixed(scale: int = 1000, repeats: int = 3, seed: int = 0):
    """Mixed-JOIN-SHAPE dashboard: whole-prefix fusion (PR 2's rule) finds
    zero fusable pairs here, the op-graph executor fuses everything.
    Served individually vs via ``submit_many``; returns walls, compile
    counts, identity, whole-prefix diversity, and fused metrics."""
    from repro.core import plan_query, segment_plan
    from repro.service import canonicalize
    from repro.core.sql import parse_sql

    db, schema = make_tpch_db(scale=scale, seed=seed)
    sqls = [sql for _, sql in MIXED_SHAPE_QUERIES]

    # document the premise: every member has a DIFFERENT whole prefix
    prefixes = {
        segment_plan(plan_query(canonicalize(parse_sql(s, schema)).query,
                                schema)).prefix_key for s in sqls}

    svc_solo = QueryService(db, schema)
    t0 = time.perf_counter()
    for _ in range(repeats):
        solo = [svc_solo.submit(sql) for sql in sqls]
    solo_s = time.perf_counter() - t0

    # disparity=inf, as in run_fused: partial fusion across join shapes is
    # machinery; whether these four SHOULD fuse is the admission gate's
    # call, exercised by the mis-fusion scenario
    svc_fused = QueryService(db, schema, fusion_disparity=float("inf"))
    t0 = time.perf_counter()
    for _ in range(repeats):
        fused = svc_fused.submit_many(sqls)
    fused_s = time.perf_counter() - t0

    identical = all(_values_equal(a.values, b.values)
                    for a, b in zip(solo, fused))
    return {
        "queries": len(sqls),
        "repeats": repeats,
        "distinct_prefixes": len(prefixes),
        "solo_s": solo_s,
        "fused_s": fused_s,
        "solo_compiles": svc_solo.metrics()["compiles"],
        "fused_compiles": svc_fused.metrics()["compiles"],
        "identical": identical,
        "fused_metrics": svc_fused.metrics(),
    }


def check_mixed(rm: dict) -> list[str]:
    """Gate the mixed-shape scenario; returns failures."""
    fails = []
    m = rm["fused_metrics"]
    if rm["distinct_prefixes"] != rm["queries"]:
        fails.append(f"premise broken: {rm['distinct_prefixes']} distinct "
                     f"prefixes over {rm['queries']} queries — whole-prefix "
                     "fusion would not be zero here")
    if not rm["identical"]:
        fails.append("mixed-shape fused answers differ from individual "
                     "serving")
    if rm["fused_compiles"] >= rm["solo_compiles"]:
        fails.append(f"mixed-shape fused used {rm['fused_compiles']} "
                     f"compiles, individual used {rm['solo_compiles']}")
    if m["partial_fusions"] < rm["repeats"]:
        fails.append(f"partial_fusions={m['partial_fusions']} < "
                     f"{rm['repeats']} — different join shapes not fusing")
    if m["subplan_saved"] <= 0:
        fails.append("subplan_saved=0 on the mixed-shape workload")
    return fails


def run_async(scale: int = 1000, threads: int = 8, seed: int = 0):
    """Concurrent-callers scenario: `threads` independent threads each
    submit ONE query from the shared-subplan dashboard via
    ``submit_async``.  The background batcher forms the batch, so the
    requests fuse exactly as a single ``submit_many`` caller's would —
    fewer compiles than requests — and answers are bitwise-identical to
    serial ``submit`` calls.  A follow-up window co-batches a malformed
    query with a valid one to show per-request fault isolation."""
    db, schema = make_tpch_db(scale=scale, seed=seed)
    sqls = [sql for _, sql in DASHBOARD_QUERIES]
    work = [sqls[i % len(sqls)] for i in range(threads)]

    svc_serial = QueryService(db, schema)
    t0 = time.perf_counter()
    serial = [svc_serial.submit(sql) for sql in work]
    serial_s = time.perf_counter() - t0

    # a wide formation window: the barrier releases all threads at once,
    # so one window captures every caller deterministically
    svc = QueryService(db, schema, async_max_wait_ms=500,
                       async_max_batch=max(64, threads))
    barrier = threading.Barrier(threads)
    futs: list = [None] * threads

    def caller(i):
        barrier.wait()
        futs[i] = svc.submit_async(work[i])

    callers = [threading.Thread(target=caller, args=(i,))
               for i in range(threads)]
    t0 = time.perf_counter()
    for t in callers:
        t.start()
    for t in callers:
        t.join()
    results = [f.result(300) for f in futs]
    async_s = time.perf_counter() - t0

    identical = all(r.error is None and _values_equal(a.values, r.values)
                    for a, r in zip(serial, results))

    # fault isolation across callers: a malformed query co-batched with a
    # valid one must fail alone
    bad_fut = svc.submit_async("SELECT MIN(x.nope) FROM no_such_relation x")
    good_fut = svc.submit_async(sqls[0])
    bad_error = bad_fut.exception(300)
    good_res = good_fut.result(300)
    good_ok = (good_res.error is None
               and _values_equal(good_res.values, serial[0].values))

    m = svc.metrics()
    svc.close()
    return {
        "threads": threads,
        "distinct": len(set(work)),
        "serial_s": serial_s,
        "async_s": async_s,
        "identical": identical,
        "bad_error": bad_error,
        "good_ok": good_ok,
        "serial_compiles": svc_serial.metrics()["compiles"],
        "metrics": m,
    }


def check_async(ra: dict) -> list[str]:
    """Gate the concurrent-callers scenario; returns failures."""
    fails = []
    m = ra["metrics"]
    if not ra["identical"]:
        fails.append("async answers differ from serial submit calls")
    if m["async_batches"] < 1:
        fails.append("async_batches=0 — the background batcher never ran")
    if m["async_requests"] < ra["threads"]:
        fails.append(f"async_requests={m['async_requests']} < "
                     f"{ra['threads']} submitted")
    if m["fused_compiles"] >= ra["distinct"]:
        fails.append(f"fused_compiles={m['fused_compiles']} not below "
                     f"{ra['distinct']} distinct fingerprints — "
                     "cross-caller batch formation is not fusing")
    if m["compiles"] >= ra["threads"]:
        fails.append(f"compiles={m['compiles']} >= {ra['threads']} "
                     "requests — no cross-caller amortisation")
    if ra["bad_error"] is None:
        fails.append("malformed query's future did not carry its error")
    if not ra["good_ok"]:
        fails.append("valid batch-mate of the malformed query was not "
                     "answered correctly")
    if m["request_errors"] != 1:
        fails.append(f"request_errors={m['request_errors']} != 1")
    if m["rejected"] != 0:
        fails.append(f"rejected={m['rejected']} — queue backpressure "
                     "tripped on an idle-sized workload")
    return fails


# ---- multi-tenant fair admission (adversarial mix) -------------------------
# The victim's client-measured p95 (submit → future resolution, exact
# wall-clock — NOT the log-bucketed histogram p95, whose ~33%/bucket
# quantisation would dominate a 2× comparison) under flood must stay
# within 2× its solo baseline; the absolute floor absorbs tiny-table
# noise on a shared box.  The per-tenant histograms still gate
# presence/shape via the metrics_v2()["tenants"] breakdown.
MT_VICTIM_P95_BOUND = 2.0
MT_VICTIM_P95_FLOOR_S = 0.05


def run_multitenant(scale: int = 1000, rounds: int = 6, seed: int = 0):
    """Adversarial tenant mix: one tenant floods malformed + oversized
    (largest-tables join) queries under a tight token-bucket quota while
    a victim tenant serves its dashboard.  The quota + per-tenant queues + DRR
    keep the victim's engine-measured p95 near its solo baseline and its
    answers bitwise-identical; a second window shows N tenants firing
    the same dashboard share ONE fused program (fused compiles <
    distinct requests across tenants) while accounting stays per-tenant."""
    db, schema = make_tpch_db(scale=scale, seed=seed)
    victim_sqls = [sql for _, sql in DASHBOARD_QUERIES[:4]]  # A-family
    # oversized: the B-family scan over the two LARGEST tables
    # (partsupp⋈part) — structurally disjoint from the victim's
    # supplier⋈nation⋈region dashboards, so union-find never groups the
    # flood with the victim and every window composition the flood
    # creates reuses warmed signatures (the fairness gate then measures
    # scheduling, not compile-on-novel-composition transients; fusing
    # ACROSS tenants is gated by the 4-tenant window below)
    flood_big = DASHBOARD_QUERIES[4][1]
    flood_bad = "SELECT MIN(x.nope) FROM no_such_relation x"

    svc0 = QueryService(db, schema)
    baseline = [svc0.submit(q) for q in victim_sqls]

    tenants = {
        "victim": TenantPolicy(weight=2.0, priority=0),
        "flood": TenantPolicy(rate=50.0, burst=8, max_queue=16,
                              priority=1),
    }

    def new_service():
        return QueryService(db, schema, async_max_wait_ms=5,
                            async_max_batch=64, tenants=tenants)

    def warm(svc):
        # warm every plan/executable so both runs measure the warm path
        # (cold compiles would swamp the fairness comparison)
        for q in victim_sqls + [flood_big]:
            svc.submit(q)
        # ...including every FUSED composition a formation window can
        # produce — a fused-program signature is a new executable even
        # when every member plan is warm.  Window splits form subsets of
        # the dashboard, and the serve-time feedback loop can demote a
        # member mid-stream and re-group the REMAINDER into a novel
        # signature (e.g. {v1,v2,v4} after v3 demotes), so compile every
        # ≥2-member subset once up front; the flood query is
        # structurally disjoint and always serves in its own singleton
        # group, so it adds no compositions
        for k in range(2, len(victim_sqls) + 1):
            for combo in itertools.combinations(victim_sqls, k):
                svc.submit_many(list(combo))
        # then drive the calibrator to its steady state on the measured
        # compositions: stop once two consecutive passes serve purely
        # from caches — the fairness gate must time the steady state,
        # not the calibration transient
        quiet = 0
        for _ in range(25):
            rs = (svc.submit_many(victim_sqls)
                  + svc.submit_many(victim_sqls + [flood_big]))
            cached = all(r.stats.exec_source in ("exec_cache",
                                                 "fused_cache")
                         for r in rs)
            quiet = quiet + 1 if cached else 0
            if quiet >= 2:
                break

    def victim_rounds(svc):
        out, lats = [], []
        for _ in range(rounds):
            futs = []
            for q in victim_sqls:
                t0 = time.perf_counter()
                f = svc.submit_async(q, tenant="victim")
                f.add_done_callback(
                    lambda _f, t0=t0: lats.append(time.perf_counter() - t0))
                futs.append(f)
            out.append([f.result(300) for f in futs])
        return out, lats

    # solo baseline: the victim alone on an identically-configured service
    svc_solo = new_service()
    warm(svc_solo)
    solo_results, solo_lats = victim_rounds(svc_solo)
    svc_solo.close(timeout=300)

    # adversarial mix: the flooder hammers as fast as it can; its quota
    # (not the victim's latency) is what bounds what gets through
    svc = new_service()
    warm(svc)
    stop = threading.Event()
    flood = {"submitted": 0, "rejected_rate": 0, "rejected_depth": 0}

    def flooder():
        i = 0
        while not stop.is_set():
            q = flood_bad if i % 2 == 0 else flood_big
            i += 1
            flood["submitted"] += 1
            try:
                svc.submit_async(q, tenant="flood")
            except TenantAdmissionError as e:
                flood[f"rejected_{e.kind}"] += 1
            time.sleep(0.0005)

    th = threading.Thread(target=flooder)
    th.start()
    mixed_results, mixed_lats = victim_rounds(svc)
    stop.set()
    th.join(30)
    svc.close(timeout=300)             # drain the flooder's leftovers
    v2 = svc.metrics_v2()

    victim_identical = all(
        r.error is None and _values_equal(b.values, r.values)
        for rnd in (solo_results, mixed_results) for row in rnd
        for b, r in zip(baseline, row))

    # cross-tenant fusion: 4 tenants × the same 2-query dashboard in one
    # formation window → one fused program, per-tenant accounting
    xt_tenants = [f"t{i}" for i in range(4)]
    xt_sqls = [sql for _, sql in DASHBOARD_QUERIES[:2]]
    svc_x = QueryService(db, schema, async_max_wait_ms=500,
                         async_max_batch=64)
    pairs = [(t, q) for t in xt_tenants for q in xt_sqls]
    barrier = threading.Barrier(len(pairs))
    xfuts: list = [None] * len(pairs)

    def xcaller(i):
        barrier.wait()
        xfuts[i] = svc_x.submit_async(pairs[i][1], tenant=pairs[i][0])

    xthreads = [threading.Thread(target=xcaller, args=(i,))
                for i in range(len(pairs))]
    for t in xthreads:
        t.start()
    for t in xthreads:
        t.join()
    xres = [f.result(300) for f in xfuts]
    x_identical = all(
        r.error is None and _values_equal(baseline[j % 2].values, r.values)
        for j, r in enumerate(xres))
    xv2 = svc_x.metrics_v2()
    svc_x.close()

    return {
        "rounds": rounds,
        "victim_queries": len(victim_sqls),
        "solo_p95_s": float(np.percentile(solo_lats, 95)),
        "mixed_p95_s": float(np.percentile(mixed_lats, 95)),
        "victim_identical": victim_identical,
        "flood_client": flood,
        "tenants": v2["tenants"],
        "metrics": {**v2["counters"], **v2["gauges"]},
        "xt_requests": len(pairs),
        "xt_distinct": len(xt_sqls),
        "xt_identical": x_identical,
        "xt_tenants": xv2["tenants"],
        "xt_metrics": {**xv2["counters"], **xv2["gauges"]},
    }


def check_multitenant(rt: dict) -> list[str]:
    """Gate the adversarial-mix scenario; returns failures."""
    fails = []
    vt = rt["tenants"].get("victim", {})
    ft = rt["tenants"].get("flood", {})
    # per-tenant counters/histograms must be present and populated
    for name, t in (("victim", vt), ("flood", ft)):
        for k in ("requests", "rejected", "fused_share", "p50_s", "p95_s",
                  "p99_s"):
            if k not in t:
                fails.append(f"metrics_v2()['tenants'][{name!r}] missing "
                             f"{k!r}")
    expected = rt["rounds"] * rt["victim_queries"]
    if vt.get("requests", 0) != expected:
        fails.append(f"victim served {vt.get('requests')} != {expected} "
                     "submitted")
    if vt.get("errors", 0) != 0:
        fails.append(f"victim errors={vt.get('errors')} — flood damage "
                     "leaked across tenants")
    if not rt["victim_identical"]:
        fails.append("victim answers under flood differ from serial "
                     "submission")
    # the flooding tenant must be held back by ITS quota...
    if ft.get("rejected", 0) < 1:
        fails.append("flooding tenant was never rejected — per-tenant "
                     "quota is not enforcing")
    # ...while whatever it got admitted stayed isolated (malformed
    # queries fail alone, under the flooder's name)
    if ft.get("errors", 0) < 1:
        fails.append("no flood error captured — malformed queries were "
                     "not served/isolated under the flooder's tenant")
    bound = (MT_VICTIM_P95_BOUND * rt["solo_p95_s"]
             + MT_VICTIM_P95_FLOOR_S)
    if rt["mixed_p95_s"] > bound:
        fails.append(f"victim p95 {rt['mixed_p95_s'] * 1e3:.1f} ms under "
                     f"flood exceeds {MT_VICTIM_P95_BOUND}x solo "
                     f"{rt['solo_p95_s'] * 1e3:.1f} ms (+ floor)")
    # cross-tenant fusion: N tenants × one dashboard = ONE program
    xm = rt["xt_metrics"]
    if not rt["xt_identical"]:
        fails.append("cross-tenant answers differ from serial submission")
    if xm["fused_compiles"] >= rt["xt_requests"]:
        fails.append(f"fused_compiles={xm['fused_compiles']} not below "
                     f"{rt['xt_requests']} distinct requests across "
                     "tenants")
    if xm["compiles"] > rt["xt_distinct"]:
        fails.append(f"compiles={xm['compiles']} > {rt['xt_distinct']} "
                     "distinct fingerprints — tenants are not sharing "
                     "programs")
    if xm["dedup_saved"] < rt["xt_requests"] - rt["xt_distinct"]:
        fails.append(f"dedup_saved={xm['dedup_saved']} — same-fingerprint "
                     "requests across tenants did not dedup")
    for t in ("t0", "t1", "t2", "t3"):
        if rt["xt_tenants"].get(t, {}).get("requests", 0) != 2:
            fails.append(f"tenant {t} accounting lost requests")
    if rt["metrics"].get("open_requests", 0) != 0:
        fails.append(f"open_requests={rt['metrics']['open_requests']} "
                     "after the mix — root spans leaked")
    return fails


# ---- observability overhead: traced vs untraced ----------------------------
TRACING_OVERHEAD_FRAC = 0.03     # the ≤ 3% warm hot-path budget
TRACING_OVERHEAD_FLOOR_S = 3e-4  # absolute noise floor for tiny tables


def run_misfusion(scale: int = 1000, repeats: int = 5, seed: int = 0):
    """Cost-gated fusion admission vs the ungated baseline, on a workload
    built to mis-fuse: one cheap lookup + two expensive dashboards whose
    DAGs overlap it.  Measures the lookup's engine-side serve time per
    round under both services (warm, compile excluded), then forces an
    observed regression on the fused big pair through the public feedback
    surface and re-serves — the next batch must demote it."""
    db, schema = make_tpch_db(scale=scale, seed=seed)
    sqls = [sql for _, sql in MISFUSION_QUERIES]

    gated = QueryService(db, schema)
    ungated = QueryService(db, schema, fusion_disparity=float("inf"))

    # warm both services (plans + XLA), then measure steady-state rounds
    gated.submit_many(sqls)
    u_first = ungated.submit_many(sqls)
    lookup_gated_s, lookup_ungated_s = [], []
    for _ in range(repeats):
        g = gated.submit_many(sqls)
        u = ungated.submit_many(sqls)
        lookup_gated_s.append(g[0].stats.run_s)
        lookup_ungated_s.append(u[0].stats.run_s)
    identical = all(_values_equal(a.values, b.values)
                    for a, b in zip(g, u))
    fa = gated.explain(sqls[0])["fusion_admission"]

    # forced regression: tell the feedback loop the fused big pair serves
    # far slower than its solo baseline; the NEXT batch must demote it
    big_fp = g[1].stats.fingerprint
    big_sig = gated.explain(sqls[1])["fusion_admission"]["signature"]
    gated.stats.observe_serve(big_fp, "", 1e-4)
    gated.stats.observe_serve(big_fp, big_sig, 1.0)
    gated.stats.observe_serve(big_fp, big_sig, 1.0)
    demoted = gated.submit_many(sqls)
    demoted_identical = all(_values_equal(a.values, b.values)
                            for a, b in zip(g, demoted))

    return {
        "queries": len(sqls),
        "repeats": repeats,
        "gated_p95_s": float(np.percentile(lookup_gated_s, 95)),
        "ungated_p95_s": float(np.percentile(lookup_ungated_s, 95)),
        "lookup_fused_gated": g[0].stats.fused,
        "lookup_fused_ungated": u_first[0].stats.fused,
        "bigs_fused_gated": g[1].stats.fused and g[2].stats.fused,
        "identical": identical,
        "rejection": fa,
        "bigs_fused_after_demotion": any(r.stats.fused for r in demoted),
        "demoted_identical": demoted_identical,
        "gated_metrics": gated.metrics(),
        "ungated_metrics": ungated.metrics(),
    }


def check_misfusion(rz: dict) -> list[str]:
    """Gate the mis-fusion scenario; returns failures.  The p95 gate runs
    in smoke too: it compares two ENGINE-measured warm serve times whose
    programs differ by orders of magnitude (3-way lookup vs 5-way fused
    dashboard), not wall-clock on a noisy box."""
    fails = []
    gm, um = rz["gated_metrics"], rz["ungated_metrics"]
    if rz["lookup_fused_gated"]:
        fails.append("cost gate OFF: the cheap lookup joined the 5-way "
                     "fusion group under the default disparity")
    if gm["fusion_cost_rejects"] < rz["repeats"]:
        fails.append(f"fusion_cost_rejects={gm['fusion_cost_rejects']} < "
                     f"{rz['repeats']} — the gate is not counting its "
                     "rejections")
    if not rz["bigs_fused_gated"]:
        fails.append("the two cost-compatible dashboards did not fuse "
                     "under the gate — banding is over-rejecting")
    if not rz["lookup_fused_ungated"]:
        fails.append("premise broken: the ungated baseline did not fuse "
                     "the lookup into the big program")
    if um["fusion_cost_rejects"] != 0:
        fails.append(f"ungated baseline counted "
                     f"{um['fusion_cost_rejects']} cost rejects — "
                     "disparity=inf must disable the gate")
    if not rz["identical"]:
        fails.append("gated answers differ from the ungated baseline — "
                     "admission policy must never change results")
    fa = rz["rejection"]
    if fa is None or fa.get("admitted") or "disparity" not in \
            str(fa.get("reason", "")):
        fails.append("explain() does not name the cost disparity for the "
                     f"rejected lookup (got {fa!r})")
    if rz["gated_p95_s"] >= rz["ungated_p95_s"]:
        fails.append(f"gated lookup p95 {rz['gated_p95_s'] * 1e3:.3f} ms "
                     f"not below ungated {rz['ungated_p95_s'] * 1e3:.3f} "
                     "ms — banding the lookup out bought nothing")
    if gm["fusion_demotions"] < 1:
        fails.append("forced serve-time regression did not demote the "
                     "fused pair (fusion_demotions=0)")
    if rz["bigs_fused_after_demotion"]:
        fails.append("demoted fusion signature was re-admitted on the "
                     "next batch")
    if not rz["demoted_identical"]:
        fails.append("answers changed after demotion — the feedback loop "
                     "must only re-route, never re-answer")
    return fails


def run_overhead(scale: int = 1000, iters: int = 30, seed: int = 0):
    """Warm hot-path cost of tracing: one traced and one untraced
    service, same query mix, interleaved measurement rounds (drift in
    either direction hits both populations equally).  Returns identity,
    medians, and the traced service's metrics_v2 snapshot — the
    per-stage histograms ``--record`` persists."""
    db, schema = make_tpch_db(scale=scale, seed=seed)
    svc_traced = QueryService(db, schema, tracing=True)
    svc_plain = QueryService(db, schema, tracing=False)
    sqls = [sql for _, sql in DISTINCT_QUERIES]
    answers = {}
    for svc in (svc_traced, svc_plain):          # cold pass: warm caches
        answers[id(svc)] = [svc.submit(sql).values for sql in sqls]
    identical = all(
        _values_equal(a, b) for a, b in zip(answers[id(svc_traced)],
                                            answers[id(svc_plain)]))

    lat = {id(svc_traced): [], id(svc_plain): []}
    for _ in range(iters):
        for svc in (svc_plain, svc_traced):      # interleaved rounds
            for sql in sqls:
                t0 = time.perf_counter()
                svc.submit(sql)
                lat[id(svc)].append(time.perf_counter() - t0)
    traced_s = float(np.median(lat[id(svc_traced)]))
    plain_s = float(np.median(lat[id(svc_plain)]))
    v2 = svc_traced.metrics_v2()
    return {
        "iters": iters,
        "identical": identical,
        "traced_median_s": traced_s,
        "untraced_median_s": plain_s,
        "overhead_frac": traced_s / plain_s - 1.0 if plain_s > 0 else 0.0,
        "histograms": v2["histograms"],
        "metrics": svc_traced.metrics(),
    }


def check_overhead(ro: dict) -> list[str]:
    """Gate the observability scenario: identity always; the overhead
    budget with an absolute floor so µs-level timer noise on tiny
    tables cannot flake CI."""
    fails = []
    if not ro["identical"]:
        fails.append("traced answers differ from tracing=False answers")
    budget = (ro["untraced_median_s"] * (1.0 + TRACING_OVERHEAD_FRAC)
              + TRACING_OVERHEAD_FLOOR_S)
    if ro["traced_median_s"] > budget:
        fails.append(
            f"tracing overhead: warm median {ro['traced_median_s'] * 1e3:.3f}"
            f" ms traced vs {ro['untraced_median_s'] * 1e3:.3f} ms untraced "
            f"(> {TRACING_OVERHEAD_FRAC:.0%} + "
            f"{TRACING_OVERHEAD_FLOOR_S * 1e3:.1f} ms floor)")
    for stage in ("parse", "plan", "pad", "compile", "run", "request"):
        h = ro["histograms"].get(stage)
        if h is None or h["count"] < 1:
            fails.append(f"traced service recorded no '{stage}' histogram")
        elif not all(k in h for k in ("p50_s", "p95_s", "p99_s")):
            fails.append(f"'{stage}' histogram lacks p50/p95/p99")
    return fails


# ---- restart scenario: cross-process warm start ----------------------------
# Two successive processes over one cache_dir: the cold child plans,
# compiles and persists; the warm child must serve the same mix from disk —
# zero plan rebuilds, XLA binaries from the persistent compilation cache,
# bitwise-identical answers.  Both phases run as real subprocesses so each
# starts with an empty in-process JAX executable cache (the thing
# persistence exists to survive).


def _encode_values(values: dict) -> dict:
    """QueryResult.values → a JSON-able, bitwise-comparable form."""
    def enc(v):
        a = np.asarray(v)
        return {"dtype": str(a.dtype), "shape": list(a.shape),
                "hex": a.tobytes().hex()}

    out = {}
    for k, v in values.items():
        out[k] = {c: enc(a) for c, a in v.items()} if k == "groups" \
            else enc(v)
    return out


def run_restart_child(cache_dir: str, scale: int, seed: int) -> dict:
    """One serving process's life: start, build the db, serve the distinct
    query mix once, report wall-clock + answers + metrics as JSON on
    stdout (the parent compares cold vs warm)."""
    t0 = time.perf_counter()
    db, schema = make_tpch_db(scale=scale, seed=seed)
    svc = QueryService(db, schema, cache_dir=cache_dir)
    answers = {}
    for name, sql in DISTINCT_QUERIES:
        answers[name] = _encode_values(svc.submit(sql).values)
    wall_s = time.perf_counter() - t0
    # gating-decision digest: the machine-readable planning trace per
    # query (explain re-serves from the warm caches — no extra builds).
    # Cold computes stats and persists them; warm must install the same
    # numbers from the store and reach every gate decision identically.
    decisions = {name: svc.explain(sql)["decisions"]
                 for name, sql in DISTINCT_QUERIES}
    m = svc.metrics()
    return {"wall_s": wall_s, "answers": answers,
            "decisions": decisions,
            "plan_builds": m["plan_builds"],
            "compiles": m["compiles"],
            "compile_s_total": m["compile_s_total"],
            "stat_refreshes": m["stat_refreshes"],
            "stats_persist_hits": m["stats_persist_hits"],
            "stats_persist_writes": m["stats_persist_writes"],
            "persist_hits": m["persist_hits"],
            "persist_misses": m["persist_misses"],
            "persist_writes": m["persist_writes"],
            "persist_corrupt_skipped": m["persist_corrupt_skipped"]}


def _spawn_restart_child(cache_dir: str, scale: int, seed: int) -> dict:
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    # the cold process must start with no compiled programs: give the
    # pair an XLA cache of their own, not the checkout's shared one
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache_dir, "xla")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--restart-child",
         cache_dir, "--scale", str(scale), "--seed", str(seed)],
        capture_output=True, text=True, env=env, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"restart child failed:\n{proc.stderr[-2000:]}")
    # the JSON report is the last non-empty stdout line (jax may chat above)
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run_restart(scale: int = 1000, seed: int = 0,
                cache_dir: str | None = None) -> dict:
    own_dir = cache_dir is None
    cache_dir = cache_dir or tempfile.mkdtemp(prefix="serving-warm-cache-")
    try:
        cold = _spawn_restart_child(cache_dir, scale, seed)
        warm = _spawn_restart_child(cache_dir, scale, seed)
    finally:
        if own_dir:          # plans + XLA binaries: don't accrete in /tmp
            shutil.rmtree(cache_dir, ignore_errors=True)
    return {"queries": len(DISTINCT_QUERIES), "cache_dir": cache_dir,
            "cold": cold, "warm": warm}


def check_restart(rr: dict) -> list[str]:
    """Gate the restart scenario's counters + identity; returns failures.
    (The compile-time and wall-clock gates are applied by the timed run
    only — smoke asserts no measured-time properties.)"""
    fails = []
    cold, warm = rr["cold"], rr["warm"]
    n = rr["queries"]
    if cold["persist_writes"] != n:
        fails.append(f"cold process persisted {cold['persist_writes']} "
                     f"plans, expected {n}")
    if warm["plan_builds"] != 0:
        fails.append(f"warm process rebuilt {warm['plan_builds']} plans — "
                     "the persistent store is not warm-starting planning")
    if warm["persist_hits"] != n:
        fails.append(f"warm persist_hits={warm['persist_hits']} != {n} "
                     "distinct fingerprints")
    if warm["answers"] != cold["answers"]:
        fails.append("warm-started answers are not bitwise-identical to "
                     "the cold process")
    if cold["stat_refreshes"] == 0 or cold["stats_persist_writes"] == 0:
        fails.append("cold process computed no table statistics "
                     f"(stat_refreshes={cold['stat_refreshes']}, "
                     f"writes={cold['stats_persist_writes']}) — the "
                     "calibration layer is not running")
    if warm["stat_refreshes"] != 0:
        fails.append(f"warm process recomputed {warm['stat_refreshes']} "
                     "table statistics — the stats store is not "
                     "warm-starting calibration")
    if warm["stats_persist_hits"] == 0:
        fails.append("warm process loaded zero persisted statistics")
    if warm["decisions"] != cold["decisions"]:
        fails.append("warm gating decisions differ from cold — persisted "
                     "stats did not reproduce the planning trace")
    return fails


# ---- mesh scenario: serving beyond one device ------------------------------
# A database 4× larger than any other scenario, sharded row-wise over an
# 8-device mesh behind the SAME QueryService surface.  Runs in a
# subprocess because the fake host device count must be fixed before jax
# initialises (XLA_FLAGS), like the tests' differential helpers.  The
# single-device reference uses min_bucket = 8 × the mesh's min_bucket:
# for a power-of-two shard count, sharded per-shard buckets and one big
# local bucket round to IDENTICAL global capacities, so mesh answers must
# match the local service to the bit.

MESH_DEVICES = 8
MESH_SCALE_FACTOR = 4    # mesh db is 4× the other scenarios' scale
MESH_MIN_BUCKET = 8


def run_mesh_child(cache_dir: str, scale: int, seed: int) -> dict:
    """One mesh serving process: shard the db over all devices, serve the
    distinct mix individually + fused, grow a relation within its
    per-shard bucket, and report answers/counters as JSON on stdout."""
    if jax.device_count() != MESH_DEVICES:
        raise RuntimeError(f"expected {MESH_DEVICES} devices, got "
                           f"{jax.device_count()} (XLA_FLAGS not set?)")
    t0 = time.perf_counter()
    db, schema = make_tpch_db(scale=scale, seed=seed)
    mesh = jax.make_mesh((MESH_DEVICES,), ("data",))
    svc = QueryService(db, schema, mesh=mesh, cache_dir=cache_dir,
                       min_bucket=MESH_MIN_BUCKET)
    # identically-padded single-device reference (no cache_dir: its
    # store partition would be separate anyway — see topology keys)
    ref = QueryService(db, schema,
                       min_bucket=MESH_MIN_BUCKET * MESH_DEVICES)

    answers, ref_answers = {}, {}
    for name, sql in DISTINCT_QUERIES:
        r = svc.submit(sql)
        if r.error is not None:
            raise RuntimeError(f"{name} failed on mesh: {r.error!r}")
        answers[name] = _encode_values(r.values)
        ref_answers[name] = _encode_values(ref.submit(sql).values)
    fused = svc.submit_many([sql for _, sql in DISTINCT_QUERIES])
    fused_answers = {name: _encode_values(r.values)
                     for (name, _), r in zip(DISTINCT_QUERIES, fused)}
    wall_s = time.perf_counter() - t0

    # within-bucket growth on the sharded service: zero recompiles, and
    # the answers keep tracking the reference bit-for-bit
    compiles_before = svc.metrics()["compiles"]
    tab = db["partsupp"]
    rng = np.random.default_rng(seed + 1)
    extra = MESH_DEVICES * 4
    cols = {}
    for cname, col in tab.columns.items():
        base = np.asarray(col)
        cols[cname] = np.concatenate(
            [base, base[rng.integers(0, len(base), extra)]])
    grown = Table.from_numpy(cols)
    svc.update_table("partsupp", grown)
    ref.update_table("partsupp", grown)
    growth_identical = _values_equal(svc.submit(COSTLY_PARTS).values,
                                     ref.submit(COSTLY_PARTS).values)

    m = svc.metrics()
    gauges = svc.metrics_v2()["gauges"]
    return {"wall_s": wall_s, "scale": scale,
            "answers": answers, "ref_answers": ref_answers,
            "fused_answers": fused_answers,
            "growth_rows": extra,
            "growth_recompiles": m["compiles"] - compiles_before,
            "growth_identical": growth_identical,
            "plan_builds": m["plan_builds"],
            "compiles": m["compiles"],
            "persist_hits": m["persist_hits"],
            "persist_writes": m["persist_writes"],
            "mesh_devices": gauges.get("mesh_devices", 0),
            "mesh_shards": gauges.get("mesh_shard_count_data", 0)}


def _spawn_mesh_child(cache_dir: str, scale: int, seed: int) -> dict:
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{MESH_DEVICES}")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--mesh-child",
         cache_dir, "--scale", str(scale), "--seed", str(seed)],
        capture_output=True, text=True, env=env, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"mesh child failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run_mesh(scale: int = 1000, seed: int = 0) -> dict:
    """Cold + warm mesh serving processes over one cache_dir, at
    ``MESH_SCALE_FACTOR ×`` the surrounding benchmark's scale."""
    mesh_scale = scale * MESH_SCALE_FACTOR
    cache_dir = tempfile.mkdtemp(prefix="serving-mesh-cache-")
    try:
        cold = _spawn_mesh_child(cache_dir, mesh_scale, seed)
        warm = _spawn_mesh_child(cache_dir, mesh_scale, seed)
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    return {"queries": len(DISTINCT_QUERIES), "scale": mesh_scale,
            "cold": cold, "warm": warm}


def check_mesh(rx: dict) -> list[str]:
    """Gate the mesh scenario; returns failures."""
    fails = []
    cold, warm = rx["cold"], rx["warm"]
    if cold["mesh_devices"] != MESH_DEVICES \
            or cold["mesh_shards"] != MESH_DEVICES:
        fails.append(f"mesh gauges report {cold['mesh_devices']} devices / "
                     f"{cold['mesh_shards']} shards, expected "
                     f"{MESH_DEVICES}")
    if cold["answers"] != cold["ref_answers"]:
        fails.append("mesh answers differ bitwise from the identically-"
                     "padded single-device service")
    if cold["fused_answers"] != cold["answers"]:
        fails.append("fused mesh answers differ from individual mesh "
                     "serving")
    if cold["growth_recompiles"] != 0:
        fails.append(f"within-bucket growth on the mesh caused "
                     f"{cold['growth_recompiles']} recompiles")
    if not cold["growth_identical"]:
        fails.append("post-growth mesh answers diverged from the "
                     "reference")
    if warm["plan_builds"] != 0:
        fails.append(f"warm mesh process rebuilt {warm['plan_builds']} "
                     "plans — the store's topology partition is not "
                     "warm-starting")
    if warm["persist_hits"] != rx["queries"]:
        fails.append(f"warm mesh persist_hits={warm['persist_hits']} != "
                     f"{rx['queries']} distinct fingerprints")
    if warm["answers"] != cold["answers"]:
        fails.append("warm mesh answers are not bitwise-identical to the "
                     "cold process")
    return fails


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiny", action="store_true",
                    help="smoke-test scale (CI)")
    ap.add_argument("--smoke", action="store_true",
                    help="fused scenario only, counter assertions, no "
                         "timing gates (what scripts/verify.sh runs)")
    ap.add_argument("--scale", type=int, default=None)
    ap.add_argument("--warm-iters", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--restart-child", metavar="CACHE_DIR", default=None,
                    help="internal: run one restart-scenario serving "
                         "process against CACHE_DIR and print its JSON "
                         "report")
    ap.add_argument("--mesh-child", metavar="CACHE_DIR", default=None,
                    help="internal: run one mesh serving process (needs "
                         "XLA_FLAGS forcing 8 host devices) against "
                         "CACHE_DIR and print its JSON report")
    ap.add_argument("--record", nargs="?", const="BENCH_serving.json",
                    default=None, metavar="PATH",
                    help="write a schema-versioned perf trajectory "
                         "(rows + per-stage latency histograms; default "
                         "PATH: BENCH_serving.json)")
    args = ap.parse_args(argv)
    tiny = args.tiny or args.smoke
    scale = args.scale or (50 if tiny else 1000)
    warm_iters = args.warm_iters or (8 if tiny else 25)

    # a CPU counter gate: neither this process nor the children it starts
    # (they inherit the variable) ever opens an accelerator
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")

    if args.restart_child is not None:
        print(json.dumps(run_restart_child(args.restart_child, scale,
                                           args.seed)))
        return 0
    if args.mesh_child is not None:
        print(json.dumps(run_mesh_child(args.mesh_child, scale,
                                        args.seed)))
        return 0

    from benchmarks.recorder import Recorder
    rec = Recorder("serving", path=args.record)
    rec.add_meta(scale=scale, tiny=tiny, smoke=args.smoke, seed=args.seed)

    rf = run_fused(scale=scale, repeats=2 if tiny else 3)
    m = rf["fused_metrics"]
    print(f"fused dashboard   {rf['queries']} distinct queries × "
          f"{rf['repeats']} rounds")
    print(f"  individual      {rf['solo_s'] * 1e3:>10.1f} ms "
          f"({rf['solo_compiles']} compiles)")
    print(f"  fused           {rf['fused_s'] * 1e3:>10.1f} ms "
          f"({rf['fused_compiles']} compiles)")
    print(f"  identical={rf['identical']} "
          f"fused_batches={m['fused_batches']} "
          f"fused_queries={m['fused_queries']} "
          f"partial_fusions={m['partial_fusions']} "
          f"subplan_saved={m['subplan_saved']} "
          f"fused cache {m['fused_hits']}/{m['fused_hits'] + m['fused_misses']} hit")
    per_q = rf["queries"] * rf["repeats"]
    rec.row("serving.fused.individual", rf["solo_s"] / per_q * 1e6,
            f"compiles={rf['solo_compiles']}")
    rec.row("serving.fused.fused", rf["fused_s"] / per_q * 1e6,
            f"compiles={rf['fused_compiles']};"
            f"subplan_saved={m['subplan_saved']}")
    fused_fails = check_fused(rf)
    if not args.smoke and rf["fused_s"] >= rf["solo_s"]:
        fused_fails.append(f"fused wall {rf['fused_s']:.3f}s not below "
                           f"individual {rf['solo_s']:.3f}s")

    rm = run_mixed(scale=scale, repeats=2 if tiny else 3)
    mm = rm["fused_metrics"]
    print(f"mixed join shapes {rm['queries']} queries, "
          f"{rm['distinct_prefixes']} distinct whole prefixes "
          f"(whole-prefix fusion: zero) × {rm['repeats']} rounds")
    print(f"  individual      {rm['solo_s'] * 1e3:>10.1f} ms "
          f"({rm['solo_compiles']} compiles)")
    print(f"  fused           {rm['fused_s'] * 1e3:>10.1f} ms "
          f"({rm['fused_compiles']} compiles)")
    print(f"  identical={rm['identical']} "
          f"partial_fusions={mm['partial_fusions']} "
          f"subplan_saved={mm['subplan_saved']}")
    per_q = rm["queries"] * rm["repeats"]
    rec.row("serving.mixed.individual", rm["solo_s"] / per_q * 1e6,
            f"compiles={rm['solo_compiles']}")
    rec.row("serving.mixed.fused", rm["fused_s"] / per_q * 1e6,
            f"compiles={rm['fused_compiles']};"
            f"partial_fusions={mm['partial_fusions']}")
    fused_fails += check_mixed(rm)
    if not args.smoke and rm["fused_s"] >= rm["solo_s"]:
        fused_fails.append(f"mixed-shape fused wall {rm['fused_s']:.3f}s "
                           f"not below individual {rm['solo_s']:.3f}s")

    ra = run_async(scale=scale, threads=8)
    ma = ra["metrics"]
    print(f"concurrent callers {ra['threads']} threads × 1 query "
          f"({ra['distinct']} distinct fingerprints)")
    print(f"  serial          {ra['serial_s'] * 1e3:>10.1f} ms "
          f"({ra['serial_compiles']} compiles)")
    print(f"  async batched   {ra['async_s'] * 1e3:>10.1f} ms "
          f"({ma['compiles']} compiles, "
          f"{ma['async_batches']} async batches)")
    print(f"  identical={ra['identical']} "
          f"async_requests={ma['async_requests']} "
          f"queue_depth_peak={ma['queue_depth_peak']} "
          f"rejected={ma['rejected']} "
          f"bad-query isolated={ra['bad_error'] is not None and ra['good_ok']}")
    rec.row("serving.async.serial", ra["serial_s"] / ra["threads"] * 1e6,
            f"compiles={ra['serial_compiles']}")
    rec.row("serving.async.batched", ra["async_s"] / ra["threads"] * 1e6,
            f"compiles={ma['compiles']};batches={ma['async_batches']};"
            f"queue_depth_peak={ma['queue_depth_peak']}")
    fused_fails += check_async(ra)

    rt = run_multitenant(scale=scale, rounds=4 if tiny else 6,
                         seed=args.seed)
    vt, ft = rt["tenants"]["victim"], rt["tenants"]["flood"]
    print(f"multi-tenant mix  victim {rt['rounds']}×"
          f"{rt['victim_queries']} dashboard queries vs a flooding "
          f"tenant ({rt['flood_client']['submitted']} attempts)")
    print(f"  victim p95      {rt['mixed_p95_s'] * 1e3:>10.1f} ms under "
          f"flood vs {rt['solo_p95_s'] * 1e3:.1f} ms solo "
          f"(identical={rt['victim_identical']}, errors={vt['errors']})")
    print(f"  flood held to   {ft['requests']:>10d} served "
          f"(rejected {ft['rejected']}: rate={ft['rejected_rate']} "
          f"depth={ft['rejected_depth']}; errors={ft['errors']} isolated)")
    print(f"  cross-tenant    {rt['xt_requests']:>10d} requests / "
          f"{rt['xt_distinct']} fingerprints over 4 tenants → "
          f"{rt['xt_metrics']['compiles']} compiles "
          f"(fused_queries={rt['xt_metrics']['fused_queries']}, "
          f"identical={rt['xt_identical']})")
    rec.row("serving.tenant.victim_solo", rt["solo_p95_s"] * 1e6,
            "p95;victim alone")
    rec.row("serving.tenant.victim_flooded", rt["mixed_p95_s"] * 1e6,
            f"p95;flood_rejected={ft['rejected']};"
            f"flood_served={ft['requests']}")
    fused_fails += check_multitenant(rt)

    rz = run_misfusion(scale=scale, repeats=3 if tiny else 5,
                       seed=args.seed)
    zg, zu = rz["gated_metrics"], rz["ungated_metrics"]
    print(f"mis-fusion gate   1 cheap lookup + {rz['queries'] - 1} "
          f"overlapping 5-way dashboards × {rz['repeats']} rounds")
    print(f"  gated lookup    {rz['gated_p95_s'] * 1e6:>10.1f} us p95 "
          f"(cost_rejects={zg['fusion_cost_rejects']}, "
          f"bigs fused={rz['bigs_fused_gated']})")
    print(f"  ungated lookup  {rz['ungated_p95_s'] * 1e6:>10.1f} us p95 "
          f"(disparity=inf: lookup fused={rz['lookup_fused_ungated']})")
    print(f"  identical={rz['identical']} "
          f"demotions={zg['fusion_demotions']} "
          f"refused-after-demotion={not rz['bigs_fused_after_demotion']}")
    rec.row("serving.misfusion.gated", rz["gated_p95_s"] * 1e6,
            f"cost_rejects={zg['fusion_cost_rejects']};"
            f"demotions={zg['fusion_demotions']}")
    rec.row("serving.misfusion.ungated", rz["ungated_p95_s"] * 1e6,
            f"disparity=inf;rejects={zu['fusion_cost_rejects']}")
    fused_fails += check_misfusion(rz)

    rr = run_restart(scale=scale, seed=args.seed)
    cold, warm = rr["cold"], rr["warm"]
    print(f"restart warm start {rr['queries']} distinct queries, "
          f"cache_dir={rr['cache_dir']}")
    print(f"  cold process    {cold['wall_s'] * 1e3:>10.1f} ms "
          f"(plan_builds={cold['plan_builds']}, "
          f"compile_s={cold['compile_s_total'] * 1e3:.1f} ms, "
          f"persist_writes={cold['persist_writes']})")
    print(f"  warm process    {warm['wall_s'] * 1e3:>10.1f} ms "
          f"(plan_builds={warm['plan_builds']}, "
          f"compile_s={warm['compile_s_total'] * 1e3:.1f} ms, "
          f"persist_hits={warm['persist_hits']})")
    print(f"  identical={warm['answers'] == cold['answers']} "
          f"stat_refreshes cold={cold['stat_refreshes']} "
          f"warm={warm['stat_refreshes']} "
          f"decisions-identical={warm['decisions'] == cold['decisions']}")
    rec.row("serving.restart.cold", cold["wall_s"] * 1e6,
            f"plan_builds={cold['plan_builds']};"
            f"persist_writes={cold['persist_writes']}")
    rec.row("serving.restart.warm", warm["wall_s"] * 1e6,
            f"plan_builds={warm['plan_builds']};"
            f"persist_hits={warm['persist_hits']}")
    fused_fails += check_restart(rr)
    # timing gates (timed run only; --smoke asserts counters + identity):
    # the persistent XLA cache must cut compile time, and the whole warm
    # start must beat the cold one on wall-clock
    if not args.smoke:
        if warm["compile_s_total"] >= max(cold["compile_s_total"], 1e-9):
            fused_fails.append(
                f"warm compile_s_total {warm['compile_s_total']:.3f}s not "
                f"below cold {cold['compile_s_total']:.3f}s — the "
                "persistent XLA compilation cache is not being hit")
        if warm["wall_s"] >= cold["wall_s"]:
            fused_fails.append(
                f"warm-start wall {warm['wall_s']:.2f}s not below cold "
                f"{cold['wall_s']:.2f}s")

    ro = run_overhead(scale=scale, iters=20 if tiny else 30,
                      seed=args.seed)
    print(f"tracing overhead  warm median "
          f"{ro['traced_median_s'] * 1e3:.3f} ms traced vs "
          f"{ro['untraced_median_s'] * 1e3:.3f} ms untraced "
          f"({ro['overhead_frac']:+.1%}), identical={ro['identical']}, "
          f"{len(ro['histograms'])} stage histograms")
    rec.row("serving.tracing.on", ro["traced_median_s"] * 1e6,
            f"overhead={ro['overhead_frac']:+.3%}")
    rec.row("serving.tracing.off", ro["untraced_median_s"] * 1e6,
            "baseline")
    rec.add_histograms(ro["histograms"])
    rec.add_metrics(ro["metrics"])
    fused_fails += check_overhead(ro)

    rx = run_mesh(scale=scale, seed=args.seed)
    cold, warm = rx["cold"], rx["warm"]
    print(f"mesh serving      {rx['queries']} distinct queries at scale="
          f"{rx['scale']} ({MESH_SCALE_FACTOR}× everything above) over "
          f"{cold['mesh_devices']} devices")
    print(f"  cold process    {cold['wall_s'] * 1e3:>10.1f} ms "
          f"(plan_builds={cold['plan_builds']}, "
          f"compiles={cold['compiles']}, "
          f"persist_writes={cold['persist_writes']})")
    print(f"  warm process    {warm['wall_s'] * 1e3:>10.1f} ms "
          f"(plan_builds={warm['plan_builds']}, "
          f"persist_hits={warm['persist_hits']})")
    print(f"  bitwise-vs-local={cold['answers'] == cold['ref_answers']} "
          f"fused-identical={cold['fused_answers'] == cold['answers']} "
          f"growth +{cold['growth_rows']} rows → "
          f"{cold['growth_recompiles']} recompiles")
    rec.row("serving.mesh.cold", cold["wall_s"] * 1e6,
            f"scale={rx['scale']};devices={cold['mesh_devices']};"
            f"plan_builds={cold['plan_builds']}")
    rec.row("serving.mesh.warm", warm["wall_s"] * 1e6,
            f"plan_builds={warm['plan_builds']};"
            f"persist_hits={warm['persist_hits']}")
    fused_fails += check_mesh(rx)

    if args.smoke:
        rec.finish()
        for f in fused_fails:
            print(f"FAIL: {f}")
        print("PASS" if not fused_fails else "FAIL")
        return 0 if not fused_fails else 1

    r = run(scale=scale, warm_iters=warm_iters)

    print(f"serving benchmark  scale={r['scale']}")
    print(f"{'query':16s} {'cold (ms)':>10s} {'speedup':>9s}")
    for name, s in r["cold_s"].items():
        sp = r["speedup_per_query"][name]
        print(f"{name:16s} {s * 1e3:>10.1f} {sp:>8.1f}x")
    print(f"warm median       {r['warm_median_s'] * 1e3:>10.2f} ms")
    print(f"warm p99          {r['warm_p99_s'] * 1e3:>10.2f} ms")
    print(f"throughput        {r['throughput_qps']:>10.0f} qps")
    print(f"batched           {r['batched_qps']:>10.0f} qps")
    print(f"cold/warm speedup {r['speedup']:>10.1f}x (min per-query)")
    print(f"eager fallback    {r['eager_s'] * 1e3:>10.1f} ms "
          f"(mode={r['eager_mode']}, never amortises)")
    print(f"growth rows       {r['growth_rows']:>10d} "
          f"(recompiles={r['growth_recompiles']})")
    m = r["metrics"]
    print(f"cache: plan {m['plan_hits']}/{m['plan_hits'] + m['plan_misses']}"
          f" hit, exec {m['exec_hits']}/{m['exec_hits'] + m['exec_misses']}"
          f" hit, compiles={m['compiles']}, "
          f"dedup_saved={m['dedup_saved']}")
    rec.row("serving.warm.median", r["warm_median_s"] * 1e6,
            f"p99_us={r['warm_p99_s'] * 1e6:.1f}")
    rec.row("serving.throughput", 1e6 / max(r["throughput_qps"], 1e-9),
            f"qps={r['throughput_qps']:.0f};batched_qps="
            f"{r['batched_qps']:.0f}")
    rec.row("serving.eager", r["eager_s"] * 1e6,
            f"mode={r['eager_mode']}")
    rec.add_metrics(m)
    rec.finish()

    ok = True
    if r["speedup"] < 10:
        print(f"FAIL: warm-cache speedup {r['speedup']:.1f}x < 10x")
        ok = False
    if r["growth_recompiles"] != 0:
        print(f"FAIL: same-bucket growth caused "
              f"{r['growth_recompiles']} recompiles")
        ok = False
    for f in fused_fails:
        print(f"FAIL: {f}")
        ok = False
    print("PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
