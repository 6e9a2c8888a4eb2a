"""Quickstart: the paper's engine in 60 lines.

Builds the TPC-H-like mini database, runs the paper's running example
(Fig. 1) in all plan classes, and shows the planner's decisions.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax

from repro.core import Executor, classify, plan_query
from repro.data import make_tpch_db
from repro.data.relational import tpch_v1_query


def main():
    db, schema = make_tpch_db(scale=2000, seed=0)

    # ---- the paper's Fig. 1 query: MIN/MAX of s_acctbal over a 5-way join
    q = tpch_v1_query("minmax")
    cls = classify(q, schema)
    print(f"acyclic={cls.acyclic} guarded={cls.guarded} "
          f"guard={cls.guard} set_safe={cls.set_safe} 0MA={cls.is_oma}")

    plan = plan_query(q, schema)          # auto → 0MA semi-join sweep
    print(plan.describe())

    ex = Executor(db, schema)
    res = ex.execute(plan)
    print(f"MIN={float(res['min(bal)']):.2f}  "
          f"MAX={float(res['max(bal)']):.2f}  "
          f"peak live tuples={res['__stats__'].peak_tuples}")

    # ---- MEDIAN variant: not set-safe → frequency propagation (Opt+)
    qm = tpch_v1_query("median")
    plan_m = plan_query(qm, schema)
    print(f"\nMEDIAN plan class: {plan_m.mode}")
    fn = ex.compile(plan_m)               # jitted, zero materialisation
    out = fn(db)
    print(f"MEDIAN={float(out['median(bal)']):.2f}")

    # ---- same result the expensive way (materialising baseline)
    ref = ex.execute(plan_query(qm, schema, mode="ref"))
    print(f"REF     MEDIAN={float(ref['median(bal)']):.2f}  "
          f"peak materialised tuples={ref['__stats__'].peak_tuples}")





def serving_example():
    """Serving queries: the pipeline as a cached, compiled service.

    Guarded plans are static-dataflow programs, so the serving tier
    (repro.service) compiles each query *structure* once and answers every
    subsequent request — under any alias/variable renaming — from cache.
    Tables are padded to power-of-two shape buckets, so data growth inside
    a bucket never recompiles.  Distinct queries sharing a scan/semi-join
    prefix are fused into one multi-query XLA program by ``submit_many``.
    """
    from repro.service import QueryService

    db, schema = make_tpch_db(scale=500, seed=0)
    svc = QueryService(db, schema)

    sql = """
        SELECT MIN(s.s_acctbal), MAX(s.s_acctbal)
        FROM region r, nation n, supplier s, partsupp ps, part p
        WHERE r.r_regionkey = n.n_regionkey
          AND n.n_nationkey = s.s_nationkey
          AND s.s_suppkey = ps.ps_suppkey
          AND ps.ps_partkey = p.p_partkey
          AND r.r_name IN (2, 3) AND p.p_price > 1200.0
    """
    renamed = """
        SELECT MAX(su.s_acctbal), MIN(su.s_acctbal)
        FROM part pa, supplier su, region re, partsupp pp, nation na
        WHERE pa.p_price > 1200.0
          AND na.n_nationkey = su.s_nationkey
          AND re.r_regionkey = na.n_regionkey
          AND pp.ps_partkey = pa.p_partkey
          AND su.s_suppkey = pp.ps_suppkey
          AND re.r_name IN (3, 2)
    """
    cold = svc.submit(sql)                       # parse + plan + compile
    warm = svc.submit(renamed)                   # same fingerprint → cached
    print(f"\n[serve] cold: compile={cold.stats.compile_s * 1e3:.1f}ms "
          f"run={cold.stats.run_s * 1e3:.2f}ms")
    print(f"[serve] warm (renamed aliases): run={warm.stats.run_s * 1e3:.2f}ms "
          f"plan_hit={warm.stats.plan_cache_hit} "
          f"exec_hit={warm.stats.exec_cache_hit}")

    # micro-batching: concurrent identical requests share one execution
    batch = svc.submit_many([sql, renamed, sql])
    print(f"[serve] batch of 3 → shared runs: "
          f"{[r.stats.shared_execution for r in batch]}")

    # cross-fingerprint fusion: DIFFERENT queries whose plan DAGs overlap
    # are compiled and run as ONE XLA program.  Overlap is judged on
    # content-addressed subplan keys (PhysicalPlan.subplan_keys), so even
    # different JOIN SHAPES fuse: the three dashboard queries below share
    # their whole supplier⋈nation⋈region prefix, while the 5-way Fig. 1
    # query shares only the filtered region scan + the first two
    # semi-joins — and all four still land in one program that computes
    # each shared sub-DAG exactly once ("partial fusion").  disparity=inf
    # turns the cost-admission gate off to show the raw machinery; the
    # calibrated-planning section next demonstrates the default policy,
    # which would band the expensive 5-way away from the cheap three.
    svc_f = QueryService(db, schema, fusion_disparity=float("inf"))
    dims = """FROM supplier s, nation n, region r
        WHERE s.s_nationkey = n.n_nationkey
          AND n.n_regionkey = r.r_regionkey AND r.r_name IN (2, 3)"""
    dashboard = [
        f"SELECT MIN(s.s_acctbal), MAX(s.s_acctbal) {dims}",
        f"SELECT SUM(s.s_acctbal) {dims}",
        f"SELECT COUNT(*) AS cnt, AVG(s.s_acctbal) AS avg {dims} "
        "GROUP BY s.s_nationkey",
        sql,                                 # the 5-way Fig. 1 query
    ]
    fused = svc_f.submit_many(dashboard)
    print(f"[serve] fused dashboard of {len(dashboard)}: "
          f"fused={[r.stats.fused for r in fused]} "
          f"group_size={fused[0].stats.fused_group_size}")
    m = svc_f.metrics()
    print(f"[serve] metrics: compiles={m['compiles']} "
          f"(fused={m['fused_compiles']}) "
          f"plan hits/misses={m['plan_hits']}/{m['plan_misses']} "
          f"exec hits/misses={m['exec_hits']}/{m['exec_misses']} "
          f"fused_queries={m['fused_queries']} "
          f"partial_fusions={m['partial_fusions']} "
          f"subplan_saved={m['subplan_saved']}")

    # why they fuse is inspectable: each plan prints its op DAG with
    # content-addressed node keys — equal keys = shared sub-DAGs
    from repro.core import parse_sql, plan_query
    from repro.service import canonicalize
    print("\n[serve] op DAGs — the 3-way and 5-way plans print the same "
          "keys for the region scan and the first two semi-joins:")
    for s in (dashboard[1], sql):
        plan = plan_query(canonicalize(parse_sql(s, schema)).query, schema)
        print(plan.describe())


def calibrated_planning_example():
    """Calibrated planning: statistics gate the rewrites and fusion.

    Every rewrite pass is a *gated transform*: a structural gate decides
    whether a rewrite COULD apply, cheap per-table statistics
    (``repro.core.stats`` — row counts, per-column ranges/distincts,
    MEASURED foreign-key orphan counts) decide whether it SHOULD, and
    either way the pass records a machine-readable ``Decision`` — so a
    plan always says which transforms fired and which gate values
    justified them.  The same catalog prices candidate fusion groups at
    serve time: a cheap lookup is never fused into a dashboard many
    times its cost (it would inherit the dashboard's latency), and
    observed serve times feed back so a fusion that *measures* slower
    than solo serving is demoted on the next batch.  With
    ``cache_dir=...`` the statistics persist beside the plans: a
    restarted service recomputes nothing (``stat_refreshes == 0``) and
    reaches bit-identical gating decisions.
    """
    from repro.core import StatsCatalog, parse_sql, plan_query
    from repro.service import QueryService

    db, schema = make_tpch_db(scale=500, seed=0)
    stats = StatsCatalog(schema)
    for name, table in db.items():
        stats.refresh(name, table, db)

    # nation⋉region is an FK→PK semi-join with zero measured orphans —
    # an identity on live rows, so the calibrated pass eliminates it
    q = parse_sql("SELECT COUNT(*) FROM nation n, region r "
                  "WHERE n.n_regionkey = r.r_regionkey", schema)
    plan = plan_query(q, schema, stats=stats)
    print("\n[calibrate] planning decisions:")
    for d in plan.decisions:
        print(f"  {d.describe()}")

    # the serving tier threads its own catalog through planning AND
    # fusion admission: the cheap lookup below shares subplans with the
    # 5-way dashboards, but costs ~100× less, so it serves solo
    svc = QueryService(db, schema)
    dims = """FROM supplier s, nation n, region r
        WHERE s.s_nationkey = n.n_nationkey
          AND n.n_regionkey = r.r_regionkey AND r.r_name IN (2, 3)"""
    five = """FROM region r, nation n, supplier s, partsupp ps, part p
        WHERE r.r_regionkey = n.n_regionkey
          AND n.n_nationkey = s.s_nationkey
          AND s.s_suppkey = ps.ps_suppkey
          AND ps.ps_partkey = p.p_partkey
          AND r.r_name IN (2, 3) AND p.p_price > 1200.0"""
    lookup = f"SELECT COUNT(*) {dims}"
    res = svc.submit_many([lookup,
                           f"SELECT MIN(s.s_acctbal) {five}",
                           f"SELECT SUM(s.s_acctbal) {five}"])
    m = svc.metrics()
    print(f"[calibrate] lookup fused={res[0].stats.fused} "
          f"dashboards fused={res[1].stats.fused} "
          f"(fusion_cost_rejects={m['fusion_cost_rejects']}, "
          f"stat_refreshes={m['stat_refreshes']})")
    fa = svc.explain(lookup)["fusion_admission"]
    print(f"[calibrate] explain names the gate: {fa['reason']}")


def async_serving_example():
    """Async serving: cross-caller batch formation.

    ``submit_many`` fuses whatever ONE caller hands it; ``submit_async``
    extends that to independent callers.  Each call enqueues its query on
    a bounded admission queue and returns a future; a background batcher
    drains the queue on a small time window and serves the whole window
    through the same fusion pipeline — so eight clients submitting one
    dashboard panel each still share subplan work and compiled programs.
    A malformed query fails only its own future (per-request fault
    isolation); a full queue rejects with AdmissionError (backpressure).
    """
    import threading

    from repro.service import QueryService

    db, schema = make_tpch_db(scale=500, seed=0)
    # widen the batching window so this demo's "clients" reliably land in
    # one batch; production keeps it at a couple of milliseconds
    svc = QueryService(db, schema, async_max_wait_ms=300)

    dims = """FROM supplier s, nation n, region r
        WHERE s.s_nationkey = n.n_nationkey
          AND n.n_regionkey = r.r_regionkey AND r.r_name IN (2, 3)"""
    panels = [
        f"SELECT MIN(s.s_acctbal), MAX(s.s_acctbal) {dims}",
        f"SELECT SUM(s.s_acctbal) {dims}",
        f"SELECT MEDIAN(s.s_acctbal) {dims}",
        f"SELECT COUNT(*) AS cnt, AVG(s.s_acctbal) AS avg {dims} "
        "GROUP BY s.s_nationkey",
    ]

    # eight independent "clients", one query each, submitting concurrently
    work = [panels[i % len(panels)] for i in range(8)]
    barrier = threading.Barrier(len(work))
    futs = [None] * len(work)

    def client(i):
        barrier.wait()
        futs[i] = svc.submit_async(work[i])

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(work))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    results = [f.result(120) for f in futs]
    m = svc.metrics()
    print(f"\n[async] {len(work)} callers × 1 query → "
          f"{m['async_batches']} batch(es), {m['compiles']} compiles "
          f"(fused={m['fused_compiles']}), "
          f"fused_group_size={results[0].stats.fused_group_size}")

    # per-request fault isolation: the bad query fails alone
    bad = svc.submit_async("SELECT MIN(x.oops) FROM no_such_table x")
    good = svc.submit_async(panels[0])
    err, res = bad.exception(120), good.result(120)
    print(f"[async] malformed batch-mate: error={type(err).__name__} "
          f"(\"{err}\"), valid mate answered="
          f"{res.error is None and bool(res.values)}")
    svc.close()


def multi_tenant_example():
    """Multi-tenant serving: fair admission, quotas, cross-tenant fusion.

    ``submit_async(sql, tenant=...)`` routes every request through a
    per-tenant admission gate before it reaches the batcher:

    * ``TenantPolicy(rate=..., burst=...)`` — a token bucket; exhausted
      → ``TenantAdmissionError`` with ``kind == "rate"``.
    * ``TenantPolicy(max_queue=...)`` — a bounded per-tenant queue;
      full → ``kind == "depth"``.  Rejections never touch other
      tenants' queues (backpressure is per tenant, not global).
    * ``weight`` / ``priority`` — batch formation claims requests by
      deficit round-robin across tenants (weights split a contended
      batch proportionally) after serving lower ``priority`` numbers
      first.

    The formed window is still ONE batch through the fusion pipeline,
    so overlapping queries from different tenants share compiled
    programs — isolation is about admission and accounting, not about
    losing cross-tenant fusion.  ``metrics_v2()["tenants"]`` breaks
    requests, rejections, fused share, and latency percentiles out per
    tenant.
    """
    import threading

    from repro.service import QueryService, TenantAdmissionError, TenantPolicy

    db, schema = make_tpch_db(scale=500, seed=0)
    svc = QueryService(db, schema, async_max_wait_ms=300, tenants={
        "dashboards": TenantPolicy(weight=2.0, priority=0),
        "adhoc": TenantPolicy(rate=50.0, burst=4, max_queue=8),
    })

    dims = """FROM supplier s, nation n, region r
        WHERE s.s_nationkey = n.n_nationkey
          AND n.n_regionkey = r.r_regionkey AND r.r_name IN (2, 3)"""
    panels = [
        f"SELECT MIN(s.s_acctbal), MAX(s.s_acctbal) {dims}",
        f"SELECT SUM(s.s_acctbal) {dims}",
    ]

    # two tenants submit concurrently; the window fuses across both
    barrier = threading.Barrier(2)
    futs: dict[str, list] = {"dashboards": [], "adhoc": []}

    def client(tenant):
        barrier.wait()
        for i in range(3):
            futs[tenant].append(
                svc.submit_async(panels[i % len(panels)], tenant=tenant))

    threads = [threading.Thread(target=client, args=(t,)) for t in futs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for fs in futs.values():
        for f in fs:
            f.result(120)

    # the adhoc bucket holds 4 tokens — a burst of 40 gets turned away
    # with a TYPED error naming the tenant and the exhausted resource
    rejected = 0
    for _ in range(40):
        try:
            futs["adhoc"].append(svc.submit_async(panels[0], tenant="adhoc"))
        except TenantAdmissionError as e:
            rejected += 1
            last = (e.tenant, e.kind)
    for f in futs["adhoc"][3:]:
        f.result(120)

    tenants = svc.metrics_v2()["tenants"]
    for name in ("dashboards", "adhoc"):
        t = tenants[name]
        print(f"[tenant] {name}: requests={t['requests']} "
              f"rejected={t['rejected']} (rate={t['rejected_rate']} "
              f"depth={t['rejected_depth']}) "
              f"fused_share={t['fused_share']:.2f} "
              f"p95={t['p95_s'] * 1e3:.1f}ms")
    m = svc.metrics()
    print(f"[tenant] burst of 40 → {rejected} rejected, last={last}; "
          f"cross-tenant fusion still on: compiles={m['compiles']} "
          f"(fused={m['fused_compiles']})")
    svc.close()


def observability_example():
    """Observing the service: traces, histograms, explain, export.

    Every request through ``QueryService`` carries a span tree (parse →
    fingerprint → plan → pad → compile → run, plus queue_wait for async
    submissions), and every span folds into a streaming per-stage latency
    histogram.  Reading it back:

    * ``svc.metrics_v2()`` — one CONSISTENT snapshot:
      ``{"counters", "gauges", "histograms"}`` with p50/p95/p99 per
      stage.  ``queue_depth_peak`` is a resettable high-water mark (max
      since the previous read).  ``svc.metrics()`` is the old flat view.
    * ``svc.explain(sql)`` — serves the query once and names HOW: which
      cache level supplied the plan (memory/disk/built) and the
      executable (exec_cache/compiled/fused_*), fusion-group membership,
      and the content-addressed graph/subplan keys.
    * ``svc.export_trace(path)`` — Chrome-trace JSON of recent request
      trees; load it at https://ui.perfetto.dev.  One fused compile that
      served a whole dashboard appears exactly once, linked from every
      member request.
    * ``QueryService(db, schema, tracing=False)`` — identical answers,
      zero tracing work: the ≤ 3 % overhead gate in
      ``benchmarks/serving_queries.py --smoke`` compares the two.
    """
    import tempfile

    from repro.service import QueryService

    db, schema = make_tpch_db(scale=500, seed=0)
    svc = QueryService(db, schema)

    dims = """FROM supplier s, nation n, region r
        WHERE s.s_nationkey = n.n_nationkey
          AND n.n_regionkey = r.r_regionkey AND r.r_name IN (2, 3)"""
    sql = f"SELECT MIN(s.s_acctbal), MAX(s.s_acctbal) {dims}"
    svc.submit_many([sql, f"SELECT SUM(s.s_acctbal) {dims}"])  # cold, fused
    for _ in range(20):
        svc.submit(sql)                                        # warm

    v2 = svc.metrics_v2()
    run = v2["histograms"]["run"]
    print(f"\n[observe] run-stage latency: n={run['count']} "
          f"p50={run['p50_s'] * 1e3:.2f}ms p95={run['p95_s'] * 1e3:.2f}ms "
          f"p99={run['p99_s'] * 1e3:.2f}ms")
    comp = v2["histograms"]["compile"]
    print(f"[observe] compile-stage: n={comp['count']} "
          f"max={comp['max_s'] * 1e3:.0f}ms (cold only — warm requests "
          "never touch it)")
    print(f"[observe] gauges: {v2['gauges']}")

    print("[observe] explain:")
    print(svc.explain(sql)["text"])

    out = tempfile.mktemp(suffix=".json", prefix="repro-trace-")
    n = svc.export_trace(out)
    print(f"[observe] {n} trace events -> {out} "
          "(open in https://ui.perfetto.dev)")


def warm_restart_example():
    """Restart with a warm cache: plans & executables outlive the process.

    ``QueryService(db, schema, cache_dir=...)`` persists every shareable
    plan into a content-addressed store under ``cache_dir`` and turns on
    JAX's persistent compilation cache (``.jax_cache`` in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names another) — so a
    RESTARTED service over the same schema re-plans nothing
    (``plan_builds == 0``, the disk level answers with ``persist_hits``)
    and loads previously compiled XLA binaries from disk instead of
    recompiling.  Damaged entries, version skew, or a read-only disk
    degrade to memory-only caching; they never fail a request.
    ``export_cache``/``import_cache`` ship a warm directory elsewhere
    (e.g. to seed a fresh fleet from one warmed pod).
    """
    import tempfile
    import time

    from repro.service import QueryService

    db, schema = make_tpch_db(scale=500, seed=0)
    cache_dir = tempfile.mkdtemp(prefix="repro-warm-cache-")
    sql = """
        SELECT MIN(s.s_acctbal), MAX(s.s_acctbal)
        FROM region r, nation n, supplier s, partsupp ps, part p
        WHERE r.r_regionkey = n.n_regionkey
          AND n.n_nationkey = s.s_nationkey
          AND s.s_suppkey = ps.ps_suppkey
          AND ps.ps_partkey = p.p_partkey
          AND r.r_name IN (2, 3) AND p.p_price > 1200.0
    """

    t0 = time.perf_counter()
    svc = QueryService(db, schema, cache_dir=cache_dir)
    svc.submit(sql)
    cold_s = time.perf_counter() - t0
    m = svc.metrics()
    print(f"\n[warm-start] cold service: {cold_s * 1e3:.1f} ms, "
          f"plan_builds={m['plan_builds']} "
          f"persist_writes={m['persist_writes']}")

    # "restart": a brand-new service over the same cache_dir (run this
    # script twice to see the effect across real processes — the restart
    # scenario in benchmarks/serving_queries.py gates exactly that)
    t0 = time.perf_counter()
    svc2 = QueryService(db, schema, cache_dir=cache_dir)
    svc2.submit(sql)
    warm_s = time.perf_counter() - t0
    m2 = svc2.metrics()
    print(f"[warm-start] restarted service: {warm_s * 1e3:.1f} ms, "
          f"plan_builds={m2['plan_builds']} "
          f"persist_hits={m2['persist_hits']} "
          f"(plans served from {cache_dir})")


def tuning_example():
    """Tuning the kernels: measured search, persisted beside the plans.

    The three physical kernels (freq_join / semi_join / segment_sum) have
    tuning knobs — pallas block shapes and the XLA dense-domain dispatch
    crossover.  ``svc.autotune()`` runs a measured search per (kernel,
    shape bucket, backend): every candidate is timed on synthetic inputs
    shaped like the service's buckets and GATED on bitwise equality with
    the untuned answer, so tuning can change speed but never results.
    Winners land in ``cache_dir/tune/<topology>/`` with the plan store's
    discipline (format-versioned, sha256-checksummed, atomic writes,
    corrupt entries evicted, read-only disks degrade to in-memory):
    one JSON entry per (kernel, shape bucket, backend) holding the
    winning ``KernelConfig`` and its measurements.  Entries key off the
    SAME power-of-two buckets as the plan cache — growth inside a bucket
    retunes nothing; a ``format_version`` bump or topology change orphans
    old entries rather than mis-reading them.  A restarted service loads
    the winners from disk: ``tune_searches == 0``, the tuning twin of
    ``plan_builds == 0``.  ``export_cache``/``import_cache`` ship them
    with the plans.
    """
    import tempfile

    from repro.service import QueryService

    db, schema = make_tpch_db(scale=500, seed=0)
    cache_dir = tempfile.mkdtemp(prefix="repro-tune-cache-")
    sql = """
        SELECT SUM(ps.ps_supplycost), COUNT(*)
        FROM partsupp ps, part p
        WHERE ps.ps_partkey = p.p_partkey AND p.p_price > 1500.0
    """

    svc = QueryService(db, schema, cache_dir=cache_dir)
    before = svc.submit(sql)
    report = svc.autotune()               # offline: seconds, not request-path
    print(f"\n[tuning] cold search: buckets={report['buckets']} "
          f"searches={report['searches']} installed={report['installed']} "
          f"gate_rejects={report['gate_rejects']}")
    after = svc.submit(sql)               # re-traced with tuned configs
    same = all(float(after.values[k]) == float(before.values[k])
               for k in before.values)
    print(f"[tuning] answers identical post-tune: {same}")

    # restart: winners come back from disk, nothing is re-measured
    svc2 = QueryService(db, schema, cache_dir=cache_dir)
    report2 = svc2.autotune()
    m = svc2.metrics()
    print(f"[tuning] warm restart: searches={report2['searches']} "
          f"tune_searches={m['tune_searches']} "
          f"tune_store_hits={m['tune_store_hits']} "
          f"(configs served from {cache_dir}/tune)")


def mesh_serving_example():
    """Serving beyond one device: the same service, sharded over a mesh.

    ``QueryService(db, schema, mesh=jax.make_mesh(...))`` shards every
    relation row-wise across the mesh's devices and lowers every compiled
    plan through the SAME op-graph interpreter — scans and semi-/freq-
    joins become ring programs (``lax.ppermute`` sweeps) inside one
    ``shard_map``, final aggregation runs replicated.  Everything else is
    unchanged: SQL in, plan/executable caches (keyed by topology, so a
    mesh program is never served to a single-device service), shape
    buckets per shard (growth inside a per-shard bucket recompiles
    nothing), fusion via ``submit_many``, tracing (a ``ring_sweep`` child
    span under ``run``), and ``cache_dir`` warm restarts.

    Answers are BITWISE-identical to a single-device service padded to
    the same capacities — the mesh moves frequency vectors, not float
    partials, so there is no reduction-order drift.  This demo runs on
    whatever devices jax sees (1 CPU here); the 8-device differential
    lives in tests/ and ``benchmarks/serving_queries.py`` (forced host
    devices in a subprocess).
    """
    from repro.service import QueryService

    db, schema = make_tpch_db(scale=500, seed=0)
    devices = jax.device_count()
    mesh = jax.make_mesh((devices,), ("data",))
    svc = QueryService(db, schema, mesh=mesh)

    sql = """
        SELECT MIN(s.s_acctbal), MAX(s.s_acctbal)
        FROM region r, nation n, supplier s, partsupp ps, part p
        WHERE r.r_regionkey = n.n_regionkey
          AND n.n_nationkey = s.s_nationkey
          AND s.s_suppkey = ps.ps_suppkey
          AND ps.ps_partkey = p.p_partkey
          AND r.r_name IN (2, 3) AND p.p_price > 1200.0
    """
    res = svc.submit(sql)
    g = svc.metrics_v2()["gauges"]
    print(f"\n[mesh] {g['mesh_devices']} device(s), "
          f"{g['mesh_shard_count_data']} shard(s) on axis 'data': "
          f"MIN={float(res.values['min(s.s_acctbal)']):.2f} "
          f"MAX={float(res.values['max(s.s_acctbal)']):.2f}")
    print("[mesh] explain shows placement:")
    exp = svc.explain(sql)
    print("\n".join(line for line in exp["text"].splitlines()
                    if "sharding" in line))
    sweep = [s for s in res.stats.trace.walk() if s.name == "ring_sweep"]
    print(f"[mesh] ring_sweep span: axes={sweep[0].args['axes']} "
          f"shards={sweep[0].args['shards']}")


def sql_example():
    """Same query through the SQL front-end."""
    from repro.core import parse_sql
    db, schema = make_tpch_db(scale=500, seed=0)
    q = parse_sql("""
        SELECT MIN(s.s_acctbal), MAX(s.s_acctbal)
        FROM region r, nation n, supplier s, partsupp ps, part p
        WHERE r.r_regionkey = n.n_regionkey
          AND n.n_nationkey = s.s_nationkey
          AND s.s_suppkey = ps.ps_suppkey
          AND ps.ps_partkey = p.p_partkey
          AND r.r_name IN (2, 3) AND p.p_price > 1200.0
    """, schema)
    plan = plan_query(q, schema)
    res = Executor(db, schema).execute(plan)
    print(f"\n[SQL] plan={plan.mode}  "
          f"MIN={float(res['min(s.s_acctbal)']):.2f}  "
          f"MAX={float(res['max(s.s_acctbal)']):.2f}")


if __name__ == "__main__":
    main()
    sql_example()
    serving_example()
    calibrated_planning_example()
    async_serving_example()
    multi_tenant_example()
    observability_example()
    warm_restart_example()
    tuning_example()
    mesh_serving_example()
