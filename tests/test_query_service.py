"""Serving tier: fingerprint invariance, plan cache, shape buckets,
micro-batching, lock granularity, and the eager fallback."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Executor, parse_sql, plan_query
from repro.core.query import Agg, AggQuery, Atom
from repro.data import make_stats_db, make_tpch_db
from repro.service import QueryService, canonicalize, fingerprint
from repro.service.plan_cache import LRUCache, PlanCache
from repro.tables.table import Table, bucket_capacity

jax.config.update("jax_platform_name", "cpu")

FIG1 = """
SELECT MIN(s.s_acctbal), MAX(s.s_acctbal)
FROM region r, nation n, supplier s, partsupp ps, part p
WHERE r.r_regionkey = n.n_regionkey AND n.n_nationkey = s.s_nationkey
  AND s.s_suppkey = ps.ps_suppkey AND ps.ps_partkey = p.p_partkey
  AND r.r_name IN (2, 3) AND p.p_price > 1200.0
"""
# the same query under alias renaming, FROM/WHERE reordering, swapped
# SELECT list, and reversed IN list
FIG1_RENAMED = """
SELECT MAX(su.s_acctbal), MIN(su.s_acctbal)
FROM part pa, supplier su, region re, partsupp pp, nation na
WHERE pa.p_price > 1200.0 AND na.n_nationkey = su.s_nationkey
  AND re.r_regionkey = na.n_regionkey AND pp.ps_partkey = pa.p_partkey
  AND su.s_suppkey = pp.ps_suppkey AND re.r_name IN (3, 2)
"""


# ---------------------------------------------------------------------------
# fingerprinting
# ---------------------------------------------------------------------------
def test_fingerprint_invariant_under_alias_renaming():
    _, schema = make_tpch_db(scale=5)
    fa = fingerprint(parse_sql(FIG1, schema))
    fb = fingerprint(parse_sql(FIG1_RENAMED, schema))
    assert fa == fb


def test_fingerprint_distinguishes_literals_and_structure():
    _, schema = make_tpch_db(scale=5)
    base = fingerprint(parse_sql(FIG1, schema))
    other = fingerprint(parse_sql(FIG1.replace("1200.0", "900.0"), schema))
    assert base != other
    min_only = fingerprint(parse_sql(
        "SELECT MIN(p.p_price) FROM part p", schema))
    max_only = fingerprint(parse_sql(
        "SELECT MAX(p.p_price) FROM part p", schema))
    assert min_only != max_only


def _supplier_nation_query(v: dict[str, str], order=(0, 1)) -> AggQuery:
    """MIN over supplier⋈nation with caller-chosen variable names and atom
    order — structurally one query."""
    atoms = [Atom("supplier", "s", (v["sk"], v["nk"], v["bal"])),
             Atom("nation", "n", (v["nk"], v["rk"]))]
    return AggQuery(
        atoms=tuple(atoms[i] for i in order),
        aggregates=(Agg("min", v["bal"]),),
        selections={"n": lambda c: c["n_regionkey"] > 1},
        selection_specs={"n": ((">", "n_regionkey", 1),)})


def test_fingerprint_invariant_under_variable_renaming_and_atom_order():
    base = _supplier_nation_query(
        {"sk": "sk", "nk": "nk", "bal": "bal", "rk": "rk"})
    renamed = _supplier_nation_query(
        {"sk": "x1", "nk": "x2", "bal": "x3", "rk": "x4"}, order=(1, 0))
    ca, cb = canonicalize(base), canonicalize(renamed)
    assert ca.fingerprint == cb.fingerprint
    assert ca.prefix_fingerprint == cb.prefix_fingerprint
    # structurally different: aggregate over a different variable
    other = AggQuery(
        atoms=base.atoms,
        aggregates=(Agg("min", "sk"),),
        selections=dict(base.selections),
        selection_specs=dict(base.selection_specs))
    assert canonicalize(other).fingerprint != ca.fingerprint
    # ...but the join structure is the same → prefix fingerprint shared
    assert canonicalize(other).prefix_fingerprint == ca.prefix_fingerprint


def test_fingerprint_opaque_selections_never_share():
    """Hand-built queries with closure-only selections are singletons."""
    q1 = AggQuery(
        atoms=(Atom("part", "p", ("pk", "price")),),
        aggregates=(Agg("count"),),
        selections={"p": lambda c: c["p_price"] > 100})
    q2 = AggQuery(
        atoms=(Atom("part", "p", ("pk", "price")),),
        aggregates=(Agg("count"),),
        selections={"p": lambda c: c["p_price"] > 999})
    c1, c2 = canonicalize(q1), canonicalize(q2)
    assert not c1.shareable and not c2.shareable
    assert c1.fingerprint != c2.fingerprint
    # ...but the SAME object keeps its fingerprint → repeat submissions
    # of one hand-built query still hit their singleton cache entry
    assert canonicalize(q1).fingerprint == c1.fingerprint


def test_canonical_query_plans_to_same_answer():
    """Canonicalisation is semantics-preserving: planning the canonical
    query gives the same result as planning the original."""
    db, schema = make_tpch_db(scale=60, seed=1)
    q = parse_sql(FIG1, schema)
    canon = canonicalize(q)
    ex = Executor(db, schema)
    want = ex.execute(plan_query(q, schema))
    got = canon.rename_results(
        ex.execute(plan_query(canon.query, schema)))
    for key in ("min(s.s_acctbal)", "max(s.s_acctbal)"):
        np.testing.assert_allclose(float(got[key]), float(want[key]))


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------
def test_lru_cache_counters_and_eviction():
    c = LRUCache(2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1           # refresh a
    c.put("c", 3)                    # evicts b (LRU)
    assert c.get("b") is None
    assert c.get("a") == 1 and c.get("c") == 3
    m = c.counters()
    assert m["evictions"] == 1 and m["hits"] == 3 and m["misses"] == 1


def test_plan_cache_invalidate_relation():
    pc = PlanCache(4, 4)
    pc.get_executable("fp1", (("part", 128), ("supplier", 64)), lambda: "x")
    pc.get_executable("fp2", (("nation", 32),), lambda: "y")
    assert pc.invalidate_relation("part") == 1
    assert PlanCache.exec_key("fp2", (("nation", 32),)) in pc.execs
    assert PlanCache.exec_key(
        "fp1", (("part", 128), ("supplier", 64))) not in pc.execs


def test_physical_plan_hashable_and_comparable():
    _, schema = make_tpch_db(scale=5)
    q = parse_sql(FIG1, schema)
    p1 = plan_query(q, schema)
    p2 = plan_query(q, schema)
    assert p1 == p2 and hash(p1) == hash(p2)
    p_ref = plan_query(q, schema, mode="ref")
    assert p1 != p_ref
    assert len({p1, p2, p_ref}) == 2


# ---------------------------------------------------------------------------
# table padding / buckets
# ---------------------------------------------------------------------------
def test_bucket_capacity_powers_of_two():
    assert bucket_capacity(1) == 8      # min floor
    assert bucket_capacity(8) == 8
    assert bucket_capacity(9) == 16
    assert bucket_capacity(4000) == 4096
    assert bucket_capacity(4096) == 4096
    assert bucket_capacity(4097) == 8192


def test_pad_to_is_semantically_free():
    db, schema = make_tpch_db(scale=40, seed=5)
    q = parse_sql(FIG1, schema)
    plan = plan_query(q, schema)
    want = Executor(db, schema).execute(plan)
    padded = {name: t.pad_to(bucket_capacity(t.capacity))
              for name, t in db.items()}
    got = Executor(padded, schema).execute(plan)
    for key in ("min(s.s_acctbal)", "max(s.s_acctbal)"):
        np.testing.assert_allclose(float(got[key]), float(want[key]))
    with pytest.raises(ValueError, match="never shrink"):
        db["part"].pad_to(1)


# ---------------------------------------------------------------------------
# QueryService
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tpch_service():
    db, schema = make_tpch_db(scale=50, seed=3)
    return QueryService(db, schema), db, schema


def test_service_warm_requests_hit_both_cache_levels(tpch_service):
    svc, db, schema = tpch_service
    cold = svc.submit(FIG1)
    assert not cold.stats.plan_cache_hit or svc.metrics()["requests"] > 1
    warm = svc.submit(FIG1_RENAMED)   # structurally identical
    assert warm.stats.plan_cache_hit and warm.stats.exec_cache_hit
    np.testing.assert_allclose(
        float(warm.values["min(su.s_acctbal)"]),
        float(cold.values["min(s.s_acctbal)"]))
    # answers match a from-scratch eager run
    want = Executor(db, schema).execute(
        plan_query(parse_sql(FIG1, schema), schema))
    np.testing.assert_allclose(float(cold.values["max(s.s_acctbal)"]),
                               float(want["max(s.s_acctbal)"]))


def test_service_microbatch_dedup(tpch_service):
    svc, _, _ = tpch_service
    before = svc.metrics()
    results = svc.submit_many([FIG1, FIG1_RENAMED, FIG1])
    after = svc.metrics()
    assert after["dedup_saved"] - before["dedup_saved"] == 2
    assert after["compiles"] == before["compiles"]  # warm fingerprint
    shared = [r.stats.shared_execution for r in results]
    assert shared == [False, True, True]
    vals = [float(r.values[next(k for k in r.values if k.startswith("min"))])
            for r in results]
    assert vals[0] == vals[1] == vals[2]


def test_service_group_by_renames_outputs(tpch_service):
    svc, db, _ = tpch_service
    res = svc.submit("""
        SELECT COUNT(*) AS cnt FROM supplier s, nation n
        WHERE s.s_nationkey = n.n_nationkey GROUP BY n.n_regionkey
    """)
    cols, valid = res.values["groups"], np.asarray(res.values["valid"])
    assert "cnt" in cols and "n.n_regionkey" in cols
    got = sum(int(c) for c, v in zip(np.asarray(cols["cnt"]), valid) if v)
    assert got == int(db["supplier"].live_count())


def test_service_same_bucket_growth_zero_recompiles():
    db, schema = make_tpch_db(scale=50, seed=7)
    svc = QueryService(db, schema)
    svc.submit(FIG1)
    compiles = svc.metrics()["compiles"]

    # grow partsupp inside its bucket: capacity 4000 → bucket 4096
    ps = db["partsupp"]
    bucket = bucket_capacity(ps.capacity)
    extra = bucket - ps.capacity
    assert extra > 0
    rng = np.random.default_rng(0)
    grown = {
        "ps_partkey": np.concatenate([np.asarray(ps.columns["ps_partkey"]),
                                      rng.integers(0, 1000, extra)]).astype(np.int32),
        "ps_suppkey": np.concatenate([np.asarray(ps.columns["ps_suppkey"]),
                                      rng.integers(0, 50, extra)]).astype(np.int32),
        "ps_supplycost": np.concatenate(
            [np.asarray(ps.columns["ps_supplycost"]),
             rng.gamma(2.0, 150.0, extra).astype(np.float32)]),
    }
    svc.update_table("partsupp", Table.from_numpy(grown))
    res = svc.submit(FIG1)
    m = svc.metrics()
    assert m["compiles"] == compiles          # zero recompiles
    assert m["bucket_invalidations"] == 0
    assert res.stats.exec_cache_hit

    # a dtype drift would be a cache "hit" that silently re-traces inside
    # jax.jit — update_table must refuse it
    bad = dict(grown)
    bad["ps_supplycost"] = bad["ps_supplycost"].astype(np.int32)
    with pytest.raises(ValueError, match="dtype"):
        svc.update_table("partsupp", Table.from_numpy(bad))

    # crossing the bucket boundary must invalidate and recompile
    bigger = {k: np.concatenate([v, v[:8]]) for k, v in grown.items()}
    svc.update_table("partsupp", Table.from_numpy(bigger))
    res2 = svc.submit(FIG1)
    m2 = svc.metrics()
    assert m2["bucket_invalidations"] == 1
    assert m2["compiles"] == compiles + 1
    assert not res2.stats.exec_cache_hit
    np.testing.assert_allclose(
        float(res2.values["min(s.s_acctbal)"]),
        float(res.values["min(s.s_acctbal)"]))


@pytest.mark.parametrize("live", [True, False])
def test_service_refuses_keys_outside_declared_domain(live):
    """A declared domain is a contract the joins read: the dense freq-join
    gives keys outside it no partner.  So data whose live rows break it is
    refused, on update and at construction, and the service goes on
    answering over the data it holds; a dead row may hold any value."""
    db, schema = make_tpch_db(scale=50, seed=7)
    svc = QueryService(db, schema)
    count = "SELECT COUNT(*) FROM supplier s, partsupp ps " \
            "WHERE s.s_suppkey = ps.ps_suppkey"
    want = int(svc.submit(count).values["count(*)"])
    dom = schema.relations["partsupp"].meta("ps_suppkey").domain
    cols = {c: np.concatenate([np.asarray(v), np.asarray(v)[:1]])
            for c, v in db["partsupp"].columns.items()}
    cols["ps_suppkey"][-1] = dom          # one row past the domain
    freq = np.ones(len(cols["ps_suppkey"]), np.int32)
    freq[-1] = int(live)
    grown = Table({c: jnp.asarray(v) for c, v in cols.items()},
                  jnp.asarray(freq))
    if live:
        with pytest.raises(ValueError, match="ps_suppkey: 1 live rows"):
            svc.update_table("partsupp", grown)
        with pytest.raises(ValueError, match="declared domains"):
            QueryService({**db, "partsupp": grown}, schema)
    else:
        svc.update_table("partsupp", grown)
    assert int(svc.submit(count).values["count(*)"]) == want


def test_service_eager_fallback_for_unguarded_plans():
    """MEDIAN over an FK/FK join is guarded only when the guard covers the
    output vars; an unguarded aggregate must fall back to the eager
    materialising path and still answer."""
    db, schema = make_stats_db(n_users=20, n_posts=50, n_comments=120,
                               n_votes=40, seed=1)
    svc = QueryService(db, schema)
    # aggregate vars spread over two atoms → no guard → ref plan
    q = AggQuery(
        atoms=(Atom("posts", "po", ("pid", "uid", "score")),
               Atom("comments", "co", ("pid", "cuid", "cscore"))),
        aggregates=(Agg("median", "score"), Agg("median", "cscore")))
    res = svc.submit(q)
    assert res.stats.mode == "ref"
    assert res.stats.exec_stats is not None
    assert res.stats.exec_stats.peak_tuples > 0
    assert svc.metrics()["eager_requests"] == 1


def test_service_concurrent_submissions_are_safe():
    db, schema = make_tpch_db(scale=30, seed=9)
    svc = QueryService(db, schema)
    svc.submit(FIG1)  # warm once so threads race on the hot path
    errors: list = []
    outs: list = []

    def worker():
        try:
            r = svc.submit(FIG1_RENAMED)
            outs.append(float(r.values["min(su.s_acctbal)"]))
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(set(outs)) == 1
    assert svc.metrics()["compiles"] == 1


def test_metrics_and_updates_not_blocked_by_compile():
    """Regression: the service lock guards only cache/db mutation — a
    long XLA compile in one thread must not block ``metrics()`` (or
    ``update_table``) in another."""
    db, schema = make_tpch_db(scale=30, seed=11)
    svc = QueryService(db, schema)
    compiling = threading.Event()
    release = threading.Event()
    real_compile = svc._jit_executor.compile

    def slow_compile(plan, **kw):
        compiling.set()
        assert release.wait(30), "test orchestration stalled"
        return real_compile(plan, **kw)

    svc._jit_executor.compile = slow_compile
    out: list = []
    t = threading.Thread(target=lambda: out.append(svc.submit(FIG1)))
    t.start()
    try:
        assert compiling.wait(30)
        t0 = time.perf_counter()
        m = svc.metrics()                       # must not wait on compile
        grown = {k: np.asarray(v)
                 for k, v in db["region"].columns.items()}
        svc.update_table("region", Table.from_numpy(grown))
        blocked_s = time.perf_counter() - t0
    finally:
        release.set()
        t.join(60)
    assert blocked_s < 1.0
    assert m["requests"] == 1 and m["compiles"] == 0
    assert out and "min(s.s_acctbal)" in out[0].values


def test_concurrent_cold_submissions_compile_once():
    """Two threads racing on the same cold fingerprint: the in-flight
    event makes the second wait for the first's executable instead of
    compiling its own."""
    db, schema = make_tpch_db(scale=30, seed=12)
    svc = QueryService(db, schema)
    results: list = []
    errors: list = []

    def worker(sql):
        try:
            r = svc.submit(sql)
            key = next(k for k in r.values if k.startswith("min"))
            results.append(float(r.values[key]))
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker,
                                args=(FIG1 if i % 2 else FIG1_RENAMED,))
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert svc.metrics()["compiles"] == 1
    assert len(set(results)) == 1


def test_submit_many_isolates_bad_requests(tpch_service):
    """Regression: one malformed query (unknown relation, SQL syntax
    error) must not abort its batch-mates — its error attaches to its own
    QueryResult, everyone else gets answers."""
    svc, _, _ = tpch_service
    want = svc.submit(FIG1)
    base = svc.metrics()
    res = svc.submit_many([FIG1,
                           "SELECT MIN(x.nope) FROM nowhere x",
                           FIG1_RENAMED,
                           "SELECT FROM WHERE"])
    assert [r.error is None for r in res] == [True, False, True, False]
    assert res[1].values == {} and res[3].values == {}
    assert "nowhere" in str(res[1].error)
    np.testing.assert_array_equal(
        np.asarray(res[0].values["min(s.s_acctbal)"]),
        np.asarray(want.values["min(s.s_acctbal)"]))
    np.testing.assert_array_equal(
        np.asarray(res[2].values["min(su.s_acctbal)"]),
        np.asarray(want.values["min(s.s_acctbal)"]))
    m = svc.metrics()
    assert m["request_errors"] - base["request_errors"] == 2
    # submit() re-raises the captured error for single-query callers
    with pytest.raises(Exception, match="nowhere"):
        svc.submit("SELECT MIN(x.nope) FROM nowhere x")


def test_submit_many_empty_batch_counts_nothing(tpch_service):
    """Regression: submit_many([]) used to increment the batches
    counter."""
    svc, _, _ = tpch_service
    before = svc.metrics()
    assert svc.submit_many([]) == []
    assert svc.submit_many(iter([])) == []
    after = svc.metrics()
    assert after["batches"] == before["batches"]
    assert after["requests"] == before["requests"]


def test_submit_many_accepts_any_iterable(tpch_service):
    """Regression: counting len(queries) up front broke generator
    inputs."""
    svc, _, _ = tpch_service
    res = svc.submit_many(q for q in [FIG1])
    assert res[0].error is None and res[0].values


def test_padded_view_cache_bounded():
    """Regression: the bucket-padded view cache was unbounded across
    relations; it is now an LRU level of the plan cache."""
    db, schema = make_tpch_db(scale=30, seed=5)
    svc = QueryService(db, schema, padded_capacity=2)
    first = svc.submit(FIG1)            # scans 5 relations
    m = svc.metrics()
    assert m["padded_relations"] <= 2
    assert m["padded_evictions"] >= 3
    # eviction is a cache concern only — answers are unaffected
    again = svc.submit(FIG1)
    np.testing.assert_array_equal(
        np.asarray(first.values["min(s.s_acctbal)"]),
        np.asarray(again.values["min(s.s_acctbal)"]))


def test_metrics_and_updates_not_blocked_by_planning(monkeypatch):
    """Regression: _plan_unit used to run the whole plan_query rewrite
    pipeline while holding the service lock; metrics()/update_table were
    stuck behind it.  Planning now builds behind an in-flight event like
    a compile."""
    import repro.service.engine as engine_mod
    db, schema = make_tpch_db(scale=30, seed=13)
    svc = QueryService(db, schema)
    planning = threading.Event()
    release = threading.Event()
    real_plan = engine_mod.plan_query

    def slow_plan(*args, **kwargs):
        planning.set()
        assert release.wait(30), "test orchestration stalled"
        return real_plan(*args, **kwargs)

    monkeypatch.setattr(engine_mod, "plan_query", slow_plan)
    out: list = []
    t = threading.Thread(target=lambda: out.append(svc.submit(FIG1)))
    t.start()
    try:
        assert planning.wait(30)
        t0 = time.perf_counter()
        m = svc.metrics()                  # must not wait on planning
        svc.update_table("region", Table.from_numpy(
            {k: np.asarray(v) for k, v in db["region"].columns.items()}))
        blocked_s = time.perf_counter() - t0
    finally:
        release.set()
        t.join(60)
    assert blocked_s < 1.0
    assert m["requests"] == 1 and m["plan_misses"] == 0
    assert out and "min(s.s_acctbal)" in out[0].values


def test_metrics_and_updates_not_blocked_by_padding(monkeypatch):
    """Regression: _snapshot used to run Table.pad_to (device work) while
    holding the service lock; padding now happens outside it against an
    immutable table snapshot."""
    db, schema = make_tpch_db(scale=30, seed=14)
    svc = QueryService(db, schema)
    padding = threading.Event()
    release = threading.Event()
    real_pad = Table.pad_to

    def slow_pad(self, cap):
        padding.set()
        assert release.wait(30), "test orchestration stalled"
        return real_pad(self, cap)

    monkeypatch.setattr(Table, "pad_to", slow_pad)
    out: list = []
    t = threading.Thread(target=lambda: out.append(svc.submit(FIG1)))
    t.start()
    try:
        assert padding.wait(30)
        t0 = time.perf_counter()
        m = svc.metrics()                  # must not wait on pad_to
        svc.update_table("region", Table.from_numpy(
            {k: np.asarray(v) for k, v in db["region"].columns.items()}))
        blocked_s = time.perf_counter() - t0
    finally:
        release.set()
        t.join(60)
    assert blocked_s < 1.0
    assert m["requests"] == 1
    assert out and "min(s.s_acctbal)" in out[0].values


def test_compile_rejects_eager_only_options():
    db, schema = make_tpch_db(scale=10)
    q = parse_sql(FIG1, schema)
    plan = plan_query(q, schema)
    guarded = Executor(db, schema, oom_guard=1000)
    with pytest.raises(ValueError, match="eager-only"):
        guarded.compile(plan)
    # jittable() strips the guard
    fn = guarded.jittable().compile(plan)
    out = fn(db)
    assert "min(s.s_acctbal)" in out
