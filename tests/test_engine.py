"""Engine behaviour tests: GYO, 0MA classification, plan-class equivalence
(ref == opt == opt_plus == brute force), the paper's running example, and
materialisation accounting (the Fig. 6 invariant)."""

import collections
import dataclasses
import itertools

import jax
import numpy as np
import pytest

from repro.core import (
    Agg,
    AggQuery,
    Atom,
    Executor,
    classify,
    build_join_tree,
    plan_query,
)
from repro.data import (
    make_graph_db,
    make_stats_db,
    make_tpch_db,
    path_query,
    tree_query,
)
from repro.core.plan import FreqJoinOp, SemiJoinOp
from repro.data.relational import stats_count_query, tpch_v1_query
from repro.kernels.ops import DENSE_DOMAIN_CAP, DENSE_RATIO
from repro.service import QueryService

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# brute force oracle over tiny databases
# ---------------------------------------------------------------------------
def brute_force_count(db, schema, query):
    """Enumerate all homomorphisms (python product loop) and count."""
    rows = {}
    for a in query.atoms:
        tab = db[a.rel]
        rel = schema.relations[a.rel]
        cols = [np.asarray(tab.columns[c]) for c in rel.column_names()]
        live = np.asarray(tab.freq) > 0
        sel = query.selections.get(a.alias)
        if sel is not None:
            m = sel({c: np.asarray(tab.columns[c])
                     for c in rel.column_names()})
            live &= np.asarray(m)
        rows[a.alias] = [tuple(c[i] for c in cols)
                         for i in range(len(live)) if live[i]]
    count = 0
    for combo in itertools.product(*[rows[a.alias] for a in query.atoms]):
        binding = {}
        ok = True
        for a, tup in zip(query.atoms, combo):
            for v, val in zip(a.vars, tup):
                if v in binding and binding[v] != val:
                    ok = False
                    break
                binding[v] = val
            if not ok:
                break
        if ok:
            count += 1
    return count


# ---------------------------------------------------------------------------
# GYO / classification
# ---------------------------------------------------------------------------
def test_path_query_is_acyclic_and_tree_connected():
    q = path_query(3)
    t = build_join_tree(q.atoms)
    assert t is not None
    # connectedness: shared var of any two atoms occurs on the path
    assert len(t.postorder()) == 4


def test_triangle_is_cyclic():
    atoms = (
        Atom("edge", "e1", ("a", "b")),
        Atom("edge", "e2", ("b", "c")),
        Atom("edge", "e3", ("c", "a")),
    )
    assert build_join_tree(atoms) is None
    q = AggQuery(atoms=atoms, aggregates=(Agg("count"),))
    _, schema = make_graph_db(10, 10)
    with pytest.raises(ValueError, match="cyclic"):
        plan_query(q, schema)


def test_count_star_is_guarded_not_set_safe():
    _, schema = make_graph_db(10, 10)
    q = path_query(2)
    cls = classify(q, schema)
    assert cls.acyclic and cls.guarded and not cls.set_safe
    assert not cls.is_oma


def test_min_max_query_is_oma():
    _, schema = make_tpch_db(scale=10)
    q = tpch_v1_query("minmax")
    cls = classify(q, schema)
    assert cls.is_oma
    # guard must hold the aggregate var (s_acctbal lives in supplier)
    assert cls.guard == "s"


def test_fkpk_makes_count_set_safe():
    """All joins in the TPC-H V.1 tree are FK→PK from parent to child once
    rooted at partsupp... but rooted at the guard `s`, the ps subtree is
    child-side FK — so COUNT over the v1 query is NOT schema-set-safe,
    while a pure FK→PK chain is."""
    _, schema = make_tpch_db(scale=10)
    atoms = (
        Atom("supplier", "s", ("sk", "nk", "bal")),
        Atom("nation", "n", ("nk", "rk")),
        Atom("region", "r", ("rk", "rname")),
    )
    q = AggQuery(atoms=atoms, aggregates=(Agg("count"),))
    cls = classify(q, schema)
    # chain supplier→nation→region is FK→PK all the way: COUNT is safe
    assert cls.guarded and cls.set_safe and cls.is_oma


def test_median_query_guarded_not_oma():
    _, schema = make_tpch_db(scale=10)
    q = tpch_v1_query("median")
    cls = classify(q, schema)
    assert cls.guarded and not cls.is_oma


# ---------------------------------------------------------------------------
# plan-class equivalence on counting queries
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("qmaker", [lambda: path_query(2),
                                    lambda: path_query(3),
                                    lambda: tree_query(1),
                                    lambda: tree_query(2),
                                    lambda: tree_query(3)])
def test_plan_classes_agree_with_brute_force(qmaker):
    db, schema = make_graph_db(n_nodes=12, n_edges=40, seed=3)
    q = qmaker()
    want = brute_force_count(db, schema, q)
    ex = Executor(db, schema)
    for mode in ("ref", "opt", "opt_plus"):
        plan = plan_query(q, schema, mode=mode)
        got = ex.execute(plan)["count(*)"]
        assert int(got) == want, (mode, int(got), want)


@pytest.mark.parametrize("use_fkpk", [False, True])
def test_stats_count_modes_agree(use_fkpk):
    db, schema = make_stats_db(n_users=40, n_posts=120, n_comments=300,
                               n_votes=200, seed=1)
    q = stats_count_query()
    ex = Executor(db, schema)
    ref = ex.execute(plan_query(q, schema, mode="ref"))["count(*)"]
    for mode in ("opt", "opt_plus"):
        plan = plan_query(q, schema, mode=mode, use_fkpk=use_fkpk)
        got = ex.execute(plan)["count(*)"]
        assert int(got) == int(ref)


# ---------------------------------------------------------------------------
# the paper's running example
# ---------------------------------------------------------------------------
def test_tpch_v1_minmax_oma_vs_ref():
    db, schema = make_tpch_db(scale=50, seed=2)
    q = tpch_v1_query("minmax")
    ex = Executor(db, schema)
    auto = plan_query(q, schema)          # should pick oma
    assert auto.mode == "oma"
    r_oma = ex.execute(auto)
    r_ref = ex.execute(plan_query(q, schema, mode="ref"))
    np.testing.assert_allclose(float(r_oma["min(bal)"]),
                               float(r_ref["min(bal)"]), rtol=1e-6)
    np.testing.assert_allclose(float(r_oma["max(bal)"]),
                               float(r_ref["max(bal)"]), rtol=1e-6)


def test_tpch_v1_median_freq_prop_vs_ref():
    db, schema = make_tpch_db(scale=30, seed=4)
    q = tpch_v1_query("median")
    ex = Executor(db, schema)
    auto = plan_query(q, schema)          # guarded, not 0MA → opt_plus
    assert auto.mode == "opt_plus"
    med_opt = float(ex.execute(auto)["median(bal)"])
    med_ref = float(ex.execute(plan_query(q, schema, mode="ref"))["median(bal)"])
    assert med_opt == med_ref


def test_tpch_v1_fkpk_plan_uses_semijoins():
    """§4.3 / Example 4.2: with FK/PK info every FreqJoin in the V.1 plan
    degrades to a semi-join."""
    from repro.core.plan import FreqJoinOp, SemiJoinOp
    _, schema = make_tpch_db(scale=10)
    q = tpch_v1_query("median")
    plan = plan_query(q, schema, mode="opt_plus", use_fkpk=True)
    kinds = [type(op).__name__ for op in plan.ops]
    assert "SemiJoinOp" in kinds
    # the ps→p and s→ps edges: ps child of s is NOT fk/pk (s holds PK),
    # so at least one FreqJoin must remain
    assert any(isinstance(op, FreqJoinOp) for op in plan.ops)


@pytest.mark.parametrize("grouped", [False, True], ids=["scalar", "grouped"])
@pytest.mark.parametrize("mode", ["ref", "opt", "opt_plus"])
def test_join_with_no_answers(mode, grouped):
    """A selection no part passes: the eager baselines' joins materialise
    no row at all, the frequency plans keep dead rows.  Every plan class
    answers as dead rows do — COUNT 0, MIN and MEDIAN at the dtype's max,
    MAX at its min, no live group — instead of failing on an empty
    array."""
    db, schema = make_tpch_db(scale=10, seed=1)
    base = tpch_v1_query("minmax", price_threshold=1e9)
    aggs = (Agg("min", "bal"), Agg("max", "bal"), Agg("median", "bal"),
            Agg("count"))
    q = AggQuery(atoms=base.atoms, aggregates=aggs,
                 group_by=("nk",) if grouped else (),
                 selections=base.selections)
    res = Executor(db, schema).execute(plan_query(q, schema, mode=mode))
    big = np.finfo(np.float32).max
    if grouped:
        assert not np.asarray(res["valid"]).any()
        return
    assert int(res["count(*)"]) == 0
    assert float(res["min(bal)"]) == big
    assert float(res["median(bal)"]) == big
    assert float(res["max(bal)"]) == -big


# ---------------------------------------------------------------------------
# group-by, avg, sum
# ---------------------------------------------------------------------------
def test_group_by_count_matches_numpy():
    db, schema = make_stats_db(n_users=30, n_posts=100, n_comments=250,
                               n_votes=150, seed=7)
    atoms = (
        Atom("posts", "po", ("pid", "uid", "score")),
        Atom("comments", "co", ("pid", "cuid", "cscore")),
    )
    q = AggQuery(atoms=atoms, aggregates=(Agg("count"),),
                 group_by=("uid",))
    ex = Executor(db, schema)
    res = ex.execute(plan_query(q, schema, mode="opt_plus"))
    got = {}
    cols, valid = res["groups"], res["valid"]
    for u, c, v in zip(np.asarray(cols["uid"]),
                       np.asarray(cols["count(*)"]), np.asarray(valid)):
        if v:
            got[int(u)] = int(c)
    # numpy oracle
    po, co = db["posts"], db["comments"]
    want: dict[int, int] = {}
    pid2uid = dict(zip(np.asarray(po.columns["p_id"]).tolist(),
                       np.asarray(po.columns["p_owner"]).tolist()))
    for pid in np.asarray(co.columns["c_post"]).tolist():
        if pid in pid2uid:
            want[pid2uid[pid]] = want.get(pid2uid[pid], 0) + 1
    assert got == want


def test_sum_avg_agree_across_modes():
    db, schema = make_stats_db(n_users=25, n_posts=80, n_comments=200,
                               n_votes=100, seed=9)
    atoms = (
        Atom("posts", "po", ("pid", "uid", "score")),
        Atom("comments", "co", ("pid", "cuid", "cscore")),
        Atom("votes", "v", ("pid", "vuid")),
    )
    q = AggQuery(atoms=atoms,
                 aggregates=(Agg("sum", "score"), Agg("avg", "score")))
    ex = Executor(db, schema)
    r_ref = ex.execute(plan_query(q, schema, mode="ref"))
    r_opt = ex.execute(plan_query(q, schema, mode="opt_plus"))
    assert int(r_ref["sum(score)"]) == int(r_opt["sum(score)"])
    np.testing.assert_allclose(float(r_ref["avg(score)"]),
                               float(r_opt["avg(score)"]), rtol=1e-5)


# ---------------------------------------------------------------------------
# materialisation accounting (Fig. 6 invariant)
# ---------------------------------------------------------------------------
def test_opt_plus_never_materialises_beyond_base_relations():
    db, schema = make_graph_db(n_nodes=15, n_edges=60, seed=11)
    q = path_query(4)
    ex = Executor(db, schema)
    plan = plan_query(q, schema, mode="opt_plus")
    stats = ex.execute(plan)["__stats__"]
    base_max = max(int(t.live_count()) for t in db.values())
    assert stats.peak_tuples <= base_max
    # ref must materialise (strictly) more on this blown-up query
    ref_stats = ex.execute(plan_query(q, schema, mode="ref"))["__stats__"]
    assert ref_stats.peak_tuples > base_max


def test_oom_guard_fires_like_paper_X_entries():
    from repro.core import MaterialisationLimit
    db, schema = make_graph_db(n_nodes=20, n_edges=300, seed=13)
    q = path_query(5)
    ex = Executor(db, schema, oom_guard=10_000)
    with pytest.raises(MaterialisationLimit):
        ex.execute(plan_query(q, schema, mode="ref"))
    # opt_plus sails through the same guard
    ex.execute(plan_query(q, schema, mode="opt_plus"))


# ---------------------------------------------------------------------------
# jit path
# ---------------------------------------------------------------------------
def test_compiled_plan_matches_eager():
    db, schema = make_graph_db(n_nodes=12, n_edges=50, seed=17)
    q = path_query(3)
    ex = Executor(db, schema)
    plan = plan_query(q, schema, mode="opt_plus")
    eager = int(ex.execute(plan)["count(*)"])
    fn = ex.compile(plan)
    assert int(fn(db)["count(*)"]) == eager
    # and again (cache hit, no retrace errors)
    assert int(fn(db)["count(*)"]) == eager


# ---------------------------------------------------------------------------
# dense-domain dispatch: declared key domains choose the scatter-add path,
# and its answers are the sorted path's (the same data, domains stripped)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("qmaker", [lambda: path_query(3),
                                    lambda: tree_query(2)])
def test_dense_domain_freqjoin_equivalence(qmaker):
    db, schema = make_graph_db(n_nodes=14, n_edges=45, seed=21)
    plan = plan_query(qmaker(), schema, mode="opt_plus")
    dense = Executor(db, schema)
    sort = Executor(db, schema.without_domains())
    assert int(dense.execute(plan)["count(*)"]) == \
        int(sort.execute(plan)["count(*)"])
    assert dense.joins["dense"] > 0 and not dense.joins["sorted"]
    assert sort.joins["sorted"] > 0 and not sort.joins["dense"]


def test_dense_domain_semijoin_equivalence():
    db, schema = make_tpch_db(scale=40, seed=6)
    plan = plan_query(tpch_v1_query("minmax"), schema, mode="oma")
    dense = Executor(db, schema)
    sort = Executor(db, schema.without_domains())
    r1, r2 = sort.execute(plan), dense.execute(plan)
    assert float(r1["min(bal)"]) == float(r2["min(bal)"])
    assert float(r1["max(bal)"]) == float(r2["max(bal)"])
    assert dense.joins["dense"] > 0 and not dense.joins["sorted"]
    assert sort.joins["sorted"] > 0 and not sort.joins["dense"]


def _with_domain(schema, domain):
    """`schema` with every declared domain replaced by `domain`."""
    return dataclasses.replace(schema, relations={
        name: dataclasses.replace(rel, columns=tuple(
            c if c.domain is None else dataclasses.replace(c, domain=domain)
            for c in rel.columns))
        for name, rel in schema.relations.items()})


@pytest.mark.parametrize("domain, path", [
    (1 << 20, "dense"),         # at the crossover max(4·n_child, 2^20)
    ((1 << 20) + 1, "sorted"),  # just above it: too sparse for the child
    (None, "sorted"),           # undeclared
])
def test_declared_domain_chooses_the_join_path(domain, path):
    db, schema = make_graph_db(n_nodes=14, n_edges=20, seed=21)
    q = path_query(3)
    plan = plan_query(q, schema, mode="opt_plus")
    want = brute_force_count(db, schema, q)
    ex = Executor(db, _with_domain(schema, domain))
    assert int(ex.execute(plan)["count(*)"]) == want
    assert set(ex.joins) == {path}
    # a compiled program tallies the same paths, counted at trace time
    joins = collections.Counter()
    fn = ex.compile(plan, joins=joins)
    assert int(fn(db)["count(*)"]) == want
    assert set(joins) == {path} and sum(joins.values()) == len(
        [op for op in plan.ops if isinstance(op, (FreqJoinOp, SemiJoinOp))])


@pytest.mark.parametrize("domain, path", [(DENSE_DOMAIN_CAP - 1, "dense"),
                                          (DENSE_DOMAIN_CAP, "sorted")])
def test_dense_domain_cap_holds_whatever_the_tuning(domain, path):
    """A child long enough for ``DENSE_RATIO`` to admit a 2^31-slot domain
    still sorts: the int32 cap binds.  The program is traced and lowered
    at that length, never run."""
    db, schema = make_graph_db(n_nodes=14, n_edges=45, seed=21)
    plan = plan_query(path_query(3), schema, mode="opt_plus")
    ex = Executor(db, _with_domain(schema, domain))
    rows = DENSE_DOMAIN_CAP // DENSE_RATIO
    spec = {rel: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((rows,), a.dtype), t)
        for rel, t in db.items()}
    joins = collections.Counter()
    ex.compile(plan, joins=joins).lower(spec)
    assert set(joins) == {path}


def test_graph_query_served_declared_and_stripped():
    """A tree query through the service over a declared schema and over
    the same schema with its domains stripped: the dense path and the
    sorted path both equal brute force."""
    db, schema = make_graph_db(n_nodes=12, n_edges=24, seed=3)
    q = tree_query(1)
    want = brute_force_count(db, schema, q)
    for sch, path in ((schema, "dense"), (_with_domain(schema, None),
                                          "sorted")):
        svc = QueryService(db, sch, mode="opt_plus")
        assert int(svc.submit(q).values["count(*)"]) == want
        m = svc.metrics()
        assert m[f"joins_{path}"] > 0
        assert m["joins_dense" if path == "sorted" else "joins_sorted"] == 0
