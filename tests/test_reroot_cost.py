"""The root of a COUNT(*) with several guards, chosen from the statistics:
where no guard is FK/PK-safe, the planner roots the join tree where the
counts are narrowest and the joins need the fewest 32-bit limbs.  A
three-edge walk over a hub-skewed graph moves to its middle atom, where
both joins scatter one limb; ties, FK/PK-safe guards and plans made
without statistics keep the guard classify chose; every path answers
numpy's int64 counts."""

import json
import re

import jax
import numpy as np
import pytest

from repro.core import Executor, parse_sql, plan_query
from repro.core.rewrite import rerooted
from repro.core.stats import StatsCatalog, count_plan, widest
from repro.data import make_tpch_db
from repro.service import QueryService
from repro.tables.table import ColumnMeta, RelSchema, Schema, Table

jax.config.update("jax_platform_name", "cpu")

VERTICES = 1024
HUB = 1 << 16            # self-loops on vertex 0: 17-bit degrees

SCHEMA = Schema(relations={"edge": RelSchema("edge", (
    ColumnMeta("src", domain=VERTICES), ColumnMeta("dst", domain=VERTICES)))},
    foreign_keys=())

WALK2 = "SELECT COUNT(*) FROM edge e0, edge e1 WHERE e0.dst = e1.src"
WALK3 = ("SELECT COUNT(*) FROM edge e0, edge e1, edge e2 "
         "WHERE e0.dst = e1.src AND e1.dst = e2.src")
STAR2 = "SELECT COUNT(*) FROM edge e0, edge e1 WHERE e0.src = e1.src"
QUERIES = {"walk2": WALK2, "walk3": WALK3, "star2": STAR2}


@pytest.fixture(scope="module")
def edges():
    """A hub of 2^16 self-loops beside 4,096 random edges: the most rows
    on one key needs 17 bits, so a limb holds 15 bits, and walk3's outer
    join under the first root carries out-degrees of 17 bits."""
    rng = np.random.default_rng(17)
    src = np.concatenate([np.zeros(HUB, np.int32),
                          rng.integers(0, VERTICES, 4096).astype(np.int32)])
    dst = np.concatenate([np.zeros(HUB, np.int32),
                          rng.integers(0, VERTICES, 4096).astype(np.int32)])
    return src, dst


@pytest.fixture(scope="module")
def db(edges):
    return {"edge": Table.from_numpy({"src": edges[0], "dst": edges[1]})}


@pytest.fixture(scope="module")
def catalog(db):
    cat = StatsCatalog(SCHEMA)
    cat.refresh("edge", db["edge"], db)
    return cat


def reference(edges) -> dict[str, int]:
    """Each count from the degree vectors, in numpy int64."""
    src, dst = (a.astype(np.int64) for a in edges)
    out = np.bincount(src, minlength=VERTICES)
    into = np.bincount(dst, minlength=VERTICES)
    return {"walk2": int((into * out).sum()),
            "walk3": int((into[src] * out[dst]).sum()),
            "star2": int((out * out).sum())}


def plan(sql, stats=None):
    return plan_query(parse_sql(sql, SCHEMA), SCHEMA, mode="opt_plus",
                      stats=stats)


def limbs(p, catalog) -> list[int]:
    counts = count_plan(p, catalog.tables())
    assert counts.bits == 64
    return sorted(n for _, n in counts.limbs.values())


def test_walk3_roots_at_its_middle_atom_where_each_join_needs_one_limb(
        catalog):
    first = plan(WALK3)
    assert first.tree.root == "e0" and not rerooted(first)
    # under the first root the outer join carries out-degrees: two limbs
    assert limbs(first, catalog) == [1, 2]
    chosen = plan(WALK3, catalog)
    assert chosen.tree.root == "e1" and rerooted(chosen)
    assert limbs(chosen, catalog) == [1, 1]
    (d,) = [d for d in chosen.decisions if d.pass_name == "reroot_guard"]
    assert d.applied and d.target == "e1"
    costs = dict(d.stats)
    assert {costs[f"{g}.bits"] for g in ("e0", "e1", "e2")} == {64}
    assert costs["e1.limb_rows"] < costs["e0.limb_rows"] \
        == costs["e2.limb_rows"]


@pytest.mark.parametrize("sql", [WALK2, STAR2], ids=["walk2", "star2"])
def test_a_tie_keeps_the_guard_classify_chose(sql, catalog):
    p = plan(sql, catalog)
    assert p.tree.root == "e0" and not rerooted(p)
    (d,) = [d for d in p.decisions if d.pass_name == "reroot_guard"]
    costs = dict(d.stats)
    assert costs["e0.limb_rows"] == costs["e1.limb_rows"]
    assert p == plan(sql)


def test_a_count_with_an_fk_pk_safe_guard_keeps_it():
    tpch_db, schema = make_tpch_db(scale=5)
    cat = StatsCatalog(schema)
    for name, t in tpch_db.items():
        cat.refresh(name, t, tpch_db)
    sql = ("SELECT COUNT(*) FROM region r, nation n, supplier s, "
           "partsupp ps, part p WHERE r.r_regionkey = n.n_regionkey AND "
           "n.n_nationkey = s.s_nationkey AND s.s_suppkey = ps.ps_suppkey "
           "AND ps.ps_partkey = p.p_partkey AND r.r_name IN (2, 3)")
    q = parse_sql(sql, schema)
    for use_fkpk in (False, True):
        p = plan_query(q, schema, use_fkpk=use_fkpk, stats=cat)
        assert p.tree.root == "ps" and not rerooted(p)
        (d,) = [d for d in p.decisions if d.pass_name == "reroot_guard"]
        assert d.reason == "tree already rooted at guard 'ps'"


def test_without_statistics_the_plan_is_the_first_roots():
    p = plan(WALK3)
    text = "\n".join([p.describe()] + [d.describe() for d in p.decisions])
    assert text == """\
plan[opt_plus] root=e0
  %0 = ScanOp(alias='e0', rel='edge', selection=None, spec=None) key=4ae93b8bbb
  %1 = ScanOp(alias='e1', rel='edge', selection=None, spec=None) key=4ae93b8bbb
  %2 = ScanOp(alias='e2', rel='edge', selection=None, spec=None) key=4ae93b8bbb
  %3 = FreqJoinOp(parent='e1', child='e2', on_vars=('e2.src',), \
pregroup=True) (%1, %2) key=ac285c4377
  %4 = FreqJoinOp(parent='e0', child='e1', on_vars=('e1.src',), \
pregroup=True) (%0, %3) key=ba5ddd13a6
  %5 = FinalAggOp(root='e0', group_by=(), aggregates=(Agg(func='count', \
var=None, distinct=False, name='count(*)'),), dedup=False) (%4) \
key=a2c4f2c799
classify: applied — mode=opt_plus [acyclic=True guard=e0 guarded=True \
oma=False set_safe=False]
reroot_guard @e0: skipped — tree already rooted at guard 'e0'
lower: applied — bottom-up opt_plus sweep over join-tree edges [atoms=3 \
mode=opt_plus]
fkpk_degrade: skipped — gate: use_fkpk off
prefilter_pushdown: skipped — gate: opt_plus sweeps already semi-filter \
every edge"""


def test_the_choice_depends_on_the_edge_relations_statistics(catalog):
    (d,) = [d for d in plan(WALK3, catalog).decisions
            if d.pass_name == "reroot_guard"]
    assert d.depends == (("edge", catalog.token("edge")),)


def test_the_rerooted_walk3_keeps_the_join_walk2_shares(catalog):
    """The middle atom joins first the child it had under the first root,
    so walk3's first join is still walk2's: one node, computed once."""
    w2, w3 = plan(WALK2, catalog), plan(WALK3, catalog)
    assert w2.subplan_keys() & w3.subplan_keys()


@pytest.mark.parametrize("path", ["single", "fused", "eager"])
def test_every_path_answers_numpys_counts(path, edges, db, catalog):
    want = reference(edges)
    if path == "eager":
        got = {}
        for name, sql in QUERIES.items():
            p = plan(sql, catalog)
            res = Executor(db, SCHEMA).execute(
                p, counts=count_plan(p, catalog.tables()))
            got[name] = res["count(*)"]
    else:
        svc = QueryService(db, SCHEMA, mode="opt_plus")
        sqls = list(QUERIES.values())
        results = (svc.submit_many(sqls) if path == "fused"
                   else [svc.submit(s) for s in sqls])
        assert all(r.ok for r in results), [r.error for r in results]
        if path == "fused":
            # walk2 and walk3 still share their join, so they fuse
            assert [r.stats.fused for r in results] == [True, True, False]
        got = dict(zip(QUERIES, (r.values["count(*)"] for r in results)))
        m = svc.metrics()
        assert (m["plans_rerooted"], m["plans_default_root"]) == (1, 2)
    assert all(np.asarray(v).dtype == np.int64 for v in got.values())
    assert {k: int(np.asarray(v)) for k, v in got.items()} == want


def test_a_plan_stored_under_the_first_guard_policy_is_replanned(
        tmp_path, edges, db):
    """A store of format 1 holds walk3 rooted at classify's guard, with no
    decision that depends on the statistics: a warm start skips it and
    plans the query anew, at the middle atom."""
    svc = QueryService(db, SCHEMA, mode="opt_plus", cache_dir=tmp_path)
    assert svc.submit(WALK3).ok
    (path,) = svc.plan_store.plans_dir.glob("*.json")
    svc.plan_store.save(path.stem, plan(WALK3))
    doc = json.loads(path.read_text())
    doc["format_version"] = 1
    path.write_text(json.dumps(doc))
    warm = QueryService(db, SCHEMA, mode="opt_plus", cache_dir=tmp_path)
    r = warm.submit(WALK3)
    assert r.ok, r.error
    assert int(np.asarray(r.values["count(*)"])) == reference(edges)["walk3"]
    m = warm.metrics()
    assert (m["persist_hits"], m["persist_corrupt_skipped"],
            m["plan_builds"], m["plans_rerooted"]) == (0, 1, 1, 1)


WALK3_BY_MIDDLE = ("SELECT e1.src, COUNT(*) FROM edge e0, edge e1, edge e2 "
                   "WHERE e0.dst = e1.src AND e1.dst = e2.src "
                   "GROUP BY e1.src")


@pytest.mark.parametrize("path", ["single", "eager"])
def test_a_grouped_walk_moves_its_root_and_answers_numpys_counts(
        path, edges, db, catalog):
    """The rule is not COUNT(*)'s alone: a walk grouped on a vertex that
    two atoms share has two guards, moves to the middle atom with its
    grouping, and answers each vertex's count exactly."""
    p = plan(WALK3_BY_MIDDLE, catalog)
    assert p.tree.root == "e1" and rerooted(p)
    assert plan(WALK3_BY_MIDDLE).tree.root == "e0"
    assert limbs(p, catalog) == [1, 1]
    if path == "eager":
        res = Executor(db, SCHEMA).execute(
            p, counts=count_plan(p, catalog.tables()))
    else:
        r = QueryService(db, SCHEMA, mode="opt_plus").submit(WALK3_BY_MIDDLE)
        assert r.ok, r.error
        res = r.values
    valid = np.asarray(res["valid"])
    keys = np.asarray(res["groups"]["e1.src"])[valid]
    got = np.asarray(res["groups"]["count(*)"])[valid]
    assert got.dtype == np.int64
    # each vertex v: indeg(v) · Σ over its out-edges of outdeg(their dst)
    src, dst = (a.astype(np.int64) for a in edges)
    onward = np.zeros(VERTICES, np.int64)
    np.add.at(onward, src, np.bincount(src, minlength=VERTICES)[dst])
    want = np.bincount(dst, minlength=VERTICES) * onward
    assert dict(zip(keys.tolist(), got.tolist())) == {
        v: int(c) for v, c in enumerate(want) if c}


def scatters(hlo: str) -> int:
    return len(re.findall(r"\bscatter\(", hlo))


@pytest.mark.parametrize("stats, want", [(False, 3), (True, 2)],
                         ids=["first_root", "chosen_root"])
def test_the_fused_walks_scatter_once_a_join(stats, want, db, catalog):
    """walk2 fused with walk3: under the first root the shared join and
    the outer join's two limbs scatter three times; at the middle atom
    the shared join and the other one, one limb each, scatter twice."""
    plans = [plan(sql, catalog if stats else None) for sql in (WALK2, WALK3)]
    counts = widest([count_plan(p, catalog.tables()) for p in plans])
    fn = Executor(db, SCHEMA).compile_multi(plans, counts=counts)
    assert scatters(fn.lower(db).compile().as_text()) == want
