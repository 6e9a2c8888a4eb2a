"""Differential property test: the op-graph executor must be bitwise
identical to the pre-refactor *linear* semantics.

``LinearReference`` replays a plan the way the pre-refactor executor did —
a single sweep over the linear op list threading one mutable state per
atom alias — while ``Executor`` interprets the op DAG (with content-key
memoisation under tracing).  On randomised acyclic queries (chain/star
join shapes, random selections, aggregates, GROUP BY, data) the two must
agree to the bit in every plan class (ref / opt / opt_plus / oma), eagerly
and compiled, and fused multi-query execution must match per-plan
compilation bitwise.  The reference reads the schema with its domains
stripped, so its joins take the sorted path; the executor runs both on
the declared domains, whose joins take the dense one, and on the stripped
schema, whose joins sort.

Runs as a hypothesis property test when hypothesis is installed, else as a
seeded sweep over the same case builder (visible, not silent, degradation).
"""

import jax
import numpy as np
import pytest

from repro.core import Executor, plan_query
from repro.core.distributed import DistributedExecutor
from repro.core.executor import ExecStats
from repro.core.plan import (
    FinalAggOp,
    FreqJoinOp,
    MaterializeJoinOp,
    ScanOp,
    SemiJoinOp,
)
from repro.core.query import Agg, AggQuery, Atom
from repro.core.stats import db_counts
from repro.tables.table import ColumnMeta, RelSchema, Schema, Table

try:  # property tests degrade to a seeded sweep without hypothesis
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

jax.config.update("jax_platform_name", "cpu")

_N_IDS = 12
SCHEMA = Schema(relations={
    "node": RelSchema("node", (
        ColumnMeta("id", domain=_N_IDS),
        ColumnMeta("grp", domain=5),
        ColumnMeta("score"),
    )),
    "edge": RelSchema("edge", (
        ColumnMeta("src", domain=_N_IDS),
        ColumnMeta("dst", domain=_N_IDS),
    )),
})


class LinearReference:
    """The pre-refactor executor semantics: one linear sweep over
    ``plan.ops``, one mutable state slot per alias.  Op-level kernels are
    shared with the graph executor, so any divergence is attributable to
    the interpretation strategy — exactly what this test pins down."""

    def __init__(self, db, schema):
        self.ex = Executor(db, schema)

    def _sweep(self, ex, plan, stats=None):
        state, results = {}, {}
        for op in plan.ops:
            if isinstance(op, ScanOp):
                state[op.alias] = st_ = ex._scan(plan, op)
                if stats is not None:
                    stats.record(f"scan({op.alias})",
                                 int(np.sum(np.asarray(st_.freq) > 0)))
            elif isinstance(op, SemiJoinOp):
                st_ = ex._semi_join(plan, op, state[op.parent],
                                    state[op.child])
                state[op.parent] = st_
                if stats is not None:
                    stats.record(f"semijoin({op.parent}⋉{op.child})",
                                 int(np.sum(np.asarray(st_.freq) > 0)))
            elif isinstance(op, FreqJoinOp):
                st_ = ex._freq_join(plan, op, state[op.parent],
                                    state[op.child])
                state[op.parent] = st_
                if stats is not None:
                    stats.record(f"freqjoin({op.parent}⋉ᶠ{op.child})",
                                 int(np.sum(np.asarray(st_.freq) > 0)))
            elif isinstance(op, MaterializeJoinOp):
                state[op.parent] = ex._materialize_join(
                    plan, op, state[op.parent], state[op.child],
                    stats if stats is not None else ExecStats())
            elif isinstance(op, FinalAggOp):
                results = ex._final_agg(plan, op, state[op.root])
        return results

    def execute(self, plan):
        stats = ExecStats()
        results = dict(self._sweep(self.ex, plan, stats))
        results["__stats__"] = stats
        return results

    def compile(self, plan):
        outer = self.ex

        def run(db):
            inner = Executor(db, outer.schema)
            return self._sweep(inner, plan)

        return jax.jit(run)


def _make_db(rng):
    n_nodes = int(rng.integers(4, 24))
    n_edges = int(rng.integers(4, 40))
    node = {
        "id": rng.integers(0, _N_IDS, n_nodes).astype(np.int32),
        "grp": rng.integers(0, 5, n_nodes).astype(np.int32),
        "score": rng.integers(0, 50, n_nodes).astype(np.float32),
    }
    edge = {
        "src": rng.integers(0, _N_IDS, n_edges).astype(np.int32),
        "dst": rng.integers(0, _N_IDS, n_edges).astype(np.int32),
    }
    return {"node": Table.from_numpy(node), "edge": Table.from_numpy(edge)}


_AGG_POOL = (("min", "sc"), ("max", "sc"), ("sum", "sc"), ("avg", "sc"),
             ("median", "sc"), ("count", None))


def _make_query(rng):
    chain_len = int(rng.integers(0, 3))
    star = bool(rng.integers(0, 2)) and chain_len > 0
    atoms = [Atom("node", "n0", ("v0", "g", "sc"))]
    if chain_len >= 1:
        atoms.append(Atom("edge", "e1", ("v0", "x1")))
    if chain_len >= 2:
        atoms.append(Atom("edge", "e2", ("x1", "x2")))
    if star:
        atoms.append(Atom("edge", "e3", ("v0", "y1")))
    n_aggs = int(rng.integers(1, 3))
    picks = rng.choice(len(_AGG_POOL), size=n_aggs, replace=False)
    aggs = tuple(Agg(_AGG_POOL[i][0], _AGG_POOL[i][1]) for i in picks)
    group_by = ("g",) if rng.integers(0, 2) else ()
    selections, specs = {}, {}
    if rng.integers(0, 2):
        lit = int(rng.integers(1, 5))
        selections["n0"] = lambda c, lit=lit: c["grp"] < lit
        specs["n0"] = (("<", "grp", lit),)
    if chain_len >= 1 and rng.integers(0, 2):
        lit = int(rng.integers(1, _N_IDS))
        selections["e1"] = lambda c, lit=lit: c["dst"] > lit
        specs["e1"] = ((">", "dst", lit),)
    return AggQuery(atoms=tuple(atoms), aggregates=aggs, group_by=group_by,
                    selections=selections, selection_specs=specs)


def _assert_bitwise(a: dict, b: dict, ctx: str = ""):
    keys_a = {k for k in a if k != "__stats__"}
    keys_b = {k for k in b if k != "__stats__"}
    assert keys_a == keys_b, ctx
    for k in keys_a:
        va, vb = a[k], b[k]
        if k == "groups":
            assert set(va) == set(vb), ctx
            for c in va:
                xa, xb = np.asarray(va[c]), np.asarray(vb[c])
                assert xa.dtype == xb.dtype and xa.shape == xb.shape, \
                    (ctx, c)
                assert xa.tobytes() == xb.tobytes(), (ctx, c)
        else:
            xa, xb = np.asarray(va), np.asarray(vb)
            assert xa.dtype == xb.dtype and xa.shape == xb.shape, (ctx, k)
            assert xa.tobytes() == xb.tobytes(), (ctx, k)


def _check_case(seed: int):
    rng = np.random.default_rng(seed)
    db = _make_db(rng)
    query = _make_query(rng)
    ref = LinearReference(db, SCHEMA.without_domains())
    new = Executor(db, SCHEMA)
    # the graph IR on both join paths: declared domains go dense, the
    # stripped schema sorts as the reference does
    sort = Executor(db, SCHEMA.without_domains())

    jit_plans, jit_results = [], []
    for mode in ("ref", "opt", "opt_plus", "oma"):
        try:
            plan = plan_query(query, SCHEMA, mode=mode)
        except ValueError:
            continue  # mode not applicable (not 0MA, say) — by design
        want = ref.execute(plan)
        if mode in ("opt_plus", "oma"):
            want_c = dict(ref.compile(plan)(db))
        for ex, path in ((new, "dense"), (sort, "sorted")):
            got = ex.execute(plan)
            _assert_bitwise(want, got, ctx=f"eager/{mode}/{path}")
            assert (want["__stats__"].peak_tuples
                    == got["__stats__"].peak_tuples), (mode, path)
            if mode in ("opt_plus", "oma"):
                got_c = dict(ex.compile(plan)(db))
                _assert_bitwise(want_c, got_c, ctx=f"compiled/{mode}/{path}")
                _assert_bitwise(want, got_c,
                                ctx=f"eager-vs-compiled/{mode}/{path}")
                if ex is new:
                    jit_plans.append(plan)
                    jit_results.append(got_c)
    assert not ref.ex.joins["dense"] and not sort.joins["dense"]
    assert not new.joins["sorted"]

    # fused multi-query execution (shared trace memo across members,
    # including an extra sibling so sub-DAGs overlap partially) must match
    # per-plan compilation bitwise
    if jit_plans:
        sibling = AggQuery(atoms=query.atoms, aggregates=(Agg("count"),),
                           group_by=query.group_by,
                           selections=dict(query.selections),
                           selection_specs=dict(query.selection_specs))
        plans = jit_plans + [plan_query(sibling, SCHEMA, mode="opt_plus")]
        solo = jit_results + [dict(new.compile(plans[-1])(db))]
        fused = new.compile_multi(plans)(db)
        for want_c, got_c in zip(solo, fused):
            _assert_bitwise(want_c, dict(got_c), ctx="fused-vs-solo")

        # the mesh lowering is the same graph interpreter with ring
        # evaluators — on a 1-device mesh it must be bitwise-equal to the
        # local executor over identically-padded tables, per-plan and fused
        mesh = jax.make_mesh((1,), ("data",))
        dex = DistributedExecutor(SCHEMA, mesh)
        sharded = dex.shard_db(db)
        host = {k: db[k].pad_to(sharded[k].capacity) for k in db}
        mesh_solo = []
        for plan in plans:
            want_c = dict(new.compile(plan)(host))
            got_c = dict(dex.compile(
                plan, counts=db_counts([plan], db, SCHEMA))(sharded))
            _assert_bitwise(want_c, got_c, ctx="mesh-vs-local")
            mesh_solo.append(got_c)
        fused = dex.compile_multi(
            plans, counts=db_counts(plans, db, SCHEMA))(sharded)
        for want_c, got_c in zip(mesh_solo, fused):
            _assert_bitwise(want_c, dict(got_c), ctx="mesh-fused-vs-solo")


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 2**32 - 1))
    @settings(max_examples=12, deadline=None)
    def test_graph_ir_matches_linear_semantics(seed):
        _check_case(seed)
else:
    @pytest.mark.parametrize("seed", range(8))
    def test_graph_ir_matches_linear_semantics(seed):
        _check_case(seed)
