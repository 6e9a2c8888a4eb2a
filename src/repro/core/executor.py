"""Graph interpreter over the fixed-shape columnar substrate.

Both execution surfaces interpret the plan's op DAG (``PhysicalPlan.root``
/ ``nodes``), with deliberately different option sets:

  * ``execute``  — eager, runs every plan class; materialising ops (ref/opt
    baselines) use dynamic shapes the way a row engine would, and the
    executor tracks the paper's headline metric (peak materialised/live
    tuples) per step → Fig. 6 reproduction.  ``oom_guard`` and ``ExecStats``
    belong to this surface only: both need concrete intermediate sizes,
    which exist eagerly but not under tracing.
  * ``compile``  — jits the zero-materialisation plan classes (oma /
    opt_plus), whose dataflow is entirely static; this is the TPU path,
    what the timing benchmarks measure, and what the serving tier caches.
    Stats-dependent options are rejected up front (a traced program cannot
    count live tuples per step), so an Executor configured with
    ``oom_guard`` refuses to compile rather than silently dropping the
    guard.  Padded tables (``Table.pad_to``) run through compiled plans
    unchanged: every operator masks by frequency, so dead rows are inert.

Under tracing, node results are memoised by their content keys
(``PlanNode.key``): a key hit reuses the already-traced frequency vector
instead of re-tracing the kernels.  ``compile_multi`` shares one memo
across *all* member plans, so any sub-DAG two members have in common — a
filtered dimension scan, a semi-join chain, even when the enclosing join
shapes differ — is computed exactly once in the fused XLA program.

An ``oom_guard`` bounds materialisation for the baselines: exceeding it
raises ``MaterialisationLimit`` (reported as the paper's X entries).

Counts are 32 or 64 bits wide (``counts`` of ``compile``,
``compile_multi`` and ``execute``: a ``repro.core.stats.Counts`` the
caller chooses from the statistics, ``count_plan``, or by default the
narrowest the executor's own tables allow, ``db_counts``): frequencies
and COUNT's sum take that width, and at 64 bits each dense freq-join
scatters its child's frequencies in the 32-bit limbs ``counts.limbs``
names for it (``kops.freq_join``, which refuses to run without them).  A 64-bit program is traced, compiled and run
under JAX's x64 types, switched on for the calling thread alone
(``count_scope``), so no 32-bit program changes.

Each semi/freq join passes the kernel its keys' declared domain (the
product of the key columns' ``ColumnMeta.domain``, None when one is
undeclared); the kernel chooses the dense or the sorted path from that
domain and the child's length (``kops.join_path``).  Every executor
tallies the paths its join calls took in ``joins``, and its freq-join
calls' rows and dense accumulator slots by the counts' width in
``join_sizes``; ``compile`` and ``compile_multi`` hand a program's
tallies, counted while it is traced, to the caller.  A memoised sub-DAG
is traced, and counted, once.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregates import grouped_aggregate, scalar_aggregate
from repro.core.plan import (
    FinalAggOp,
    FreqJoinOp,
    MaterializeJoinOp,
    PhysicalPlan,
    PlanNode,
    ScanOp,
    SemiJoinOp,
)
from repro.core.stats import Counts, db_counts, join_id
from repro.kernels import ops as kops
from repro.tables.table import Schema, Table, pack_keys


COUNT_DTYPES = {32: jnp.int32, 64: jnp.int64}


def count_scope(count_bits: int):
    """The context a program with ``count_bits``-wide counts is traced,
    compiled and run in: JAX's x64 types for 64, on this thread only."""
    if count_bits not in COUNT_DTYPES:
        raise ValueError(f"count_bits {count_bits}: one of "
                         f"{sorted(COUNT_DTYPES)}")
    return jax.enable_x64(True) if count_bits == 64 \
        else contextlib.nullcontext()


class _Wide:
    """A jitted program with 64-bit counts: every call and lowering runs
    under ``count_scope(64)``, so its trace holds s64 counts while the
    process's other programs stay 32-bit."""

    def __init__(self, fn: Callable):
        self._fn = fn

    def __call__(self, *args):
        with count_scope(64):
            return self._fn(*args)

    def lower(self, *args):
        with count_scope(64):
            return self._fn.lower(*args)


class MaterialisationLimit(RuntimeError):
    """Raised when a baseline plan exceeds the allowed intermediate size
    (the paper's 'X — out of memory' condition)."""


@dataclasses.dataclass
class ExecStats:
    peak_tuples: int = 0
    steps: list = dataclasses.field(default_factory=list)

    def record(self, opname: str, n: int):
        self.steps.append((opname, int(n)))
        self.peak_tuples = max(self.peak_tuples, int(n))


@dataclasses.dataclass
class _State:
    cols: dict[str, Any]     # var → column array
    freq: Any                # frequency column


class Executor:
    def __init__(self, db: dict[str, Table], schema: Schema,
                 oom_guard: int | None = None):
        self.db = db
        self.schema = schema
        # the frequencies' dtype while tracing or executing (count_bits)
        self.count_dtype = jnp.int32
        self.oom_guard = oom_guard
        # join kernel calls by path ("dense", "sorted")
        self.joins: collections.Counter = collections.Counter()
        # freq-join kernel calls' rows and dense slots, by the counts'
        # width: "rows<w>" (child + parent), "parent_rows<w>", "slots<w>"
        self.join_sizes: collections.Counter = collections.Counter()

    def jittable(self) -> "Executor":
        """Copy with eager-only options stripped — the configuration
        ``compile()`` accepts.  Use when one benchmark harness drives both
        guarded eager baselines and jitted plans."""
        return Executor(self.db, self.schema, oom_guard=None)

    # ------------------------------------------------------------------
    def _domains(self, plan: PhysicalPlan, alias: str) -> dict[str, int | None]:
        atom = plan.tree.atoms[alias]
        rel = self.schema.relations[atom.rel]
        return {v: rel.columns[i].domain for i, v in enumerate(atom.vars)}

    def _scan(self, plan: PhysicalPlan, op: ScanOp) -> _State:
        tab = self.db[op.rel]
        atom = plan.tree.atoms[op.alias]
        rel = self.schema.relations[atom.rel]
        if op.selection is not None:
            tab = tab.select(op.selection)
        cols = {}
        for i, cname in enumerate(rel.column_names()):
            cols[atom.vars[i]] = tab.columns[cname]
        return _State(cols, tab.freq.astype(self.count_dtype))

    def _key(self, plan: PhysicalPlan, alias: str, st: _State,
             on_vars: tuple[str, ...]):
        """Packed join key + its declared domain (the product of the key
        columns' domains; None when one is undeclared)."""
        if not on_vars:
            return jnp.zeros(st.freq.shape, jnp.int32), 1
        doms = self._domains(plan, alias)
        dlist = [doms.get(v) for v in on_vars]
        key = pack_keys([st.cols[v] for v in on_vars], dlist)
        domain = None
        if all(d is not None for d in dlist):
            domain = 1
            for d in dlist:
                domain *= d
        return key, domain

    def _join_path(self, n_child: int, domain: int | None) -> str:
        """The path of one join kernel call, tallied."""
        path = kops.join_path(domain, n_child)
        self.joins[path] += 1
        return path

    def _semi_join(self, plan: PhysicalPlan, op: SemiJoinOp,
                   p: _State, c: _State) -> _State:
        pk, _pd = self._key(plan, op.parent, p, op.on_vars)
        ck, cdom = self._key(plan, op.child, c, op.on_vars)
        self._join_path(ck.shape[0], cdom)
        freq = kops.semi_join(pk, p.freq, ck, c.freq, domain=cdom)
        return _State(p.cols, freq)

    def _freq_join(self, plan: PhysicalPlan, op: FreqJoinOp,
                   p: _State, c: _State,
                   limbs: tuple[int, int] | None = None) -> _State:
        pk, _pd = self._key(plan, op.parent, p, op.on_vars)
        ck, cdom = self._key(plan, op.child, c, op.on_vars)
        path = self._join_path(ck.shape[0], cdom)
        # bytes the call must move, by the counts' width: both key
        # columns, the child's and the parent's frequencies, and the dense
        # path's accumulator
        bits = 8 * jnp.dtype(self.count_dtype).itemsize
        self.join_sizes[f"rows{bits}"] += pk.shape[0] + ck.shape[0]
        self.join_sizes[f"parent_rows{bits}"] += pk.shape[0]
        if path == "dense":
            self.join_sizes[f"slots{bits}"] += cdom
        cf = c.freq
        if op.pregroup and path != "dense":
            with jax.named_scope("pregroup"):
                ck, cf, _valid = kops.group_by_sum(ck, cf)
        freq = kops.freq_join(pk, p.freq, ck, cf, domain=cdom, limbs=limbs)
        return _State(p.cols, freq)

    # ------------------------------------------------------------------
    def _counts(self, plans: list[PhysicalPlan],
                counts: Counts | None) -> Counts:
        """``counts``, or where the caller gives none the narrowest its
        own tables allow (``db_counts``)."""
        if counts is None:
            counts = db_counts(plans, self.db, self.schema)
        return counts

    def execute(self, plan: PhysicalPlan, stats: ExecStats | None = None,
                counts: Counts | None = None):
        """Eager DAG interpretation (every plan class, per-step stats),
        with ``counts`` as in ``compile``.

        Intermediate states are dropped after their last consumer, so peak
        host memory tracks the largest live intermediate — matching the
        linear interpreter this replaced, whose per-alias state slots were
        overwritten in place (a ref-mode chain of materialising joins must
        not retain every expanded intermediate until the end)."""
        counts = self._counts([plan], counts)
        with count_scope(counts.bits):
            dtype, self.count_dtype = \
                self.count_dtype, COUNT_DTYPES[counts.bits]
            try:
                return self._execute(plan, stats, counts)
            finally:
                self.count_dtype = dtype

    def _execute(self, plan: PhysicalPlan, stats: ExecStats | None,
                 counts: Counts):
        stats = stats if stats is not None else ExecStats()
        consumers: dict[int, int] = {}
        for node in plan.nodes:
            for i in node.inputs:
                consumers[id(i)] = consumers.get(id(i), 0) + 1
        vals: dict[int, Any] = {}
        results: dict[str, Any] = {}
        for node in plan.nodes:
            op = node.op
            ins = [vals[id(i)] for i in node.inputs]
            if isinstance(op, ScanOp):
                st = self._scan(plan, op)
                stats.record(f"scan({op.alias})", int(jnp.sum(st.freq > 0)))
            elif isinstance(op, SemiJoinOp):
                st = self._semi_join(plan, op, ins[0], ins[1])
                stats.record(f"semijoin({op.parent}⋉{op.child})",
                             int(jnp.sum(st.freq > 0)))
            elif isinstance(op, FreqJoinOp):
                st = self._freq_join(plan, op, ins[0], ins[1],
                                     limbs=counts.limbs.get(join_id(node)))
                stats.record(f"freqjoin({op.parent}⋉ᶠ{op.child})",
                             int(jnp.sum(st.freq > 0)))
            elif isinstance(op, MaterializeJoinOp):
                st = self._materialize_join(plan, op, ins[0], ins[1], stats)
            elif isinstance(op, FinalAggOp):
                st = results = self._final_agg(plan, op, ins[0])
            else:  # pragma: no cover
                raise TypeError(op)
            vals[id(node)] = st
            for i in node.inputs:
                consumers[id(i)] -= 1
                if consumers[id(i)] == 0:
                    del vals[id(i)]
        results = dict(results)
        results["__stats__"] = stats
        return results

    # ------------------------------------------------------------------
    def _materialize_join(self, plan, op: MaterializeJoinOp,
                          p: _State, c: _State, stats) -> _State:
        """Eager row-expanding join (the ref/opt baselines)."""
        pk = np.asarray(self._key(plan, op.parent, p, op.on_vars)[0])
        ck = np.asarray(self._key(plan, op.child, c, op.on_vars)[0])
        pf = np.asarray(p.freq)
        cf = np.asarray(c.freq)
        plive = np.flatnonzero(pf > 0)
        clive = np.flatnonzero(cf > 0)
        pk, pf = pk[plive], pf[plive]
        ck, cf = ck[clive], cf[clive]
        order = np.argsort(ck, kind="stable")
        cks, cfs = ck[order], cf[order]
        lo = np.searchsorted(cks, pk, side="left")
        hi = np.searchsorted(cks, pk, side="right")
        counts = hi - lo
        total = int(counts.sum())
        if self.oom_guard is not None and total > self.oom_guard:
            raise MaterialisationLimit(
                f"join {op.parent}⋈{op.child} would materialise {total} "
                f"tuples (> {self.oom_guard})")
        stats.record(f"join({op.parent}⋈{op.child})", total)
        pidx = np.repeat(np.arange(len(pk)), counts)
        offs = np.cumsum(counts) - counts    # also right with no live rows
        within = np.arange(total) - np.repeat(offs, counts)
        cidx = order[np.repeat(lo, counts) + within]

        out_cols: dict[str, np.ndarray] = {}
        for v, col in p.cols.items():
            out_cols[v] = np.asarray(col)[plive][pidx]
        for v, col in c.cols.items():
            if v not in out_cols:
                out_cols[v] = np.asarray(col)[clive][cidx]
        out_freq = pf[pidx] * cf[cidx]

        if op.regroup:
            # §4.2 Opt: group straight back to the parent's attributes
            parent_vars = list(p.cols.keys())
            sort_keys = tuple(out_cols[v] for v in reversed(parent_vars))
            if sort_keys:
                gorder = np.lexsort(sort_keys)
            else:
                gorder = np.arange(total)
            freq_sorted = out_freq[gorder]
            cols_sorted = {v: out_cols[v][gorder] for v in parent_vars}
            if total == 0:
                boundary = np.zeros(0, bool)
            else:
                boundary = np.zeros(total, bool)
                boundary[0] = True
                for v in parent_vars:
                    col = cols_sorted[v]
                    boundary[1:] |= col[1:] != col[:-1]
            starts = np.flatnonzero(boundary)
            sums = np.add.reduceat(freq_sorted, starts) if total else \
                np.zeros(0, freq_sorted.dtype)
            new_cols = {v: jnp.asarray(cols_sorted[v][starts])
                        for v in parent_vars}
            stats.record(f"regroup({op.parent})", len(starts))
            return _State(new_cols, jnp.asarray(sums))

        return _State({v: jnp.asarray(a) for v, a in out_cols.items()},
                      jnp.asarray(out_freq))

    # ------------------------------------------------------------------
    def _final_agg(self, plan, op: FinalAggOp, st: _State):
        out: dict[str, Any] = {}
        if not op.group_by:
            for ag in op.aggregates:
                out[ag.name] = scalar_aggregate(ag, st.cols, st.freq,
                                                op.dedup)
            return out
        doms = self._domains(plan, op.root) \
            if op.root in plan.tree.atoms else {}
        cols, valid = grouped_aggregate(op.group_by, op.aggregates,
                                        st.cols, st.freq, doms, op.dedup)
        out["groups"] = cols
        out["valid"] = valid
        return out

    # ------------------------------------------------------------------
    def _check_jittable(self, plans) -> None:
        for plan in plans:
            if any(isinstance(op, MaterializeJoinOp) for op in plan.ops):
                raise ValueError(f"plan mode {plan.mode} materialises joins; "
                                 "only oma/opt_plus plans are jittable")
        if self.oom_guard is not None:
            raise ValueError(
                "oom_guard is an eager-only option: it needs concrete "
                "per-step tuple counts, which do not exist under jit "
                "tracing (and compiled oma/opt_plus plans never "
                "materialise beyond the base relations anyway). Use "
                "execute() for guarded baselines, or build the Executor "
                "without oom_guard to compile.")

    def _inner_executor(self, db: dict[str, Table],
                        count_bits: int) -> "Executor":
        """The node evaluator ``_trace_plan`` traces with — a fresh
        executor bound to the traced-through database, with
        ``count_bits``-wide frequencies.  Subclasses swap in alternative
        evaluators here (``DistributedExecutor`` returns one whose
        semi/freq joins are ring sweeps over the mesh); the traversal
        itself — content-key memoisation, sub-DAG dedup, multi-plan fusion
        — is shared and lives only in ``_trace_plan``."""
        ex = Executor(db, self.schema)
        ex.count_dtype = COUNT_DTYPES[count_bits]
        return ex

    def _trace_plan(self, db: dict[str, Table], plan: PhysicalPlan,
                    memo: dict | None = None,
                    root: PlanNode | None = None,
                    joins: collections.Counter | None = None,
                    counts: Counts = Counts(),
                    sizes: collections.Counter | None = None) -> Any:
        """One plan's DAG evaluation, for use under tracing.

        ``memo`` maps node content keys (``PlanNode.key``) to the frequency
        vectors already computed this trace: a key hit reuses the cached
        vector (only the column views of the node's parent chain are
        rebuilt — free) and skips tracing the node's kernels AND its entire
        child sub-DAG.  Shared across plans by ``compile_multi``, this is
        how a fused multi-query program runs each common sub-DAG exactly
        once even when the member plans' overall join shapes differ.

        Each node's own work runs under a ``jax.named_scope`` of its
        operator (``scan``, ``semi_join``, ``freq_join``, ``final_agg``),
        entered after its inputs are evaluated, so a device operation's
        ``op_name`` names the operator that emitted it and no other.

        ``root`` selects where evaluation stops (default: the whole plan,
        ``plan.root``).  The mesh path evaluates to ``plan.root.inputs[0]``
        — the pre-aggregate root state — inside its shard_map program and
        aggregates outside, so the same traversal serves both lowerings.

        ``joins``, when given, gains the paths of the join kernel calls
        traced (``Executor.joins``), and ``sizes`` their rows and slots
        (``Executor.join_sizes``)."""
        inner = self._inner_executor(db, counts.bits)
        vals: dict[int, _State] = {}

        def ev(node: PlanNode) -> Any:
            st = vals.get(id(node))
            if st is not None:
                return st
            op = node.op
            key = node.key() if memo is not None else None
            if isinstance(op, ScanOp):
                with jax.named_scope("scan"):
                    st = inner._scan(plan, op)
                if key is not None:
                    if key in memo:
                        st = _State(st.cols, memo[key])
                    else:
                        memo[key] = st.freq
            elif isinstance(op, (SemiJoinOp, FreqJoinOp)):
                p = ev(node.inputs[0])
                if key is not None and key in memo:
                    st = _State(p.cols, memo[key])
                else:
                    c = ev(node.inputs[1])
                    if isinstance(op, SemiJoinOp):
                        with jax.named_scope("semi_join"):
                            st = inner._semi_join(plan, op, p, c)
                    else:
                        with jax.named_scope("freq_join"):
                            st = inner._freq_join(
                                plan, op, p, c,
                                limbs=counts.limbs.get(join_id(node)))
                    if key is not None:
                        memo[key] = st.freq
            elif isinstance(op, FinalAggOp):
                child = ev(node.inputs[0])
                with jax.named_scope("final_agg"):
                    st = inner._final_agg(plan, op, child)
            else:  # pragma: no cover — _check_jittable rejects these
                raise TypeError(op)
            vals[id(node)] = st
            return st

        out = ev(plan.root if root is None else root)
        if joins is not None:
            joins.update(inner.joins)
        if sizes is not None:
            sizes.update(inner.join_sizes)
        return out

    def compile(self, plan: PhysicalPlan, name: str = "run",
                joins: collections.Counter | None = None,
                counts: Counts | None = None,
                sizes: collections.Counter | None = None):
        """Jit the static plan classes (oma / opt_plus): db → aggregates.
        ``name`` names the program: its XLA module is ``jit_<name>``, the
        name a profiler trace gives its runs.  ``joins`` and ``sizes``,
        when given, hold the paths of the program's join kernel calls and
        their rows and slots (``Executor.join_sizes``) once it is traced.
        ``counts``: how it holds its counts, by default the narrowest the
        executor's own tables allow (``repro.core.stats.db_counts``)."""
        self._check_jittable([plan])
        counts = self._counts([plan], counts)

        def run(db: dict[str, Table]):
            _clear(joins, sizes)
            # a fresh memo still dedups repeated sub-DAGs *within* the plan
            # (self-joins scanning one relation twice, say)
            return self._trace_plan(db, plan, memo={}, joins=joins,
                                    counts=counts, sizes=sizes)

        return _jit(run, name, counts.bits)

    def compile_multi(self, plans: list[PhysicalPlan], name: str = "run",
                      joins: collections.Counter | None = None,
                      counts: Counts | None = None,
                      sizes: collections.Counter | None = None):
        """Jit several static plans into ONE program: db → [aggregates].

        The member plans' DAG evaluations share a trace-level memo keyed by
        node content keys, so every sub-DAG that is structurally identical
        across members — a whole prefix, or just a shared scan/semi-join
        chain under different join shapes — is computed once and its
        frequency vector fanned out to every consumer.  One XLA compilation
        serves every member query; results are returned in plan order.
        ``name``, ``joins``, ``counts`` and ``sizes`` as in
        ``compile``."""
        if not plans:
            raise ValueError("compile_multi needs at least one plan")
        self._check_jittable(plans)
        counts = self._counts(plans, counts)

        def run(db: dict[str, Table]):
            _clear(joins, sizes)
            memo: dict = {}
            return [self._trace_plan(db, plan, memo, joins=joins,
                                     counts=counts, sizes=sizes)
                    for plan in plans]

        return _jit(run, name, counts.bits)


def _clear(*tallies) -> None:
    """Empty the caller's tallies before a trace fills them."""
    for t in tallies:
        if t is not None:
            t.clear()


def _jit(run: Callable, name: str, count_bits: int) -> Callable:
    fn = jax.jit(named(run, name))
    return _Wide(fn) if count_bits == 64 else fn


def named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``: ``jax.jit`` names its program after the
    function, so the XLA module is ``jit_<name>``."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def shared_subplan_savings(plans: list[PhysicalPlan]) -> int:
    """How many non-trivial subplan evaluations ``compile_multi`` saves by
    fusing `plans`, versus compiling each alone: the multiset of the
    members' shareable subplan keys minus its distinct support."""
    sets = [plan.subplan_keys() for plan in plans]
    union: set = set()
    total = 0
    for s in sets:
        total += len(s)
        union |= s
    return total - len(union)
