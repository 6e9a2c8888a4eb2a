"""Distributed Yannakakis sweep: Ring-FreqJoin over the device mesh.

The paper runs on Spark, whose physical layer hash-shuffles both join sides.
A TPU mesh has no shuffle service, and all-to-all hash partitioning needs
worst-case per-destination capacities (dynamic shapes).  We instead exploit
the additive-semiring law the FreqJoin computes with (property-tested in
tests/test_kernels.py):

    mult(R, S₁ ⊎ S₂) = mult(R, S₁) + mult(R, S₂)

so with the child relation row-sharded over the mesh, each parent shard can
accumulate exact multipliers by visiting every child shard once around a
ring (`lax.ppermute`), exactly like ring attention:

    for step in range(axis_size):
        mult += local_multiplier(parent_keys, child_shard)
        child_shard = ppermute(child_shard, +1)

Parent rows never move; no shuffle capacities; static shapes throughout; and
the per-step compute (sort once, then searchsorted) overlaps with the
ppermute of the next shard (XLA latency hiding).  The semi-join sweep is the
same ring in the Boolean semiring (max instead of +).

Multi-pod: the ring nests — a full `data`-ring per `pod` step — so
inter-pod (DCI) hops happen once per pod, not once per shard.

There is ONE plan interpreter: ``DistributedExecutor`` subclasses
``core.executor.Executor`` and reuses its node-keyed graph traversal
(``_trace_plan``) verbatim — the mesh lowering only swaps the node
evaluator (``_RingExecutor``: semi/freq joins become ring sweeps) and
runs the traversal inside one ``shard_map`` program per compile, stopping
at the pre-aggregate root state.  Content-key memoisation, sub-DAG dedup
and ``compile_multi`` fusion therefore work unchanged on the mesh: a
fused multi-query mesh program runs every shared sub-DAG's ring sweep
exactly once.

Final aggregates run *outside* the shard_map, on the root columns
constrained to a REPLICATED layout: the sweep's exact integer frequencies
are identical to the local engine's, and aggregating replicated arrays
executes the same single-device reduction program on every device — which
is what makes mesh answers bitwise-equal to a single-device reference over
identically-padded tables (see ``tables.table.sharded_bucket_capacity``).
"""

from __future__ import annotations

import collections
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.executor import Executor, _State, named
from repro.core.stats import Counts
from repro.core.plan import (
    FreqJoinOp,
    PhysicalPlan,
    PlanNode,
    PlanningError,
    SemiJoinOp,
)
from repro.tables.table import (
    Schema,
    Table,
    sharded_bucket_capacity,
)


def _local_multiplier(pk, ck, cf, mode: str):
    """Exact multiplier of parent keys against ONE child shard
    (sort + prefix-sum + searchsorted; same algorithm as kernels.ops)."""
    order = jnp.argsort(ck)
    cks = ck[order]
    cfs = cf[order]
    if mode == "any":
        cfs = (cfs > 0).astype(cfs.dtype)
    prefix = jnp.concatenate([jnp.zeros((1,), cfs.dtype), jnp.cumsum(cfs)])
    lo = jnp.searchsorted(cks, pk, side="left")
    hi = jnp.searchsorted(cks, pk, side="right")
    return prefix[hi] - prefix[lo]


def ring_freq_join(pk, pf, ck, cf, *, ring_axes: Sequence[str],
                   mode: str = "sum", presort: bool = False):
    """Inside shard_map: exact FreqJoin with the child sharded over
    `ring_axes` (innermost axis rotates fastest).  Returns new parent freq.

    presort=False — baseline: each ring step sorts the visiting shard
        (what a naive port of the paper's sort-merge join does: Spark
        re-sorts per shuffle partition).
    presort=True  — beyond-paper: each shard sorts its child block ONCE
        and the ring rotates (sorted keys, prefix sums); every step is
        then two searchsorteds + a gather.  Saves (P−1) sorts per join —
        see EXPERIMENTS.md §Perf (engine cell).
    """
    mult = lax.pcast(jnp.zeros(pk.shape, pf.dtype), tuple(ring_axes),
                     to="varying")

    def rotate(x, axis):
        size = lax.psum(1, axis)
        perm = [(i, (i + 1) % size) for i in range(size)]
        return lax.ppermute(x, axis, perm)

    if presort:
        order = jnp.argsort(ck)
        cks = ck[order]
        cfs = cf[order]
        if mode == "any":
            cfs = (cfs > 0).astype(pf.dtype)
        prefix = jnp.concatenate(
            [jnp.zeros((1,), cfs.dtype), jnp.cumsum(cfs)])
        payload = (cks, prefix)

        def local(payload_):
            cks_, prefix_ = payload_
            lo = jnp.searchsorted(cks_, pk, side="left")
            hi = jnp.searchsorted(cks_, pk, side="right")
            return (prefix_[hi] - prefix_[lo]).astype(pf.dtype)
    else:
        payload = (ck, cf)

        def local(payload_):
            ck_, cf_ = payload_
            return _local_multiplier(pk, ck_, cf_, mode).astype(pf.dtype)

    # nested rings: data-ring innermost (ICI), pod-ring outermost (DCI)
    axes = list(ring_axes)
    sizes = [lax.psum(1, a) for a in axes]

    def body(carry, _):
        payload_, mult_ = carry
        m = local(payload_)
        mult_ = jnp.maximum(mult_, m) if mode == "any" else mult_ + m
        payload_ = jax.tree.map(lambda x: rotate(x, axes[-1]), payload_)
        return (payload_, mult_), None

    total_inner = sizes[-1]
    carry = (payload, mult)
    if len(axes) == 1:
        carry, _ = lax.scan(body, carry, None, length=total_inner)
    else:
        outer_axis, outer_size = axes[0], sizes[0]

        def outer_body(carry, _):
            carry, _ = lax.scan(body, carry, None, length=total_inner)
            payload_, mult_ = carry
            payload_ = jax.tree.map(lambda x: rotate(x, outer_axis),
                                    payload_)
            return (payload_, mult_), None

        carry, _ = lax.scan(outer_body, carry, None, length=outer_size)
    _, mult = carry
    if mode == "any":
        mult = (mult > 0).astype(pf.dtype)
    return pf * mult


def allreduce_freq_join(pk, pf, ck, cf, *, ring_axes: Sequence[str],
                        mode: str = "sum", domain: int):
    """Beyond-paper distributed FreqJoin for dense key domains: each shard
    scatter-adds its child block into a domain-sized accumulator, ONE psum
    over the ring axes produces the global multiplier table, and parents
    gather locally.  Replaces P ring steps (P ppermutes + P searchsorted
    passes) with one all-reduce of `domain` elements — the distributed
    twin of the local dense-domain FreqJoin (EXPERIMENTS §Perf)."""
    cfx = (cf > 0).astype(pf.dtype) if mode == "any" else cf.astype(pf.dtype)
    acc = jnp.zeros((domain,), pf.dtype)
    acc = acc.at[jnp.clip(ck, 0, domain - 1)].add(
        jnp.where((ck >= 0) & (ck < domain), cfx, 0))
    for a in ring_axes:
        acc = lax.psum(acc, a)
    mult = acc[jnp.clip(pk, 0, domain - 1)]
    mult = jnp.where((pk >= 0) & (pk < domain), mult, 0)
    if mode == "any":
        mult = (mult > 0).astype(pf.dtype)
    return pf * mult


def shard_table(table: Table, sharding) -> Table:
    """Place every column (and freq) of `table` under `sharding`."""
    cols = {c: jax.device_put(a, sharding) for c, a in table.columns.items()}
    return Table(cols, jax.device_put(table.freq, sharding))


class _RingExecutor(Executor):
    """Per-shard node evaluator: the ``Executor`` semantics with semi/freq
    joins replaced by ring (or dense-domain all-reduce) sweeps over the
    mesh axes.  Instantiated by ``DistributedExecutor._inner_executor``
    inside its shard_map program — every other node type (scans, the
    content-key memo, selection masking) is inherited unchanged, which is
    the whole point: one interpreter, two lowerings.

    The all-reduce variant runs where ``dense_domain`` is set and the keys
    declare a domain; every other join is a ring.  The tally
    (``Executor.joins``) counts the all-reduce as "dense" and the ring,
    which sorts and searches, as "sorted"."""

    def __init__(self, db: dict[str, Table], schema: Schema,
                 ring_axes: Sequence[str], presort: bool,
                 dense_domain: bool):
        super().__init__(db, schema)
        self.ring_axes = tuple(ring_axes)
        self.presort = presort
        self.dense_domain = dense_domain

    def _key(self, plan, alias, st, on_vars):
        key, dom = super()._key(plan, alias, st, on_vars)
        if not self.dense_domain or (dom is not None and dom >= (1 << 31)):
            # the all-reduce variant scatter-adds into a domain-sized
            # accumulator per shard — only where asked, and capped at
            # int32 indexing range; otherwise the ring
            dom = None
        return key, dom

    def _ring(self, pk, pf, ck, cf, cdom, mode: str):
        self.joins["sorted" if cdom is None else "dense"] += 1
        if cdom is not None:
            return allreduce_freq_join(pk, pf, ck, cf,
                                       ring_axes=self.ring_axes,
                                       mode=mode, domain=cdom)
        return ring_freq_join(pk, pf, ck, cf, ring_axes=self.ring_axes,
                              mode=mode, presort=self.presort)

    def _semi_join(self, plan, op: SemiJoinOp, p: _State,
                   c: _State) -> _State:
        pk, _pd = self._key(plan, op.parent, p, op.on_vars)
        ck, cdom = self._key(plan, op.child, c, op.on_vars)
        return _State(p.cols, self._ring(pk, p.freq, ck, c.freq, cdom,
                                         "any"))

    def _freq_join(self, plan, op: FreqJoinOp, p: _State, c: _State,
                   limbs=None) -> _State:
        # op.pregroup (pre-summing duplicate child keys) is a local-engine
        # micro-optimisation; the ring accumulates exact per-shard sums
        # anyway, so it is ignored — identical integers by the semiring law
        pk, _pd = self._key(plan, op.parent, p, op.on_vars)
        ck, cdom = self._key(plan, op.child, c, op.on_vars)
        return _State(p.cols, self._ring(pk, p.freq, ck, c.freq, cdom,
                                         "sum"))

    def _final_agg(self, plan, op, st):  # pragma: no cover — guarded
        raise TypeError("final aggregation must not run per-shard; "
                        "DistributedExecutor aggregates outside shard_map")


class DistributedExecutor(Executor):
    """The graph interpreter lowered onto a device mesh.

    Tables are row-sharded over `data_axes` (e.g. ("pod", "data") on the
    production mesh).  ``compile``/``compile_multi`` emit ONE jitted
    program per call: the inherited ``_trace_plan`` traversal runs inside
    a single ``shard_map`` with ``_RingExecutor`` as the node evaluator —
    every semi/freq join a ring sweep, every memo hit shared across member
    plans — evaluated up to each plan's pre-aggregate root state; final
    aggregation then runs outside the shard_map on replicated root
    columns, so answers are bitwise-equal to a single-device run over the
    same padded capacities.
    """

    def __init__(self, schema: Schema, mesh: jax.sharding.Mesh,
                 data_axes: Sequence[str] = ("data",),
                 presort: bool = False, dense_domain: bool = False):
        super().__init__({}, schema)
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        self.presort = presort
        # all-reduce joins on declared key domains (``_RingExecutor``)
        self.dense_domain = dense_domain

    def jittable(self) -> "DistributedExecutor":
        return self          # never carries eager-only options

    # -- sharding helpers --------------------------------------------------
    @property
    def n_shards(self) -> int:
        n = 1
        for a in self.data_axes:
            n *= self.mesh.shape[a]
        return n

    def topology(self) -> tuple[tuple[str, ...], tuple[int, ...]]:
        """(axis names, shard counts) — the shape-relevant mesh identity
        the serving tier folds into its executable-cache keys."""
        return (self.data_axes,
                tuple(self.mesh.shape[a] for a in self.data_axes))

    def row_sharding(self):
        return jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(self.data_axes))

    def replicated_sharding(self):
        return jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec())

    def shard_capacity(self, n_rows: int, min_bucket: int = 8) -> int:
        """Global padded capacity for an n-row table on this mesh: each
        shard gets a power-of-two block, so within-bucket per-shard growth
        never changes the compiled program's shapes."""
        return sharded_bucket_capacity(n_rows, self.n_shards, min_bucket)

    def shard_db(self, db: dict[str, Table],
                 min_bucket: int = 8) -> dict[str, Table]:
        """Pad each table to its per-shard power-of-two bucket
        (``sharded_bucket_capacity``) and shard rows over the mesh."""
        sh = self.row_sharding()
        return {name: shard_table(t.pad_to(self.shard_capacity(t.capacity,
                                                               min_bucket)),
                                  sh)
                for name, t in db.items()}

    # -- plan execution ----------------------------------------------------
    def _inner_executor(self, db: dict[str, Table],
                        count_bits: int) -> Executor:
        return _RingExecutor(db, self.schema, self.data_axes, self.presort,
                             self.dense_domain)

    def _counts(self, plans: list[PhysicalPlan],
                counts: Counts | None) -> Counts:
        """``counts``, which the caller must give (the mesh executor holds
        no tables to bound them from: ``repro.core.stats.db_counts`` of
        the unsharded ones does), and only at 32 bits."""
        if counts is None:
            raise ValueError(
                "the mesh executor holds no tables to bound the counts "
                "from: pass counts (repro.core.stats.db_counts)")
        if counts.bits != 32:
            raise PlanningError(
                f"this query needs {counts.bits}-bit counts, and the mesh "
                "executor's ring sweeps hold 32-bit counts; serve it on "
                "one device")
        return counts

    @staticmethod
    def _agg_state_node(plan: PhysicalPlan) -> PlanNode:
        """The pre-aggregate root state — where the shard_map stops."""
        return plan.root.inputs[0]

    @staticmethod
    def _agg_cols(plan: PhysicalPlan) -> set[str]:
        """Root-state columns the final aggregate actually reads; only
        these leave the shard_map (smaller out-specs, nothing else is
        gathered)."""
        op = plan.root.op
        need = set(op.group_by)
        for ag in op.aggregates:
            if ag.var is not None:
                need.add(ag.var)
        return need

    def _ring_program(self, plans: list[PhysicalPlan],
                      joins: collections.Counter | None):
        """db → [result dict per plan]: one shard_map sweep evaluating
        every member to its root state (shared trace memo, exactly like
        the local ``compile_multi``), then replicated final aggregation.
        ``joins`` as in ``Executor.compile``."""
        spec = jax.sharding.PartitionSpec(self.data_axes)
        rep = self.replicated_sharding()

        def sweep(db: dict[str, Table]):
            if joins is not None:
                joins.clear()
            memo: dict = {}
            outs = []
            for plan in plans:
                st = self._trace_plan(db, plan, memo,
                                      root=self._agg_state_node(plan),
                                      joins=joins)
                need = self._agg_cols(plan)
                outs.append(({v: c for v, c in st.cols.items()
                              if v in need}, st.freq))
            return outs

        def run(db: dict[str, Table]):
            specs = jax.tree.map(lambda _: spec, db)
            outs = jax.shard_map(sweep, mesh=self.mesh, in_specs=(specs,),
                                 out_specs=spec)(db)
            results = []
            for plan, (cols, freq) in zip(plans, outs):
                # replicate the (exact, order-independent) sweep output so
                # the aggregate program is the single-device one on every
                # device — bitwise parity with the local executor.
                # ``reshard`` (not a sharding constraint) also changes the
                # array's sharding TYPE, which meshes with Explicit axes
                # (``jax.make_mesh``'s default) need before the aggregate's
                # gathers can resolve their output sharding
                cols = {v: jax.sharding.reshard(c, rep)
                        for v, c in cols.items()}
                freq = jax.sharding.reshard(freq, rep)
                with jax.named_scope("final_agg"):
                    results.append(self._final_agg(plan, plan.root.op,
                                                   _State(cols, freq)))
            return results

        return run

    def compile(self, plan: PhysicalPlan, name: str = "run",
                joins: collections.Counter | None = None,
                counts: Counts | None = None,
                sizes: collections.Counter | None = None):
        """Jit one plan's ring program: sharded db → aggregates.  Its
        counts are 32-bit: ``counts`` is required, and wider ones are
        refused.  The ring sweeps tally no ``sizes``."""
        self._check_jittable([plan])
        self._counts([plan], counts)
        ring = self._ring_program([plan], joins)
        return jax.jit(named(lambda db: ring(db)[0], name))

    def compile_multi(self, plans: list[PhysicalPlan], name: str = "run",
                      joins: collections.Counter | None = None,
                      counts: Counts | None = None,
                      sizes: collections.Counter | None = None):
        """Jit several plans into ONE mesh program (shared ring sweeps):
        sharded db → [aggregates], results in plan order; 32-bit counts
        as in ``compile``."""
        if not plans:
            raise ValueError("compile_multi needs at least one plan")
        self._check_jittable(plans)
        self._counts(plans, counts)
        return jax.jit(named(self._ring_program(list(plans), joins), name))
