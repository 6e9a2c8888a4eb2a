"""Planner statistics: per-table/per-column stats, cost model, feedback.

This module is the single home for every cardinality/selectivity policy
constant the planner and serving tier consult (``scripts/lint.py``
enforces that threshold constants live here and nowhere else).  The shape
mirrors the decision cards in SNIPPETS.md: each rewrite/fusion decision is
a structural gate followed by a *calibration* against numbers kept here.

Four layers:

  * ``TableStats`` / ``ColumnStats`` — cheap per-relation summaries (live
    row counts, the largest base frequency, distinct counts, min/max, the
    largest multiplicity of one value, FK orphan counts) computed once per
    table load/update from the numpy columns.  Each carries the
    table's content ``token`` so a consumer can tell exactly which data
    version a decision was calibrated against.
  * ``StatsCatalog`` — the live registry the planner reads: selectivity
    estimation for declarative selection specs, a padded-shape cost model
    for fusion admission, and decision-dependency validation (a recorded
    decision is stale iff a table it consulted changed token).
  * ``count_bound`` / ``count_bits`` / ``count_plan`` — a sound upper
    bound on every frequency a plan's sweep produces and on its COUNT,
    from those summaries, the width of the counts it needs (32 bits, 64
    bits, or a ``PlanningError`` that names the bound), and at 64 bits the
    32-bit limbs each dense join scatters (``Counts``, ``limb_split``).
  * serve-time feedback — EWMA solo vs. fused serve times per
    (fingerprint, fusion-group signature); a fusion that consistently
    regresses a member vs. its solo baseline is *demoted* and the grouper
    stops forming it.

Grounded in Memory-Efficient Group-by Aggregates over Multi-Way Joins
(PAPERS.md, 1906.05745): statistics sized by the *relations*, never the
join.
"""

from __future__ import annotations

import dataclasses
import math
import threading

import numpy as np

from repro.core.plan import (
    FinalAggOp,
    FreqJoinOp,
    MaterializeJoinOp,
    PhysicalPlan,
    PlanningError,
    ScanOp,
    SemiJoinOp,
)
from repro.tables.table import Schema, Table

STATS_VERSION = 2

# ---------------------------------------------------------------------------
# Policy constants (the ONLY allowed home for these — see scripts/lint.py).
# ---------------------------------------------------------------------------

#: FK-join elimination only fires on a verified-clean FK edge: the child
#: must have zero orphan references or dropping the join changes answers.
FK_ELIM_MAX_ORPHANS = 0

#: Pre-filter pushdown wants a genuinely selective dimension…
PREFILTER_MAX_SELECTIVITY = 0.25
#: …feeding a parent big enough that shrinking the materialised
#: intermediate is worth an extra semi-join (tiny tables: overhead wins).
PREFILTER_MIN_PARENT_ROWS = 64

#: Fusion admission: a plan never joins a fusion group whose maximum
#: estimated (padded-shape) cost is ≥ this multiple of its own.
FUSION_COST_DISPARITY = 8.0

#: Feedback demotion: a fusion is demoted for a member once observed at
#: least this many times fused AND its fused EWMA serve time exceeds the
#: solo baseline by this factor.
DEMOTION_MIN_OBSERVATIONS = 2
DEMOTION_REGRESSION_FACTOR = 1.5

#: Smoothing for observed serve times (newest observation's weight).
SERVE_EWMA_ALPHA = 0.5


# ---------------------------------------------------------------------------
# Per-table statistics
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ColumnStats:
    """Summary of one column over the *live* (freq > 0) rows."""

    distinct: int
    lo: float | None = None
    hi: float | None = None
    #: the most live rows that hold one value
    max_mult: int = 0

    def to_payload(self) -> dict:
        return {"distinct": self.distinct, "lo": self.lo, "hi": self.hi,
                "max_mult": self.max_mult}

    @classmethod
    def from_payload(cls, p: dict) -> "ColumnStats":
        return cls(distinct=int(p["distinct"]),
                   lo=p.get("lo"), hi=p.get("hi"),
                   max_mult=int(p["max_mult"]))


@dataclasses.dataclass(frozen=True)
class TableStats:
    """Summary of one relation at one data version (``token``)."""

    relation: str
    rows: int                  # live tuples (freq > 0)
    max_freq: int              # the largest base frequency of a row
    capacity: int              # padded physical capacity
    token: str                 # Table.content_token() of the data version
    columns: dict[str, ColumnStats]
    #: orphan reference counts per declared outgoing FK, keyed
    #: "src_col->dst.dst_col" — 0 means every live src value has a live
    #: unique partner in dst (the soundness condition for FK-join
    #: elimination; referential integrity is measured, never assumed).
    fk_orphans: dict[str, int]

    def to_payload(self) -> dict:
        return {
            "version": STATS_VERSION,
            "relation": self.relation,
            "rows": self.rows,
            "max_freq": self.max_freq,
            "capacity": self.capacity,
            "token": self.token,
            "columns": {c: s.to_payload() for c, s in self.columns.items()},
            "fk_orphans": dict(self.fk_orphans),
        }

    @classmethod
    def from_payload(cls, p: dict) -> "TableStats":
        if p.get("version") != STATS_VERSION:
            raise ValueError(f"stats version {p.get('version')!r} != "
                             f"{STATS_VERSION}")
        return cls(
            relation=p["relation"], rows=int(p["rows"]),
            max_freq=int(p["max_freq"]), capacity=int(p["capacity"]),
            token=p["token"],
            columns={c: ColumnStats.from_payload(s)
                     for c, s in p["columns"].items()},
            fk_orphans={k: int(v) for k, v in p["fk_orphans"].items()},
        )


def _live_column(table: Table, name: str, live: np.ndarray) -> np.ndarray:
    return np.asarray(table.columns[name])[live]


def compute_table_stats(name: str, table: Table, schema: Schema,
                        db: dict[str, Table]) -> TableStats:
    """One full pass over a table's live rows: numpy-cheap, O(rows)."""
    freq = np.asarray(table.freq)
    live = freq > 0
    rows = int(live.sum())
    columns: dict[str, ColumnStats] = {}
    for col in table.column_names:
        vals = _live_column(table, col, live)
        if vals.size == 0:
            columns[col] = ColumnStats(distinct=0)
            continue
        _, mult = np.unique(vals, return_counts=True)
        lo = hi = None
        if np.issubdtype(vals.dtype, np.number):
            lo, hi = float(vals.min()), float(vals.max())
        columns[col] = ColumnStats(distinct=int(mult.size), lo=lo, hi=hi,
                                   max_mult=int(mult.max()))

    fk_orphans: dict[str, int] = {}
    for fk in schema.foreign_keys:
        if fk.src != name or fk.dst not in db:
            continue
        dst = db[fk.dst]
        src_vals = _live_column(table, fk.src_col, live)
        dst_live = np.asarray(dst.freq) > 0
        dst_vals = _live_column(dst, fk.dst_col, dst_live)
        orphans = int((~np.isin(src_vals, dst_vals)).sum())
        fk_orphans[f"{fk.src_col}->{fk.dst}.{fk.dst_col}"] = orphans

    return TableStats(relation=name, rows=rows,
                      max_freq=int(freq.max(initial=0)),
                      capacity=table.capacity,
                      token=table.content_token(), columns=columns,
                      fk_orphans=fk_orphans)


# ---------------------------------------------------------------------------
# The width of the counts
# ---------------------------------------------------------------------------

#: the largest count each width holds exactly, narrowest first
COUNT_LIMITS = ((32, 2 ** 31 - 1), (64, 2 ** 63 - 1))


@dataclasses.dataclass(frozen=True)
class Counts:
    """How a program holds its counts: ``bits`` wide (32 or 64) and, at 64
    bits, for each freq-join (``join_id``) the split of the child's
    frequencies into 32-bit limbs (``limb_split``) that its dense
    scatter-add needs.  Those two are a compiled program's identity.  The
    rest records what they were chosen from: ``bound``, the largest count
    of the plans; ``joins``, each freq-join's most child rows on one key
    and child frequency bound; ``tokens``, the data version of each
    relation whose statistics were read."""

    bits: int = 32
    limbs: dict = dataclasses.field(default_factory=dict)
    bound: int = dataclasses.field(default=0, compare=False)
    joins: dict = dataclasses.field(default_factory=dict, compare=False)
    tokens: dict = dataclasses.field(default_factory=dict, compare=False)

    def current(self, tables: dict[str, "TableStats"]) -> bool:
        """True iff ``tables`` describe the data these counts were chosen
        from."""
        return all((st := tables.get(rel)) is not None and st.token == tok
                   for rel, tok in self.tokens.items())


def join_id(node) -> object:
    """The key of a freq-join in ``Counts.limbs``: its content key, which
    a fused program's shared sub-DAGs share, or, below a materialising
    join (no content key; eager plans only), the node itself."""
    key = node.key()
    return key if key is not None else id(node)


def limb_split(mult: int, freq: int) -> tuple[int, int]:
    """(bits a limb, limbs) to split child frequencies up to ``freq`` into
    so that each limb's sum over at most ``mult`` rows stays below 2^32:
    a dense scatter-add of 64-bit counts in 32-bit words, exact."""
    bits = 32 - max(int(mult), 1).bit_length()
    return bits, max(1, -(-max(int(freq), 1).bit_length() // bits))


def count_plan(plan: PhysicalPlan,
               tables: dict[str, "TableStats"]) -> Counts:
    """The ``Counts`` of ``plan`` over the data ``tables`` describe: the
    narrowest width that holds its counts and, at 64 bits, each join's
    limbs.  A ``PlanningError`` where no width holds them."""
    joins: dict = {}
    bound = count_bound(plan, tables, joins)
    return _counts(bound, joins,
                   {rel: tables[rel].token for rel in plan.scanned_rels()})


def widest(counts: list[Counts]) -> Counts:
    """The ``Counts`` of one program that runs plans counted apart: the
    width of the widest, with limbs for every member's joins."""
    if len(counts) == 1:
        return counts[0]
    return _counts(max(c.bound for c in counts),
                   {k: v for c in counts for k, v in c.joins.items()},
                   {k: v for c in counts for k, v in c.tokens.items()})


def db_counts(plans: list[PhysicalPlan], db: dict[str, Table],
              schema: Schema) -> Counts:
    """The ``Counts`` of one program running ``plans`` over ``db``, from
    statistics computed on the spot: for callers that hold their tables
    without a catalog."""
    rels = sorted({rel for p in plans for rel in p.scanned_rels()})
    tables = {rel: compute_table_stats(rel, db[rel], schema, db)
              for rel in rels}
    return widest([count_plan(p, tables) for p in plans])


def _counts(bound: int, joins: dict, tokens: dict) -> Counts:
    bits = count_bits(bound)
    limbs = {k: limb_split(m, f) for k, (m, f) in joins.items()} \
        if bits == 64 else {}
    return Counts(bits, limbs, bound, joins, tokens)


def count_bound(plan: PhysicalPlan, tables: dict[str, "TableStats"],
                joins: dict | None = None) -> int:
    """A sound upper bound on every frequency ``plan``'s sweep produces
    and on its COUNT, from the statistics of the relations it scans.

    Per node, ``rows`` bounds its state's live rows and ``freq`` each
    row's frequency.  A scan holds its relation's live rows at their
    largest base frequency; a semi-join keeps its parent's bounds; a
    freq-join multiplies its parent's ``freq`` by the most child rows one
    key can match (the least ``max_mult`` of the child's key columns,
    since the sweep keeps the child relation's rows) and by the child's
    ``freq``.  A materialising join (the eager baselines) bounds its rows
    and, regrouped, its groups the same way.  The answer's bound is the
    root's rows times its ``freq``.  ``joins``, when given, gains for each
    freq-join (``join_id``) the most child rows one key holds and the
    child's ``freq``."""
    rows: dict[int, int] = {}
    freq: dict[int, int] = {}
    base: dict[int, str | None] = {}   # relation whose rows a state holds

    def mult(node, alias: str, on_vars) -> int:
        rel = base[id(node)]
        if rel is None:
            return rows[id(node)]
        cols = tables[rel].columns
        names = [plan.var_cols[alias][v] for v in on_vars]
        return min((cols[c].max_mult for c in names),
                   default=rows[id(node)])

    for node in plan.nodes:
        op, i = node.op, id(node)
        if isinstance(op, ScanOp):
            st = tables[op.rel]
            rows[i], freq[i], base[i] = st.rows, st.max_freq, op.rel
            continue
        p = node.inputs[0]
        rows[i], freq[i], base[i] = rows[id(p)], freq[id(p)], base[id(p)]
        if isinstance(op, FreqJoinOp):
            c = node.inputs[1]
            m = mult(c, op.child, op.on_vars)
            freq[i] *= m * freq[id(c)]
            if joins is not None:
                joins[join_id(node)] = (m, freq[id(c)])
        elif isinstance(op, MaterializeJoinOp):
            c = node.inputs[1]
            m = mult(c, op.child, op.on_vars)
            if op.regroup:
                # a group sums the parent rows that share every value
                freq[i] *= mult(p, op.parent, tuple(plan.var_cols[op.parent])
                                ) * m * freq[id(c)]
            else:
                rows[i] *= m
                freq[i] *= freq[id(c)]
            base[i] = None
    root = id(plan.root)
    return max(max(freq.values()), rows[root] * freq[root])


def count_bits(bound: int) -> int:
    """The narrowest width that holds every count up to ``bound``
    exactly; a ``PlanningError`` naming the bound where none does."""
    for bits, limit in COUNT_LIMITS:
        if bound <= limit:
            return bits
    raise PlanningError(
        f"the counts of this query are bounded by {bound} "
        f"(2^{math.log2(bound):.1f}), past 2^63 - 1: no exact width holds "
        "them")


# ---------------------------------------------------------------------------
# Serve-time feedback
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FeedbackRecord:
    """EWMA serve times for one (fingerprint, fusion-group signature).

    ``signature == ""`` is the solo baseline for the fingerprint."""

    ewma_s: float = 0.0
    count: int = 0

    def observe(self, serve_s: float) -> None:
        if self.count == 0:
            self.ewma_s = serve_s
        else:
            a = SERVE_EWMA_ALPHA
            self.ewma_s = a * serve_s + (1.0 - a) * self.ewma_s
        self.count += 1

    def to_payload(self) -> dict:
        return {"ewma_s": self.ewma_s, "count": self.count}

    @classmethod
    def from_payload(cls, p: dict) -> "FeedbackRecord":
        return cls(ewma_s=float(p["ewma_s"]), count=int(p["count"]))


# ---------------------------------------------------------------------------
# The catalog
# ---------------------------------------------------------------------------

class StatsCatalog:
    """Live statistics registry: tables, cost model, serve-time feedback.

    Thread-safe; every method takes the internal lock.  Table entries are
    installed either by :meth:`refresh` (a full compute — the caller's
    ``stat_refreshes`` counter should track these) or :meth:`install`
    (e.g. loaded from a warm :class:`~repro.service.stats_store.StatsStore`
    after a token match — no compute, no refresh counted).
    """

    def __init__(self, schema: Schema):
        self.schema = schema
        self._tables: dict[str, TableStats] = {}
        self._feedback: dict[tuple[str, str], FeedbackRecord] = {}
        self._lock = threading.Lock()

    # -- table stats ------------------------------------------------------

    def refresh(self, name: str, table: Table,
                db: dict[str, Table]) -> TableStats:
        st = compute_table_stats(name, table, self.schema, db)
        with self._lock:
            self._tables[name] = st
        return st

    def install(self, stats: TableStats) -> None:
        with self._lock:
            self._tables[stats.relation] = stats

    def get(self, name: str) -> TableStats | None:
        with self._lock:
            return self._tables.get(name)

    def token(self, name: str) -> str | None:
        st = self.get(name)
        return st.token if st is not None else None

    def tables(self) -> dict[str, TableStats]:
        with self._lock:
            return dict(self._tables)

    # -- decision-dependency validation -----------------------------------

    def validate_depends(self, depends: dict[str, str]) -> bool:
        """True iff every (relation → token) a decision recorded still
        matches the catalog — i.e. the decision's inputs are current."""
        with self._lock:
            return all(
                (st := self._tables.get(rel)) is not None
                and st.token == tok
                for rel, tok in depends.items())

    # -- selectivity estimation -------------------------------------------

    def estimate_selectivity(self, rel: str, spec) -> float | None:
        """Estimated live-row fraction passing a declarative selection
        spec (AND-ed ``(op, col, literal)`` terms).  ``None`` when the
        relation has no stats — callers must treat that as "gate fails",
        never as "assume selective"."""
        st = self.get(rel)
        if st is None or spec is None:
            return None
        frac = 1.0
        for op, col, val in spec:
            cs = st.columns.get(col)
            if cs is None or cs.distinct <= 0:
                return None
            if op == "=":
                f = 1.0 / cs.distinct
            elif op == "in":
                f = min(len(tuple(val)) / cs.distinct, 1.0)
            elif op == "!=":
                f = 1.0 - 1.0 / cs.distinct
            elif op in ("<", ">", "<=", ">="):
                if cs.lo is None or cs.hi is None or cs.hi <= cs.lo:
                    f = 0.5
                else:
                    span = cs.hi - cs.lo
                    if op in ("<", "<="):
                        f = (float(val) - cs.lo) / span
                    else:
                        f = (cs.hi - float(val)) / span
            else:
                return None
            frac *= min(max(f, 0.0), 1.0)
        return frac

    # -- cost model --------------------------------------------------------

    def estimate_plan_cost(self, plan: PhysicalPlan,
                           rows: dict[str, int] | None = None) -> float:
        """Estimated work for one execution of ``plan``.

        The engine is static-shape: sweeps run over *padded* capacities
        regardless of live counts or selections, so the honest unit of
        work per node is the padded rows it touches.  Pass ``rows``
        mapping relation → padded bucket capacity for serve-time costs;
        falls back to catalog live row counts (planner-side estimates).
        """
        sizes: dict[int, float] = {}
        cost = 0.0
        for node in plan.root.postorder():
            op = node.op
            if isinstance(op, ScanOp):
                if rows is not None and op.rel in rows:
                    r = float(rows[op.rel])
                else:
                    st = self.get(op.rel)
                    r = float(st.rows) if st is not None else 1.0
                sizes[id(node)] = r
                cost += r
            elif isinstance(op, (SemiJoinOp, FreqJoinOp)):
                p = sizes[id(node.inputs[0])]
                c = sizes[id(node.inputs[1])]
                sizes[id(node)] = p       # sweeps keep the parent's shape
                cost += p + c
            elif isinstance(op, MaterializeJoinOp):
                p = sizes[id(node.inputs[0])]
                c = sizes[id(node.inputs[1])]
                sizes[id(node)] = p * max(c, 1.0) ** 0.5  # growth, damped
                cost += p + c + sizes[id(node)]
            elif isinstance(op, FinalAggOp):
                r = sizes[id(node.inputs[0])]
                sizes[id(node)] = r
                cost += r
        return cost

    # -- serve-time feedback ----------------------------------------------

    def observe_serve(self, fingerprint: str, signature: str,
                      serve_s: float) -> None:
        """Record an observed serve time.  ``signature`` is the fusion
        group signature the request ran under ("" = served solo)."""
        with self._lock:
            rec = self._feedback.setdefault((fingerprint, signature),
                                            FeedbackRecord())
            rec.observe(serve_s)

    def is_demoted(self, fingerprint: str, signature: str) -> bool:
        """True iff this fusion has been observed regressing this member
        vs. its solo baseline — the grouper must not re-form it."""
        with self._lock:
            fused = self._feedback.get((fingerprint, signature))
            solo = self._feedback.get((fingerprint, ""))
            if fused is None or solo is None or solo.count == 0:
                return False
            return (fused.count >= DEMOTION_MIN_OBSERVATIONS
                    and fused.ewma_s
                    > DEMOTION_REGRESSION_FACTOR * solo.ewma_s)

    def demotions(self) -> list[dict]:
        """Currently-demoted (fingerprint, signature) pairs with numbers."""
        with self._lock:
            keys = list(self._feedback)
        out = []
        for fp, sig in keys:
            if sig and self.is_demoted(fp, sig):
                with self._lock:
                    fused = self._feedback[(fp, sig)]
                    solo = self._feedback.get((fp, ""), FeedbackRecord())
                out.append({"fingerprint": fp, "signature": sig,
                            "fused_ewma_s": fused.ewma_s,
                            "solo_ewma_s": solo.ewma_s})
        return out

    def feedback_payload(self) -> dict:
        """JSON-able snapshot of the feedback table (for the store)."""
        with self._lock:
            return {
                "version": STATS_VERSION,
                "records": [
                    {"fingerprint": fp, "signature": sig,
                     **rec.to_payload()}
                    for (fp, sig), rec in sorted(self._feedback.items())
                ],
            }

    def load_feedback(self, payload: dict) -> int:
        """Install a persisted feedback snapshot; returns records loaded.
        Existing in-memory records win (they are newer)."""
        if payload.get("version") != STATS_VERSION:
            return 0
        n = 0
        with self._lock:
            for r in payload.get("records", ()):
                key = (r["fingerprint"], r["signature"])
                if key not in self._feedback:
                    self._feedback[key] = FeedbackRecord.from_payload(r)
                    n += 1
        return n

    def feedback_len(self) -> int:
        with self._lock:
            return len(self._feedback)


__all__ = [
    "ColumnStats",
    "TableStats",
    "FeedbackRecord",
    "StatsCatalog",
    "compute_table_stats",
    "count_bound",
    "count_bits",
    "count_plan",
    "Counts",
    "db_counts",
    "join_id",
    "limb_split",
    "widest",
    "COUNT_LIMITS",
    "FK_ELIM_MAX_ORPHANS",
    "PREFILTER_MAX_SELECTIVITY",
    "PREFILTER_MIN_PARENT_ROWS",
    "FUSION_COST_DISPARITY",
    "DEMOTION_MIN_OBSERVATIONS",
    "DEMOTION_REGRESSION_FACTOR",
    "SERVE_EWMA_ALPHA",
    "STATS_VERSION",
]
