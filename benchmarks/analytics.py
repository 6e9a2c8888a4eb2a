"""Paper Table 2: analytic benchmarks.

  TPC-H V.1  — the paper's running example (Fig. 1): MIN/MAX (0MA) and the
               MEDIAN variant (guarded → frequency propagation), with and
               without FK/PK information (§4.3).
  STATS-CEB  — FK/FK COUNT(*) over the stack-exchange-like schema, end to
               end over a family of queries (all guarded COUNT → all
               optimisable, as in the paper).
"""

from __future__ import annotations

import time

import jax
import numpy as np

from repro.core import Executor, MaterialisationLimit, plan_query
from repro.core.query import Agg, AggQuery, Atom
from repro.data import make_stats_db, make_tpch_db
from repro.data.relational import tpch_v1_query

OOM_GUARD = 20_000_000


def _time(fn, repeats=3):
    fn()
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.mean(ts)), float(np.std(ts))


def _bench_query(ex, db, schema, q, use_fkpk=False, repeats=3,
                 oma_ok=True):
    row = {}
    auto = plan_query(q, schema, mode="auto", use_fkpk=use_fkpk)
    row["plan"] = auto.mode
    fn = ex.jittable().compile(auto)

    def run_opt():
        out = fn(db)
        jax.block_until_ready(list(out.values()))
        return out

    row["opt_plus_s"], _ = _time(run_opt, repeats)
    try:
        row["ref_s"], _ = _time(
            lambda: ex.execute(plan_query(q, schema, mode="ref")), 1)
    except MaterialisationLimit:
        row["ref_s"] = None
    return row


def stats_query_family():
    """A STATS-CEB-like family: COUNT(*) joins of growing width."""
    u = Atom("users", "u", ("uid", "rep"))
    po = Atom("posts", "po", ("pid", "uid", "score"))
    co = Atom("comments", "co", ("pid", "cuid", "cscore"))
    v = Atom("votes", "v", ("pid", "vuid"))
    fams = [
        ("q1 posts-comments", (po, co)),
        ("q2 posts-votes", (po, v)),
        ("q3 users-posts-comments", (u, po, co)),
        ("q4 full", (u, po, co, v)),
        ("q5 comments-votes via posts", (po, co, v)),
    ]
    return [(n, AggQuery(atoms=a, aggregates=(Agg("count"),)))
            for n, a in fams]


def run(tpch_scale=5000, repeats=3):
    rows = []
    with jax.enable_x64(True):
        db, schema = make_tpch_db(scale=tpch_scale, seed=0)
        ex = Executor(db, schema, oom_guard=OOM_GUARD)
        for name, agg, fkpk in [
            ("TPC-H V.1 minmax (0MA)", "minmax", False),
            ("TPC-H V.1 median", "median", False),
            ("TPC-H V.1 median +FK/PK", "median", True),
        ]:
            q = tpch_v1_query(agg)
            r = _bench_query(ex, db, schema, q, use_fkpk=fkpk,
                             repeats=repeats)
            r["query"] = name
            rows.append(r)

        sdb, sschema = make_stats_db(n_users=20_000, n_posts=100_000,
                                     n_comments=400_000, n_votes=250_000)
        sex = Executor(sdb, sschema,
                       oom_guard=OOM_GUARD)
        e2e_opt, e2e_ref = 0.0, 0.0
        ref_failed = False
        for name, q in stats_query_family():
            r = _bench_query(sex, sdb, sschema, q, repeats=repeats)
            r["query"] = f"STATS {name}"
            rows.append(r)
            e2e_opt += r["opt_plus_s"]
            if r["ref_s"] is None:
                ref_failed = True
            else:
                e2e_ref += r["ref_s"]
        rows.append({"query": "STATS-CEB e2e", "plan": "opt_plus",
                     "opt_plus_s": e2e_opt,
                     "ref_s": None if ref_failed else e2e_ref})
    return rows


def main():
    rows = run()
    print(f"{'query':32s} {'plan':9s} {'Ref':>10s} {'Opt+':>10s} "
          f"{'speedup':>8s}")
    for r in rows:
        ref = f"{r['ref_s']:.3f}" if r.get("ref_s") else "X"
        sp = (f"{r['ref_s'] / r['opt_plus_s']:.2f}x" if r.get("ref_s")
              else "inf")
        print(f"{r['query']:32s} {r['plan']:9s} {ref:>10s} "
              f"{r['opt_plus_s']:>10.4f} {sp:>8s}")
    return rows


if __name__ == "__main__":
    main()
