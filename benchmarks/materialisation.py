"""Paper Fig. 6: peak materialised/live tuples per plan class.

The paper's headline systems metric: Opt⁺ never materialises a tuple
beyond the largest base relation; Ref blows up by orders of magnitude;
Opt sits in between (pairwise joins materialise, then regroup).
"""

from __future__ import annotations

import jax

from repro.core import Executor, MaterialisationLimit, plan_query
from repro.data import make_graph_db, make_stats_db, path_query
from repro.data.relational import stats_count_query

OOM_GUARD = 50_000_000


def peak_tuples(ex, db, schema, q, mode):
    try:
        stats = ex.execute(plan_query(q, schema, mode=mode))["__stats__"]
        return stats.peak_tuples
    except MaterialisationLimit:
        return None  # exceeded guard (reported as > guard)


def run():
    rows = []
    with jax.enable_x64(True):
        db, schema = make_graph_db(5_000, 60_000, seed=2)
        ex = Executor(db, schema, oom_guard=OOM_GUARD)
        base_max = max(int(t.live_count()) for t in db.values())
        for k in (2, 3, 4):
            q = path_query(k)
            row = {"query": f"path-{k:02d}", "base_max": base_max}
            for mode in ("ref", "opt", "opt_plus"):
                row[mode] = peak_tuples(ex, db, schema, q, mode)
            rows.append(row)

        sdb, sschema = make_stats_db(n_users=5_000, n_posts=20_000,
                                     n_comments=100_000, n_votes=60_000)
        sex = Executor(sdb, sschema,
                       oom_guard=OOM_GUARD)
        base_max = max(int(t.live_count()) for t in sdb.values())
        q = stats_count_query()
        row = {"query": "stats-full", "base_max": base_max}
        for mode in ("ref", "opt", "opt_plus"):
            row[mode] = peak_tuples(sex, sdb, sschema, q, mode)
        rows.append(row)
    return rows


def main():
    rows = run()
    print(f"{'query':12s} {'base-max':>10s} {'Ref':>12s} {'Opt':>12s} "
          f"{'Opt+':>10s}")
    ok = True
    for r in rows:
        ref = str(r["ref"]) if r["ref"] is not None else f">{OOM_GUARD}"
        opt = str(r["opt"]) if r["opt"] is not None else f">{OOM_GUARD}"
        print(f"{r['query']:12s} {r['base_max']:>10d} {ref:>12s} "
              f"{opt:>12s} {r['opt_plus']:>10d}")
        # the paper's invariant: Opt+ peak == largest scanned relation
        ok &= r["opt_plus"] <= r["base_max"]
    print(f"Opt+ ≤ max base relation: {'OK' if ok else 'VIOLATED'}")
    return rows


if __name__ == "__main__":
    main()
