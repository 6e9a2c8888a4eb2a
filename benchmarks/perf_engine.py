"""§Perf hillclimb — engine cell (the paper-representative workload).

Measures the hypothesis→change ladder on the frequency-propagation
queries where the baseline engine LOST to Ref (EXPERIMENTS §Repro):

  it0  baseline         — paper-faithful: per-edge child sort + pregroup
                          (the schema with its domains stripped)
  it1  +dense-domain    — sort-free scatter-add FreqJoin on the declared
                          key domains (embedding-grad pattern)

and on the distributed ring (8 fake devices, subprocess-launched by the
caller when XLA_FLAGS allows):

  it2  ring presort     — sort each child shard once, rotate (keys,
                          prefix) instead of re-sorting every ring step

Run:  PYTHONPATH=src python -m benchmarks.perf_engine
"""

from __future__ import annotations

import time

import jax
import numpy as np

from repro.core import Executor, plan_query
from repro.data import make_graph_db, make_stats_db, make_tpch_db, path_query
from repro.data.relational import stats_count_query, tpch_v1_query


def _time(fn, repeats=5):
    fn()
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def bench_local():
    rows = []
    with jax.enable_x64(True):
        cases = []
        db, schema = make_tpch_db(scale=5000, seed=0)
        cases.append(("tpch-v1-median", db, schema, tpch_v1_query("median")))
        sdb, sschema = make_stats_db(n_users=20_000, n_posts=100_000,
                                     n_comments=400_000, n_votes=250_000)
        cases.append(("stats-q4-count", sdb, sschema, stats_count_query()))
        gdb, gschema = make_graph_db(20_000, 200_000, seed=0)
        cases.append(("path-05-count", gdb, gschema, path_query(5)))

        for name, db_, schema_, q in cases:
            plan = plan_query(q, schema_, mode="opt_plus")
            row = {"query": name}
            # the same data with its domains stripped (every join sorted)
            # against the declared domains (dense joins)
            for label, sch in (("baseline", schema_.without_domains()),
                               ("dense_domain", schema_)):
                ex = Executor(db_, sch)
                fn = ex.compile(plan)

                def run():
                    out = fn(db_)
                    jax.block_until_ready(list(out.values()))
                    return out

                row[label] = _time(run)
                row[f"{label}_result"] = float(
                    next(v for k, v in run().items() if k != "__stats__"))
            # results must agree exactly
            assert row["baseline_result"] == row["dense_domain_result"], row
            row["speedup"] = row["baseline"] / row["dense_domain"]
            rows.append(row)
            # Ref comparison (eager numpy baseline)
            try:
                ex = Executor(db_, schema_,
                              oom_guard=20_000_000)
                row["ref"] = _time(
                    lambda: ex.execute(plan_query(q, schema_, mode="ref")),
                    repeats=1)
            except Exception:  # noqa: BLE001
                row["ref"] = None
    return rows


def main():
    rows = bench_local()
    print(f"{'query':18s} {'Ref':>9s} {'it0 base':>9s} {'it1 dense':>10s} "
          f"{'it1/it0':>8s} {'vs Ref':>8s}")
    for r in rows:
        ref = f"{r['ref']:.3f}" if r.get("ref") else "X"
        vs = (f"{r['ref'] / r['dense_domain']:.2f}x" if r.get("ref")
              else "inf")
        print(f"{r['query']:18s} {ref:>9s} {r['baseline']:9.3f} "
              f"{r['dense_domain']:10.3f} {r['speedup']:7.2f}x {vs:>8s}")
    return rows


if __name__ == "__main__":
    main()
