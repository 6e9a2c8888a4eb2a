"""Homomorphism counting on a graph — the paper's SNAP experiment, and the
distributed Ring-FreqJoin on a multi-device mesh.

    PYTHONPATH=src python examples/graph_counting.py
    # multi-device (8 fake devices):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/graph_counting.py --distributed
"""

import argparse

import jax

from repro.core import Executor, plan_query
from repro.core.plan import PlanningError
from repro.core.stats import db_counts
from repro.data import make_graph_db, path_query, tree_query


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--nodes", type=int, default=5000)
    ap.add_argument("--edges", type=int, default=60000)
    args = ap.parse_args()

    with jax.enable_x64(True):
        db, schema = make_graph_db(args.nodes, args.edges, seed=0)
        ex = Executor(db, schema)

        for name, q in [("path-03", path_query(3)),
                        ("path-05", path_query(5)),
                        ("tree-02", tree_query(2))]:
            plan = plan_query(q, schema, mode="opt_plus")
            try:
                # exact counts in the width the statistics' bound needs;
                # past 2^63 the query is refused, never wrapped
                res = ex.execute(plan)
            except PlanningError as e:
                print(f"{name}: refused: {e}")
                continue
            print(f"{name}: {int(res['count(*)'])} homomorphisms, "
                  f"peak tuples {res['__stats__'].peak_tuples} "
                  f"(largest relation {args.edges})")

        if args.distributed:
            from repro.core.distributed import DistributedExecutor
            n = len(jax.devices())
            mesh = jax.make_mesh(
                (n,), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))
            dex = DistributedExecutor(schema, mesh, data_axes=("data",))
            sharded = dex.shard_db(db)
            plan = plan_query(path_query(1), schema, mode="opt_plus")
            try:
                # the ring sweeps hold 32-bit counts: a query whose bound
                # passes 2^31 is refused, never wrapped
                fn = dex.compile(plan, counts=db_counts([plan], db, schema))
            except PlanningError as e:
                print(f"[distributed x{n}] path-01: refused: {e}")
                return
            out = fn(sharded)
            print(f"[distributed x{n}] path-01: {int(out['count(*)'])}")


if __name__ == "__main__":
    main()
