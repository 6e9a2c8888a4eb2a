"""Subprocess helper: runs the distributed engine on 8 fake devices and
compares against the local executor BITWISE.  Exits non-zero on mismatch.

The mesh program and the local program see identically-padded tables
(``shard_db`` pads to per-shard power-of-two buckets; the host reference
pads to the same global capacities), so every aggregate — including float
SUM/AVG/MEDIAN and GROUP BY — must agree to the bit: the ring sweep
produces the exact integer frequencies of the local sweep, and final
aggregation runs replicated on the same arrays.  An eager run on the
UNPADDED tables sanity-checks values with np.isclose on top.

Run as:  python tests/helpers/distributed_engine_check.py
(the test wrapper sets XLA_FLAGS before interpreter start).
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import Executor, plan_query  # noqa: E402
from repro.core.distributed import DistributedExecutor  # noqa: E402
from repro.core.stats import db_counts  # noqa: E402
from repro.data import make_graph_db, path_query, tree_query  # noqa: E402
from repro.data.relational import (  # noqa: E402
    make_stats_db,
    make_tpch_db,
    stats_count_query,
    tpch_v1_query,
)


def assert_bitwise(want: dict, got: dict, ctx: str):
    keys = {k for k in want if k != "__stats__"}
    assert keys == {k for k in got if k != "__stats__"}, ctx
    for k in keys:
        va, vb = want[k], got[k]
        if k == "groups":
            assert set(va) == set(vb), ctx
            for c in va:
                xa, xb = np.asarray(va[c]), np.asarray(vb[c])
                assert xa.dtype == xb.dtype and xa.shape == xb.shape, (ctx, c)
                assert xa.tobytes() == xb.tobytes(), (ctx, c)
        else:
            xa, xb = np.asarray(va), np.asarray(vb)
            assert xa.dtype == xb.dtype and xa.shape == xb.shape, (ctx, k)
            assert xa.tobytes() == xb.tobytes(), (ctx, k, xa, xb)


def check(db, schema, q, mode, mesh, data_axes, name, **dex_opts):
    dex = DistributedExecutor(schema, mesh, data_axes=data_axes, **dex_opts)
    sharded = dex.shard_db(db)
    # the single-device reference over the SAME padded capacities
    host = {k: db[k].pad_to(sharded[k].capacity) for k in db}
    ex = Executor(db, schema)
    plan = plan_query(q, schema, mode=mode)

    want = dict(ex.compile(plan)(host))
    got = dict(dex.compile(plan, counts=db_counts([plan], db, schema))(
        sharded))
    assert_bitwise(want, got, name)

    # eager sanity on the unpadded tables (float tolerance: different
    # reduction lengths)
    eager = ex.execute(plan)
    for k, v in eager.items():
        if k in ("__stats__", "groups", "valid"):
            continue
        assert np.isclose(float(got[k]), float(v), rtol=1e-5), (name, k)
    print(f"ok {name}: " + ", ".join(
        f"{k}={float(v)}" for k, v in got.items()
        if k not in ("groups", "valid")))
    return dex, sharded, plan, got


def check_fused(dex, sharded, plans, solo, name):
    """compile_multi (shared ring sweeps) must match per-plan compiles."""
    fused = dex.compile_multi(
        plans, counts=db_counts(plans, sharded, dex.schema))(sharded)
    for i, (want, got) in enumerate(zip(solo, fused)):
        assert_bitwise(dict(want), dict(got), f"{name}[{i}]")
    print(f"ok {name}: {len(plans)} plans, one mesh program")


def main():
    assert jax.device_count() == 8, jax.device_count()

    # single-axis ring (one pod); 120 edges keep every query's count bound
    # inside the 32 bits the ring sweeps hold (at 500 the zipf hub of
    # src, 192 edges, bounds path-03 by 2^31.7 and the mesh refuses it)
    mesh1 = jax.make_mesh((8,), ("data",))
    db, schema = make_graph_db(n_nodes=30, n_edges=120, seed=1)
    check(db, schema, path_query(3), "opt_plus", mesh1, ("data",),
          "path-03/1-axis")
    check(db, schema, tree_query(2), "opt_plus", mesh1, ("data",),
          "tree-02/1-axis")

    # nested pod×data ring (multi-pod)
    mesh2 = jax.make_mesh((2, 4), ("pod", "data"))
    check(db, schema, path_query(4), "opt_plus", mesh2, ("pod", "data"),
          "path-04/2-axis")

    sdb, sschema = make_stats_db(n_users=64, n_posts=256, n_comments=1000,
                                 n_votes=600, seed=3)
    check(sdb, sschema, stats_count_query(), "opt_plus", mesh2,
          ("pod", "data"), "stats-count/2-axis")

    # 0MA semi-join ring sweep + per-shard bucketing variants
    tdb, tschema = make_tpch_db(scale=64, seed=5)
    dex, sharded, p_minmax, r_minmax = check(
        tdb, tschema, tpch_v1_query("minmax"), "oma", mesh1, ("data",),
        "tpch-v1-minmax/1-axis")
    _, _, p_median, r_median = check(
        tdb, tschema, tpch_v1_query("median"), "opt_plus", mesh1,
        ("data",), "tpch-v1-median/1-axis")
    check(tdb, tschema, tpch_v1_query("minmax"), "oma", mesh2,
          ("pod", "data"), "tpch-v1-minmax/2-axis")
    check(tdb, tschema, tpch_v1_query("median"), "opt_plus", mesh1,
          ("data",), "tpch-v1-median/presort", presort=True)
    check(tdb, tschema, tpch_v1_query("minmax"), "oma", mesh1, ("data",),
          "tpch-v1-minmax/dense", dense_domain=True)

    # fused multi-query mesh program vs per-plan compiles (shared memo)
    check_fused(dex, sharded, [p_minmax, p_median], [r_minmax, r_median],
                "tpch-fused/1-axis")

    # per-shard power-of-two bucketing: shard_db pads every relation so
    # each shard holds a power-of-two block
    for rel, t in sharded.items():
        per_shard = t.capacity // 8
        assert per_shard >= 8 and (per_shard & (per_shard - 1)) == 0, \
            (rel, t.capacity)
    print("ok shard_db per-shard power-of-two buckets")
    print("ALL DISTRIBUTED CHECKS PASSED")


if __name__ == "__main__":
    main()
