"""§Perf engine iteration 2: Ring-FreqJoin presort (8 fake devices).

Baseline rotates raw (keys, freq) and sorts the visiting shard at every
ring step (P sorts per join per shard); presort sorts once per shard and
rotates (sorted keys, prefix sums).  Exactness asserted, wall time printed.

The query walks three edges backwards (each join's child key is the
uniform ``dst`` column), so its count bound stays inside the 32 bits the
ring sweeps hold; forward paths join on the zipf hub of ``src`` and are
refused.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import plan_query  # noqa: E402
from repro.core.distributed import DistributedExecutor  # noqa: E402
from repro.core.query import Agg, AggQuery, Atom  # noqa: E402
from repro.core.stats import db_counts  # noqa: E402
from repro.data import make_graph_db  # noqa: E402
from repro.launch.mesh import make_auto_mesh  # noqa: E402


def bench(presort: bool, db, schema, plan, sharded):
    dex = DistributedExecutor(schema, make_auto_mesh((8,), ("data",)),
        data_axes=("data",), presort=presort)
    fn = dex.compile(plan, counts=db_counts([plan], db, schema))
    out = fn(sharded)
    jax.block_until_ready(list(out.values()))
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = fn(sharded)
        jax.block_until_ready(list(out.values()))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), float(next(iter(out.values())))


def bench_dense(db, schema, plan, sharded):
    dex = DistributedExecutor(schema, make_auto_mesh((8,), ("data",)),
        data_axes=("data",), dense_domain=True)
    fn = dex.compile(plan, counts=db_counts([plan], db, schema))
    out = fn(sharded)
    jax.block_until_ready(list(out.values()))
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = fn(sharded)
        jax.block_until_ready(list(out.values()))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), float(next(iter(out.values())))


def in_path(k: int) -> AggQuery:
    """COUNT(*) of (k+1)-edge paths walked against the edges."""
    atoms = tuple(Atom("edge", f"e{i}", (f"x{i + 1}", f"x{i}"))
                  for i in range(k + 1))
    return AggQuery(atoms=atoms, aggregates=(Agg("count"),))


def main():
    with jax.enable_x64(True):
        db, schema = make_graph_db(40_000, 400_000, seed=0)
        plan = plan_query(in_path(2), schema, mode="opt_plus")
        mesh = make_auto_mesh((8,), ("data",))
        dex = DistributedExecutor(schema, mesh, data_axes=("data",))
        sharded = dex.shard_db(db)
        t0, r0 = bench(False, db, schema, plan, sharded)
        t1, r1 = bench(True, db, schema, plan, sharded)
        t2, r2 = bench_dense(db, schema, plan, sharded)
        assert r0 == r1 == r2, (r0, r1, r2)
        print(f"ring in-path-02 (8 shards): baseline {t0:.3f}s  "
              f"presort {t1:.3f}s ({t0 / t1:.2f}x)  "
              f"dense-psum {t2:.3f}s ({t0 / t2:.2f}x)  count={r0:.4e}")


if __name__ == "__main__":
    main()
