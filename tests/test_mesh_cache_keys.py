"""Topology-aware cache keys: a mesh-lowered executable must never be
served to a single-device service (or to a differently-shaped mesh), in
memory or across process restarts.

Runs in-process on a 1-device mesh — topology keying is about the KEY
(``(axis_names, shard_counts)``), not the device count, so one CPU device
is enough to pin the behaviour.  The 8-device paths are covered by the
subprocess differentials in ``test_distributed_engine.py``.
"""

import jax
import numpy as np
import pytest

from repro.data.relational import make_tpch_db, tpch_v1_query
from repro.service import QueryService
from repro.service.plan_cache import PlanCache
from repro.service.plan_store import store_fingerprint

TOPO1 = (("data",), (1,))
TOPO8 = (("data",), (8,))
TOPO24 = (("pod", "data"), (2, 4))


def _mesh1():
    return jax.make_mesh((1,), ("data",))


# ---------------------------------------------------------------- keys

def test_exec_and_fused_keys_distinct_across_topologies():
    bucket = (("edge", 64), ("node", 32))
    keys = {PlanCache.exec_key("fp", bucket, topo)
            for topo in ((), TOPO1, TOPO8, TOPO24)}
    assert len(keys) == 4
    fkeys = {PlanCache.fused_key("sig", bucket, topo)
             for topo in ((), TOPO1, TOPO8, TOPO24)}
    assert len(fkeys) == 4
    # default stays the local key — pre-mesh entries keep hitting
    assert PlanCache.exec_key("fp", bucket) == ("fp", (), bucket)


def test_invalidate_relation_spans_topologies():
    """Bucket sits LAST in every key shape, so capacity invalidation hits
    local and mesh entries for the relation alike."""
    cache = PlanCache()
    bucket = (("edge", 64),)
    other = (("node", 32),)
    for topo in ((), TOPO8):
        cache.execs.put(PlanCache.exec_key("fp", bucket, topo), "x")
        cache.execs.put(PlanCache.exec_key("fp", other, topo), "y")
        cache.fused.put(PlanCache.fused_key("sig", bucket, topo), "z")
    assert cache.invalidate_relation("edge") == 4
    assert len(cache.execs) == 2          # the "node"-bucket entries survive
    assert len(cache.fused) == 0


def test_describe_is_topology_scoped():
    cache = PlanCache()
    bucket = (("edge", 64),)
    cache.execs.put(PlanCache.exec_key("fp", bucket, TOPO8), "x")
    assert cache.describe("fp", bucket, topo=TOPO8)["exec_in_memory"]
    assert not cache.describe("fp", bucket)["exec_in_memory"]
    assert not cache.describe("fp", bucket, topo=TOPO1)["exec_in_memory"]


def test_store_fingerprint_topology_sensitivity():
    _, schema = make_tpch_db(scale=2, seed=0)
    local = store_fingerprint(schema)
    assert local == store_fingerprint(schema, topology=())
    fps = {local, store_fingerprint(schema, topology=TOPO1),
           store_fingerprint(schema, topology=TOPO8),
           store_fingerprint(schema, topology=TOPO24)}
    assert len(fps) == 4


# ------------------------------------------------------- live services

@pytest.fixture(scope="module")
def tpch():
    return make_tpch_db(scale=8, seed=7)


GROUPBY = """
SELECT COUNT(*) AS suppliers, AVG(s.s_acctbal) AS avg_bal
FROM supplier s, nation n
WHERE s.s_nationkey = n.n_nationkey
GROUP BY s.s_nationkey
"""


def _assert_bitwise(want: dict, got: dict, ctx: str):
    assert set(want) - {"__stats__"} == set(got) - {"__stats__"}, ctx
    for k in set(want) - {"__stats__"}:
        a, b = want[k], got[k]
        pairs = ([(np.asarray(a[c]), np.asarray(b[c])) for c in a]
                 if isinstance(a, dict) else [(np.asarray(a),
                                               np.asarray(b))])
        for x, y in pairs:
            assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), (ctx, k)


@pytest.mark.parametrize("query", ["median", "groupby"])
def test_distributed_executor_on_default_explicit_mesh(tpch, query):
    """``jax.make_mesh`` gives Explicit axes by default.  MEDIAN and GROUP
    BY gather on the root columns after the shard_map, which only resolve
    once those columns are replicated: the mesh answer must equal the
    local executor's bitwise over identically padded tables."""
    from repro.core import Executor, parse_sql, plan_query
    from repro.core.distributed import DistributedExecutor
    from repro.core.stats import db_counts

    db, schema = tpch
    mesh = _mesh1()
    assert mesh.axis_types == (jax.sharding.AxisType.Explicit,)
    q = (tpch_v1_query("median") if query == "median"
         else parse_sql(GROUPBY, schema))
    plan = plan_query(q, schema, mode="opt_plus")
    dex = DistributedExecutor(schema, mesh)
    sharded = dex.shard_db(db)
    host = {k: db[k].pad_to(sharded[k].capacity) for k in db}
    want = dict(Executor(db, schema).compile(plan)(host))
    counts = db_counts([plan], db, schema)
    _assert_bitwise(want, dict(dex.compile(plan, counts=counts)(sharded)),
                    query)


def test_mesh_and_local_services_occupy_distinct_exec_entries(tpch):
    db, schema = tpch
    q = tpch_v1_query("minmax")
    mesh_svc = QueryService(db, schema, mesh=_mesh1())
    local_svc = QueryService(db, schema)
    mr, lr = mesh_svc.submit(q), local_svc.submit(q)
    assert mr.error is None and lr.error is None
    for svc, topo in ((mesh_svc, TOPO1), (local_svc, ())):
        exec_keys = [k for k, _ in svc.cache.execs.items()]
        assert exec_keys and all(k[1] == topo for k in exec_keys), exec_keys
    # 1-device mesh with matching min_bucket pads identically → bitwise
    for k in mr.values:
        a, b = np.asarray(mr.values[k]), np.asarray(lr.values[k])
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), k


def test_plan_store_is_topology_partitioned(tmp_path, tpch):
    """A mesh service warm-starts from its OWN store partition
    (plan_builds == 0 on restart) and never reads a local service's —
    and vice versa: no topology leaks through ``cache_dir``."""
    db, schema = tpch
    # SQL text → shareable fingerprint (opaque-selection queries are
    # process-salted and bypass the store by design)
    q = """
    SELECT MIN(s.s_acctbal), MAX(s.s_acctbal)
    FROM supplier s, partsupp ps, part p
    WHERE s.s_suppkey = ps.ps_suppkey AND ps.ps_partkey = p.p_partkey
      AND p.p_price > 900.0
    """
    cache_dir = str(tmp_path / "plans")

    cold = QueryService(db, schema, mesh=_mesh1(), cache_dir=cache_dir)
    assert cold.submit(q).error is None
    assert cold.metrics()["plan_builds"] == 1
    assert len(cold.plan_store) == 1

    # warm mesh restart: the disk level answers, nothing is re-planned
    warm = QueryService(db, schema, mesh=_mesh1(), cache_dir=cache_dir)
    assert warm.submit(q).error is None
    assert warm.metrics()["plan_builds"] == 0
    assert warm.metrics()["persist_hits"] >= 1

    # a LOCAL service over the same cache_dir sees an empty partition
    local = QueryService(db, schema, cache_dir=cache_dir)
    assert len(local.plan_store) == 0
    assert local.submit(q).error is None
    assert local.metrics()["plan_builds"] == 1

    # ...and a differently-shaped mesh would get its own partition too
    assert (store_fingerprint(schema, topology=TOPO1)
            != store_fingerprint(schema, topology=TOPO8))


def test_mesh_observability_surfaces(tpch):
    db, schema = tpch
    q = tpch_v1_query("minmax")
    svc = QueryService(db, schema, mesh=_mesh1())
    res = svc.submit(q)
    assert res.error is None

    gauges = svc.metrics_v2()["gauges"]
    assert gauges["mesh_devices"] == 1
    assert gauges["mesh_shard_count_data"] == 1

    # the run span carries a ring_sweep child annotated with the topology
    spans = list(res.stats.trace.walk())
    sweeps = [s for s in spans if s.name == "ring_sweep"]
    assert sweeps, [s.name for s in spans]
    assert sweeps[0].args["axes"] == "data"
    assert sweeps[0].args["shards"] == 1
    run = next(s for s in spans if s.name == "run")
    assert any(c.name == "ring_sweep" for c in run.children)

    exp = svc.explain(q)
    assert exp["topology"] == TOPO1
    assert exp["sharding"]["data_axes"] == ["data"]
    assert exp["sharding"]["placement"]
    assert "rows over data (1 shards)" in exp["text"]

    # a local service reports the absence explicitly
    local = QueryService(db, schema)
    lexp = local.explain(q)
    assert lexp["topology"] == ()
    assert lexp["sharding"] is None
    assert "single-device" in lexp["text"]
