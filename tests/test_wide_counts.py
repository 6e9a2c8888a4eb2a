"""Exact counts above 2^31: the width of a program's counts comes from a
bound the statistics give, not from a flag.

Every case runs on both join paths — the dense scatter-add (declared key
domains) and the sorted one (``Schema.without_domains()``) — and compares
with a brute-force enumerator in Python ints, which knows nothing of the
engine."""

import collections
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Executor, plan_query
from repro.core.distributed import DistributedExecutor
from repro.core.plan import PlanningError
from repro.core.stats import db_counts, limb_split
from repro.core.scopes import scope_table
from repro.core.sql import parse_sql
from repro.kernels import ops as kops
from repro.service import QueryService
from repro.tables.table import ColumnMeta, RelSchema, Schema, Table

DOMAIN = 4096
HUB_EDGES = 2048

SCHEMA = Schema(relations={"edge": RelSchema("edge", (
    ColumnMeta("src", domain=DOMAIN), ColumnMeta("dst", domain=DOMAIN)))},
    foreign_keys=())

WALK2 = "SELECT COUNT(*) FROM edge e0, edge e1 WHERE e0.dst = e1.src"
WALK3 = ("SELECT COUNT(*) FROM edge e0, edge e1, edge e2 "
         "WHERE e0.dst = e1.src AND e1.dst = e2.src")
STAR2 = "SELECT COUNT(*) FROM edge e0, edge e1 WHERE e0.src = e1.src"


def star(k: int) -> str:
    """Out-k-stars: k edges out of one vertex."""
    names = [f"e{i}" for i in range(k)]
    where = " AND ".join(f"e0.src = {n}.src" for n in names[1:])
    return f"SELECT COUNT(*) FROM {', '.join(f'edge {n}' for n in names)} " \
        f"WHERE {where}"


PATHS = {"dense": SCHEMA, "sorted": SCHEMA.without_domains()}


def brute_count(sql: str, edges, freq=None) -> int:
    """COUNT(*) of a self-join of ``edge`` by enumeration in Python ints:
    atoms joined one at a time over bindings of the variables, each
    binding with its multiplicity; a variable no later atom reads is
    summed out."""
    q = parse_sql(sql, SCHEMA)
    rows = [(int(s), int(d), 1 if freq is None else int(f))
            for s, d, f in zip(edges[0], edges[1],
                               freq if freq is not None else edges[0])]
    states: dict[tuple, int] = {(): 1}
    bound: tuple = ()
    for i, atom in enumerate(q.atoms):
        later = {v for a in q.atoms[i + 1:] for v in a.vars}
        nxt: collections.Counter = collections.Counter()
        names = bound + tuple(v for v in dict.fromkeys(atom.vars)
                              if v not in bound)
        keep = tuple(v for v in names if v in later)
        for binding, m in states.items():
            env = dict(zip(bound, binding))
            for s, d, f in rows:
                e = dict(env)
                if any(e.setdefault(v, x) != x
                       for v, x in zip(atom.vars, (s, d))):
                    continue
                nxt[tuple(e[v] for v in keep)] += m * f
        states, bound = nxt, keep
    return sum(states.values())


def ring_edges(n: int):
    """n edges i → i+1 mod n: every multiplicity 1."""
    src = np.arange(n, dtype=np.int32)
    return src, (src + 1) % n


def hub_edges(n: int = HUB_EDGES):
    """A star: vertex 0 with n out-edges, to 1..n."""
    return (np.zeros(n, np.int32), np.arange(1, n + 1, dtype=np.int32))


def table(edges, freq=None) -> Table:
    t = Table.from_numpy({"src": edges[0], "dst": edges[1]})
    if freq is not None:
        t = Table(t.columns, jax.numpy.asarray(freq, jax.numpy.int32))
    return t


def answer(res) -> np.ndarray:
    assert res.ok, res.error
    return np.asarray(res.values["count(*)"])


@pytest.mark.parametrize("path", PATHS)
def test_a_bound_under_2_31_keeps_the_int32_program(path):
    rng = np.random.default_rng(7)
    edges = (rng.integers(0, 16, 64).astype(np.int32),
             rng.integers(0, 16, 64).astype(np.int32))
    svc = QueryService({"edge": table(edges)}, PATHS[path])
    for sql in (WALK2, WALK3, STAR2):
        got = answer(svc.submit(sql))
        assert got.dtype == np.int32
        assert int(got) == brute_count(sql, edges)
    m = svc.metrics()
    assert m["programs_count32"] == 3 and m["programs_count64"] == 0
    assert m[f"joins_{path}"] == 4
    assert m["join_rows32"] == 4 * 2 * 64 and m["join_rows64"] == 0
    assert m["join_parent_rows32"] == 4 * 64
    assert m["join_slots32"] == (4 * DOMAIN if path == "dense" else 0)


@pytest.mark.parametrize("path", PATHS)
def test_a_hub_of_2048_out_edges_counts_out_3_stars_in_64_bits(path):
    edges = hub_edges()
    svc = QueryService({"edge": table(edges)}, PATHS[path])
    sql = star(3)
    got = answer(svc.submit(sql))
    assert got.dtype == np.int64
    assert int(got) == brute_count(sql, edges) == 2 ** 33
    # 2^22 two-stars fit 32 bits: that program stays narrow
    assert answer(svc.submit(STAR2)).dtype == np.int32
    m = svc.metrics()
    assert m["programs_count64"] == 1 and m["programs_count32"] == 1
    assert m["join_rows64"] == 2 * 2 * HUB_EDGES
    assert m["join_slots64"] == (2 * DOMAIN if path == "dense" else 0)


@pytest.mark.parametrize("path", PATHS)
def test_base_frequencies_widen_the_eager_baseline(path):
    """A table whose rows carry frequencies above 1: the bound reads them,
    and the materialising baseline counts in 64 bits too."""
    edges = (np.array([0, 0, 1, 2], np.int32), np.array([1, 2, 2, 0],
                                                       np.int32))
    freq = np.full(4, 2 ** 20, np.int32)
    svc = QueryService({"edge": table(edges, freq)}, PATHS[path], mode="ref")
    got = answer(svc.submit(STAR2))
    assert got.dtype == np.int64
    assert int(got) == brute_count(STAR2, edges, freq) == 6 * 2 ** 40
    assert svc.metrics()["programs_count64"] == 1


@pytest.mark.parametrize("path", PATHS)
def test_a_bound_past_2_63_is_refused(path):
    svc = QueryService({"edge": table(hub_edges())}, PATHS[path])
    res = svc.submit_many([star(6), STAR2])
    assert isinstance(res[0].error, PlanningError)
    assert "2^66.0" in str(res[0].error)
    assert int(answer(res[1])) == HUB_EDGES ** 2


@pytest.mark.parametrize("path", PATHS)
def test_an_update_that_crosses_2_31_recompiles_and_stays_exact(path):
    ring = ring_edges(HUB_EDGES)
    svc = QueryService({"edge": table(ring)}, PATHS[path])
    sql = star(3)
    assert int(answer(svc.submit(sql))) == brute_count(sql, ring)
    before = svc.metrics()
    # the same bucket: only the width can make the program stale
    svc.update_table("edge", table(hub_edges()))
    got = answer(svc.submit(sql))
    assert got.dtype == np.int64 and int(got) == 2 ** 33
    after = svc.metrics()
    assert after["compiles_invalidated"] == before["compiles_invalidated"] + 1
    assert after["compiles"] == before["compiles"] + 1
    # and back: the narrow program again
    svc.update_table("edge", table(ring))
    got = answer(svc.submit(sql))
    assert got.dtype == np.int32 and int(got) == HUB_EDGES
    assert svc.metrics()["compiles_invalidated"] \
        == before["compiles_invalidated"] + 2


@pytest.mark.parametrize("path", PATHS)
def test_the_mesh_refuses_64_bit_counts_and_answers_32_bit_ones(path):
    edges = hub_edges()
    svc = QueryService({"edge": table(edges)}, PATHS[path],
                       mesh=jax.make_mesh((1,), ("data",)),
                       mesh_dense_domain=path == "dense")
    wide, narrow = svc.submit_many([star(3), STAR2])
    assert isinstance(wide.error, PlanningError)
    assert "mesh" in str(wide.error)
    assert int(answer(narrow)) == brute_count(STAR2, edges)


@pytest.mark.parametrize("path", PATHS)
def test_the_64_bit_kernels_keep_their_scopes(path):
    """At s64 the dense path's scatter-add and gather, and the sorted
    path's sort and search, carry the kernel scopes the device trace's
    reduction reads."""
    db = {"edge": table(hub_edges())}
    plan = plan_query(parse_sql(star(3), SCHEMA), SCHEMA, mode="opt_plus")
    fn = Executor(db, PATHS[path]).compile(plan)
    text = fn.lower(db).compile().as_text()
    assert "s64[" in text
    kernels = {"dense": ("scatter", "gather"), "sorted": ("sort", "search")}
    assert {f"freq_join/freq_join/{k}" for k in kernels[path]} \
        <= set(scope_table(text).values())
    if path == "dense":
        assert not s64_scatters(text)
    assert int(np.asarray(fn(db)["count(*)"])) == 2 ** 33


def s64_scatters(hlo: str) -> list[str]:
    """The scatter instructions of a compiled program that write s64."""
    return re.findall(r"^.*= s64\[[^\n]*\bscatter\(.*$", hlo, re.M)


@pytest.mark.parametrize("keys", [3, 1])
def test_the_dense_join_sums_64_bit_frequencies_in_32_bit_limbs(keys):
    """Slot sums near 2^50 are exact from uint32 limbs split by the most
    rows one key holds and the largest frequency, as the statistics give
    them, with the child's rows over three keys or all on one; no scatter
    writes s64."""
    rng = np.random.default_rng(1)
    ck = rng.integers(0, keys, 3000).astype(np.int32)
    cf = rng.integers(2 ** 39, 2 ** 40, 3000).astype(np.int64)
    pk = np.arange(-1, 5, dtype=np.int32)      # keys outside [0, 3)
    pf = np.arange(1, 7, dtype=np.int64)
    want = [int(f) * sum(int(x) for x in cf[ck == k]) for f, k in zip(pf, pk)]
    limbs = limb_split(np.bincount(ck).max(), cf.max())
    fn = jax.jit(lambda *a: kops.freq_join(*a, domain=3, limbs=limbs))
    with jax.enable_x64(True):
        args = [jnp.asarray(a) for a in (pk, pf, ck, cf)]
        got = np.asarray(fn(*args))
        text = fn.lower(*args).compile().as_text()
    assert got.dtype == np.int64 and got.tolist() == want
    assert not s64_scatters(text)
    assert limbs == {3: (22, 2), 1: (20, 2)}[keys]


def test_the_dense_join_refuses_64_bit_frequencies_without_limbs():
    """No split is guessed from the shapes: the caller brings it."""
    keys = jnp.zeros(4, jnp.int32)
    with jax.enable_x64(True), pytest.raises(ValueError, match="limb"):
        kops.freq_join(keys, jnp.ones(4, jnp.int64), keys,
                       jnp.ones(4, jnp.int64), domain=3)


@pytest.mark.parametrize("path", PATHS)
def test_an_executor_told_no_width_counts_from_its_own_tables(path):
    """``compile`` and ``execute`` without ``counts`` bound them from the
    executor's tables: wide where they must be, refused past 2^63; the
    mesh executor, which holds no tables, asks for them."""
    db = {"edge": table(hub_edges())}
    ex = Executor(db, PATHS[path])
    plan = plan_query(parse_sql(star(3), SCHEMA), SCHEMA, mode="opt_plus")
    for got in (ex.compile(plan)(db), ex.execute(plan)):
        got = np.asarray(got["count(*)"])
        assert got.dtype == np.int64 and int(got) == 2 ** 33
    assert db_counts([plan], db, PATHS[path]).bits == 64
    with pytest.raises(PlanningError, match="2\\^66.0"):
        ex.compile(plan_query(parse_sql(star(6), SCHEMA), SCHEMA))
    dex = DistributedExecutor(PATHS[path], jax.make_mesh((1,), ("data",)))
    with pytest.raises(ValueError, match="pass counts"):
        dex.compile(plan)
    with pytest.raises(PlanningError, match="mesh"):
        dex.compile(plan, counts=db_counts([plan], db, PATHS[path]))


def base_freq_edges():
    """Four edges; STAR2 over them with every base frequency f counts
    6·f²."""
    return (np.array([0, 0, 1, 2], np.int32), np.array([1, 2, 2, 0],
                                                      np.int32))


@pytest.mark.parametrize("serve", ["single", "fused", "eager"])
@pytest.mark.parametrize("path", PATHS)
def test_an_update_between_planning_and_running_stays_exact(path, serve,
                                                            monkeypatch):
    """A request planned under the old statistics and run over the new
    data, which crosses 2^31: the program's width comes from the
    statistics its snapshot took with the data, not from planning's."""
    if serve == "eager":
        old, new = (table(base_freq_edges()),
                    table(base_freq_edges(), np.full(4, 2 ** 20, np.int32)))
        svc = QueryService({"edge": old}, PATHS[path], mode="ref")
        sqls, want = [STAR2], [6 * 2 ** 40]
    else:
        old, new = table(ring_edges(HUB_EDGES)), table(hub_edges())
        svc = QueryService({"edge": old}, PATHS[path])
        sqls = [star(3)] if serve == "single" else [star(3), STAR2]
        want = [2 ** 33, HUB_EDGES ** 2][:len(sqls)]
    for sql in sqls:                       # narrow programs, cached
        assert answer(svc.submit(sql)).dtype == np.int32
    plan_unit = svc._plan_unit

    def then_update(group):
        unit = plan_unit(group)
        if unit.counts.bits == 32 and svc._db["edge"] is old:
            svc.update_table("edge", new)
        return unit

    monkeypatch.setattr(svc, "_plan_unit", then_update)
    res = svc.submit_many(sqls)
    got = [int(answer(r)) for r in res]
    assert got == want
    assert answer(res[0]).dtype == np.int64
    m = svc.metrics()
    assert m["fused_compiles"] == (1 if serve == "fused" else 0)


@pytest.mark.parametrize("path", PATHS)
def test_a_submit_while_an_update_computes_its_statistics_stays_exact(
        path, monkeypatch):
    """An update that crosses 2^31 stalls after computing its statistics:
    a request in the meantime runs on the old data with the old
    statistics, and the next one on the new with the new."""
    sql = star(3)
    svc = QueryService({"edge": table(ring_edges(HUB_EDGES))}, PATHS[path])
    assert int(answer(svc.submit(sql))) == HUB_EDGES
    computed, release = threading.Event(), threading.Event()
    table_stats = svc._table_stats

    def stalled(*args):
        st = table_stats(*args)
        computed.set()
        assert release.wait(60), "test orchestration stalled"
        return st

    monkeypatch.setattr(svc, "_table_stats", stalled)
    update = threading.Thread(target=svc.update_table,
                              args=("edge", table(hub_edges())))
    update.start()
    try:
        assert computed.wait(60)
        got = answer(svc.submit(sql))
        assert got.dtype == np.int32 and int(got) == HUB_EDGES
    finally:
        release.set()
        update.join(60)
    got = answer(svc.submit(sql))
    assert got.dtype == np.int64 and int(got) == 2 ** 33
