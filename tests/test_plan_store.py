"""Plan persistence: serialisation round-trip (property test), the
on-disk store's corruption tolerance and version skew handling,
cross-process warm starts, and write-failure degradation.

The round-trip test mirrors ``test_graph_ir_differential``'s harness: a
hypothesis property test when hypothesis is installed, else a seeded sweep
over the same randomised case builder (visible, not silent, degradation).
The property pinned: ``plan_from_payload(plan_to_payload(plan))`` — with a
JSON round trip in between, exactly what the store does — preserves
``graph_key()``, ``subplan_keys()``, the topological op list, and bitwise
execution results across every plan class (ref / opt / opt_plus / oma).
"""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import (
    Executor,
    parse_sql,
    plan_from_payload,
    plan_query,
    plan_to_payload,
)
from repro.core.plan import PlanNotSerialisable, ScanOp
from repro.core.query import Agg, AggQuery, Atom, selection_from_spec
from repro.data import make_tpch_db
from repro.service import (
    PlanStore,
    QueryService,
    canonicalize,
    schema_fingerprint,
    store_fingerprint,
)
from repro.service.plan_store import FORMAT_VERSION
from repro.tables.table import ColumnMeta, RelSchema, Schema, Table

try:  # property tests degrade to a seeded sweep without hypothesis
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

jax.config.update("jax_platform_name", "cpu")

FIG1 = """
SELECT MIN(s.s_acctbal), MAX(s.s_acctbal)
FROM region r, nation n, supplier s, partsupp ps, part p
WHERE r.r_regionkey = n.n_regionkey AND n.n_nationkey = s.s_nationkey
  AND s.s_suppkey = ps.ps_suppkey AND ps.ps_partkey = p.p_partkey
  AND r.r_name IN (2, 3) AND p.p_price > 1200.0
"""
COSTLY_PARTS = """
SELECT SUM(ps.ps_supplycost), COUNT(*)
FROM partsupp ps, part p
WHERE ps.ps_partkey = p.p_partkey AND p.p_price > 1500.0
"""

# ---------------------------------------------------------------------------
# randomised case builder (same pattern as test_graph_ir_differential)
# ---------------------------------------------------------------------------
_N_IDS = 12
SCHEMA = Schema(relations={
    "node": RelSchema("node", (
        ColumnMeta("id", domain=_N_IDS),
        ColumnMeta("grp", domain=5),
        ColumnMeta("score"),
    )),
    "edge": RelSchema("edge", (
        ColumnMeta("src", domain=_N_IDS),
        ColumnMeta("dst", domain=_N_IDS),
    )),
})


def _make_db(rng):
    n_nodes = int(rng.integers(4, 24))
    n_edges = int(rng.integers(4, 40))
    node = {
        "id": rng.integers(0, _N_IDS, n_nodes).astype(np.int32),
        "grp": rng.integers(0, 5, n_nodes).astype(np.int32),
        "score": rng.integers(0, 50, n_nodes).astype(np.float32),
    }
    edge = {
        "src": rng.integers(0, _N_IDS, n_edges).astype(np.int32),
        "dst": rng.integers(0, _N_IDS, n_edges).astype(np.int32),
    }
    return {"node": Table.from_numpy(node), "edge": Table.from_numpy(edge)}


_AGG_POOL = (("min", "sc"), ("max", "sc"), ("sum", "sc"), ("avg", "sc"),
             ("median", "sc"), ("count", None))


def _make_query(rng):
    chain_len = int(rng.integers(0, 3))
    star = bool(rng.integers(0, 2)) and chain_len > 0
    atoms = [Atom("node", "n0", ("v0", "g", "sc"))]
    if chain_len >= 1:
        atoms.append(Atom("edge", "e1", ("v0", "x1")))
    if chain_len >= 2:
        atoms.append(Atom("edge", "e2", ("x1", "x2")))
    if star:
        atoms.append(Atom("edge", "e3", ("v0", "y1")))
    n_aggs = int(rng.integers(1, 3))
    picks = rng.choice(len(_AGG_POOL), size=n_aggs, replace=False)
    aggs = tuple(Agg(_AGG_POOL[i][0], _AGG_POOL[i][1]) for i in picks)
    group_by = ("g",) if rng.integers(0, 2) else ()
    selections, specs = {}, {}
    if rng.integers(0, 2):
        lit = int(rng.integers(1, 5))
        selections["n0"] = lambda c, lit=lit: c["grp"] < lit
        specs["n0"] = (("<", "grp", lit),)
    if chain_len >= 1 and rng.integers(0, 2):
        # same selection shape as the differential test (">" keeps rows
        # live for the ref baseline's grouped aggregates); the "in" op's
        # round trip is pinned deterministically by the FIG1 store tests
        lit = int(rng.integers(1, _N_IDS))
        specs["e1"] = ((">", "dst", lit),)
        selections["e1"] = selection_from_spec(specs["e1"])
    return AggQuery(atoms=tuple(atoms), aggregates=aggs, group_by=group_by,
                    selections=selections, selection_specs=specs)


def _assert_bitwise(a: dict, b: dict, ctx: str = ""):
    keys_a = {k for k in a if k != "__stats__"}
    keys_b = {k for k in b if k != "__stats__"}
    assert keys_a == keys_b, ctx
    for k in keys_a:
        va, vb = a[k], b[k]
        if k == "groups":
            assert set(va) == set(vb), ctx
            for c in va:
                xa, xb = np.asarray(va[c]), np.asarray(vb[c])
                assert xa.dtype == xb.dtype and xa.shape == xb.shape, \
                    (ctx, c)
                assert xa.tobytes() == xb.tobytes(), (ctx, c)
        else:
            xa, xb = np.asarray(va), np.asarray(vb)
            assert xa.dtype == xb.dtype and xa.shape == xb.shape, (ctx, k)
            assert xa.tobytes() == xb.tobytes(), (ctx, k)


def _ops_modulo_selection(plan):
    """The topological op list with rebuilt-by-spec selection callables
    normalised away (they compare by identity; the spec is the stable
    content)."""
    return [dataclasses.replace(op, selection=None)
            if isinstance(op, ScanOp) else op for op in plan.ops]


def _check_roundtrip(seed: int):
    rng = np.random.default_rng(seed)
    db = _make_db(rng)
    query = _make_query(rng)
    ex = Executor(db, SCHEMA)
    for mode in ("ref", "opt", "opt_plus", "oma"):
        try:
            plan = plan_query(query, SCHEMA, mode=mode)
        except ValueError:
            continue  # mode not applicable (not 0MA, say) — by design
        # through actual JSON text, exactly as the store writes it
        payload = json.loads(json.dumps(plan_to_payload(plan)))
        plan2 = plan_from_payload(payload)
        assert plan2.mode == plan.mode
        assert plan2.graph_key() == plan.graph_key(), mode
        assert plan2.subplan_keys() == plan.subplan_keys(), mode
        assert _ops_modulo_selection(plan2) == _ops_modulo_selection(plan)
        assert plan2.tree == plan.tree and plan2.var_cols == plan.var_cols
        _assert_bitwise(ex.execute(plan), ex.execute(plan2),
                        ctx=f"eager/{mode}")
        if mode in ("opt_plus", "oma"):
            _assert_bitwise(dict(ex.compile(plan)(db)),
                            dict(ex.compile(plan2)(db)),
                            ctx=f"compiled/{mode}")


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 2**32 - 1))
    @settings(max_examples=12, deadline=None)
    def test_plan_serialisation_roundtrip(seed):
        _check_roundtrip(seed)
else:
    @pytest.mark.parametrize("seed", range(8))
    def test_plan_serialisation_roundtrip(seed):
        _check_roundtrip(seed)


def test_opaque_selections_are_not_serialisable(tmp_path):
    q = AggQuery(
        atoms=(Atom("node", "n0", ("v0", "g", "sc")),),
        aggregates=(Agg("count"),),
        selections={"n0": lambda c: c["grp"] > 1})   # no declarative spec
    plan = plan_query(q, SCHEMA)
    with pytest.raises(PlanNotSerialisable, match="opaque"):
        plan_to_payload(plan)
    store = PlanStore(tmp_path, schema_fingerprint(SCHEMA))
    assert store.save("f" * 64, plan) is False   # swallowed, not raised
    assert store.metrics()["persist_entries"] == 0


# ---------------------------------------------------------------------------
# the on-disk store
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tpch():
    db, schema = make_tpch_db(scale=30, seed=3)
    return db, schema


def test_plan_store_roundtrip_across_instances(tmp_path, tpch):
    """A second PlanStore over the same directory (a fresh process, in
    effect) serves the plan the first one persisted."""
    db, schema = tpch
    canon = canonicalize(parse_sql(FIG1, schema))
    plan = plan_query(canon.query, schema)
    store = PlanStore(tmp_path, schema_fingerprint(schema))
    assert store.save(canon.fingerprint, plan)
    assert store.metrics()["persist_writes"] == 1
    assert store.metrics()["persist_entries"] == 1

    fresh = PlanStore(tmp_path, schema_fingerprint(schema))
    loaded = fresh.load(canon.fingerprint)
    assert loaded is not None
    assert loaded.graph_key() == plan.graph_key()
    assert loaded.subplan_keys() == plan.subplan_keys()
    _assert_bitwise(Executor(db, schema).execute(plan),
                    Executor(db, schema).execute(loaded))
    assert fresh.load("0" * 64) is None
    m = fresh.metrics()
    assert m["persist_hits"] == 1 and m["persist_misses"] == 1
    assert m["persist_corrupt_skipped"] == 0


def _single_entry(store: PlanStore):
    paths = list(store.plans_dir.glob("*.json"))
    assert len(paths) == 1
    return paths[0]


@pytest.mark.parametrize("damage", ["truncated", "flipped", "version",
                                    "schema"])
def test_corrupt_and_skewed_entries_skipped_and_evicted(
        tmp_path, tpch, damage):
    """A damaged entry — truncated file, flipped payload byte, wrong
    format version, foreign schema fingerprint — is skipped with
    ``persist_corrupt_skipped`` incremented and evicted; the query is
    still served correctly via re-plan (and re-persisted)."""
    db, schema = tpch
    want = QueryService(db, schema).submit(FIG1)

    svc = QueryService(db, schema, cache_dir=tmp_path)
    svc.submit(FIG1)
    path = _single_entry(svc.plan_store)
    raw = path.read_bytes()
    if damage == "truncated":
        path.write_bytes(raw[:len(raw) // 2])
    elif damage == "flipped":
        doc = json.loads(raw)
        doc["payload"]["mode"] = "omx"          # checksum now mismatches
        path.write_text(json.dumps(doc))
    elif damage == "version":
        doc = json.loads(raw)
        doc["format_version"] = FORMAT_VERSION + 99
        path.write_text(json.dumps(doc))
    else:
        doc = json.loads(raw)
        doc["schema_fingerprint"] = "f" * 64
        path.write_text(json.dumps(doc))

    svc2 = QueryService(db, schema, cache_dir=tmp_path)
    res = svc2.submit(FIG1)
    assert res.error is None
    np.testing.assert_array_equal(
        np.asarray(res.values["min(s.s_acctbal)"]),
        np.asarray(want.values["min(s.s_acctbal)"]))
    m = svc2.metrics()
    assert m["persist_corrupt_skipped"] == 1
    assert m["persist_hits"] == 0
    assert m["plan_builds"] == 1                 # served via re-plan
    assert m["persist_writes"] == 1              # ...and re-persisted
    # the damaged file was evicted (then replaced by the fresh write)
    assert json.loads(_single_entry(svc2.plan_store).read_text())[
        "format_version"] == FORMAT_VERSION


def test_store_warm_start_in_process(tmp_path, tpch):
    """cache_dir warm start: a second service over the same directory
    replans nothing and answers bitwise-identically."""
    db, schema = tpch
    svc = QueryService(db, schema, cache_dir=tmp_path)
    cold = [svc.submit(FIG1), svc.submit(COSTLY_PARTS)]
    m = svc.metrics()
    assert m["plan_builds"] == 2 and m["persist_writes"] == 2

    warm_svc = QueryService(db, schema, cache_dir=tmp_path)
    warm = [warm_svc.submit(FIG1), warm_svc.submit(COSTLY_PARTS)]
    m2 = warm_svc.metrics()
    assert m2["plan_builds"] == 0
    assert m2["persist_hits"] == 2 and m2["persist_misses"] == 0
    for a, b in zip(cold, warm):
        _assert_bitwise(a.values, b.values)


@pytest.mark.persistence
def test_cross_process_warm_start(tmp_path, tpch):
    """A subprocess builds and persists the plans; a fresh in-test
    QueryService over the same cache_dir serves the same queries with
    persist hits, zero re-plans, and bitwise-equal answers."""
    db, schema = tpch
    child = f"""
import json
import jax
jax.config.update("jax_platform_name", "cpu")
import numpy as np
from repro.data import make_tpch_db
from repro.service import QueryService

db, schema = make_tpch_db(scale=30, seed=3)
svc = QueryService(db, schema, cache_dir={str(tmp_path)!r})
out = {{}}
for name, sql in (("fig1", {FIG1!r}), ("costly", {COSTLY_PARTS!r})):
    r = svc.submit(sql)
    out[name] = {{k: np.asarray(v).tobytes().hex()
                 for k, v in r.values.items()}}
m = svc.metrics()
print(json.dumps({{"answers": out, "plan_builds": m["plan_builds"],
                   "persist_writes": m["persist_writes"]}}))
"""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    proc = subprocess.run([sys.executable, "-c", child],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["plan_builds"] == 2 and report["persist_writes"] == 2

    svc = QueryService(db, schema, cache_dir=tmp_path)
    got = {"fig1": svc.submit(FIG1), "costly": svc.submit(COSTLY_PARTS)}
    m = svc.metrics()
    assert m["plan_builds"] == 0                  # zero re-plans
    assert m["persist_hits"] == 2
    for name, res in got.items():
        assert res.error is None
        want = report["answers"][name]
        assert {k: np.asarray(v).tobytes().hex()
                for k, v in res.values.items()} == want


def test_failed_write_degrades_to_memory_only(tmp_path, tpch):
    """Regression (composes with PR 4's fault isolation): a failing disk
    write attaches NO error to the request and the service degrades to
    memory-only caching."""
    db, schema = tpch
    svc = QueryService(db, schema, cache_dir=tmp_path / "store")
    # sabotage the store after init: replace the plans directory with a
    # regular file, so every write (even as root, where chmod is decor)
    # fails with NotADirectoryError
    plans_dir = svc.plan_store.plans_dir
    plans_dir.rmdir()
    plans_dir.write_text("not a directory")

    res = svc.submit(FIG1)
    assert res.error is None and res.values
    batch = svc.submit_many([FIG1, COSTLY_PARTS])
    assert all(r.error is None for r in batch)
    m = svc.metrics()
    assert m["persist_write_errors"] >= 1
    assert m["persist_writes"] == 0
    # memory-only caching still works: the repeat was a plan-cache hit
    assert m["plan_hits"] >= 1 and m["plan_builds"] == 2


def test_unwritable_cache_dir_never_crashes_construction(tmp_path, tpch):
    """cache_dir pointing under a regular file: construction, serving,
    and metrics all work; persistence is simply off."""
    db, schema = tpch
    blocker = tmp_path / "blocker"
    blocker.write_text("file, not dir")
    svc = QueryService(db, schema, cache_dir=blocker / "nested")
    res = svc.submit(COSTLY_PARTS)
    assert res.error is None and res.values
    m = svc.metrics()
    assert m["persist_hits"] == 0 and m["persist_entries"] == 0
    assert m["persist_write_errors"] >= 1


def test_executable_cache_directory(tmp_path, monkeypatch, tpch):
    """The XLA cache is never placed under ``cache_dir``: the directory
    JAX read from ``JAX_COMPILATION_CACHE_DIR`` is left alone, and
    without the variable the cache goes to one fixed path in the
    checkout."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.service.plan_store import (
        EXECUTABLE_CACHE_DIR,
        enable_executable_cache,
    )

    db, schema = tpch
    was = jax.config.jax_compilation_cache_dir
    env_dir = str(tmp_path / "from-env")
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        jax.config.update("jax_compilation_cache_dir", env_dir)  # as read
        svc = QueryService(db, schema, cache_dir=tmp_path / "plans")
        assert svc.submit(COSTLY_PARTS).error is None
        assert jax.config.jax_compilation_cache_dir == env_dir
        assert enable_executable_cache() == env_dir
        assert not list((tmp_path / "plans").rglob("xla"))

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_executable_cache() == str(EXECUTABLE_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir \
            == str(EXECUTABLE_CACHE_DIR)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert EXECUTABLE_CACHE_DIR.parent == Path(repo)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        cc.reset_cache()


def test_export_import_cache(tmp_path, tpch):
    """export_cache → import_cache moves a warm plan cache between
    services with no re-planning on the importer."""
    db, schema = tpch
    svc = QueryService(db, schema)                # no cache_dir at all
    svc.submit(FIG1)
    svc.submit(COSTLY_PARTS)
    assert svc.export_cache(tmp_path / "exported") == 2

    svc2 = QueryService(db, schema)
    assert svc2.import_cache(tmp_path / "exported") == 2
    a = svc2.submit(FIG1)
    b = svc2.submit(COSTLY_PARTS)
    assert a.error is None and b.error is None
    m = svc2.metrics()
    assert m["plan_builds"] == 0 and m["plan_hits"] == 2
    _assert_bitwise(a.values, QueryService(db, schema).submit(FIG1).values)


def test_import_from_foreign_store_never_evicts(tmp_path, tpch):
    """Regression: importing a directory written under ANOTHER schema (or
    format version) must skip every entry — not delete them.  The source
    may be a shared warm store that other services still depend on."""
    db, schema = tpch
    svc = QueryService(db, schema, cache_dir=tmp_path)
    svc.submit(FIG1)
    path = _single_entry(svc.plan_store)
    doc = json.loads(path.read_text())
    doc["schema_fingerprint"] = "f" * 64          # a foreign service's store
    path.write_text(json.dumps(doc))

    svc2 = QueryService(db, schema)
    assert svc2.import_cache(tmp_path) == 0       # nothing usable
    assert path.exists()                          # ...and nothing destroyed


def test_schema_fingerprint_sensitivity(tpch):
    _, schema = tpch
    fp = schema_fingerprint(schema)
    assert fp == schema_fingerprint(schema)       # deterministic
    mutated = Schema(relations=dict(schema.relations),
                     foreign_keys=schema.foreign_keys[:-1])
    assert schema_fingerprint(mutated) != fp


def test_store_keyed_by_planner_config(tmp_path, tpch):
    """Regression: persisted plans are planner OUTPUT — a store warmed by
    a mode='ref' service must not hand materialising plans to a default
    (auto → 0MA/Opt⁺) service sharing the cache_dir, and vice versa."""
    db, schema = tpch
    assert store_fingerprint(schema) != store_fingerprint(schema,
                                                          mode="ref")
    assert store_fingerprint(schema) != store_fingerprint(schema,
                                                          use_fkpk=True)

    ref_svc = QueryService(db, schema, mode="ref", cache_dir=tmp_path)
    res_ref = ref_svc.submit(FIG1)
    assert res_ref.stats.mode == "ref"
    assert ref_svc.metrics()["persist_writes"] == 1

    auto_svc = QueryService(db, schema, cache_dir=tmp_path)
    res_auto = auto_svc.submit(FIG1)
    m = auto_svc.metrics()
    assert res_auto.stats.mode != "ref"           # its own planner ran
    assert m["persist_hits"] == 0 and m["plan_builds"] == 1
    # ...and neither store evicted the other's entry
    assert ref_svc.metrics()["persist_entries"] == 1
    assert m["persist_entries"] == 1

    # the ref service still warm-starts from its own scoped entries
    ref2 = QueryService(db, schema, mode="ref", cache_dir=tmp_path)
    assert ref2.submit(FIG1).stats.mode == "ref"
    assert ref2.metrics()["plan_builds"] == 0
    assert ref2.metrics()["persist_hits"] == 1
