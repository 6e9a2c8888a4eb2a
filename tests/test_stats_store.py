"""StatsStore: the persisted statistics catalog.

The store tests damage one entry at a time and assert skip-and-evict,
mirroring ``test_plan_store.py``; the service tests assert what a
``cache_dir`` buys the catalog: a warm restart over identical data
computes no statistics (``stat_refreshes == 0``), and a change to one
table's data recomputes that table alone.
"""

import hashlib
import json

import jax
import numpy as np
import pytest

from repro.core.stats import compute_table_stats
from repro.data import make_graph_db, make_tpch_db
from repro.service import QueryService
from repro.service.plan_store import schema_fingerprint
from repro.service.stats_store import (
    STATS_FORMAT_VERSION,
    StatsStore,
    _canonical_body,
)
from repro.tables.table import Table

jax.config.update("jax_platform_name", "cpu")

COSTLY_PARTS = """
SELECT SUM(ps.ps_supplycost), COUNT(*)
FROM partsupp ps, part p
WHERE ps.ps_partkey = p.p_partkey AND p.p_price > 1500.0
"""


@pytest.fixture(scope="module")
def tpch():
    return make_tpch_db(scale=20, seed=5)


def _stats(tpch, name="partsupp"):
    db, schema = tpch
    return compute_table_stats(name, db[name], schema, db)


def _store(tmp_path, tpch):
    return StatsStore(tmp_path, schema_fingerprint(tpch[1]))


def _single_entry(store: StatsStore):
    paths = list(store.stats_dir.glob("*.json"))
    assert len(paths) == 1
    return paths[0]


def _reshuffled(table: Table, seed: int) -> Table:
    """The same rows of `table` in another order, with one value column
    redrawn: new data under the same schema and declared domains."""
    cols = {c: np.asarray(a) for c, a in table.columns.items()}
    perm = np.random.default_rng(seed).permutation(len(table.freq))
    cols = {c: a[perm] for c, a in cols.items()}
    cols["ps_supplycost"] = cols["ps_supplycost"] * np.float32(1.5)
    return Table.from_numpy(cols)


# ---------------------------------------------------------------------------
# the store discipline
# ---------------------------------------------------------------------------
def test_store_roundtrip_across_instances(tmp_path, tpch):
    stats = _stats(tpch)
    store = _store(tmp_path, tpch)
    assert store.save(stats, token="t1")
    assert store.metrics()["stats_persist_writes"] == 1

    fresh = _store(tmp_path, tpch)
    assert fresh.load("partsupp", "t1") == stats
    assert fresh.load("partsupp", "t2") is None        # other data: a miss
    assert fresh.load("part", "t1") is None            # never written
    m = fresh.metrics()
    assert m["stats_persist_hits"] == 1 and m["stats_persist_misses"] == 2
    assert m["stats_persist_corrupt_skipped"] == 0
    assert m["stats_persist_entries"] == 1


@pytest.mark.parametrize("damage", ["truncated", "flipped", "version",
                                    "key", "fields"])
def test_corrupt_entries_skipped_and_evicted(tmp_path, tpch, damage):
    """Truncation, payload bit-flips, format-version skew, key-field
    mismatch, and payload-schema drift all skip + evict + count — never
    raise, never install damaged statistics."""
    store = _store(tmp_path, tpch)
    store.save(_stats(tpch), token="t1")
    path = _single_entry(store)
    raw = path.read_bytes()
    doc = json.loads(raw)
    if damage == "truncated":
        path.write_bytes(raw[:len(raw) // 2])
    elif damage == "flipped":
        doc["payload"]["rows"] += 1                    # checksum mismatch
        path.write_text(json.dumps(doc))
    elif damage == "version":
        doc["format_version"] = STATS_FORMAT_VERSION + 99
        path.write_text(json.dumps(doc))
    elif damage == "key":
        doc["relation"] = "part"                       # moved-file aliasing
        path.write_text(json.dumps(doc))
    else:  # fields: checksum VALID but the payload lost a field
        del doc["payload"]["rows"]
        doc["payload_sha256"] = hashlib.sha256(
            _canonical_body(doc["payload"])).hexdigest()
        path.write_text(json.dumps(doc))

    fresh = _store(tmp_path, tpch)
    assert fresh.load("partsupp", "t1") is None
    m = fresh.metrics()
    assert m["stats_persist_corrupt_skipped"] == 1
    assert m["stats_persist_hits"] == 0
    assert not path.exists()                           # evicted


def test_schema_fingerprint_scopes_entries(tmp_path, tpch):
    """Services over different schemas sharing a cache_dir never read
    each other's statistics."""
    _db, graph_schema = make_graph_db(n_nodes=10, n_edges=20, seed=1)
    mine = _store(tmp_path, tpch)
    other = StatsStore(tmp_path, schema_fingerprint(graph_schema))
    assert mine.stats_dir != other.stats_dir
    assert mine.save(_stats(tpch), token="t1")
    assert other.load("partsupp", "t1") is None
    assert other.metrics()["stats_persist_entries"] == 0
    assert mine.load("partsupp", "t1") is not None


def test_unwritable_store_degrades(tmp_path, tpch):
    """Write failure (dir replaced by a file — root-proof sabotage, as in
    the plan-store test) returns False + counts; loads simply miss."""
    store = _store(tmp_path, tpch)
    store.stats_dir.rmdir()
    store.stats_dir.write_text("not a directory")
    assert store.save(_stats(tpch), token="t1") is False
    assert store.save_feedback({"version": 0, "records": []}) is False
    m = store.metrics()
    assert m["stats_persist_write_errors"] == 2
    assert m["stats_persist_writes"] == 0
    assert store.load("partsupp", "t1") is None
    assert store.load_feedback() is None


# ---------------------------------------------------------------------------
# through the service
# ---------------------------------------------------------------------------
def _refreshes(svc) -> int:
    return svc.metrics()["stat_refreshes"]


def test_warm_restart_reads_every_table_from_disk(tmp_path, tpch):
    db, schema = tpch
    cold = QueryService(db, schema, cache_dir=tmp_path)
    assert _refreshes(cold) == len(db)
    warm = QueryService(db, schema, cache_dir=tmp_path)
    assert _refreshes(warm) == 0
    assert warm.metrics()["stats_persist_hits"] >= len(db)
    assert warm.stats.tables() == cold.stats.tables()
    want = cold.submit(COSTLY_PARTS).values
    got = warm.submit(COSTLY_PARTS).values
    for k, v in want.items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(got[k]))


def test_update_table_refreshes_that_table_alone(tmp_path, tpch):
    """No foreign key points at partsupp, so its update recomputes its
    own statistics and no other table's; a restart over the updated data
    then reads them all from disk."""
    db, schema = tpch
    svc = QueryService(db, schema, cache_dir=tmp_path)
    before = svc.stats.tables()
    n = _refreshes(svc)
    new = _reshuffled(db["partsupp"], seed=1)
    svc.update_table("partsupp", new)
    assert _refreshes(svc) == n + 1
    after = svc.stats.tables()
    assert after["partsupp"] != before["partsupp"]
    assert after["partsupp"] == compute_table_stats(
        "partsupp", new, schema, {**db, "partsupp": new})
    assert {k: v for k, v in after.items() if k != "partsupp"} \
        == {k: v for k, v in before.items() if k != "partsupp"}
    warm = QueryService({**db, "partsupp": new}, schema, cache_dir=tmp_path)
    assert _refreshes(warm) == 0


def test_changed_data_recomputes_only_the_changed_table(tmp_path, tpch):
    db, schema = tpch
    QueryService(db, schema, cache_dir=tmp_path)
    changed = {**db, "partsupp": _reshuffled(db["partsupp"], seed=2)}
    svc = QueryService(changed, schema, cache_dir=tmp_path)
    assert _refreshes(svc) == 1
    assert svc.stats.tables()["partsupp"] == compute_table_stats(
        "partsupp", changed["partsupp"], schema, changed)


def test_feedback_payload_round_trips(tmp_path, tpch):
    """Serve-time feedback written after a served batch comes back in a
    restarted service as the same payload."""
    db, schema = tpch
    svc = QueryService(db, schema, cache_dir=tmp_path)
    assert svc.submit(COSTLY_PARTS).error is None
    payload = svc.stats.feedback_payload()
    assert payload["records"]
    assert svc.stats_store.load_feedback() == payload
    warm = QueryService(db, schema, cache_dir=tmp_path)
    assert warm.stats.feedback_len() == svc.stats.feedback_len()
    assert warm.stats.feedback_payload() == payload
