"""The ``tpch_sf10`` configuration's own queries, from the SQL its file
states, served at a small size on both join paths: the declared key
domains send every join down the dense path, the same schema with its
domains stripped down the sorted one.  Each query alone through
``submit`` and ``submit_async``, the three through ``submit_many``, and
after ``update_table`` with new money values, each equal to the
configuration's plain reference."""

import copy
import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, common, system  # noqa: E402

NAME = "tpch_sf10"
SEED = 2 ** 31 + 7
QUERIES = ["minmax", "median", "count"]
PATHS = ["dense", "sorted"]
# the row counts of the benchmark's own CPU tests
SMALL_ROWS = common.load_module(ROOT / "tests" / "bench" / "conftest.py",
                                "bench_tests_conftest").SMALL_ROWS[NAME]


def _strip(schema):
    """``schema`` with no declared domain: every join sorts."""
    return dataclasses.replace(schema, relations={
        name: dataclasses.replace(rel, columns=tuple(
            dataclasses.replace(c, domain=None) for c in rel.columns))
        for name, rel in schema.relations.items()})


def _data(spec, seed):
    gen = common.config_module(NAME)
    data = gen.generate(spec, seed)
    data.update({rel: {c: np.asarray(a) for c, a in cols.items()}
                 for rel, cols in gen.generate_on_device(spec, seed).items()})
    return data


@pytest.fixture(scope="module")
def cell():
    spec = copy.deepcopy(common.config_spec(NAME))
    spec["rows"] = dict(SMALL_ROWS)
    return spec, common.config_module(NAME), _data(spec, SEED)


def _service(spec, data, path):
    sch = system.schema(spec)
    return system.service(spec, system.load(spec, data),
                          sch if path == "dense" else _strip(sch))


@pytest.fixture(scope="module")
def services(cell):
    spec, _gen, data = cell
    svcs = {path: _service(spec, data, path) for path in PATHS}
    yield svcs
    for svc in svcs.values():
        svc.close()


def _assert_equal(spec, want, names, results):
    assert all(r.ok for r in results), [r.error for r in results]
    answers = [(q, {k: np.asarray(v) for k, v in r.values.items()})
               for q, r in zip(names, results)]
    numbers, failed = check.compare(spec["checks"], want, answers)
    assert check.passed(numbers) and failed == 0, numbers


def _assert_path(svc, path):
    m = svc.metrics()
    other = "sorted" if path == "dense" else "dense"
    assert m[f"joins_{path}"] > 0 and m[f"joins_{other}"] == 0


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("call", ["submit", "submit_async"])
@pytest.mark.parametrize("query", QUERIES)
def test_query_alone_equals_the_reference(cell, services, query, call,
                                          path):
    spec, gen, data = cell
    svc = services[path]
    sql = spec["queries"][query]
    r = (svc.submit(sql) if call == "submit"
         else svc.submit_async(sql).result(timeout=300))
    _assert_equal(spec, gen.reference(spec, data), [query], [r])
    _assert_path(svc, path)


def test_submit_many_on_the_stripped_schema(cell, services):
    spec, gen, data = cell
    svc = services["sorted"]
    results = svc.submit_many([spec["queries"][q] for q in QUERIES])
    assert all(r.stats.fused for r in results)
    _assert_equal(spec, gen.reference(spec, data), QUERIES, results)
    _assert_path(svc, "sorted")


@pytest.mark.parametrize("path", PATHS)
def test_update_table_inside_the_domains(cell, path):
    """New ``s_acctbal`` and ``p_retailprice`` values, keys untouched: the
    service answers the new data as the reference does."""
    spec, gen, data = cell
    svc = _service(spec, data, path)
    sqls = [spec["queries"][q] for q in QUERIES]
    _assert_equal(spec, gen.reference(spec, data), QUERIES,
                  svc.submit_many(sqls))
    rng = np.random.default_rng(11)
    new = copy.deepcopy(data)
    new["supplier"]["s_acctbal"] = rng.permutation(
        new["supplier"]["s_acctbal"]) + np.float32(0.5)
    new["part"]["p_retailprice"] = rng.permutation(
        new["part"]["p_retailprice"])
    want = gen.reference(spec, new)
    assert want != gen.reference(spec, data)
    tables = system.load(spec, new)
    for rel in ("supplier", "part"):
        svc.update_table(rel, tables[rel])
    _assert_equal(spec, want, QUERIES, svc.submit_many(sqls))
    _assert_path(svc, path)
    svc.close()
