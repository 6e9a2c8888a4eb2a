"""The ``graph500_s22`` configuration's own queries, from the SQL its
file states, served at SCALE 12 (2^16 edges, where walk3's program
counts in 64 bits) on both join paths: the declared vertex domain sends
every join down the dense path, the same schema with its domains
stripped down the sorted one.  Each query alone through ``submit`` and
``submit_async``, the three through ``submit_many``, and after
``update_table`` with another seed's edge list, each equal to the
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

NAME = "graph500_s22"
SCALE = 12
SEED = 2 ** 31 + 7
QUERIES = ["walk2", "walk3", "star2"]
PATHS = ["dense", "sorted"]


def _strip(schema):
    """``schema`` with no declared domain: every join sorts."""
    return dataclasses.replace(schema, relations={
        name: dataclasses.replace(rel, columns=tuple(
            dataclasses.replace(c, domain=None) for c in rel.columns))
        for name, rel in schema.relations.items()})


@pytest.fixture(scope="module")
def cell():
    """The configuration at SCALE 12: the edges and the vertex domain
    follow the scale, everything else as the configuration states it."""
    spec = copy.deepcopy(common.config_spec(NAME))
    spec["scale"] = SCALE
    spec["rows"] = {"edge": spec["edgefactor"] << SCALE}
    for col in spec["schema"]["edge"].values():
        col["domain"] = 1 << SCALE
    gen = common.config_module(NAME)
    return spec, gen, gen.generate(spec, SEED)


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
    # walk3's bound passes 2^31 at this scale: its program is 64-bit
    wide = np.asarray(r.values["count(*)"]).dtype == np.int64
    assert wide == (query == "walk3")


def test_submit_many_on_the_stripped_schema(cell, services):
    spec, gen, data = cell
    svc = services["sorted"]
    results = svc.submit_many([spec["queries"][q] for q in QUERIES])
    # walk2's join is walk3's first: the two fuse, and that join runs once
    assert [r.stats.fused for r in results] == [True, True, False]
    _assert_equal(spec, gen.reference(spec, data), QUERIES, results)
    _assert_path(svc, "sorted")


@pytest.mark.parametrize("path", PATHS)
def test_update_table_inside_the_domains(cell, path):
    """Another seed's edge list at the same SCALE: the service answers
    the new graph as the reference does."""
    spec, gen, data = cell
    svc = _service(spec, data, path)
    sqls = [spec["queries"][q] for q in QUERIES]
    _assert_equal(spec, gen.reference(spec, data), QUERIES,
                  svc.submit_many(sqls))
    new = gen.generate(spec, SEED + 1)
    want = gen.reference(spec, new)
    assert want != gen.reference(spec, data)
    svc.update_table("edge", system.load(spec, new)["edge"])
    _assert_equal(spec, want, QUERIES, svc.submit_many(sqls))
    _assert_path(svc, path)
    svc.close()
