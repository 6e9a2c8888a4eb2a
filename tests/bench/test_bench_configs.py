"""Each configuration of the benchmark: the service's answers equal the
plain reference at a small size, the generator follows its source, and
the control (the reference one precision step down) fails the comparison
at the cell's own size."""

import numpy as np
import pytest

from bench import check, common, system

CONFIGS = ["tpch_sf10"]


def _data(name, spec, seed):
    """Every table of the configuration: those made on the host, and
    those made on the device where the configuration makes some there."""
    gen = common.config_module(name)
    data = gen.generate(spec, seed)
    made = getattr(gen, "generate_on_device", None)
    if made is not None:
        data.update({rel: {c: np.asarray(a) for c, a in cols.items()}
                     for rel, cols in made(spec, seed).items()})
    return data


@pytest.mark.parametrize("name", CONFIGS)
def test_service_answers_equal_the_reference(name, small_spec):
    spec = small_spec(name)
    gen = common.config_module(name)
    data = _data(name, spec, 2 ** 31 + 7)
    svc = system.service(spec, system.load(spec, data), system.schema(spec))
    sqls = list(spec["queries"].values())
    results = svc.submit_many(sqls)
    assert all(r.ok for r in results)
    assert all(r.stats.fused for r in results)
    answers = [(q, {k: np.asarray(v) for k, v in r.values.items()})
               for q, r in zip(spec["queries"], results)]
    numbers, failed = check.compare(spec["checks"], gen.reference(spec, data),
                                    answers)
    assert check.passed(numbers), numbers
    assert failed == 0


@pytest.mark.parametrize("name", CONFIGS)
def test_control_fails_at_the_cells_size(name):
    """The control, at the configuration's own sizes, on three seeds: one
    of the cell's numbers exceeds its limit on every seed."""
    from bench import control

    for seed in (3, 2 ** 31 + 11, 912345678):
        numbers, passed = control.readings(name, seed)
        assert not passed, (seed, numbers)


@pytest.mark.parametrize("name", CONFIGS)
def test_data_depends_on_the_seed_alone(name, small_spec):
    spec = small_spec(name)
    a, b, c = (_data(name, spec, s) for s in (5, 5, 2 ** 33 + 6))
    for rel, cols in spec["schema"].items():
        for col in cols:
            np.testing.assert_array_equal(a[rel][col], b[rel][col])
            assert a[rel][col].shape == (spec["rows"][rel],)
    for rel in spec["schema"]:
        if rel not in ("region", "nation"):
            assert any(not np.array_equal(a[rel][k], c[rel][k])
                       for k in spec["schema"][rel]), rel


def test_tpch_follows_dbgen():
    spec = common.config_spec("tpch_sf10")
    d = common.config_module("tpch_sf10").generate(spec, 1)
    s, p, ps = d["supplier"], d["part"], d["partsupp"]
    assert {t: len(next(iter(c.values()))) for t, c in d.items()} \
        == {t: n for t, n in spec["rows"].items() if t in d}
    # at SF10 the spec's formula gives every part four distinct suppliers
    supp = np.sort(ps["ps_suppkey"].reshape(-1, 4), axis=1)
    assert (supp[:, 1:] != supp[:, :-1]).all()
    assert supp.min() == 1 and supp.max() == spec["rows"]["supplier"]
    assert p["p_retailprice"][0] == np.float32(901.0)      # P_PARTKEY 1
    assert -999.99 <= s["s_acctbal"].min() and s["s_acctbal"].max() <= 9999.99
    assert 1.0 <= ps["ps_supplycost"].min() \
        and ps["ps_supplycost"].max() <= 1000.0
    assert (p["p_brand"] // 5 == p["p_mfgr"]).all()
    assert p["p_type"].max() == 149 and p["p_container"].max() == 39


def test_tpch_device_tables_follow_dbgen(small_spec):
    spec = small_spec("tpch_sf10")
    gen = common.config_module("tpch_sf10")
    d = _data("tpch_sf10", spec, 2 ** 31 + 9)
    c, o, li = d["customer"], d["orders"], d["lineitem"]
    rows = spec["rows"]
    assert gen.lineitem_rows(rows["orders"]) == rows["lineitem"]
    # the first 8 keys of every 32, each order's lines 1..n in a row
    assert ((o["o_orderkey"] % 32 >= 1) & (o["o_orderkey"] % 32 <= 8)).all()
    assert len(np.unique(o["o_orderkey"])) == rows["orders"]
    assert (o["o_custkey"] % 3 != 0).all()
    assert np.isin(o["o_custkey"], c["c_custkey"]).all()
    lines = np.bincount(np.searchsorted(o["o_orderkey"], li["l_orderkey"]))
    per_count = np.bincount(lines, minlength=8)[1:]
    assert per_count.max() - per_count.min() <= 1
    starts = np.r_[0, np.cumsum(lines)[:-1]]
    assert (li["l_linenumber"][starts] == 1).all()
    assert li["l_linenumber"].max() == 7
    # PARTSUPP's supplier formula, and the price of the part
    pk, n_s = li["l_partkey"].astype(np.int64), rows["supplier"]
    options = [(pk + i * (n_s // 4 + (pk - 1) // n_s)) % n_s + 1
               for i in range(4)]
    assert np.any([li["l_suppkey"] == opt for opt in options], axis=0).all()
    price = (90000 + (pk // 10) % 20001 + 100 * (pk % 1000)) / 100
    np.testing.assert_allclose(li["l_extendedprice"],
                               li["l_quantity"] * price, rtol=1e-6)
    # dates, and the flags they decide
    od = o["o_orderdate"][np.searchsorted(o["o_orderkey"], li["l_orderkey"])]
    assert (gen.START_DATE <= o["o_orderdate"]).all() \
        and (o["o_orderdate"] <= gen.LAST_ORDER_DATE).all()
    assert ((li["l_shipdate"] - od >= 1) & (li["l_shipdate"] - od <= 121)).all()
    assert ((li["l_commitdate"] - od >= 30)
            & (li["l_commitdate"] - od <= 90)).all()
    late = li["l_receiptdate"] > gen.CURRENT_DATE
    assert (li["l_returnflag"][late] == 1).all()
    assert np.isin(li["l_returnflag"][~late], [0, 2]).all()
    assert (li["l_linestatus"] == (li["l_shipdate"] > gen.CURRENT_DATE)).all()
    # the order's status and total follow from its lines
    owner = np.repeat(np.arange(rows["orders"]), lines)
    n_o = np.bincount(owner, li["l_linestatus"] == 1)
    want = np.where(n_o == 0, 0, np.where(n_o == lines, 1, 2))
    np.testing.assert_array_equal(o["o_orderstatus"], want)
    total = np.bincount(owner, li["l_extendedprice"] * (1 - li["l_discount"])
                        * (1 + li["l_tax"]))
    np.testing.assert_allclose(o["o_totalprice"], total, atol=0.02 * 7)
