"""The eight tables of TPC-H by the dbgen rules of the specification (v3,
clause 4.2.3), and the plain numpy reference of the paper's Fig. 1
aggregates over the 5-way join of REGION, NATION, SUPPLIER, PARTSUPP and
PART.

``generate`` makes on the host the tables the reference reads:

* REGION: the five fixed rows, R_REGIONKEY 0..4; R_NAME dictionary-encoded
  as its key (AFRICA, AMERICA, ASIA, EUROPE, MIDDLE EAST sort in key order).
* NATION: the 25 fixed rows and their fixed N_REGIONKEY; N_NAME
  dictionary-encoded as its key.
* SUPPLIER: S_SUPPKEY 1..S, S = 10,000·SF; S_NATIONKEY uniform in [0, 24];
  S_ACCTBAL uniform in [-999.99, 9,999.99].
* PART: P_PARTKEY 1..P, P = 200,000·SF; P_SIZE uniform in [1, 50];
  P_RETAILPRICE = (90000 + ((P_PARTKEY/10) mod 20001)
  + 100·(P_PARTKEY mod 1000)) / 100; P_MFGR M uniform in [1, 5], P_BRAND
  M·N with N uniform in [1, 5], P_TYPE one of the 150 syllable triples,
  P_CONTAINER one of the 40 syllable pairs, each dictionary-encoded in
  the order the specification lists its words.
* PARTSUPP: four rows per part, PS_SUPPKEY = (PS_PARTKEY + i·(S/4
  + (PS_PARTKEY-1)/S)) mod S + 1 for i in 0..3; PS_AVAILQTY uniform in
  [1, 9,999]; PS_SUPPLYCOST uniform in [1.00, 1,000.00].

``generate_on_device`` makes the tables no query of the configuration
reads, in one jitted call on the default device:

* CUSTOMER: C_CUSTKEY 1..C, C = 150,000·SF; C_NATIONKEY uniform in
  [0, 24]; C_ACCTBAL uniform in [-999.99, 9,999.99]; C_MKTSEGMENT one of 5.
* ORDERS: 1,500,000·SF rows; O_ORDERKEY sparse, the first 8 keys of every
  32; O_CUSTKEY uniform over the customers whose key is not a multiple of
  3; O_ORDERDATE uniform in [1992-01-01, 1998-12-31 - 151 days];
  O_ORDERPRIORITY one of 5; O_CLERK uniform in [1, 1,000·SF];
  O_SHIPPRIORITY 0; O_TOTALPRICE the sum over the order's lines of
  L_EXTENDEDPRICE·(1 - L_DISCOUNT)·(1 + L_TAX); O_ORDERSTATUS F where
  every line is F, O where every line is O, else P.
* LINEITEM: 1 to 7 lines per order; L_PARTKEY uniform in [1, P],
  L_SUPPKEY by PARTSUPP's formula with i uniform in 0..3; L_QUANTITY
  uniform in [1, 50]; L_EXTENDEDPRICE = L_QUANTITY·P_RETAILPRICE;
  L_DISCOUNT uniform in [0.00, 0.10]; L_TAX uniform in [0.00, 0.08];
  L_SHIPDATE = O_ORDERDATE + [1, 121] days, L_COMMITDATE = O_ORDERDATE
  + [30, 90], L_RECEIPTDATE = L_SHIPDATE + [1, 30]; L_RETURNFLAG R or A
  where L_RECEIPTDATE is on or before 1995-06-17, else N; L_LINESTATUS O
  where L_SHIPDATE is after 1995-06-17, else F; L_SHIPINSTRUCT one of 4,
  L_SHIPMODE one of 7.

So that every seed holds the same number of rows, the lines per order are
the values 1..7 in equal shares (to within one order), in an order drawn
from the seed, rather than each drawn alone.  Dates are days since
1970-01-01.  Text of a fixed list of words is dictionary-encoded in the
order the specification lists the words (flags and statuses
alphabetically).  Money is drawn in cents and held as float32.  The row
counts come from the configuration's ``rows``, so a test can run the same
rules at a tiny scale.  This file imports nothing of the engine.
"""

from __future__ import annotations

import functools

import numpy as np

# N_REGIONKEY of nations 0..24, as the specification lists them
NATION_REGION = np.array([0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0,
                          1, 2, 3, 4, 2, 3, 3, 1], dtype=np.int32)


# days since 1970-01-01 of the specification's STARTDATE, CURRENTDATE and
# ENDDATE - 151 days (the last order date)
START_DATE, CURRENT_DATE, LAST_ORDER_DATE = 8035, 9298, 10440


def _money(rng, lo_cents: int, hi_cents: int, n: int) -> np.ndarray:
    cents = rng.integers(lo_cents, hi_cents + 1, n)
    return (cents / 100.0).astype(np.float32)


def generate(spec: dict, seed: int) -> dict[str, dict[str, np.ndarray]]:
    rows = spec["rows"]
    n_supp, n_part = rows["supplier"], rows["part"]
    if rows["partsupp"] != 4 * n_part or rows["region"] != 5 \
            or rows["nation"] != 25 \
            or rows["lineitem"] != lineitem_rows(rows["orders"]):
        raise ValueError(f"rows {rows} do not follow TPC-H's ratios")
    rng = np.random.default_rng([seed, 0])
    supplier = {
        "s_suppkey": np.arange(1, n_supp + 1, dtype=np.int32),
        "s_nationkey": rng.integers(0, 25, n_supp).astype(np.int32),
        "s_acctbal": _money(rng, -99999, 999999, n_supp),
    }
    partkey = np.arange(1, n_part + 1, dtype=np.int64)
    price_cents = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    part = {
        "p_partkey": partkey.astype(np.int32),
        "p_size": rng.integers(1, 51, n_part).astype(np.int32),
        "p_retailprice": (price_cents / 100.0).astype(np.float32),
    }
    ps_part = np.repeat(partkey, 4)
    i = np.tile(np.arange(4, dtype=np.int64), n_part)
    ps_supp = (ps_part + i * (n_supp // 4 + (ps_part - 1) // n_supp)) \
        % n_supp + 1
    n_ps = 4 * n_part
    partsupp = {
        "ps_partkey": ps_part.astype(np.int32),
        "ps_suppkey": ps_supp.astype(np.int32),
        "ps_availqty": rng.integers(1, 10000, n_ps).astype(np.int32),
        "ps_supplycost": _money(rng, 100, 100000, n_ps),
    }
    # drawn after every column above, so those keep their values per seed
    mfgr = rng.integers(0, 5, n_part)
    part["p_mfgr"] = mfgr.astype(np.int32)
    part["p_brand"] = (5 * mfgr + rng.integers(0, 5, n_part)).astype(np.int32)
    part["p_type"] = rng.integers(0, 150, n_part).astype(np.int32)
    part["p_container"] = rng.integers(0, 40, n_part).astype(np.int32)
    region = {"r_regionkey": np.arange(5, dtype=np.int32),
              "r_name": np.arange(5, dtype=np.int32)}
    nation = {"n_nationkey": np.arange(25, dtype=np.int32),
              "n_name": np.arange(25, dtype=np.int32),
              "n_regionkey": NATION_REGION.copy()}
    return {"region": region, "nation": nation, "supplier": supplier,
            "part": part, "partsupp": partsupp}


def lineitem_rows(n_orders: int) -> int:
    """LINEITEM's rows: 1..7 lines per order in equal shares, the first
    ``n_orders mod 7`` of the values once more."""
    r = n_orders % 7
    return 28 * (n_orders // 7) + r * (r + 1) // 2


def generate_on_device(spec: dict, seed: int) -> dict:
    """CUSTOMER, ORDERS and LINEITEM as arrays on the default device."""
    import jax

    rows = spec["rows"]
    key = jax.random.key(int(np.random.default_rng([seed, 2])
                             .integers(0, 2 ** 31)))
    return _device_tables(key, rows["customer"], rows["orders"],
                          rows["part"], rows["supplier"])


@functools.cache
def _device_tables_fn():
    import jax
    import jax.numpy as jnp

    def money(k, lo, hi, n):
        cents = jax.random.randint(k, (n,), lo, hi + 1)
        return cents.astype(jnp.float32) / 100

    def build(key, n_cust, n_ord, n_part, n_supp):
        i32 = jnp.int32
        k = iter(jax.random.split(key, 20))
        uniform = lambda lo, hi, n: jax.random.randint(next(k), (n,), lo,
                                                       hi + 1, dtype=i32)
        customer = {
            "c_custkey": jnp.arange(1, n_cust + 1, dtype=i32),
            "c_nationkey": uniform(0, 24, n_cust),
            "c_acctbal": money(next(k), -99999, 999999, n_cust),
            "c_mktsegment": uniform(0, 4, n_cust),
        }
        o = jnp.arange(n_ord, dtype=i32)
        orderkey = (o // 8) * 32 + o % 8 + 1
        j = uniform(0, 2 * (n_cust // 3) - 1, n_ord)
        orderdate = uniform(START_DATE, LAST_ORDER_DATE, n_ord)
        lines = jax.random.permutation(next(k), o % 7 + 1)
        n_line = lineitem_rows(n_ord)
        owner = jnp.repeat(o, lines, total_repeat_length=n_line)
        first = jnp.cumsum(lines) - lines
        l_date = orderdate[owner]
        partkey = uniform(1, n_part, n_line)
        supp_i = uniform(0, 3, n_line)
        suppkey = (partkey + supp_i * (n_supp // 4 + (partkey - 1) // n_supp)) \
            % n_supp + 1
        qty = uniform(1, 50, n_line)
        price_cents = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
        ext_cents = qty * price_cents
        disc, tax = uniform(0, 10, n_line), uniform(0, 8, n_line)
        ship = l_date + uniform(1, 121, n_line)
        receipt = ship + uniform(1, 30, n_line)
        # A 0, N 1, R 2; F 0, O 1
        flag = jnp.where(receipt <= CURRENT_DATE, 2 * uniform(0, 1, n_line),
                         1)
        status = (ship > CURRENT_DATE).astype(i32)
        lineitem = {
            "l_orderkey": orderkey[owner],
            "l_partkey": partkey,
            "l_suppkey": suppkey,
            "l_linenumber": jnp.arange(n_line, dtype=i32) - first[owner] + 1,
            "l_quantity": qty.astype(jnp.float32),
            "l_extendedprice": ext_cents.astype(jnp.float32) / 100,
            "l_discount": disc.astype(jnp.float32) / 100,
            "l_tax": tax.astype(jnp.float32) / 100,
            "l_returnflag": flag,
            "l_linestatus": status,
            "l_shipdate": ship,
            "l_commitdate": l_date + uniform(30, 90, n_line),
            "l_receiptdate": receipt,
            "l_shipinstruct": uniform(0, 3, n_line),
            "l_shipmode": uniform(0, 6, n_line),
        }
        # dbgen's integer cents: (e·(100 - d) / 100)·(100 + t) / 100
        line_cents = (ext_cents * (100 - disc) // 100) * (100 + tax) // 100
        seg = functools.partial(jax.ops.segment_sum, segment_ids=owner,
                                num_segments=n_ord, indices_are_sorted=True)
        all_f = seg(status) == 0
        all_o = seg(status) == lines
        orders = {
            "o_orderkey": orderkey,
            "o_custkey": 3 * (j // 2) + j % 2 + 1,
            # F 0, O 1, P 2
            "o_orderstatus": jnp.where(all_f, 0, jnp.where(all_o, 1, 2)),
            "o_totalprice": seg(line_cents).astype(jnp.float32) / 100,
            "o_orderdate": orderdate,
            "o_orderpriority": uniform(0, 4, n_ord),
            "o_clerk": uniform(1, max(1, n_part // 200), n_ord),
            "o_shippriority": jnp.zeros((n_ord,), i32),
        }
        return {"customer": customer, "orders": orders, "lineitem": lineitem}

    return jax.jit(build, static_argnums=(1, 2, 3, 4))


def _device_tables(key, n_cust, n_ord, n_part, n_supp):
    return _device_tables_fn()(key, n_cust, n_ord, n_part, n_supp)


def _lookup(keys: np.ndarray, values: np.ndarray,
            probe: np.ndarray) -> np.ndarray:
    """``values`` of the row whose key equals each probe (keys unique, every
    probe present)."""
    order = np.argsort(keys, kind="stable")
    ks = keys[order]
    pos = np.minimum(np.searchsorted(ks, probe), ks.shape[0] - 1)
    if not np.array_equal(ks[pos], probe):
        raise ValueError("a foreign key has no matching primary key")
    return values[order][pos]


def _answers(data, spec, bal_dtype=np.float32, price_dtype=np.float32):
    lit = spec["literals"]
    r, n, s, p, ps = (data[t] for t in ("region", "nation", "supplier",
                                        "part", "partsupp"))
    bal = s["s_acctbal"].astype(bal_dtype).astype(np.float32)
    price = p["p_retailprice"].astype(price_dtype).astype(np.float32)
    nation_rname = _lookup(r["r_regionkey"], r["r_name"],
                           n["n_regionkey"])
    supp_rname = _lookup(n["n_nationkey"], nation_rname, s["s_nationkey"])
    keep = _lookup(p["p_partkey"], price, ps["ps_partkey"]) \
        > np.float32(lit["min_retailprice"])
    keep &= np.isin(_lookup(s["s_suppkey"], supp_rname, ps["ps_suppkey"]),
                    lit["r_name_codes"])
    vals = np.sort(_lookup(s["s_suppkey"], bal, ps["ps_suppkey"][keep]))
    k = vals.shape[0]
    if k == 0:
        raise ValueError("the join is empty at this seed")
    return {
        "minmax": {"min(s.s_acctbal)": vals[0], "max(s.s_acctbal)": vals[-1]},
        "median": {"median(s.s_acctbal)": vals[(k + 1) // 2 - 1]},
        "count": {"count(*)": np.int64(k)},
    }


def reference(spec: dict, data: dict) -> dict[str, dict[str, np.generic]]:
    """The answers of every query: partsupp filtered through
    ``p_retailprice`` and the supplier → nation → region chain, then
    ``s_acctbal`` of the surviving rows aggregated.  MEDIAN is the lower
    median: the ((k + 1) // 2)-th smallest of the k values."""
    return _answers(data, spec)


def control(spec: dict, data: dict) -> dict[str, dict[str, np.generic]]:
    """The reference one precision step down: the money columns rounded
    to bfloat16.  It has to fail the comparison."""
    import ml_dtypes

    return _answers(data, spec, bal_dtype=ml_dtypes.bfloat16,
                    price_dtype=ml_dtypes.bfloat16)
