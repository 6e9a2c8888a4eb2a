"""Smoke run of the serving path on a TPU, at TPC-H SF10 cardinalities.

    python chip_smoke.py            # one chip
    python chip_smoke.py --mesh     # four chips: mesh service vs one device

Builds the mini TPC-H database (``make_tpch_db``) at ``scale=100_000``:
supplier 100k, part 2M and partsupp 8M rows, which are TPC-H SF10's
cardinalities for those tables.  The tables are loaded onto the device,
and the paper's V.1 queries (MIN/MAX, MEDIAN and COUNT(*) of
``s_acctbal`` over the 5-way join) are served as SQL through
``QueryService`` in ``opt_plus``, by one service with ``use_fkpk`` off
and one with it on, through ``submit_many`` (one fused batch),
``submit_async`` and, with ``use_fkpk`` off, ``submit``.  Every answer
is compared with a plain numpy reference: COUNT exactly, MIN/MAX
bitwise, MEDIAN as the same element.

``--mesh`` runs only the mesh path: the same queries served as one
fused batch by ``QueryService(mesh=...)`` on a four-device ``data``
mesh, compared bitwise with a one-device service padded to the same
capacities and with the reference, and partsupp checked to sit a
quarter on each device.

The last line of standard output is one JSON object, ``{"ok": true,
"device": {...}}``, printed only when every check passed.  Without a TPU
the script fails before doing any work: there is no CPU fallback.  The
timings it prints are a smoke reading, not a benchmark.  Where
``JAX_COMPILATION_CACHE_DIR`` is not set, compiled programs are cached
in ``.jax_cache`` at the root of the checkout, and the script reports
how many of its compiles that cache answered.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

_SRC = str(Path(__file__).resolve().parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.data.relational import make_tpch_db  # noqa: E402
from repro.service import QueryService  # noqa: E402
from repro.service.plan_store import enable_executable_cache  # noqa: E402

SCALE = 100_000            # supplier 100k, part 2M, partsupp 8M rows
MESH_DEVICES = 4
MIN_BUCKET = 8             # the service default; per shard on a mesh
WARM_REPEATS = 5
RESULT_TIMEOUT_S = 600

REGIONS = (2, 3)
PRICE = 1200.0
JOIN = f"""
FROM region r, nation n, supplier s, partsupp ps, part p
WHERE r.r_regionkey = n.n_regionkey AND n.n_nationkey = s.s_nationkey
  AND s.s_suppkey = ps.ps_suppkey AND ps.ps_partkey = p.p_partkey
  AND r.r_name IN {REGIONS} AND p.p_price > {PRICE}
"""
QUERIES = {
    "minmax": f"SELECT MIN(s.s_acctbal), MAX(s.s_acctbal) {JOIN}",
    "median": f"SELECT MEDIAN(s.s_acctbal) {JOIN}",
    "count": f"SELECT COUNT(*) {JOIN}",
}


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


class CacheEvents:
    """Counts JAX's persistent compilation-cache hits and misses."""

    def __init__(self):
        self.hits = 0
        self.misses = 0

    def __call__(self, event: str, **_kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def device_line() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


# ---------------------------------------------------------------- reference

def reference(db) -> dict[str, dict[str, np.ndarray]]:
    """The V.1 answers by plain numpy over the loaded columns: filter
    partsupp through ``p_price[ps_partkey]`` and the supplier → nation →
    region chain, then aggregate ``s_acctbal`` of the surviving rows.
    MEDIAN is the lower weighted median of ``kernels/ops.py``: the first
    value, in sorted order, whose cumulative count reaches half."""
    col = {(rel, c): np.asarray(a)
           for rel, t in db.items() for c, a in t.columns.items()}
    for rel, key in (("region", "r_regionkey"), ("nation", "n_nationkey"),
                     ("supplier", "s_suppkey"), ("part", "p_partkey")):
        k = col[rel, key]                 # keys are row numbers
        if not np.array_equal(k, np.arange(k.shape[0], dtype=k.dtype)):
            raise SmokeFailure(f"{rel}.{key} is not 0..n-1")
    ps_part = col["partsupp", "ps_partkey"]
    ps_supp = col["partsupp", "ps_suppkey"]
    region = col["nation", "n_regionkey"][col["supplier", "s_nationkey"]]
    keep = col["part", "p_price"][ps_part] > np.float32(PRICE)
    keep &= np.isin(col["region", "r_name"][region[ps_supp]], REGIONS)
    bal = np.sort(col["supplier", "s_acctbal"][ps_supp[keep]])
    n = bal.shape[0]
    if n == 0:
        raise SmokeFailure("the reference join is empty")
    return {
        "minmax": {"min(s.s_acctbal)": bal[0], "max(s.s_acctbal)": bal[-1]},
        "median": {"median(s.s_acctbal)": bal[(n + 1) // 2 - 1]},
        "count": {"count(*)": np.int64(n)},
    }


def check_answer(ctx: str, values: dict, want: dict) -> None:
    if set(values) != set(want):
        raise SmokeFailure(f"{ctx}: answer names {sorted(values)} != "
                           f"{sorted(want)}")
    for k, w in want.items():
        got = np.asarray(values[k])
        if np.issubdtype(w.dtype, np.integer):
            ok = got.shape == () and int(got) == int(w)
        else:
            ok = got.dtype == w.dtype and got.tobytes() == w.tobytes()
        if not ok:
            raise SmokeFailure(f"{ctx}: {k} = {got!r}, reference {w!r}")


def check_same(ctx: str, a: dict, b: dict) -> None:
    """Bitwise equality of two services' answers."""
    if set(a) != set(b):
        raise SmokeFailure(f"{ctx}: answer names differ")
    for k in a:
        x, y = np.asarray(a[k]), np.asarray(b[k])
        if x.dtype != y.dtype or x.tobytes() != y.tobytes():
            raise SmokeFailure(f"{ctx}: {k} = {x!r} vs {y!r}")


def results_ok(ctx: str, results) -> None:
    for name, res in zip(QUERIES, results):
        if res.error is not None:
            raise SmokeFailure(f"{ctx}/{name}: {res.error!r}")


def finish_service(ctx: str, svc: QueryService) -> dict:
    svc.close()
    m = svc.metrics()
    if m["request_errors"]:
        raise SmokeFailure(f"{ctx}: request_errors={m['request_errors']}")
    return m


# ---------------------------------------------------------------- phases

def describe_tables(db) -> None:
    for rel, t in db.items():
        arrays = [*t.columns.values(), t.freq]
        nbytes = sum(a.nbytes for a in arrays)
        devs = {d for a in arrays for d in a.devices()}
        print(f"table {rel:9s} rows={t.capacity:>9d} "
              f"device_bytes={nbytes:>10d} on {sorted(map(str, devs))}")


def warm_seconds(call) -> list[float]:
    """Host-clock seconds of ``WARM_REPEATS`` calls, each to its answer
    on the host."""
    lat = []
    for _ in range(WARM_REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        lat.append(time.perf_counter() - t0)
    return lat


def serve_fused(ctx: str, svc: QueryService, want) -> list:
    """The three queries as one ``submit_many`` batch, which must run as
    one fused program; a fused program that failed would have fallen back
    to serving the members one by one."""
    fused_before = svc.metrics()["fused_batches"]
    batch = svc.submit_many(list(QUERIES.values()))
    results_ok(f"{ctx}/submit_many", batch)
    for name, res in zip(QUERIES, batch):
        check_answer(f"{ctx}/submit_many/{name}", res.values, want[name])
    fused_batches = svc.metrics()["fused_batches"] - fused_before
    if fused_batches < 1 or not all(r.stats.fused for r in batch):
        raise SmokeFailure(
            f"{ctx}: submit_many did not run fused (fused_batches "
            f"+{fused_batches}, fused={[r.stats.fused for r in batch]})")
    print(f"[{ctx}] submit_many: fused_batches +{fused_batches}, "
          f"group_size={batch[0].stats.fused_group_size}, "
          f"compile_s={batch[0].stats.compile_s}")
    return batch


def run_single(db, schema, want) -> None:
    """One device, for ``use_fkpk`` off and on: a fused ``submit_many``
    batch and ``submit_async`` (which forms the same fused batch), and
    with ``use_fkpk`` off also ``submit`` per query.  Every distinct
    program costs a cold compile, so the solo programs are compiled for
    one of the two services only."""
    for use_fkpk in (False, True):
        ctx = f"opt_plus/use_fkpk={use_fkpk}"
        svc = QueryService(db, schema, mode="opt_plus", use_fkpk=use_fkpk)
        serve_fused(ctx, svc, want)

        futures = [svc.submit_async(sql) for sql in QUERIES.values()]
        results = [f.result(timeout=RESULT_TIMEOUT_S) for f in futures]
        results_ok(f"{ctx}/submit_async", results)
        for name, res in zip(QUERIES, results):
            check_answer(f"{ctx}/submit_async/{name}", res.values,
                         want[name])

        sqls = list(QUERIES.values())
        lat = warm_seconds(lambda: [r.values for r in svc.submit_many(sqls)])
        print(f"[{ctx}] warm fused batch (smoke reading, not a benchmark): "
              f"seconds={lat}")
        if not use_fkpk:
            for name, sql in QUERIES.items():
                res = svc.submit(sql)
                check_answer(f"{ctx}/submit/{name}", res.values, want[name])
                print(f"[{ctx}] cold submit {name}: "
                      f"compile_s={res.stats.compile_s} "
                      f"total_s={res.stats.total_s}")
                lat = warm_seconds(lambda: svc.submit(sql).values)
                print(f"[{ctx}] warm submit {name} (smoke reading, not a "
                      f"benchmark): seconds={lat}")

        m = finish_service(ctx, svc)
        print(f"[{ctx}] compiles={m['compiles']} "
              f"fused_compiles={m['fused_compiles']} "
              f"compile_s_total={m['compile_s_total']} "
              f"requests={m['requests']} request_errors=0")


def partsupp_shards(svc: QueryService, n_devices: int) -> None:
    """partsupp as the mesh service placed it: one quarter (1/n) of its
    rows on each device."""
    padded = dict(svc.cache.padded.items())["partsupp"][1]
    cap = padded.capacity
    shards = padded.freq.addressable_shards
    devices = {s.device for s in shards}
    rows = [s.data.shape[0] for s in shards]
    live = [int(np.count_nonzero(np.asarray(s.data))) for s in shards]
    print(f"[mesh] partsupp capacity={cap}: rows per device={rows} "
          f"live rows per device={live} on {sorted(map(str, devices))}")
    if len(devices) != n_devices or rows != [cap // n_devices] * n_devices:
        raise SmokeFailure(f"partsupp is not split evenly over "
                           f"{n_devices} devices: {rows} on {devices}")


def run_mesh(db, schema, want, devices) -> None:
    """``QueryService(mesh=...)`` over ``devices`` against a one-device
    service padded identically: for a power-of-two mesh the per-shard
    bucket times the shard count equals the one-device bucket at
    ``min_bucket × shards``, so both serve the same padded arrays.  The
    mesh is ``jax.make_mesh``'s default, with Explicit axes."""
    n = len(devices)
    mesh = jax.make_mesh((n,), ("data",), devices=devices)
    ctx = "mesh/opt_plus"
    msvc = QueryService(db, schema, mode="opt_plus", mesh=mesh,
                        min_bucket=MIN_BUCKET)
    lsvc = QueryService(db, schema, mode="opt_plus",
                        min_bucket=MIN_BUCKET * n)
    # the two services compile their fused programs at the same time
    with ThreadPoolExecutor(2) as pool:
        mesh_batch, local_batch = pool.map(
            lambda svc: serve_fused(ctx, svc, want), (msvc, lsvc))
    for name, m, loc in zip(QUERIES, mesh_batch, local_batch):
        check_same(f"{ctx}/{name} mesh vs one device", m.values, loc.values)
    partsupp_shards(msvc, n)
    for label, svc in (("mesh", msvc), ("one device", lsvc)):
        met = finish_service(f"{ctx}/{label}", svc)
        print(f"[{ctx}] {label}: compiles={met['compiles']} "
              f"compile_s_total={met['compile_s_total']}")
    print(f"[{ctx}] {len(QUERIES)} queries: mesh == one device (bitwise) "
          "== reference")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help=f"run only the {MESH_DEVICES}-chip mesh path and "
                         "its one-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = device_line()
    print(f"devices: {jax.devices()}")
    print(f"platform={dev['platform']} device_kind={dev['kind']} "
          f"count={dev['count']}")
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found platform {dev['platform']!r}, not a "
              "TPU; this script has no CPU fallback", file=sys.stderr)
        return 2
    if args.mesh and dev["count"] < MESH_DEVICES:
        print(f"chip_smoke: --mesh needs {MESH_DEVICES} devices, JAX found "
              f"{dev['count']}", file=sys.stderr)
        return 2

    cache_dir = enable_executable_cache()
    events = CacheEvents()
    jax.monitoring.register_event_listener(events)

    t0 = time.perf_counter()
    db, schema = make_tpch_db(scale=SCALE, seed=args.seed)
    jax.block_until_ready(db)
    print(f"data: make_tpch_db(scale={SCALE}, seed={args.seed}) in "
          f"{time.perf_counter() - t0} s")
    describe_tables(db)
    want = reference(db)
    print("reference: " + ", ".join(f"{k}={v.item()}" for a in want.values()
                                    for k, v in a.items()))

    try:
        if args.mesh:
            run_mesh(db, schema, want, jax.devices()[:MESH_DEVICES])
        else:
            run_single(db, schema, want)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1

    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}")
    print(f"compile cache: dir={cache_dir} hits={events.hits} "
          f"misses={events.misses}")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
