"""What the program shows a profiler trace, and the counters beside it:
operator and kernel scopes read back from the optimised HLO
(``repro.core.scopes``), the service's stages on the profiler's clock, the
padded-row and compile-cause counters, and the two benchmark readers that
use them (``freq_join_device_ms``, ``padded_row_pct``)."""

import collections
import glob
import os
import pathlib
import re
import sys
import types

import jax
import pytest
from jax.profiler import ProfileData

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import common  # noqa: E402
from bench.devtrace import Op, Trace  # noqa: E402
from bench.drive import Request, Window  # noqa: E402
from bench.record import Run  # noqa: E402
from repro.core import Executor, parse_sql, plan_query  # noqa: E402
from repro.core.scopes import SCOPES, scope_path, scope_table  # noqa: E402
from repro.data import make_tpch_db  # noqa: E402
from repro.service import QueryService  # noqa: E402
from repro.service import engine  # noqa: E402
from repro.tables.table import bucket_capacity  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

FIG1 = """
SELECT MIN(s.s_acctbal), MAX(s.s_acctbal)
FROM region r, nation n, supplier s, partsupp ps, part p
WHERE r.r_regionkey = n.n_regionkey AND n.n_nationkey = s.s_nationkey
  AND s.s_suppkey = ps.ps_suppkey AND ps.ps_partkey = p.p_partkey
  AND r.r_name IN (2, 3) AND p.p_price > 1200.0
"""
COUNT = FIG1.replace("MIN(s.s_acctbal), MAX(s.s_acctbal)", "COUNT(*)")
SUPPLIERS = """SELECT SUM(s.s_acctbal) FROM supplier s, nation n
WHERE s.s_nationkey = n.n_nationkey"""
FIG1_RELS = ("region", "nation", "supplier", "partsupp", "part")


@pytest.fixture(scope="module")
def tpch():
    return make_tpch_db(scale=40)


# ---------------------------------------------------------------------------
# scope paths and the scope table
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("op_name, path", [
    ("jit(q_ab)/freq_join/freq_join/jit(_freq_join_impl)/search/"
     "jit(searchsorted)/vmap()/while", "freq_join/freq_join/search"),
    ("jit(q_ab)/freq_join/freq_join/jit(_freq_join_impl)/search/"
     "jit(searchsorted)/vmap()/while/body/closed_call/gather",
     "freq_join/freq_join/search"),
    ("jit(q_ab)/freq_join/freq_join/jit(_freq_join_impl)/sort/"
     "jit(argsort)/sort", "freq_join/freq_join/sort"),
    ("jit(q_ab)/freq_join/freq_join/jit(_freq_join_impl)/search/"
     "jit(searchsorted)", "freq_join/freq_join/search"),
    ("jit(q_ab)/final_agg/jit(weighted_percentile)/weighted_percentile/"
     "cumsum", "final_agg/weighted_percentile"),
    ("sort", ""),
    ("reduce_window_sum", ""),
])
def test_scope_path_keeps_the_programs_own_scopes(op_name, path):
    assert scope_path(op_name) == path


HLO = """\
HloModule jit_q_ab, is_scheduled=true

%body (p: (s32[], s32[8])) -> (s32[], s32[8]) {
  %p = (s32[], s32[8]{0}) parameter(0)
  %gte = s32[] get-tuple-element(%p), index=0
  %step = s32[] fusion(%gte), kind=kLoop, calls=%fused_step
  ROOT %t = (s32[], s32[8]{0}) tuple(%step, %gte)
}

%fused_step (a: s32[]) -> s32[] {
  %a = s32[] parameter(0)
  ROOT %add.1 = s32[] add(%a, %a), metadata={op_name="jit(q_ab)/freq_join/freq_join/search/jit(searchsorted)/vmap()/while/body/add"}
}

%cond (c: (s32[], s32[8])) -> pred[] {
  %c = (s32[], s32[8]{0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

%fused_mix (x: s32[8], y: s32[8]) -> s32[8] {
  %x = s32[8]{0} parameter(0)
  %y = s32[8]{0} parameter(1)
  %m1 = s32[8]{0} multiply(%x, %y), metadata={op_name="jit(q_ab)/freq_join/freq_join/mul"}
  ROOT %s1 = s32[8]{0} add(%m1, %y), metadata={op_name="jit(q_ab)/freq_join/freq_join/sort/add"}
}

ENTRY %main (arg: s32[8]) -> s32[8] {
  %arg = s32[8]{0} parameter(0), metadata={op_name="db"}
  %init = (s32[], s32[8]{0}) tuple(%arg, %arg)
  %while.7 = (s32[], s32[8]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(q_ab)/freq_join/freq_join/search/jit(searchsorted)/vmap()/while"}
  %mix = s32[8]{0} fusion(%arg, %arg), kind=kLoop, calls=%fused_mix
  %bare = s32[8]{0} fusion(%arg), kind=kLoop, calls=%fused_nothing
  ROOT %out = s32[8]{0} add(%mix, %bare), metadata={op_name="jit(q_ab)/final_agg/add"}
}

%fused_nothing (z: s32[8]) -> s32[8] {
  ROOT %z = s32[8]{0} parameter(0)
}
"""


def test_scope_table_inherits_from_callers_and_from_what_is_fused():
    table = scope_table(HLO)
    search = "freq_join/freq_join/search"
    assert table["while.7"] == search
    # a while body's instructions take the while's scope; an instruction
    # with a scope of its own keeps it
    for name in ("gte", "step", "t", "c", "lt"):
        assert table[name] == search
    # a fusion without metadata: the common scope of what it fuses
    assert table["mix"] == "freq_join/freq_join"
    assert table["out"] == "final_agg"
    # nothing resolves for these, and nothing maps to an empty scope
    assert "bare" not in table and "arg" not in table and "init" not in table
    assert all(table.values())


def test_scope_table_of_a_compiled_plan(tpch):
    db, schema = tpch
    plan = plan_query(parse_sql(FIG1, schema), schema, mode="opt_plus")
    # no declared domains: every join takes the sorted path
    fn = Executor(db, schema.without_domains()).compile(plan, name="q_fig1")
    text = fn.lower(db).compile().as_text()
    assert text.startswith("HloModule jit_q_fig1,")
    table = scope_table(text)
    loops = [m.group(1) for m in re.finditer(
        r"%(\S+) = .* while\(.*op_name=\"[^\"]*searchsorted", text)]
    assert loops
    for name in loops:
        assert table[name].startswith("freq_join/"), (name, table[name])
    assert all(table.values())
    assert {p.split("/")[0] for p in table.values()} <= {
        "scan", "semi_join", "freq_join", "final_agg"}


def test_scope_table_of_the_dense_path(tpch):
    db, schema = tpch
    plan = plan_query(parse_sql(FIG1, schema), schema, mode="opt_plus")
    joins = collections.Counter()
    fn = Executor(db, schema).compile(plan, name="q_fig1", joins=joins)
    text = fn.lower(db).compile().as_text()
    assert joins["dense"] and not joins["sorted"]
    table = scope_table(text)
    scatters = [m.group(1) for m in re.finditer(
        r"^\s+(?:ROOT )?%(\S+) = \S+ scatter\(", text, re.M)]
    assert scatters
    for name in scatters:
        assert table[name] == "freq_join/freq_join/scatter", (name,
                                                              table[name])
    paths = set(table.values())
    assert {"freq_join/freq_join/scatter",
            "freq_join/freq_join/gather"} <= paths
    assert not any(p.endswith(("/sort", "/search")) or "pregroup" in p
                   for p in paths)


def test_every_named_scope_of_the_programs_is_known():
    names = set()
    for rel in ("core/executor.py", "core/distributed.py", "kernels/ops.py"):
        src = (ROOT / "src" / "repro" / rel).read_text()
        names |= set(re.findall(r'named_scope\("([^"]+)"\)', src))
    assert names == SCOPES


# ---------------------------------------------------------------------------
# the service on the profiler's clock
# ---------------------------------------------------------------------------
def _host_events(log_dir) -> set[str]:
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return {ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def _run_span(res):
    (span,) = [c for c in res.stats.trace.children if c.name == "run"]
    return span


@pytest.mark.parametrize("profile", [True, False])
def test_stages_on_the_profiler_clock_only_when_asked(tpch, tmp_path,
                                                      monkeypatch, profile):
    db, schema = tpch
    if not profile:
        def no_table(text):
            raise AssertionError("scope table built with annotations off")
        monkeypatch.setattr(engine, "scope_table", no_table)
    svc = QueryService(db, schema, profile_annotations=profile)
    with jax.profiler.trace(str(tmp_path)):
        res = svc.submit_many([FIG1, COUNT])
    names = _host_events(tmp_path)
    stages = {f"service.{s}" for s in ("parse", "fingerprint", "plan", "pad",
                                       "compile", "run")}
    run = _run_span(res[0])
    assert run.args["program"].startswith("jit_fused_")
    if profile:
        assert stages <= names
        scopes = run.args["scopes"]
        assert scopes and all(scopes.values())
    else:
        assert not {n for n in names if n.startswith("service.")}
        assert "scopes" not in run.args


def test_queue_wait_recorded_where_the_batcher_claims_it(tpch, tmp_path):
    db, schema = tpch
    svc = QueryService(db, schema, profile_annotations=True)
    svc.submit(FIG1)                     # compile outside the capture
    with jax.profiler.trace(str(tmp_path)):
        svc.submit_async(FIG1).result(timeout=60)
    svc.close()
    assert {"service.queue_wait", "service.batch_form",
            "service.run"} <= _host_events(tmp_path)


def test_single_programs_are_named_by_fingerprint(tpch):
    db, schema = tpch
    res = QueryService(db, schema).submit(FIG1)
    assert _run_span(res).args["program"] \
        == f"jit_q_{res.stats.fingerprint[:12]}"


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------
def test_pad_rows_count_bucket_padding_of_the_scanned_relations(tpch):
    db, schema = tpch
    svc = QueryService(db, schema)
    svc.submit(FIG1)
    svc.submit(FIG1)
    held = sum(db[r].capacity for r in FIG1_RELS)
    added = sum(bucket_capacity(db[r].capacity) for r in FIG1_RELS) - held
    assert added > 0
    m = svc.metrics()
    assert m["pad_rows_held"] == 2 * held
    assert m["pad_rows_added"] == 2 * added


@pytest.mark.parametrize("strip, path", [(False, "joins_dense"),
                                         (True, "joins_sorted")])
def test_join_paths_counted_once_per_executed_program(tpch, strip, path):
    db, schema = tpch
    svc = QueryService(db, schema.without_domains() if strip else schema)
    svc.submit_many([FIG1, COUNT])           # one fused program, compiled
    first = svc.metrics()
    svc.submit_many([FIG1, COUNT])           # the same program, cached
    m = svc.metrics()
    other = ({"joins_dense", "joins_sorted"} - {path}).pop()
    assert first[path] > 0 and first[other] == 0
    # a cached program adds its tally again, once per execution
    assert m[path] == 2 * first[path] and m[other] == 0


CAUSES = ("new_program", "new_bucket", "invalidated", "evicted")


def _causes(svc):
    m = svc.metrics()
    assert sum(m[f"compiles_{c}"] for c in CAUSES) == m["compiles"]
    return {c: m[f"compiles_{c}"] for c in CAUSES}


def test_compiles_by_cause_sum_to_compiles(tpch):
    db, schema = tpch
    svc = QueryService(db, schema)
    svc.submit(FIG1)
    assert _causes(svc) == dict(new_program=1, new_bucket=0,
                                invalidated=0, evicted=0)
    region = db["region"]
    svc.update_table("region", region.pad_to(
        2 * bucket_capacity(region.capacity)))   # crosses its bucket
    svc.submit(FIG1)
    assert _causes(svc) == dict(new_program=1, new_bucket=1,
                                invalidated=0, evicted=0)
    svc.update_table("region", region)           # and back
    svc.submit(FIG1)
    assert _causes(svc) == dict(new_program=1, new_bucket=1,
                                invalidated=1, evicted=0)


def test_a_compile_after_an_eviction_counts_as_evicted(tpch):
    db, schema = tpch
    svc = QueryService(db, schema, exec_capacity=1)
    svc.submit(FIG1)
    svc.submit(SUPPLIERS)
    svc.submit(FIG1)
    assert _causes(svc) == dict(new_program=2, new_bucket=0,
                                invalidated=0, evicted=1)


# ---------------------------------------------------------------------------
# the benchmark's readers of the above
# ---------------------------------------------------------------------------
def _read(name, run):
    return common.metric_module(name).read(run)


def _span(args):
    return types.SimpleNamespace(name="run", duration_s=1.0, args=args)


def _result(run_span):
    tree = types.SimpleNamespace(children=[run_span])
    return types.SimpleNamespace(ok=True, stats=types.SimpleNamespace(
        trace=tree))


def _run(spans, ops=(), before=None, after=None):
    reqs = [Request(("a",), 0.0, 0.0, 1.0, [_result(s)]) for s in spans]
    trace = Trace(list(ops), [], (0.0, 1e10), 1)
    return Run({"name": "x"}, {}, {}, Window(reqs, 1.0), 1.0,
               before or {}, after or {}, trace)


SCOPES_A = {"while.134": "freq_join/freq_join/search",
            "fusion.124": "freq_join/freq_join/search",
            "fusion.9": "freq_join/pregroup/group_by_sum",
            "fusion.16": "semi_join/semi_join",
            "sort.1": "final_agg/weighted_percentile"}


def _op(name, start_ms, end_ms, module="jit_fused_abc(77)"):
    return Op(0, f"%{name} = s32[8]{{0}} {name.split('.')[0]}(...)", module,
              start_ms * 1e6, end_ms * 1e6)


def test_freq_join_device_ms_takes_the_union_per_execution():
    ops = [
        # one refresh: a while loop with its body op inside it, then a
        # pregroup fusion; a semi-join op and an unscoped op do not count
        _op("while.134", 0, 100), _op("fusion.124", 10, 90),
        _op("fusion.9", 100, 110), _op("fusion.16", 110, 150),
        _op("copy.3", 150, 160),
        # the second refresh of the same program
        _op("while.134", 200, 280), _op("fusion.124", 205, 275),
        # another program's op under a name the table knows
        _op("while.134", 300, 400, module="jit_q_other(5)"),
    ]
    spans = [_span({"program": "jit_fused_abc", "scopes": SCOPES_A})
             for _ in range(2)]
    got = _read("freq_join_device_ms", _run(spans, ops))
    assert got == pytest.approx((100 + 10 + 80) / 2)


def test_freq_join_device_ms_reads_nothing_without_scope_tables():
    ops = [_op("while.134", 0, 100)]
    assert _read("freq_join_device_ms", _run([_span({})], ops)) is None
    assert _read("freq_join_device_ms", _run(
        [_span({"program": "jit_fused_abc"})], ops)) is None
    run = _run([_span({"program": "jit_fused_abc", "scopes": SCOPES_A})])
    run.trace = None
    assert _read("freq_join_device_ms", run) is None


def test_padded_row_pct_over_the_window():
    before = {"pad_rows_held": 100, "pad_rows_added": 10}
    after = {"pad_rows_held": 100 + 3 * 10_100_030,
             "pad_rows_added": 10 + 3 * 516_842}
    got = _read("padded_row_pct", _run([], before=before, after=after))
    assert got == pytest.approx(100 * 516_842 / 10_616_872)
    assert round(got, 3) == 4.868
    # a service without the counters
    assert _read("padded_row_pct", _run([], before={"compiles": 1},
                                        after={"compiles": 1})) is None
