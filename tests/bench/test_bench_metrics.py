"""The metric arithmetic: exact percentiles, the window-closing rule of
``qps``, the span and counter readers, and which metrics a cell
reports."""

import statistics
import types

import pytest

from bench import common, quantiles
from bench.drive import Request, Window
from bench.record import Run


def test_percentile_is_a_sample_by_nearest_rank():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert quantiles.percentile(xs, 50) == 3.0
    assert quantiles.percentile(xs, 95) == 5.0
    assert quantiles.percentile(xs, 20) == 1.0
    assert quantiles.percentile(list(range(1, 201)), 95) == 190
    with pytest.raises(ValueError):
        quantiles.percentile([], 50)


def test_spread_uses_the_statistics_quartiles():
    xs = [10.0, 10.2, 9.9, 10.1, 10.4, 9.7]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert quantiles.spread(xs) == pytest.approx((q3 - q1)
                                                 / statistics.median(xs))


class _Span:
    def __init__(self, name, dur, children=()):
        self.name, self.duration_s, self.children = name, dur, list(children)

    def child_duration(self, name):
        return sum(c.duration_s for c in self.children if c.name == name)


def _result(run_span, queue_s=0.0, prep=(0.001, 0.0005, 0.002, 0.0015)):
    stages = [_Span(n, d) for n, d in zip(("parse", "fingerprint", "plan",
                                           "pad"), prep)]
    tree = _Span("request", 1.0, stages + [run_span])
    stats = types.SimpleNamespace(trace=tree, queue_s=queue_s)
    return types.SimpleNamespace(ok=True, stats=stats, values={})


def _run(requests, close, before=None, after=None, spec=None, trace=None):
    before = before or {"requests": 0, "fused_queries": 0, "compiles": 3}
    after = after or {"requests": 10, "fused_queries": 4, "compiles": 3}
    return Run({"name": "x"}, spec or {}, {}, Window(requests, close),
               12.5, before, after, trace)


def read(name, run):
    return common.metric_module(name).read(run)


def test_qps_counts_every_answer_over_the_closed_window():
    # three requests sent before a 10 s window; the last answered at 10.8
    s = _Span("run", 0.1)
    reqs = [Request(("a", "b"), due=t, sent=t, done=t + 0.8,
                    results=[_result(s), _result(s)]) for t in (0, 4, 8)]
    run = _run(reqs, close=8.8)
    assert read("qps", run) == pytest.approx(6 / 8.8)
    assert read("latency_p50_ms", run) == pytest.approx(800.0)
    assert read("setup_s", run) == 12.5


def test_open_loop_latency_runs_from_the_due_time():
    s = _Span("run", 0.1)
    reqs = [Request(("a",), due=float(i), sent=i + 0.5, done=i + 0.1 * i,
                    results=[_result(s)]) for i in range(1, 21)]
    run = _run(reqs, close=22.0)
    lat = sorted(0.1 * i for i in range(1, 21))
    assert read("latency_p50_ms", run) == pytest.approx(lat[9] * 1e3)


def test_span_and_counter_readers():
    shared, solo = _Span("run", 0.3), _Span("run", 0.1)
    reqs = [Request(("a", "b"), 0.0, 0.0, 1.0,
                    [_result(shared, 0.010), _result(shared, 0.030)]),
            Request(("c",), 1.0, 1.0, 2.0, [_result(solo, 0.020)])]
    run = _run(reqs, close=2.0)
    assert read("run_ms", run) == pytest.approx(200.0)     # two programs
    assert read("host_prep_ms", run) == pytest.approx(5.0)
    assert read("window_compiles", run) == 0
    assert [sorted(q) for _, q in run.programs()] == [["a", "b"], ["c"]]


def test_trace_readers_find_nothing_without_a_trace():
    run = _run([], close=1.0)
    assert read("device_idle_pct", run) is None


def test_cells_report_their_own_metrics():
    suite = common.benchmark()
    for w in suite["workloads"]:
        e2e = {m["name"] for m in common.cell_metrics(suite, w["name"], False)}
        layer = {m["name"] for m in common.cell_metrics(suite, w["name"],
                                                        True)}
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        for m in suite["per_layer"]:
            if m["name"] in layer:
                assert m["moves"] in e2e
    for m in suite["end_to_end"] + suite["per_layer"]:
        common.metric_module(m["name"])         # every metric has a reader
