"""The trace reduction, on a trace recorded on a TPU v5e:
``tpch_sf10.dashboard`` traced for 12 s, three refreshes of one fused
program (``data/tpch_dashboard.xplane.pb.gz``)."""

from pathlib import Path

import pytest

from bench import devtrace
from bench.drive import Window
from bench.record import Run

TRACE = Path(__file__).resolve().parent / "data" \
    / "tpch_dashboard.xplane.pb.gz"


@pytest.fixture(scope="module")
def trace():
    return devtrace.load(str(TRACE))


def test_window_and_busy_time(trace):
    assert trace.window_s == pytest.approx(17.322984805)
    assert devtrace.busy_s(trace) == pytest.approx(17.294878343)
    assert all(trace.window[0] <= o.start_ns <= o.end_ns <= trace.window[1]
               for o in trace.ops)


def test_device_idle_pct(trace):
    from bench import common

    run = Run({}, {}, {}, Window([], 17.3), 0.0, {}, {}, trace)
    idle = common.metric_module("device_idle_pct").read(run)
    assert idle == pytest.approx(100 * (1 - 17.294878343 / 17.322984805))
    assert 0 < idle < 1


def test_ops_belong_to_their_program(trace):
    modules = {o.module for o in trace.ops}
    assert modules == {"jit_run(6533928681366228445)"}
    # six loops over the 2^23 rows of partsupp in each of the three
    # refreshes (the searchsorted of the freq-joins)
    loops = [o for o in trace.ops if " while(" in o.name
             and "s32[8388608]" in o.name]
    assert len(loops) == 3 * 6


def test_breakdown(trace):
    b = devtrace.breakdown(trace)
    assert len(b["device_ops"]) == devtrace.TOP
    name, seconds = b["device_ops"][0]
    assert name.startswith("jit_run(6533928681366228445): while.")
    assert " while (s32[], s32[8388608]" in name
    assert seconds == pytest.approx(3.9675512360000003)
    assert [s for _, s in b["device_ops"]] == sorted(
        (s for _, s in b["device_ops"]), reverse=True)
    gaps = dict(b["idle_gaps"])
    # the host was inside the client's request while the device idled
    assert max(gaps, key=gaps.get) == "bench.request"
    assert sum(gaps.values()) == pytest.approx(
        trace.window_s - devtrace.busy_s(trace), rel=1e-6)


def test_short_name():
    hlo = ("%fusion.127 = s32[8388608]{0:T(1024)S(1)} fusion(s32[2097152]"
           "{0:T(1024)S(1)} %copy-done.3), kind=kCustom")
    assert devtrace.short_name(hlo) == "fusion.127 fusion s32[8388608]"
    assert devtrace.short_name("tpu-op") == "tpu-op"
