"""The ``count64_program_pct`` and ``freq_join_roofline`` readers: the
share of the window's programs with 64-bit counts, and the freq-join's
least bytes at the device's bandwidth over its device time, from the
service's ``programs_count*`` and ``join_*`` counters."""

import pytest

from bench import common
from bench.drive import Window
from bench.record import Run

ZERO = {f"{c}{w}": 0 for w in (32, 64)
        for c in ("programs_count", "join_rows", "join_parent_rows",
                  "join_slots")}


def _run(before, after):
    return Run({"name": "x"}, {}, {}, Window([], 1.0), 1.0,
               dict(ZERO, **before), dict(ZERO, **after))


def _read(name, run):
    return common.metric_module(name).read(run)


def test_count64_share_of_the_window_programs():
    before = {"programs_count32": 4, "programs_count64": 1}
    assert _read("count64_program_pct", _run(before, {
        "programs_count32": 4, "programs_count64": 7})) == 100.0
    assert _read("count64_program_pct", _run(before, {
        "programs_count32": 10, "programs_count64": 3})) == pytest.approx(25.0)
    assert _read("count64_program_pct", _run(before, {
        "programs_count32": 9, "programs_count64": 1})) == 0.0


def test_count64_reads_nothing_without_counters_or_programs():
    run = Run({"name": "x"}, {}, {}, Window([], 1.0), 1.0, {"compiles": 0},
              {"compiles": 0})
    assert _read("count64_program_pct", run) is None
    assert _read("count64_program_pct", _run({}, {})) is None


def test_least_bytes_of_a_call():
    least = common.metric_module("freq_join_roofline").least_bytes
    # keys 4·(c + p), frequencies w·(c + 2p), accumulator 2·w·slots
    assert least(child=10, parent=20, slots=0, w=4) == 120 + 200
    assert least(child=10, parent=20, slots=5, w=8) == 120 + 400 + 80
    # a dense 2^26-row self-join into 2^22 slots with 64-bit counts
    assert least(2 ** 26, 2 ** 26, 2 ** 22, 8) == 33 * 2 ** 26


def test_roofline_share_per_executed_program(monkeypatch):
    mod = common.metric_module("freq_join_roofline")
    monkeypatch.setattr(mod, "peak_bytes_per_s", lambda: 1e9)
    device_ms = {"value": 2.0}
    monkeypatch.setattr(common, "metric_module", lambda name: type(
        "M", (), {"read": staticmethod(lambda run: device_ms["value"])}))
    # two programs; each ran one 64-bit call (child 100, parent 200,
    # 50 slots) and one 32-bit call (child 10, parent 20, no slots)
    run = _run({}, {"programs_count64": 1, "programs_count32": 1,
                    "join_rows64": 600, "join_parent_rows64": 400,
                    "join_slots64": 100, "join_rows32": 60,
                    "join_parent_rows32": 40})
    per_program = (mod.least_bytes(200, 400, 100, 8)
                   + mod.least_bytes(20, 40, 0, 4)) / 2
    want = 100 * (per_program / 1e9 * 1e3) / 2.0
    assert mod.read(run) == pytest.approx(want)
    device_ms["value"] = None
    assert mod.read(run) is None


def test_roofline_reads_nothing_without_counters_or_programs():
    mod = common.metric_module("freq_join_roofline")
    run = Run({"name": "x"}, {}, {}, Window([], 1.0), 1.0, {"compiles": 0},
              {"compiles": 0})
    assert mod.read(run) is None
    assert mod.read(_run({}, {})) is None


def test_an_unknown_device_has_no_bandwidth(monkeypatch):
    import jax

    mod = common.metric_module("freq_join_roofline")
    assert mod.PEAK_BYTES_PER_S["TPU v5 lite"] == 819e9
    monkeypatch.setattr(jax, "devices", lambda: [type(
        "D", (), {"device_kind": "no such chip"})()])
    with pytest.raises(KeyError, match="no such chip"):
        mod.peak_bytes_per_s()
