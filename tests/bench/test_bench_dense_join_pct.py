"""The ``dense_join_pct`` reader: the share of the window's join kernel
calls on the dense path, from the service's ``joins_*`` counters."""

import pytest

from bench import common
from bench.drive import Window
from bench.record import Run


def _read(before, after):
    run = Run({"name": "x"}, {}, {}, Window([], 1.0), 1.0, before, after)
    return common.metric_module("dense_join_pct").read(run)


def test_share_of_the_window_joins_on_the_dense_path():
    before = {"joins_dense": 5, "joins_sorted": 2, "compiles": 1}
    # three refreshes of a program with five dense joins
    assert _read(before, {"joins_dense": 20, "joins_sorted": 2,
                          "compiles": 1}) == 100.0
    assert _read(before, {"joins_dense": 8, "joins_sorted": 3,
                          "compiles": 1}) == pytest.approx(75.0)
    assert _read(before, {"joins_dense": 5, "joins_sorted": 6,
                          "compiles": 1}) == 0.0


def test_reads_nothing_without_the_counters():
    assert _read({"compiles": 1}, {"compiles": 1}) is None


def test_reads_nothing_when_the_window_ran_no_join():
    counters = {"joins_dense": 5, "joins_sorted": 2}
    assert _read(counters, dict(counters)) is None
