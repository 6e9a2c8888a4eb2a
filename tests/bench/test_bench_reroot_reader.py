"""The ``rerooted_plan_pct`` reader: the share of the window's executed
member plans whose root the statistics moved, from the service's
``plans_rerooted`` and ``plans_default_root`` counters."""

import pytest

from bench import common
from bench.drive import Window
from bench.record import Run

ZERO = {"plans_rerooted": 0, "plans_default_root": 0}


def _run(before, after):
    return Run({"name": "x"}, {}, {}, Window([], 1.0), 1.0,
               dict(ZERO, **before), dict(ZERO, **after))


def _read(run):
    return common.metric_module("rerooted_plan_pct").read(run)


@pytest.mark.parametrize("after, want", [
    ({"plans_rerooted": 4, "plans_default_root": 8}, 100 / 3),
    ({"plans_rerooted": 1, "plans_default_root": 14}, 0.0),
    ({"plans_rerooted": 6, "plans_default_root": 2}, 100.0),
])
def test_the_share_of_the_window_plans_the_statistics_rerooted(after, want):
    before = {"plans_rerooted": 1, "plans_default_root": 2}
    assert _read(_run(before, after)) == pytest.approx(want)


def test_no_counters_or_no_plans_read_nothing():
    run = Run({"name": "x"}, {}, {}, Window([], 1.0), 1.0, {"compiles": 0},
              {"compiles": 0})
    assert _read(run) is None
    assert _read(_run({}, {})) is None
