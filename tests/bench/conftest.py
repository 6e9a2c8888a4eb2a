"""The benchmark's CPU tests import it as the ``bench`` package from the
root of the checkout; small copies of its configurations keep them fast."""

import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

SMALL_ROWS = {
    "tpch_sf10": {"region": 5, "nation": 25, "supplier": 40, "part": 800,
                  "partsupp": 3200, "customer": 60, "orders": 600,
                  "lineitem": 2395},
}


@pytest.fixture
def small_spec():
    """``config_spec`` of the benchmark, with the row counts cut to a test's
    size; everything else as the configuration states it."""
    from bench import common

    full = common.config_spec     # before a test patches it with ``load``

    def load(name, *args, **kwargs):
        spec = copy.deepcopy(full(name, *args, **kwargs))
        spec["rows"] = dict(SMALL_ROWS[name])
        return spec
    return load
