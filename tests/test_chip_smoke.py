"""``chip_smoke.py`` on the CPU.

The script only runs to its end on a TPU.  Here it must refuse the CPU
without printing a result, and its phases — driven directly at a tiny
scale — must serve answers equal to its numpy reference, so the path the
chip run checks and the reference it checks against stay honest between
chip runs.
"""

import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.data.relational import make_tpch_db

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tpch():
    return make_tpch_db(scale=40, seed=3)


def test_refuses_a_platform_that_is_not_tpu(smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "'cpu'" in err
    assert '"ok"' not in out


@pytest.mark.parametrize("phase", ["single", "mesh"])
def test_phase_matches_reference(smoke, tpch, phase):
    db, schema = tpch
    want = smoke.reference(db)
    assert int(want["count"]["count(*)"]) > 0
    if phase == "single":
        smoke.run_single(db, schema, want)
    else:
        smoke.run_mesh(db, schema, want, jax.devices()[:1])


def test_answers_are_checked_exactly(smoke, tpch):
    db, _ = tpch
    want = smoke.reference(db)
    smoke.check_answer("ok", dict(want["minmax"]), want["minmax"])
    n = want["count"]["count(*)"]
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_answer("count", {"count(*)": n + 1}, want["count"])
    lo = want["minmax"]["min(s.s_acctbal)"]
    off = np.nextafter(lo, np.float32(np.inf))    # one ulp away
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_answer("min", {**want["minmax"], "min(s.s_acctbal)": off},
                           want["minmax"])
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_answer("names", {"count": n}, want["count"])
