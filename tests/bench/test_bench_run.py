"""``bench/run.py`` on the CPU.

Without a TPU it prints no result and exits non-zero, and so it does in a
directory that holds only ``BENCHMARK.json`` and the benchmark's files.
With its look for a chip skipped, a whole run at a small size is correct,
and the same run with the timed path broken underneath is not: once with
an answer altered where the service produces it, once with half of the
rows of the largest table a query reads left out of what the service
serves.  Besides the committed cell, an open-loop mix over the same
configuration drives the generator's other loop.
"""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import common
from bench import run as bench_run
from bench import system

ROOT = Path(__file__).resolve().parents[2]

CELLS = ["tpch_sf10.dashboard", "tpch_sf10.test_open"]
# a cell of this test alone: one query at a time through submit_async
OPEN_CELL = {"name": "tpch_sf10.test_open", "config": "tpch_sf10",
             "traffic": "test_open", "chips": 1, "why": "test"}
OPEN_MIX = {"loop": "open", "call": "submit_async", "request": "one_query",
            "pick": "uniform", "arrivals": "poisson", "rate_per_s": 12.0}


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTEST_CURRENT_TEST", None)
    return env


def _last_json(stdout: str):
    lines = [ln for ln in stdout.splitlines() if ln.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def test_exits_nonzero_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(2 ** 31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_cpu_env(), capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert _last_json(p.stdout) is None
    assert "not a TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmarks_files(tmp_path):
    suite = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in suite["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = _cpu_env()
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, *suite["command"][1:], "--workload", CELLS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert _last_json(p.stdout) is None


def _alter_answers(monkeypatch):
    from repro.service import QueryService

    orig = QueryService.submit_many

    def altered(self, queries, **kw):
        out = orig(self, queries, **kw)
        for res in out:
            if res.ok:
                res.values = {k: v + 1 for k, v in res.values.items()}
        return out
    monkeypatch.setattr(QueryService, "submit_many", altered)


def _drop_half_of_the_largest_table(monkeypatch):
    orig = system.service

    def halved(spec, db, sch, **kw):
        sql = " ".join(spec["queries"].values())
        read = [r for r in db if re.search(rf"\b{r}\b", sql)]
        rel = max(read, key=lambda r: db[r].capacity)
        t = db[rel]
        db = dict(db, **{rel: t.with_freq(
            t.freq.at[t.capacity // 2:].set(jnp.zeros((), t.freq.dtype)))})
        return orig(spec, db, sch, **kw)
    monkeypatch.setattr(system, "service", halved)


FAULTS = {"none": None, "answer_altered": _alter_answers,
          "half_left_out": _drop_half_of_the_largest_table}


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_a_run_is_correct_and_a_broken_one_is_not(cell, fault, monkeypatch,
                                                   capsys, small_spec):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    monkeypatch.setattr(bench_run, "require_chip", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": chips})
    monkeypatch.setattr(bench_run, "_cache_setup", lambda: None)
    monkeypatch.setattr(bench_run, "_memory_peak", lambda n: 1)
    monkeypatch.setattr(common, "config_spec", small_spec)
    suite = common.benchmark()
    if cell == OPEN_CELL["name"]:
        suite["workloads"].append(OPEN_CELL)
        monkeypatch.setattr(common, "benchmark", lambda: suite)
        mixes = common.traffic_spec
        monkeypatch.setattr(common, "traffic_spec", lambda name: (
            OPEN_MIX if name == OPEN_CELL["traffic"] else mixes(name)))
    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch)
    rc = bench_run.main(["--workload", cell, "--seed", str(2 ** 31 + 21),
                         "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 0
    line = _last_json(out)
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) >= {"qps", "latency_p50_ms", "setup_s"}
    assert line["attempted"] > 0
    assert "check missing_answers" in err.splitlines()[-1]
    if fault == "none":
        assert line["correct"] is True and line["failed"] == 0, line
    else:
        assert line["correct"] is False, line
        assert line["failed"] > 0
