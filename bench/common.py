"""Find the parts of a cell by name.

Nothing here knows a particular configuration, mix or metric: each is a
file of its own under ``bench/``, looked up by the name that
``BENCHMARK.json`` gives it, so a later cell adds files and edits none.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
BENCHMARK_JSON = ROOT / "BENCHMARK.json"
CONFIGS_DIR = BENCH_DIR / "configs"
TRAFFIC_DIR = BENCH_DIR / "traffic"
METRICS_DIR = BENCH_DIR / "metrics"


class BenchError(Exception):
    """The benchmark cannot run as asked (an unknown name, a bad file)."""


def load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"missing file {path}") from None


def load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(path: Path = BENCHMARK_JSON) -> dict:
    return load_json(path)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json; known: "
                     f"{[w['name'] for w in bench['workloads']]}")


def config_spec(name: str, configs_dir: Path = CONFIGS_DIR) -> dict:
    """The configuration as it is run: ``configs/<name>.json``."""
    return load_json(configs_dir / f"{name}.json")


def config_module(name: str, configs_dir: Path = CONFIGS_DIR) -> ModuleType:
    """The generator and plain reference beside the configuration:
    ``configs/<name>.py``."""
    return load_module(configs_dir / f"{name}.py", f"bench_config_{name}")


def traffic_spec(name: str, traffic_dir: Path = TRAFFIC_DIR) -> dict:
    return load_json(traffic_dir / f"{name}.json")


def metric_module(name: str, metrics_dir: Path = METRICS_DIR) -> ModuleType:
    return load_module(metrics_dir / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_")
                       .replace("-", "_"))


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: with tracing off the
    end-to-end metrics, with tracing on the per-layer ones.  A metric with
    a ``workloads`` key is reported in those cells; a per-layer metric
    without one in every cell that reports the metric it moves."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]

