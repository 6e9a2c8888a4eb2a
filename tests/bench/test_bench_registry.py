"""A configuration, a traffic mix and a metric added only as new files
are found by the names a new ``BENCHMARK.json`` entry gives them."""

import json
import shutil

from bench import common, traffic


def test_new_files_are_found_by_name(tmp_path):
    configs, mixes, metrics = (tmp_path / d for d in ("configs", "traffic",
                                                      "metrics"))
    for d in (configs, mixes, metrics):
        d.mkdir()
    spec = dict(common.config_spec("tpch_sf10"), name="tpch_copy")
    (configs / "tpch_copy.json").write_text(json.dumps(spec))
    shutil.copy(common.CONFIGS_DIR / "tpch_sf10.py", configs / "tpch_copy.py")
    (mixes / "burst.json").write_text(json.dumps({
        "loop": "open", "call": "submit_async", "request": "one_query",
        "pick": "uniform", "arrivals": "poisson", "rate_per_s": 3.0}))
    (metrics / "answers_per_request.py").write_text(
        "def read(run):\n    return 2.0\n")
    suite = {
        "workloads": [{"name": "tpch_copy.burst",
                       "config": "tpch_copy", "traffic": "burst",
                       "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "answers_per_request", "unit": "count",
                        "better": "higher", "bound": 0.01,
                        "source": "host_clock"}],
        "per_layer": [],
    }
    cell = common.workload(suite, "tpch_copy.burst")
    got = common.config_spec(cell["config"], configs)
    assert got["name"] == "tpch_copy"
    gen = common.config_module(cell["config"], configs)
    assert gen.lineitem_rows(got["rows"]["orders"]) == got["rows"]["lineitem"]
    mix = common.traffic_spec(cell["traffic"], mixes)
    sched = traffic.schedule(mix, list(got["queries"]), 1, 10)
    assert len(sched.arrivals) == 30
    names = [m["name"] for m in common.cell_metrics(suite, cell["name"],
                                                    False)]
    assert names == ["answers_per_request"]
    assert common.metric_module(names[0], metrics).read(None) == 2.0


def test_unknown_names_are_errors():
    import pytest

    suite = common.benchmark()
    with pytest.raises(common.BenchError):
        common.workload(suite, "no_such.cell")
    with pytest.raises(common.BenchError):
        common.config_spec("no_such_config")
    with pytest.raises(common.BenchError):
        common.metric_module("no_such_metric")
    with pytest.raises(common.BenchError):
        common.traffic_spec("no_such_mix")
