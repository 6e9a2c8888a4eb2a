"""``benchmarks/report.py --compare``: the perf diff between two
benchmark records."""

import importlib.util
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_module(name, rel_path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, rel_path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench_doc(rows):
    return {"bench_schema_version": 1, "benchmark": "t",
            "created_unix": 0.0, "meta": {}, "metrics": {},
            "histograms": {},
            "rows": [{"section": "s", "name": n, "us_per_call": us,
                      "derived": ""} for n, us in rows]}


def test_report_compare_flags_regressions(tmp_path):
    report = _load_module("bench_report", "benchmarks/report.py")
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps(_bench_doc(
        [("a", 100.0), ("b", 50.0), ("gone", 1.0), ("untimed", None)])))
    new.write_text(json.dumps(_bench_doc(
        [("a", 100.0), ("b", 200.0), ("fresh", 1.0), ("untimed", None)])))
    assert report.compare(str(old), str(new)) == 3     # b regressed 4x
    assert report.compare(str(old), str(old)) == 0
    assert report.compare(str(old), str(new), threshold=5.0) == 0
    assert report.compare(str(tmp_path / "absent.json"), str(new)) == 2
    (tmp_path / "junk.json").write_text("{not json")
    assert report.compare(str(tmp_path / "junk.json"), str(new)) == 2


def test_report_compare_names_added_and_removed_rows(tmp_path, capsys):
    """Coverage drift is reported explicitly: dropped scenarios under a
    'removed rows' header, new ones under 'added rows'."""
    report = _load_module("bench_report", "benchmarks/report.py")
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps(_bench_doc([("a", 100.0), ("gone", 1.0)])))
    new.write_text(json.dumps(_bench_doc([("a", 100.0), ("fresh", 2.0)])))
    report.compare(str(old), str(new))
    out = capsys.readouterr().out
    assert "removed rows (1" in out and "- s/gone" in out
    assert "added rows (1" in out and "+ s/fresh" in out
    report.compare(str(old), str(old))
    out = capsys.readouterr().out
    assert "row coverage unchanged" in out
