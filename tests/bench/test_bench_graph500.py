"""The ``graph500_s22`` configuration: its generator follows the Graph500
specification's section 3, its reference equals brute force, the service
equals its reference at a small SCALE with 64-bit counts, and its control
fails where every count already exceeds 2^31; whole runs of its cell and
of the ``solo`` mix over ``tpch_sf10`` on the CPU are correct."""

import copy

import jax
import numpy as np
import pytest

from bench import check, common, control, system, traffic
from bench import run as bench_run

NAME = "graph500_s22"
CONFIG_SPEC = common.config_spec     # before a test patches it


def graph_spec(scale: int) -> dict:
    """The configuration at SCALE ``scale``: the edges and the key domains
    follow it, everything else as the configuration states it."""
    spec = copy.deepcopy(CONFIG_SPEC(NAME))
    spec["scale"] = scale
    spec["rows"] = {"edge": spec["edgefactor"] << scale}
    for col in spec["schema"]["edge"].values():
        col["domain"] = 1 << scale
    return spec


def test_the_configuration_is_the_specifications_scale_22():
    spec = common.config_spec(NAME)
    assert spec["scale"] == 22 and spec["edgefactor"] == 16
    assert spec["initiator"] == [0.57, 0.19, 0.19, 0.05]
    assert spec["rows"]["edge"] == 2 ** 26
    assert all(c["domain"] == 2 ** 22
               for c in spec["schema"]["edge"].values())


def test_the_generator_follows_section_3():
    scale, ef = 12, 16
    src, dst = (np.asarray(a) for a in common.config_module(NAME).kronecker(
        jax.random.key(3), scale, ef, (0.57, 0.19, 0.19, 0.05), False))
    assert src.shape == dst.shape == (ef << scale,)
    assert src.dtype == dst.dtype == np.int32
    assert 0 <= min(src.min(), dst.min())
    assert max(src.max(), dst.max()) < 1 << scale
    # every level draws a quadrant of the initiator: the top bit's and
    # the bottom bit's shares are A, B, C, D
    for bit in (scale - 1, 0):
        quad = 2 * ((src >> bit) & 1) + ((dst >> bit) & 1)
        share = np.bincount(quad, minlength=4) / quad.size
        np.testing.assert_allclose(share, [0.57, 0.19, 0.19, 0.05],
                                   atol=0.01)


def test_the_edges_depend_on_the_seed_alone():
    spec = graph_spec(10)
    gen = common.config_module(NAME)
    a, b, c = (gen.generate(spec, s)["edge"] for s in
               (5, 5, 2 ** 33 + 6))
    for col in ("src", "dst"):
        np.testing.assert_array_equal(a[col], b[col])
        assert a[col].shape == (spec["rows"]["edge"],)
        assert not np.array_equal(a[col], c[col])
    # the labels are permuted: a vertex's id says nothing of its degree
    deg = np.bincount(a["src"], minlength=1 << 10)
    assert deg.argmax() != 0


def _brute(edges) -> dict[str, int]:
    """Each count by the matrix of edge pairs: ``adj[i, j]`` when edge i
    ends where edge j starts."""
    src, dst = edges["src"], edges["dst"]
    adj = (dst[:, None] == src[None, :]).astype(np.float64)
    return {"walk2": int(adj.sum()),
            "walk3": int((adj @ adj).sum()),
            "star2": int((src[:, None] == src[None, :]).sum())}


@pytest.mark.parametrize("scale", [6, 8])
def test_the_reference_equals_brute_force(scale):
    spec = graph_spec(scale)
    gen = common.config_module(NAME)
    data = gen.generate(spec, 2 ** 31 + scale)
    want = gen.reference(spec, data)
    got = {q: int(v["count(*)"]) for q, v in want.items()}
    assert got == _brute(data["edge"])
    assert all(v["count(*)"].dtype == np.int64 for v in want.values())


def test_the_reference_refuses_counts_it_cannot_compare_exactly():
    """2^18 self-loops on one vertex: 2^54 three-edge walks."""
    gen = common.config_module(NAME)
    loops = np.zeros(2 ** 18, np.int32)
    with pytest.raises(ValueError, match="walk3 .* not below 2\\^52"):
        gen.reference(graph_spec(18), {"edge": {"src": loops,
                                                "dst": loops}})


def test_the_service_equals_the_reference_in_64_bits():
    spec = graph_spec(12)
    gen = common.config_module(NAME)
    data = gen.generate(spec, 2 ** 31 + 7)
    svc = system.service(spec, system.load(spec, data), system.schema(spec))
    results = svc.submit_many(list(spec["queries"].values()))
    assert all(r.ok for r in results)
    # walk2's join is walk3's first: the two fuse, and that join runs once
    assert [r.stats.fused for r in results] == [True, True, False]
    answers = [(q, {k: np.asarray(v) for k, v in r.values.items()})
               for q, r in zip(spec["queries"], results)]
    # walk3's bound passes 2^31 here, so its program counts in 64 bits
    assert answers[1][1]["count(*)"].dtype == np.int64
    numbers, failed = check.compare(spec["checks"], gen.reference(spec, data),
                                    answers)
    assert check.passed(numbers) and failed == 0, numbers
    m = svc.metrics()
    assert m["programs_count64"] >= 1
    assert m["programs_count64"] + m["programs_count32"] == 2
    assert m["joins_dense"] == 3 and not m["joins_sorted"]
    assert m["join_slots64"] + m["join_slots32"] == 3 << 12


def test_the_control_fails_at_scale_18(monkeypatch):
    """At SCALE 18 every count already exceeds 2^31, so int32 counts wrap
    on every seed."""
    monkeypatch.setattr(common, "config_spec", lambda name: graph_spec(18))
    for seed in (3, 2 ** 31 + 11):
        numbers, passed = control.readings(NAME, seed)
        assert not passed, (seed, numbers)
        assert all(numbers[f"{q}_abs_err"]["value"] > 0
                   for q in ("walk2", "walk3", "star2"))


def test_the_solo_mix_does_the_same_work_on_every_seed():
    mix = common.traffic_spec("solo")
    names = list(common.config_spec("tpch_sf10")["queries"])
    scheds = [traffic.schedule(mix, names, s, 15) for s in
              (1, 2 ** 31 + 5, 2 ** 33)]
    for s in scheds:
        assert s.call == "submit" and s.loop == "closed"
        assert s.client_requests == scheds[0].client_requests
        assert all(r == tuple(names) for r in s.client_requests[0])
        assert s.warm_batches == [tuple(names)]


CELLS = {"graph500_s22.dashboard": lambda name: graph_spec(10),
         "tpch_sf10.solo": None}
# a cell of this test alone: the solo mix is ready for a cell, which six
# runs on the chip found too noisy for the 1 % bound (PERF.md)
SOLO_CELL = {"name": "tpch_sf10.solo", "config": "tpch_sf10",
             "traffic": "solo", "chips": 1, "why": "test"}


@pytest.mark.parametrize("cell", list(CELLS))
def test_a_whole_run_of_the_cell_is_correct(cell, monkeypatch, capsys,
                                            small_spec):
    import json

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    monkeypatch.setattr(bench_run, "require_chip", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": chips})
    monkeypatch.setattr(bench_run, "_cache_setup", lambda: None)
    monkeypatch.setattr(bench_run, "_memory_peak", lambda n: 1)
    monkeypatch.setattr(common, "config_spec", CELLS[cell] or small_spec)
    suite = common.benchmark()
    if cell == SOLO_CELL["name"]:
        suite["workloads"].append(SOLO_CELL)
    monkeypatch.setattr(common, "benchmark", lambda: suite)
    rc = bench_run.main(["--workload", cell, "--seed", str(2 ** 31 + 21),
                         "--seconds", "1", "--trace", "0"])
    out, _ = capsys.readouterr()
    assert rc == 0
    line = json.loads([ln for ln in out.splitlines()
                       if ln.startswith("{")][-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["attempted"] > 0 and line["attempted"] % 3 == 0
    assert set(line["metrics"]) == {"qps", "latency_p50_ms", "setup_s"}
