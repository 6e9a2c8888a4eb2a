"""The one traffic generator: a schedule depends on the mix, the query
names, the window and the seed alone, and every seed gets the same set of
work in another order."""

import collections

import pytest

from bench import common, traffic

NAMES = ["minmax", "median", "count"]


def open_mix(rate=5.0):
    """Poisson arrivals of single queries through ``submit_async``."""
    return {"loop": "open", "call": "submit_async", "request": "one_query",
            "pick": "uniform", "arrivals": "poisson", "rate_per_s": rate}


def test_open_schedule_depends_on_the_seed_alone():
    a = traffic.schedule(open_mix(), NAMES, 2 ** 31 + 5, 51)
    b = traffic.schedule(open_mix(), NAMES, 2 ** 31 + 5, 51)
    c = traffic.schedule(open_mix(), NAMES, 2 ** 31 + 6, 51)
    assert a == b
    assert a.arrivals != c.arrivals


def test_every_seed_gets_the_same_work():
    scheds = [traffic.schedule(open_mix(5.0), NAMES, s, 51)
              for s in (1, 2, 2 ** 33)]
    counts = [collections.Counter(q for _, q in s.arrivals) for s in scheds]
    assert all(c == counts[0] for c in counts)
    assert sum(counts[0].values()) == 255
    assert max(counts[0].values()) - min(counts[0].values()) <= 1
    for s in scheds:
        due = [t for t, _ in s.arrivals]
        assert due == sorted(due) and due[0] == 0.0 and due[-1] < 51
    # the gaps are one set, shuffled: two seeds' gaps differ only in the
    # one gap that falls after the last arrival
    gaps = [collections.Counter(round(b - a, 9) for (a, _), (b, _)
                                in zip(s.arrivals, s.arrivals[1:]))
            for s in scheds]
    assert sum((gaps[0] & gaps[1]).values()) >= 255 - 2
    assert scheds[0].arrivals != scheds[1].arrivals


def test_open_loop_warms_every_subset():
    s = traffic.schedule(open_mix(), NAMES, 1, 10)
    assert sorted(s.warm_batches) == sorted(
        [("minmax",), ("median",), ("count",), ("minmax", "median"),
         ("minmax", "count"), ("median", "count"),
         ("minmax", "median", "count")])


def test_one_query_at_a_time_warms_each_alone():
    mix = dict(open_mix(), loop="closed", call="submit", clients=2)
    s = traffic.schedule(mix, NAMES, 2 ** 31 + 4, 15)
    assert sorted(s.warm_batches) == [("count",), ("median",), ("minmax",)]
    assert len(s.client_requests) == 2
    for reqs in s.client_requests:
        shares = collections.Counter(reqs)
        assert max(shares.values()) - min(shares.values()) <= 1


def test_closed_dashboard_sends_the_whole_set():
    mix = common.traffic_spec("dashboard")
    s = traffic.schedule(mix, ["minmax", "median", "count"], 9, 51)
    assert s.loop == "closed" and s.call == "submit_many"
    assert len(s.client_requests) == 1
    assert set(s.client_requests[0]) == {("minmax", "median", "count")}
    assert s.warm_batches == [("minmax", "median", "count")]


@pytest.mark.parametrize("bad", [{"loop": "sideways"},
                                 {"call": "submit_later"},
                                 {"arrivals": "bursty"}])
def test_unknown_parameters_are_refused(bad):
    with pytest.raises(ValueError):
        traffic.schedule(dict(open_mix(), **bad), NAMES, 1, 10)
