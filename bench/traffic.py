"""The one general traffic generator: a mix file of parameters in, a
schedule out.  The schedule depends on the mix, the configuration's query
names, ``--seconds`` and ``--seed`` alone.

A mix file (``bench/traffic/<mix>.json``) holds:

* ``loop``: ``"closed"`` (each client sends its next request when the
  last one is answered) or ``"open"`` (requests are due on a schedule,
  whatever the service does);
* ``call``: the service entry a request goes through — ``submit_many``,
  ``submit`` or ``submit_async``;
* ``request``: ``"all_queries"`` (one request is the configuration's whole
  query set, as one batch) or ``"one_query"``;
* ``pick`` (``one_query``): ``"uniform"`` — every query an equal share of
  the requests, in an order drawn from the seed;
* ``clients`` (closed loop): how many clients loop at once;
* ``rate_per_s`` (open loop): the offered rate, fixed in the file;
* ``arrivals`` (open loop): ``"poisson"`` — exponential gaps at the rate.

So that a seed changes the order of the work and not its amount, every
seed gets the same set: an open loop has round(rate × seconds) arrivals
whose gaps are the exponential law's quantiles at (i + 1/2) / n, shuffled,
and the queries' shares are equal to within one request.
"""

from __future__ import annotations

import dataclasses
import itertools

import numpy as np

LOOPS = ("closed", "open")
CALLS = ("submit_many", "submit", "submit_async")
REQUESTS = ("all_queries", "one_query")


@dataclasses.dataclass
class Schedule:
    loop: str
    call: str
    # open loop: one (due second, query names) per arrival, in due order
    arrivals: list[tuple[float, tuple[str, ...]]]
    # closed loop: the requests each client sends in turn (cycled)
    client_requests: list[list[tuple[str, ...]]]
    # the batches whose programs set-up has to warm
    warm_batches: list[tuple[str, ...]]


def _picks(mix: dict, names: list[str], n: int,
           rng: np.random.Generator) -> list[tuple[str, ...]]:
    if mix["request"] == "all_queries":
        return [tuple(names)] * n
    if mix.get("pick") != "uniform":
        raise ValueError(f"unknown pick {mix.get('pick')!r}")
    idx = np.arange(n) % len(names)
    extra = rng.permutation(len(names))        # who gets the remainder
    idx = extra[idx]
    rng.shuffle(idx)
    return [(names[i],) for i in idx]


def warm_batches(mix: dict, names: list[str]) -> list[tuple[str, ...]]:
    """Every batch the mix can put before the service at once.  A request
    of the whole set is one batch.  Single queries sent through
    ``submit_async`` can meet in one batching window in any combination,
    so every non-empty subset is warmed; through ``submit`` only each
    query alone."""
    if mix["request"] == "all_queries":
        return [tuple(names)]
    if mix["call"] == "submit_async":
        return [c for k in range(1, len(names) + 1)
                for c in itertools.combinations(names, k)]
    return [(n,) for n in names]


def schedule(mix: dict, names: list[str], seed: int,
             seconds: float) -> Schedule:
    for key, allowed in (("loop", LOOPS), ("call", CALLS),
                         ("request", REQUESTS)):
        if mix.get(key) not in allowed:
            raise ValueError(f"mix {key}={mix.get(key)!r}: one of {allowed}")
    rng = np.random.default_rng([int(seed), 1])   # stream 0 is the data's
    warm = warm_batches(mix, names)
    if mix["loop"] == "closed":
        clients = int(mix.get("clients", 1))
        # enough distinct requests that a client never runs out of new
        # ones before cycling: a request cannot take less than 1 ms
        per_client = max(len(names), 1) * 64
        reqs = [_picks(mix, names, per_client, rng) for _ in range(clients)]
        return Schedule(mix["loop"], mix["call"], [], reqs, warm)
    if mix.get("arrivals") != "poisson":
        raise ValueError(f"unknown arrivals {mix.get('arrivals')!r}")
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng.shuffle(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    if due[-1] >= seconds:          # keep every arrival inside the window
        due *= (seconds * (1 - 1e-9)) / (due[-1] + gaps[-1])
    picks = _picks(mix, names, n, rng)
    return Schedule(mix["loop"], mix["call"],
                    [(float(t), q) for t, q in zip(due, picks)], [], warm)
