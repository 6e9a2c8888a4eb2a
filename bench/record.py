"""What one run hands to the metric readers (``bench/metrics/<name>.py``).

Each reader is a module with ``read(run: Run) -> float | None``.  It
returns None where it finds nothing to read, and the run then leaves the
metric out of its line.
"""

from __future__ import annotations

import dataclasses
from typing import Any

from bench.devtrace import Trace
from bench.drive import Window


@dataclasses.dataclass
class Run:
    cell: dict                      # the workload entry of BENCHMARK.json
    spec: dict                      # the configuration as it is run
    mix: dict                       # the traffic mix
    window: Window
    setup_s: float
    counters_before: dict[str, Any]  # QueryService.metrics() at the open
    counters_after: dict[str, Any]   # ... and once the window has closed
    trace: Trace | None = None       # with --trace 1

    def delta(self, counter: str) -> float:
        return self.counters_after[counter] - self.counters_before[counter]

    def results(self):
        """Every answered query's ``QueryResult`` of the window."""
        for r in self.window.requests:
            for res in r.results:
                if getattr(res, "ok", False):
                    yield res

    def programs(self) -> list[tuple[Any, list[str]]]:
        """The programs the window ran, from the requests' span trees: one
        ``run`` span per execution, shared by the requests it answered,
        with the names of their queries."""
        by_span: dict[int, tuple[Any, list[str]]] = {}
        for r in self.window.requests:
            for q, res in zip(r.queries, r.results):
                tree = getattr(getattr(res, "stats", None), "trace", None)
                if tree is None:
                    continue
                for span in tree.children:
                    if span.name == "run":
                        by_span.setdefault(id(span), (span, []))[1].append(q)
        return list(by_span.values())
