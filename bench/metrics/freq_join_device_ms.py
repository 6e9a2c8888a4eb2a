"""freq_join_device_ms: kernels — the device time of the freq-join
operator per executed program of the window, in ms.

Each execution's ``run`` span notes its program (``program``, the XLA
module name) and, in a traced run, its scope table (``scopes``:
``{HLO instruction: scope path}``).  An operation of the trace belongs to
the operator when its program is one of those and its instruction's path
begins with the ``freq_join`` operator scope.  Per program, the union of
those operations' intervals counts (a ``while`` op's interval holds its
body's ops, so a sum would count the loop twice), averaged over the
devices; the total is divided by the executions.  A program without the
notes (one that names no scopes) reads nothing.
"""

import collections

from bench import devtrace

OPERATOR = "freq_join"


def _in_operator(path) -> bool:
    return path is not None and (path == OPERATOR
                                 or path.startswith(OPERATOR + "/"))


def read(run):
    if run.trace is None:
        return None
    runs: collections.Counter = collections.Counter()
    tables: dict[str, dict] = {}
    for span, _ in run.programs():
        args = getattr(span, "args", None) or {}
        if "program" in args and "scopes" in args:
            runs[args["program"]] += 1
            tables.setdefault(args["program"], {}).update(args["scopes"])
    if not runs:
        return None
    by_device = collections.defaultdict(list)
    for o in run.trace.ops:
        table = tables.get(o.module.split("(")[0])
        if table is not None and _in_operator(
                table.get(devtrace.short_name(o.name).split(" ")[0])):
            by_device[o.device].append((o.start_ns, o.end_ns))
    busy_ns = sum(e - s for ivs in by_device.values()
                  for s, e in devtrace._union(ivs))
    return busy_ns / run.trace.devices / sum(runs.values()) / 1e6
