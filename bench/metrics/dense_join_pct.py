"""dense_join_pct: kernels — the share of the window's join kernel calls
that took the dense scatter-add path, in percent: 100 · Δ``joins_dense``
/ (Δ``joins_dense`` + Δ``joins_sorted``).  The service adds each executed
program's tally of its join calls by path once per execution.  A service
without the counters, or a window that ran no join, reads nothing."""


def read(run):
    if "joins_dense" not in run.counters_after:
        return None
    dense = run.delta("joins_dense")
    joins = dense + run.delta("joins_sorted")
    return 100.0 * dense / joins if joins else None
