"""padded_row_pct: SQL front end and planner (``pad``) — the share of the
rows swept by the window's executed programs that are bucket padding, in
percent: 100 · Δ``pad_rows_added`` / (Δ``pad_rows_held`` +
Δ``pad_rows_added``).  A service without the counters reads nothing."""


def read(run):
    if "pad_rows_added" not in run.counters_after:
        return None
    added = run.delta("pad_rows_added")
    swept = run.delta("pad_rows_held") + added
    return 100.0 * added / swept if swept else None
