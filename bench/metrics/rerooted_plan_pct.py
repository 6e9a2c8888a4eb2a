"""rerooted_plan_pct: SQL front end and planner — the share of the
window's executed member plans whose root the statistics moved off the
guard the planner's classification chose, in percent: 100 ·
Δ``plans_rerooted`` / (Δ``plans_rerooted`` + Δ``plans_default_root``).
The service counts each member plan of each executed program once.  A
service without the counters, or a window that ran no plan, reads
nothing."""


def read(run):
    if "plans_rerooted" not in run.counters_after:
        return None
    moved = run.delta("plans_rerooted")
    plans = moved + run.delta("plans_default_root")
    return 100.0 * moved / plans if plans else None
