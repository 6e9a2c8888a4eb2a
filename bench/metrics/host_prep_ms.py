"""host_prep_ms: SQL front end and planner — the mean, per answered
query, of its parse, fingerprint, plan and pad spans (each query's own
span tree, ``QueryResult.stats.trace``), in ms."""

STAGES = ("parse", "fingerprint", "plan", "pad")


def read(run):
    per = []
    for res in run.results():
        tree = res.stats.trace
        if tree is not None:
            per.append(sum(tree.child_duration(s) for s in STAGES))
    return sum(per) / len(per) * 1e3 if per else None
