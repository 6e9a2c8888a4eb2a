"""run_ms: executor — the mean host-clock time of one executed program
(its ``run`` span, shared by the queries it answered), in ms."""


def read(run):
    spans = [span for span, _ in run.programs()]
    return sum(s.duration_s for s in spans) / len(spans) * 1e3 \
        if spans else None
