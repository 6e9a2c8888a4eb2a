"""latency_p50_ms: the median request latency of the window, exact, in
ms: from send in a closed loop, from the due time in an open one."""

from bench.quantiles import percentile


def read(run):
    lat = [r.latency for r in run.window.answered]
    return percentile(lat, 50) * 1e3 if lat else None
