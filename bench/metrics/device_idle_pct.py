"""device_idle_pct: 1 − (union of the device's operation intervals) /
(traced window), from the profiler trace, in percent."""

from bench import devtrace


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - devtrace.busy_s(run.trace) / run.trace.window_s)
