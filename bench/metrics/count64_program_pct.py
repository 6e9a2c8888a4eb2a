"""count64_program_pct: kernels — the share of the window's executed
programs whose counts are 64 bits wide, in percent: 100 ·
Δ``programs_count64`` / (Δ``programs_count64`` + Δ``programs_count32``).
The service counts each execution by the width the statistics chose for
its counts.  A service without the counters, or a window that ran no
program, reads nothing."""


def read(run):
    if "programs_count64" not in run.counters_after:
        return None
    wide = run.delta("programs_count64")
    programs = wide + run.delta("programs_count32")
    return 100.0 * wide / programs if programs else None
