"""window_compiles: the service's ``compiles`` counter over the window.
Every program is warmed in set-up, so it should read 0."""


def read(run):
    return run.delta("compiles")
