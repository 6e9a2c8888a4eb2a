"""setup_s: from the start of the process to the open of the window:
data, load, the service and its statistics catalog, and warming every
program the traffic can use (compiling, where the cache misses)."""


def read(run):
    return run.setup_s
