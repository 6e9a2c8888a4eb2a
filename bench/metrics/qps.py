"""qps: queries answered over the whole window, per second.  The window
runs from its open until the last request sent or due before
``--seconds`` has been answered, so the count has no step at its end."""


def read(run):
    answers = sum(1 for _ in run.results())
    return answers / run.window.close if answers else None
