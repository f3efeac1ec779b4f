"""Training tokens of every iteration of every trial completed in the
window, over the time from the window's opening (the sweep's launch) to the
last of those results (host clock): a fixed-budget sweep's makespan,
inverted."""


def read(ctx):
    if not ctx["window_s"] or not ctx["tokens"]:
        return None
    return ctx["tokens"] / ctx["window_s"]
