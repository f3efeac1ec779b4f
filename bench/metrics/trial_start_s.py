"""Mean, over the trials started in the window, of the time from the
executor building the trial to its first result: build, compile and the
first iteration (host clock)."""


def read(ctx):
    starts = ctx["trial_starts"]
    return sum(starts) / len(starts) if starts else None
