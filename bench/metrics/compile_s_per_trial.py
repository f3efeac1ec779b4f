"""Seconds of backend compilation inside the window (JAX's monitoring
events), over the trials started in the window."""


def read(ctx):
    if not ctx["trial_starts"]:
        return None
    return sum(ctx["compiles"]) / len(ctx["trial_starts"])
