"""Seconds of tracing and lowering the train step (the program's
``jit.trace`` and ``jit.lower`` spans whose ``fun_name`` is the train step's),
over the trials that have a ``build`` span.  Other functions, eager ops and
the nested traces of the step's own callees are not counted."""

STEP = ("train_step", "jit(train_step)")
PHASES = ("jit.trace", "jit.lower")


def read(ctx):
    spans = ctx["spans"] or []
    trials = {s.trace for s in spans if s.name == "build"}
    lowers = [s.dur for s in spans if s.name in PHASES and s.trace in trials
              and s.args.get("fun_name") in STEP]
    return sum(lowers) / len(trials) if trials and lowers else None
