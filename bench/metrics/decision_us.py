"""Mean duration of the program's ``schedule.decision`` spans: the runner's
choice of the next trial to launch, in microseconds."""


def read(ctx):
    spans = [s.dur for s in ctx["spans"] or [] if s.name == "schedule.decision"]
    return 1e6 * sum(spans) / len(spans) if spans else None
