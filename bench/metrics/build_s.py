"""Mean duration of the program's ``build`` spans: instantiating a trial's
trainable (model and optimizer state, the step function), in seconds."""


def read(ctx):
    spans = [s.dur for s in ctx["spans"] or [] if s.name == "build"]
    return sum(spans) / len(spans) if spans else None
