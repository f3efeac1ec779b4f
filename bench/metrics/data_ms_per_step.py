"""Mean duration of the program's ``data`` spans: building one step's batch
on the host and handing it to the device, in milliseconds."""


def read(ctx):
    spans = [s.dur for s in ctx["spans"] or [] if s.name == "data"]
    return 1e3 * sum(spans) / len(spans) if spans else None
