"""Set-up time: process start to the window's opening (host clock)."""


def read(ctx):
    return ctx["setup_s"]
