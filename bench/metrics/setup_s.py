"""Set-up seconds: process start to the window's start (imports, traces
written, the program built, warm-up and any compile)."""


def read(ctx):
    return ctx["setup_s"]
