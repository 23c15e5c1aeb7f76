"""Seconds per exploration: the closed loop's whole window over the
explorations completed in it."""


def read(ctx):
    n = len(ctx["explorations"])
    if n == 0:
        return None
    return ctx["window_s"] / n
