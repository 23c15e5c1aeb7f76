"""Share of the traced window in which the chip ran no operation, in
percent: 1 - (union of device op intervals) / window."""

import devtrace


def read(ctx):
    if ctx["win"] is None:
        return None
    busy = devtrace.busy_seconds(ctx["events"], ctx["win"])
    win = (ctx["win"][1] - ctx["win"][0]) * 1e-9
    return None if busy is None or win <= 0 else 100.0 * (1.0 - busy / win)
