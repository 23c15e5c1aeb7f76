"""Share of the device calls' host time in which the chip ran nothing, in
percent: 100 x (1 - device busy seconds / seconds inside the calls), both
over the traced window.  What is left is launch, copies and fetch inside
the calls (``calls_s.explore`` names the calls)."""

import devtrace

CALLS = ("spac.stage2.scan", "spac.stage4.round1", "spac.stage4.replay",
         "spac.stage4.scan")


def read(ctx):
    try:
        from repro.analysis import spans
    except ImportError:             # a program without spans
        return None
    if ctx["win"] is None:
        return None
    recs = spans.records()
    roots = {r.id for r in recs if r.name == "spac.explore" and r.parent is None}
    if not roots or spans.dropped():
        return None
    calls = sum(r.end_ns - r.start_ns for r in recs
                if r.root in roots and r.name in CALLS) * 1e-9
    busy = devtrace.busy_seconds(ctx["events"], ctx["win"])
    if busy is None or calls <= 0:
        return None
    return 100.0 * (1.0 - busy / calls)
