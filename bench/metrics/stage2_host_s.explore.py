"""Mean seconds per exploration that stage 2 spends on the host: the
``spac.stage2`` spans less their device call (``spac.stage2.scan``:
copies in, the scan, the fetch back), so timeline, service times, latency
quantiles and the occupancy count; over the completed ``spac.explore``
roots of the traced window."""

CALLS = ("spac.stage2.scan",)


def read(ctx):
    try:
        from repro.analysis import spans
    except ImportError:             # a program without spans
        return None
    recs = spans.records()
    roots = {r.id for r in recs if r.name == "spac.explore" and r.parent is None}
    if not roots or spans.dropped():
        return None
    stage = sum(r.end_ns - r.start_ns for r in recs
                if r.root in roots and r.name == "spac.stage2")
    calls = sum(r.end_ns - r.start_ns for r in recs
                if r.root in roots and r.name in CALLS)
    return (stage - calls) * 1e-9 / len(roots)
