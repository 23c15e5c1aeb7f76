"""Mean seconds per exploration that stage 4 spends on the host: the
``spac.stage4`` spans less their device calls (``spac.stage4.round1``,
``.replay``, and ``.scan`` on the ring-scan path), so timeline, service
times and dedup, host admission rounds, the reduction and serial-fallback
rows; over the completed ``spac.explore`` roots of the traced window."""

CALLS = ("spac.stage4.round1", "spac.stage4.replay", "spac.stage4.scan")


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
                if r.root in roots and r.name == "spac.stage4")
    calls = sum(r.end_ns - r.start_ns for r in recs
                if r.root in roots and r.name in CALLS)
    return (stage - calls) * 1e-9 / len(roots)
